"""The benchmark harness: set-up, the measured window, the checks and the
reduction of what a run saw into metrics.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own that the harness finds by the name in
``BENCHMARK.json`` (see ``bench/README.md``)."""
