"""Mean wire size of the window's bundles."""


def read(run):
    return sum(run.wire_bytes) / len(run.wire_bytes) if run.wire_bytes \
        else None
