"""Share of the proved lanes that carried a query: lanes / (lanes + pad
lanes), from the service's counters over the window."""


def read(run):
    c = run.stats["counters"]
    total = c["lanes"] + c["pad_lanes"]
    return 100.0 * c["lanes"] / total if total else None
