"""Share of the ids the ADDITION-NUMBER prefilter scanned on add events
that it kept for the full diff (ledger counters ``planner.prefilter_*``)."""


def read(view):
    f = view["facts"]
    if not f.get("prefilter_scanned"):
        return None
    return 100.0 * f["prefilter_kept"] / f["prefilter_scanned"]
