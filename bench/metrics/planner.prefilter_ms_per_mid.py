"""Wall time of the ADDITION-NUMBER prefilter per million ids it scanned:
the program's ``planner.prefilter`` spans (the AN dispatch, its copy back
and the mask) over the ledger's ``planner.prefilter_scanned``."""


def read(view):
    span_s, _ = view["trace"].busy_in("planner.prefilter")
    scanned = view["facts"].get("prefilter_scanned", 0)
    if span_s <= 0 or scanned == 0:
        return None
    return 1e3 * span_s / (scanned / 1e6)
