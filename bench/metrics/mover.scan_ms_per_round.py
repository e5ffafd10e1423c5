"""Wall time of the mover's device round scan per admission round: the
program's ``mover.scan`` spans (the ``_scan_rounds`` dispatch and the copy
back of the landed bitmap and the k movement matrices) over the rounds."""


def read(view):
    span_s, _ = view["trace"].busy_in("mover.scan")
    rounds = view["facts"].get("rounds", 0)
    if span_s <= 0 or rounds == 0:
        return None
    return 1e3 * span_s / rounds
