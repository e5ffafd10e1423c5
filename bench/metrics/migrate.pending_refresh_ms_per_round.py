"""Wall time of the per-slot pending view's refresh per mover round: the
program's ``migrate.pending_refresh`` spans (the host rebuild of the
sorted sentinel-padded (R, P) view from the landed bitmap, and its upload)
over the window's rounds."""


def read(view):
    span_s, _ = view["trace"].busy_in("migrate.pending_refresh")
    rounds = view["facts"].get("rounds", 0)
    if span_s <= 0 or rounds == 0:
        return None
    return 1e3 * span_s / rounds
