"""Wall time of the dual-version replica diff per million ids diffed: the
program's ``planner.diff`` spans (pad, dispatch and the four copies back)
over the ids that reached the diff (the prefilter's kept ids on an add,
every id on a remove)."""


def read(view):
    span_s, _ = view["trace"].busy_in("planner.diff")
    ids = view["facts"].get("diff_ids", 0)
    if span_s <= 0 or ids == 0:
        return None
    return 1e3 * span_s / (ids / 1e6)
