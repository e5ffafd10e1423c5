"""Host time of the planner per million ids: the time spanned by the
benchmark's ``rebalance.plan`` spans less the device's busy time inside
them, over the ids planned."""


def read(view):
    span_s, busy_s = view["trace"].busy_in("rebalance.plan")
    ids = view["facts"].get("ids_planned", 0)
    if span_s <= 0 or ids == 0:
        return None
    return 1e3 * (span_s - busy_s) / (ids / 1e6)
