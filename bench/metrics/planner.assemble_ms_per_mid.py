"""Host time of plan assembly per million ids planned: the program's
``planner.assemble`` spans (``np.nonzero`` and the row gathers per chunk,
the final concatenation and ``MigrationPlan``) over the ids planned."""


def read(view):
    span_s, _ = view["trace"].busy_in("planner.assemble")
    ids = view["facts"].get("ids_planned", 0)
    if span_s <= 0 or ids == 0:
        return None
    return 1e3 * span_s / (ids / 1e6)
