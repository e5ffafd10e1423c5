"""Host time of the mover per admission round: the program's
``mover.prepare`` spans (plan-constant argsorts and uploads, once per
mover) and ``mover.matrices`` spans (landing rows, building each round's
movement dict) over the rounds."""


def read(view):
    span_s = sum(view["trace"].busy_in(n)[0] for n in ("mover.prepare", "mover.matrices"))
    rounds = view["facts"].get("rounds", 0)
    if span_s <= 0 or rounds == 0:
        return None
    return 1e3 * span_s / rounds
