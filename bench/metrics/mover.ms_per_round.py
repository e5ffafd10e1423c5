"""Wall time of the mover's drains over the admission rounds they ran."""


def read(view):
    rounds = view["facts"].get("rounds", 0)
    spans = view["rec"].spans_named("rebalance.drain")
    if rounds == 0:
        return None
    return 1e3 * sum(b - a for a, b in spans) / rounds
