"""Device time of the fused route body (``route_batch``'s jit) per batch."""

PROGRAM = "body"


def read(view):
    seconds, runs = view["trace"].program(PROGRAM)
    if runs == 0:
        return None
    return 1e3 * seconds / runs
