"""Device time of the migrating route program (``route_batch``'s jit with
a live migration: v+1 replica ladder, per-slot pending probe, merge, pow2
select and count) per batch."""

PROGRAM = "route_migrating"


def read(view):
    seconds, runs = view["trace"].program(PROGRAM)
    if runs == 0:
        return None
    return 1e3 * seconds / runs
