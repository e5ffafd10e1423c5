"""The migrating route program's share of its memory roofline: the least
time its bytes take at the chip's HBM bandwidth over the device time it
took.  The window's batches route through one of two pending views (the
rack joining, the rack leaving), each with its own shapes: a run's bytes
are their mean over the window's batches."""

import migrating_bytes

PROGRAM = "route_migrating"


def read(view):
    seconds, runs = view["trace"].program(PROGRAM)
    f = view["facts"]
    if runs == 0 or seconds <= 0 or not f.get("route_shapes"):
        return None
    shapes = f["route_shapes"]
    per_batch = sum(n * migrating_bytes.migrating_route_bytes(
        f["batch"], f["n_bins"], table_len, f["replicas"], pad)
        for n, table_len, pad in shapes) / sum(n for n, _, _ in shapes)
    return 100.0 * runs * per_batch / view["peaks"]["hbm_bytes_per_s"] / seconds
