"""The route body's share of its memory roofline: the least time its bytes
take at the chip's HBM bandwidth over the device time it took."""

import bytes_model

PROGRAM = "body"


def read(view):
    seconds, runs = view["trace"].program(PROGRAM)
    f = view["facts"]
    if runs == 0 or seconds <= 0:
        return None
    need = runs * bytes_model.route_bytes(f["batch"], f["n_bins"], f["table_len"])
    return 100.0 * need / view["peaks"]["hbm_bytes_per_s"] / seconds
