"""The dual-version replica diff's share of its memory roofline over the
window's diff calls: the least time their bytes take at the chip's HBM
bandwidth over the device time the diff program took."""

import bytes_model

PROGRAM = "_diff_replicas_fused_ref"


def read(view):
    seconds, runs = view["trace"].program(PROGRAM)
    f = view["facts"]
    if runs == 0 or seconds <= 0:
        return None
    need = bytes_model.diff_bytes(f["diff_ids"], f["replicas"], 0) + runs * bytes_model.diff_bytes(
        0, f["replicas"], f["table_len"])
    return 100.0 * need / view["peaks"]["hbm_bytes_per_s"] / seconds
