"""The least bytes the migrating route program (``route_migrating``) must
move, from its call's shapes: ``route_bytes``'s count of the flat route
body, plus the per-slot pending probe.

Each lane must find its id in each of the R sorted pending slots: the
least a search of P sorted ids reads is a binary search's ``ceil(log2 P)``
u32 words (the program's two-level probe reads a whole row of
``migrate.live.PROBE_ROW`` ids instead, which this count leaves out as it
is not needed), then one int32 gather of the aligned source, so
``R * (ceil(log2 P) + 1)`` words a lane; the pending view's R live counts
are read once.  Memory bound only, like ``bytes_model.py``.
"""

from __future__ import annotations

import math

from bytes_model import U32, route_bytes


def migrating_route_bytes(batch: int, n_bins: int, table_len: int, n_replicas: int,
                          pending_pad: int) -> int:
    """One ``route_batch(..., migration=m)`` call over ``batch`` keys, with
    ``v_to``'s tables of ``table_len`` lane-padded entries and a pending
    view of ``n_replicas`` x ``pending_pad`` rows."""
    probe = batch * n_replicas * (math.ceil(math.log2(pending_pad)) + 1) * U32
    return route_bytes(batch, n_bins, table_len) + probe + n_replicas * U32
