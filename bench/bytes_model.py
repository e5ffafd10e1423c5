"""The least bytes a program must move, from its call's shapes.

The placement ladder is uint32 VPU work, which the published MXU peaks do
not bound, and no integer VPU peak is published: a routing or diff
program's roofline share is taken against memory bandwidth alone.  Each
function counts what the call has to read and write at least once.
"""

from __future__ import annotations

U32 = 4


def route_bytes(batch: int, n_bins: int, table_len: int) -> int:
    """One ``route_batch`` call: ``batch`` u32 keys in and int32 chosen nodes
    out; the served counters and the queue read and written, the service
    rates read and one queue-history row written, per node; the length and
    node tables (``table_len`` lane-padded entries each) read once."""
    per_key = 2 * U32
    per_node = (2 + 2 + 1 + 1) * U32
    return batch * per_key + n_bins * per_node + 2 * table_len * U32


def diff_bytes(n_ids: int, n_replicas: int, table_len: int) -> int:
    """One dual-version replica diff over ``n_ids`` ids: the u32 ids in; per
    id and slot a moved flag (1 byte) and int32 source, destination and
    source slot out; both versions' length and node tables read once."""
    per_id = U32 + n_replicas * (1 + 3 * U32)
    return n_ids * per_id + 2 * 2 * table_len * U32
