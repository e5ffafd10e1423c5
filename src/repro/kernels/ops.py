"""Jitted public wrappers for batched ASURA placement and replication.

Two tiers of entry points (DESIGN.md sections 3.2-3.4, 6):

  * ``*_on_table_device`` -- the fully device-resident path: placement,
    the p < 2**-53 non-converged tail (resolved on device against the
    precomputed u64-cumsum halves, bit-identical to
    ``repro.core.asura.resolve_tail_np``) and, for the ``nodes`` variants,
    the fused seg->node gather all run on device and return device arrays
    with ZERO host syncs -- the path the ``PlacementEngine`` device
    variants and device-chained consumers (router, data pipeline,
    checkpoint store) use.
  * ``place_on_table`` / ``place_replicas_on_table`` -- host-facing: the
    same device computation plus exactly ONE device->host transfer of the
    final result (no jnp->np->jnp ping-pong; historically the tail was
    resolved on the host and the fixed-up result re-uploaded).

``asura_place*`` are the table-deriving conveniences: they canonicalize the
segment table (via ``core.asura.lengths_to_u32``, which validates lengths
in [0, 1) exactly like the NumPy path) and dispatch to the kernels --
Pallas (interpret mode off the TPU; Mosaic does not lower these kernels
yet, so on a TPU they fail to compile) or the jnp reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.asura import (
    DEFAULT_PARAMS,
    AsuraParams,
    _upper_bound,
    lengths_to_u32,
    tail_cumsum_halves,
)

from .asura_place import (
    DEFAULT_ROWS,
    LANE,
    diff_nodes_pallas,
    diff_replicas_pallas,
    place_fused_pallas,
    place_pallas,
    place_replicas_pallas,
)
from .hierarchy import hier_place_replicas_pallas, hier_place_replicas_ref
from .ref import (
    addition_numbers_ref,
    depth_histogram,
    draws_histogram,
    place_ref,
    replica_ladder,
    resolve_tail_dev,
)

__all__ = [
    "table_prep",
    "node_table_prep",
    "tail_prep",
    "place_on_table",
    "place_on_table_device",
    "place_nodes_on_table_device",
    "place_replicas_on_table",
    "place_replicas_on_table_device",
    "diff_nodes_on_tables_device",
    "diff_replicas_on_tables_device",
    "hier_place_replicas_on_tables",
    "hier_place_replicas_on_tables_device",
    "hier_diff_replicas_on_tables_device",
    "addition_numbers_on_table_device",
    "asura_place",
    "asura_place_nodes",
    "asura_place_replicas",
]


@functools.partial(jax.jit, static_argnames=("multiple",))
def _pad_ids(x: jax.Array, multiple: int) -> jax.Array:
    """Zero-pad ids to a block multiple ON DEVICE (jitted so the pad
    constant is baked at compile time -- no per-call host->device scalar)."""
    pad = (-x.shape[0]) % multiple
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.zeros((pad,), dtype=x.dtype)])


def _lane_pad_np(x: np.ndarray, fill) -> np.ndarray:
    pad = (-x.shape[0]) % LANE
    if pad == 0:
        return x
    return np.concatenate([x, np.full(pad, fill, dtype=x.dtype)])


def table_prep(seg_lengths, params: AsuraParams = DEFAULT_PARAMS):
    """Host-side: canonical u32 table (lane-padded) + static top level.

    Uses ``core.asura.lengths_to_u32`` -- the single canonicalization spec
    -- so out-of-range lengths raise here exactly as on the NumPy path
    instead of silently wrapping on device.
    """
    lengths = np.asarray(seg_lengths, dtype=np.float64)
    top_level = params.level_for(_upper_bound(lengths))
    len32 = lengths_to_u32(lengths)
    return jnp.asarray(_lane_pad_np(len32, np.uint32(0))), top_level


def node_table_prep(seg_to_node) -> jax.Array:
    """Host-side: int32 seg->node map, lane-padded with -1 (hole marker)."""
    node_of = np.asarray(seg_to_node, dtype=np.int32)
    return jnp.asarray(_lane_pad_np(node_of, np.int32(-1)))


def tail_prep(len32) -> tuple[jax.Array, jax.Array]:
    """Host-side: u64 length-cumsum as two lane-padded u32 halves on device.

    The device-resident tail tables (DESIGN.md section 3.2): computed once
    per table version from the (already lane-padded) u32 length table;
    padding entries carry cumsum == total mass and can never win the tail
    draw.  One upload alongside the length/node tables.
    """
    cum_hi, cum_lo = tail_cumsum_halves(np.asarray(len32, dtype=np.uint32))
    return jnp.asarray(cum_hi), jnp.asarray(cum_lo)


@functools.partial(
    jax.jit,
    static_argnames=("top_level", "s_log2", "max_draws", "emit_nodes", "emit_stats"),
)
def _place_fused_ref(
    ids: jax.Array,
    len32: jax.Array,
    cum_hi: jax.Array,
    cum_lo: jax.Array,
    node_of: jax.Array,
    *,
    top_level: int,
    s_log2: int,
    max_draws: int,
    emit_nodes: bool,
    emit_stats: bool = False,
):
    """jnp-reference analogue of ``place_fused_pallas``: total, on-device.

    ``emit_stats=True`` returns ``(out, tail_count)`` where ``tail_count``
    is the uint32 number of lanes that fell through the bounded draw loop
    into the 95-bit tail resolution (obs device plane; p < 2**-53 per lane,
    so a nonzero count is itself a signal).  Outputs are bit-identical
    either way."""
    segs = place_ref(
        ids, len32, top_level=top_level, s_log2=s_log2, max_draws=max_draws
    )
    tail_count = jnp.sum((segs < 0).astype(jnp.uint32)) if emit_stats else None
    segs = resolve_tail_dev(ids, segs, cum_hi, cum_lo, top_level)
    if emit_nodes:
        segs = jnp.take(node_of, segs, axis=0)
    if emit_stats:
        return segs, tail_count
    return segs


@functools.partial(jax.jit, static_argnames=("n",))
def _head(x: jax.Array, n: int) -> jax.Array:
    """x[:n] ON DEVICE (jitted: an eager slice materializes its start
    indices as host scalars, which a transfer guard rightly rejects)."""
    return x[:n]


@functools.partial(
    jax.jit,
    static_argnames=(
        "top_level", "s_log2", "max_draws", "n_replicas", "emit_nodes",
        "emit_stats",
    ),
)
def _place_replicas_fused_ref(
    ids: jax.Array,
    len32: jax.Array,
    node_of: jax.Array,
    *,
    top_level: int,
    s_log2: int,
    max_draws: int,
    n_replicas: int,
    emit_nodes: bool,
    emit_stats: bool = False,
):
    """jnp-reference replica placement (one jit so no eager scalar ops
    escape to the host between calls).  ``emit_nodes=True`` returns the
    node ids the ladder already carries, with no gather of its own.

    ``emit_stats=True`` returns ``(out, stats)`` where ``stats`` is the
    (DEPTH_BINS + 1 + DRAW_BINS,) uint32 vector ``[ladder_depth_hist...,
    nonconverged, ladder_draws_hist...]`` the obs device plane accumulates
    into its slab -- placements stay bit-identical (tested)."""
    segs, nodes, counters = replica_ladder(
        ids,
        len32,
        node_of,
        top_level=top_level,
        s_log2=s_log2,
        max_draws=max_draws,
        n_replicas=n_replicas,
        emit_stats=emit_stats,
    )
    out = nodes.T if emit_nodes else segs.T
    if emit_stats:
        nonconv = jnp.sum((segs < 0).astype(jnp.uint32))
        stats = jnp.concatenate(
            [
                depth_histogram(counters, top_level),
                nonconv[None],
                draws_histogram(counters),
            ]
        )
        return out, stats
    return out


def _default_interpret(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def place_on_table_device(
    datum_ids,
    len32: jax.Array,
    cum_hi: jax.Array,
    cum_lo: jax.Array,
    node_of: jax.Array | None = None,
    *,
    top_level: int,
    params: AsuraParams = DEFAULT_PARAMS,
    use_pallas: bool = True,
    interpret: bool | None = None,
    rows_per_block: int = DEFAULT_ROWS,
    emit_nodes: bool = False,
) -> jax.Array:
    """Fully device-resident placement -> (batch,) int32 device array.

    Total (the tail is resolved on device, bit-identical to the host spec)
    and sync-free: inputs already on device stay there, the output is a
    device array, and nothing round-trips through the host.  With
    ``emit_nodes=True`` the seg->node gather is fused and the result is
    node ids (``node_of`` required).
    """
    interpret = _default_interpret(interpret)
    ids = jnp.asarray(datum_ids).astype(jnp.uint32)
    n = ids.shape[0]
    if emit_nodes and node_of is None:
        raise ValueError("emit_nodes=True requires the node table")
    if node_of is None:
        node_of = jnp.full(len32.shape, -1, dtype=jnp.int32)
    if n == 0:
        return jnp.zeros((0,), dtype=jnp.int32)
    if use_pallas:
        block = rows_per_block * LANE
        padded = _pad_ids(ids, block)
        out = place_fused_pallas(
            padded,
            len32,
            cum_hi,
            cum_lo,
            node_of,
            top_level=top_level,
            s_log2=params.s_log2,
            max_draws=params.max_draws,
            rows_per_block=rows_per_block,
            interpret=interpret,
            emit_nodes=emit_nodes,
        )
        return _head(out, n)
    return _place_fused_ref(
        ids,
        len32,
        cum_hi,
        cum_lo,
        node_of,
        top_level=top_level,
        s_log2=params.s_log2,
        max_draws=params.max_draws,
        emit_nodes=emit_nodes,
    )


@functools.partial(
    jax.jit, static_argnames=("top_a", "top_b", "s_log2", "max_draws")
)
def _diff_fused_ref(
    ids: jax.Array,
    len32_a: jax.Array,
    cum_hi_a: jax.Array,
    cum_lo_a: jax.Array,
    node_a: jax.Array,
    len32_b: jax.Array,
    cum_hi_b: jax.Array,
    cum_lo_b: jax.Array,
    node_b: jax.Array,
    *,
    top_a: int,
    top_b: int,
    s_log2: int,
    max_draws: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """jnp-reference version diff: both placements + the compare in ONE jit
    (no eager scalar ops escape to the host between the two sweeps)."""
    src = _place_fused_ref(
        ids, len32_a, cum_hi_a, cum_lo_a, node_a,
        top_level=top_a, s_log2=s_log2, max_draws=max_draws, emit_nodes=True,
    )
    dst = _place_fused_ref(
        ids, len32_b, cum_hi_b, cum_lo_b, node_b,
        top_level=top_b, s_log2=s_log2, max_draws=max_draws, emit_nodes=True,
    )
    return src != dst, src, dst


@jax.jit
def _neq(src: jax.Array, dst: jax.Array) -> jax.Array:
    """src != dst ON DEVICE (jitted so no eager dispatch can stage through
    host scalars under a transfer guard)."""
    return src != dst


@functools.partial(jax.jit, static_argnames=("n",))
def _split_diff(out: jax.Array, n: int) -> tuple[jax.Array, jax.Array]:
    """(2, padded) kernel output -> (src[:n], dst[:n]) ON DEVICE (an eager
    row index would materialize its start index as a host scalar)."""
    return out[0, :n], out[1, :n]


def diff_nodes_on_tables_device(
    datum_ids,
    len32_a: jax.Array,
    cum_hi_a: jax.Array,
    cum_lo_a: jax.Array,
    node_a: jax.Array,
    len32_b: jax.Array,
    cum_hi_b: jax.Array,
    cum_lo_b: jax.Array,
    node_b: jax.Array,
    *,
    top_a: int,
    top_b: int,
    params: AsuraParams = DEFAULT_PARAMS,
    use_pallas: bool = True,
    interpret: bool | None = None,
    rows_per_block: int = DEFAULT_ROWS,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Version diff against two prebuilt tables -> (moved, src, dst).

    Places every id under table A (version v) and table B (version v+1) in
    one device pass and emits the migration planner's triple: ``moved``
    (bool, owner changed), ``src`` / ``dst`` (int32 node ids under v / v+1).
    All three are DEVICE arrays and nothing round-trips through the host --
    the planner's ``plan_stream`` chains chunks of this with zero syncs
    (DESIGN.md section 8).
    """
    interpret = _default_interpret(interpret)
    ids = jnp.asarray(datum_ids).astype(jnp.uint32)
    n = ids.shape[0]
    if n == 0:
        empty = jnp.zeros((0,), dtype=jnp.int32)
        return jnp.zeros((0,), dtype=bool), empty, empty
    if use_pallas:
        block = rows_per_block * LANE
        padded = _pad_ids(ids, block)
        out = diff_nodes_pallas(
            padded,
            len32_a, cum_hi_a, cum_lo_a, node_a,
            len32_b, cum_hi_b, cum_lo_b, node_b,
            top_a=top_a,
            top_b=top_b,
            s_log2=params.s_log2,
            max_draws=params.max_draws,
            rows_per_block=rows_per_block,
            interpret=interpret,
        )
        src, dst = _split_diff(out, n)
        return _neq(src, dst), src, dst
    return _diff_fused_ref(
        ids,
        len32_a, cum_hi_a, cum_lo_a, node_a,
        len32_b, cum_hi_b, cum_lo_b, node_b,
        top_a=top_a,
        top_b=top_b,
        s_log2=params.s_log2,
        max_draws=params.max_draws,
    )


@functools.partial(jax.jit, static_argnames=("n_replicas",))
def _align_replica_sets(
    before: jax.Array, after: jax.Array, *, n_replicas: int
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-slot minimal alignment of two (batch, R) replica-node sets.

    The jitted device twin of ``core.asura.align_replica_sets`` (same exact
    integer formulation, bit-identical -- tested): slots index the AFTER
    set; ``moved[b, r]`` iff ``after[b, r]`` is not in ``before[b, :]``
    (exactly the section-5 minimal replica mass), ``src`` is the
    rank-matched vacated node for moved slots (``after[b, r]`` itself
    otherwise), ``src_slot`` its before-set position (rollback re-indexing).
    Returns ``(moved, src, dst, src_slot)``, all (batch, R); ``dst`` is
    ``after`` cast to int32.
    """
    before = before.astype(jnp.int32)
    after = after.astype(jnp.int32)
    new = ~jnp.any(after[:, :, None] == before[:, None, :], axis=2)
    lost = ~jnp.any(before[:, :, None] == after[:, None, :], axis=2)
    new_i = new.astype(jnp.int32)
    lost_i = lost.astype(jnp.int32)
    rank_new = jnp.cumsum(new_i, axis=1) - new_i
    rank_lost = jnp.cumsum(lost_i, axis=1) - lost_i
    match = lost[:, None, :] & (rank_lost[:, None, :] == rank_new[:, :, None])
    picked_src = jnp.sum(jnp.where(match, before[:, None, :], 0), axis=2)
    slots = jnp.arange(n_replicas, dtype=jnp.int32)
    picked_slot = jnp.sum(jnp.where(match, slots[None, None, :], 0), axis=2)
    src = jnp.where(new, picked_src, after)
    src_slot = jnp.where(new, picked_slot, slots[None, :])
    return new, src, after, src_slot


@functools.partial(
    jax.jit,
    static_argnames=("top_a", "top_b", "s_log2", "max_draws", "n_replicas"),
)
def _diff_replicas_fused_ref(
    ids: jax.Array,
    len32_a: jax.Array,
    node_a: jax.Array,
    len32_b: jax.Array,
    node_b: jax.Array,
    *,
    top_a: int,
    top_b: int,
    s_log2: int,
    max_draws: int,
    n_replicas: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """jnp-reference replica-set version diff: both R-replica placements +
    the per-slot alignment in ONE jit (no eager scalar ops escape to the
    host between the two sweeps)."""
    before = _place_replicas_fused_ref(
        ids, len32_a, node_a,
        top_level=top_a, s_log2=s_log2, max_draws=max_draws,
        n_replicas=n_replicas, emit_nodes=True,
    )
    after = _place_replicas_fused_ref(
        ids, len32_b, node_b,
        top_level=top_b, s_log2=s_log2, max_draws=max_draws,
        n_replicas=n_replicas, emit_nodes=True,
    )
    return _align_replica_sets(before, after, n_replicas=n_replicas)


@functools.partial(jax.jit, static_argnames=("n",))
def _split_diff_sets(out: jax.Array, n: int) -> tuple[jax.Array, jax.Array]:
    """(2, padded, R) kernel output -> (before[:n], after[:n]) ON DEVICE."""
    return out[0, :n], out[1, :n]


def diff_replicas_on_tables_device(
    datum_ids,
    len32_a: jax.Array,
    node_a: jax.Array,
    len32_b: jax.Array,
    node_b: jax.Array,
    *,
    top_a: int,
    top_b: int,
    n_replicas: int,
    params: AsuraParams = DEFAULT_PARAMS,
    use_pallas: bool = True,
    interpret: bool | None = None,
    rows_per_block: int = DEFAULT_ROWS,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Replica-set version diff against two prebuilt tables
    -> ``(moved, src, dst, src_slot)``, each a (batch, R) DEVICE array.

    Places every id's FULL R-replica set under table A (version v) and
    table B (version v+1) in one device pass (``diff_replicas_pallas`` /
    the fused jnp reference) and aligns the two sets per slot
    (``_align_replica_sets``): ``moved[b, r]`` iff slot r's owner actually
    changed, ``src`` the vacated v-side node for moved slots, ``dst`` the
    v+1 set, ``src_slot`` the before-set position for rollback.  Nothing
    round-trips through the host -- the replica planner's
    ``plan_replicas_stream`` chains chunks of this with zero syncs
    (DESIGN.md section 10).
    """
    interpret = _default_interpret(interpret)
    ids = jnp.asarray(datum_ids).astype(jnp.uint32)
    n = ids.shape[0]
    if n == 0:
        empty = jnp.zeros((0, n_replicas), dtype=jnp.int32)
        return jnp.zeros((0, n_replicas), dtype=bool), empty, empty, empty
    if use_pallas:
        block = rows_per_block * LANE
        padded = _pad_ids(ids, block)
        sets = diff_replicas_pallas(
            padded,
            len32_a,
            node_a,
            len32_b,
            node_b,
            top_a=top_a,
            top_b=top_b,
            s_log2=params.s_log2,
            max_draws=params.max_draws,
            n_replicas=n_replicas,
            rows_per_block=rows_per_block,
            interpret=interpret,
        )
        before, after = _split_diff_sets(sets, n)
        return _align_replica_sets(before, after, n_replicas=n_replicas)
    return _diff_replicas_fused_ref(
        ids,
        len32_a,
        node_a,
        len32_b,
        node_b,
        top_a=top_a,
        top_b=top_b,
        s_log2=params.s_log2,
        max_draws=params.max_draws,
        n_replicas=n_replicas,
    )


def addition_numbers_on_table_device(
    datum_ids,
    len32: jax.Array,
    node_of: jax.Array,
    *,
    top_level: int,
    n_replicas: int = 1,
    extra_levels: int | None = None,
    params: AsuraParams = DEFAULT_PARAMS,
) -> jax.Array:
    """Device-resident ADDITION NUMBERs against a prebuilt table.

    Runs the trace ``extra_levels`` generator levels ABOVE the entry level
    (default: up to 4, capped by the 2**31 segment-space bound).  Extension
    is how the scalar oracle handles the common "placed on the first draw,
    no anterior number" case, and it is exact here too: by the section 2.B
    invariance the extended stream only INSERTS numbers, every inserted
    number is a miss (its value exceeds every segment), and numbers emitted
    at level l lie in the disjoint range [2**(s+l-1), 2**(s+l)), so the
    minimum unused anterior is unchanged when the unextended trace has one
    and equals the minimally-extended scalar result when it does not.

    -1 marks the remaining lanes (needs more extension than the static
    budget, or non-convergence) -- checking on device would force a sync,
    so callers treat -1 as "candidate", which keeps the prefilter sound.
    Both engine backends route through the jitted jnp reference
    (``addition_numbers_ref``); the trace is metadata work off the
    placement hot path, so it has no Pallas variant.
    """
    if extra_levels is None:
        extra_levels = max(0, min(4, 31 - params.s_log2 - top_level))
    ids = jnp.asarray(datum_ids).astype(jnp.uint32)
    if ids.shape[0] == 0:
        return jnp.zeros((0,), dtype=jnp.int32)
    return addition_numbers_ref(
        ids,
        len32,
        node_of,
        top_level=top_level + extra_levels,
        s_log2=params.s_log2,
        max_draws=params.max_draws,
        n_replicas=n_replicas,
    )


def place_nodes_on_table_device(
    datum_ids,
    len32: jax.Array,
    cum_hi: jax.Array,
    cum_lo: jax.Array,
    node_of: jax.Array,
    **kwargs,
) -> jax.Array:
    """Device-resident placement straight to node ids (fused gather)."""
    return place_on_table_device(
        datum_ids, len32, cum_hi, cum_lo, node_of, emit_nodes=True, **kwargs
    )


def place_on_table(
    datum_ids,
    len32: jax.Array,
    *,
    top_level: int,
    cum_hi: jax.Array | None = None,
    cum_lo: jax.Array | None = None,
    params: AsuraParams = DEFAULT_PARAMS,
    use_pallas: bool = True,
    interpret: bool | None = None,
    rows_per_block: int = DEFAULT_ROWS,
) -> np.ndarray:
    """Placement against a prebuilt (lane-padded) device table -> int64 segs.

    Host-facing: runs the device-resident path (including the on-device
    tail, bit-identical to the NumPy ``place_batch`` fallback) and pays
    exactly one device->host transfer for the final result.  Callers that
    chain into further device work should use ``place_on_table_device``
    instead.  ``cum_hi``/``cum_lo`` are the precomputed tail tables
    (``tail_prep``); if omitted they are derived here (one extra table
    read), which only table-per-call conveniences do.
    """
    if cum_hi is None or cum_lo is None:
        cum_hi, cum_lo = tail_prep(np.asarray(len32))
    segs = place_on_table_device(
        datum_ids,
        len32,
        cum_hi,
        cum_lo,
        top_level=top_level,
        params=params,
        use_pallas=use_pallas,
        interpret=interpret,
        rows_per_block=rows_per_block,
    )
    return np.asarray(segs).astype(np.int64)


def place_replicas_on_table_device(
    datum_ids,
    len32: jax.Array,
    node_of: jax.Array,
    n_replicas: int,
    *,
    top_level: int,
    params: AsuraParams = DEFAULT_PARAMS,
    use_pallas: bool = True,
    interpret: bool | None = None,
    rows_per_block: int = DEFAULT_ROWS,
    emit_nodes: bool = False,
) -> jax.Array:
    """Device-resident replica placement -> (batch, R) int32 device array.

    ``emit_nodes=True`` returns node ids via the fused in-kernel gather
    (primary first).  Non-converged entries stay -1 -- checking would force
    a device->host sync, so the device path documents the marker instead of
    raising; the host wrapper ``place_replicas_on_table`` raises.
    """
    interpret = _default_interpret(interpret)
    ids = jnp.asarray(datum_ids).astype(jnp.uint32)
    n = ids.shape[0]
    if n == 0:
        return jnp.zeros((0, n_replicas), dtype=jnp.int32)
    if use_pallas:
        block = rows_per_block * LANE
        padded = _pad_ids(ids, block)
        out = place_replicas_pallas(
            padded,
            len32,
            node_of,
            top_level=top_level,
            s_log2=params.s_log2,
            max_draws=params.max_draws,
            n_replicas=n_replicas,
            rows_per_block=rows_per_block,
            interpret=interpret,
            emit_nodes=emit_nodes,
        )
        return _head(out, n)
    return _place_replicas_fused_ref(
        ids,
        len32,
        node_of,
        top_level=top_level,
        s_log2=params.s_log2,
        max_draws=params.max_draws,
        n_replicas=n_replicas,
        emit_nodes=emit_nodes,
    )


def place_replicas_on_table(
    datum_ids,
    len32: jax.Array,
    node_of: jax.Array,
    n_replicas: int,
    *,
    top_level: int,
    params: AsuraParams = DEFAULT_PARAMS,
    use_pallas: bool = True,
    interpret: bool | None = None,
    rows_per_block: int = DEFAULT_ROWS,
) -> np.ndarray:
    """Replica placement against a prebuilt table -> (batch, R) int64 segs.

    Raises on non-convergence (more replicas requested than distinct nodes
    can supply within the bounded loop), matching the NumPy batch path.
    """
    result = place_replicas_on_table_device(
        datum_ids,
        len32,
        node_of,
        n_replicas,
        top_level=top_level,
        params=params,
        use_pallas=use_pallas,
        interpret=interpret,
        rows_per_block=rows_per_block,
    )
    out = np.asarray(result).astype(np.int64)
    if (out < 0).any():
        raise RuntimeError("replication did not converge; too few distinct nodes?")
    return out


@functools.partial(jax.jit, static_argnames=("n",))
def _hier_head(out: jax.Array, n: int) -> jax.Array:
    """(2, R, padded) kernel output -> (2, R, n) ON DEVICE."""
    return out[:, :, :n]


def hier_place_replicas_on_tables_device(
    datum_ids,
    tables,
    *,
    top_level: int,
    max_top: int,
    s_pad: int,
    n_replicas: int,
    params: AsuraParams = DEFAULT_PARAMS,
    use_pallas: bool = True,
    interpret: bool | None = None,
    rows_per_block: int = DEFAULT_ROWS,
) -> jax.Array:
    """Fused two-level replication -> (2, R, batch) int32 DEVICE array.

    ``tables`` is the 8-tuple of prebuilt device operands (top length +
    domain-slot tables, stacked per-domain length/node/cumsum tables,
    per-domain top levels and domain ids -- the hierarchical artifact's
    device view).  Plane 0 holds domain ids, plane 1 node ids; -1 marks
    level-1 non-convergence (too few distinct domains).  Zero host syncs.
    """
    interpret = _default_interpret(interpret)
    ids = jnp.asarray(datum_ids).astype(jnp.uint32)
    n = ids.shape[0]
    if n == 0:
        return jnp.zeros((2, n_replicas, 0), dtype=jnp.int32)
    kw = dict(
        top_level=top_level,
        max_top=max_top,
        s_log2=params.s_log2,
        max_draws=params.max_draws,
        s_pad=s_pad,
        n_replicas=n_replicas,
    )
    if use_pallas:
        block = rows_per_block * LANE
        padded = _pad_ids(ids, block)
        out = hier_place_replicas_pallas(
            padded, *tables, rows_per_block=rows_per_block, interpret=interpret, **kw
        )
        return _hier_head(out, n)
    return hier_place_replicas_ref(ids, *tables, **kw)


def hier_place_replicas_on_tables(datum_ids, tables, **kwargs) -> np.ndarray:
    """Host wrapper -> (batch, R, 2) int64 [domain, node] pairs.

    Raises on level-1 non-convergence, matching the oracle's
    ``place_replicas_u32`` behaviour (more replicas than distinct domains).
    """
    out = np.asarray(hier_place_replicas_on_tables_device(datum_ids, tables, **kwargs))
    if (out[0] < 0).any():
        raise RuntimeError(
            "hierarchical replication did not converge; too few distinct domains?"
        )
    return out.transpose(2, 1, 0).astype(np.int64)


@functools.partial(jax.jit, static_argnames=("n_replicas",))
def _hier_align(before: jax.Array, after: jax.Array, *, n_replicas: int):
    """Align two (2, R, batch) two-level placements on their NODE plane.

    Node ids are globally unique across domains (the hierarchical engine
    validates this), so the flat rank-matched alignment applies unchanged;
    the domain planes ride along: ``src_dom[b, r]`` is the vacated node's
    domain under v (gathered at ``src_slot``), ``dst_dom`` the v+1 set's
    domains.  Returns ``(moved, src, dst, src_slot, src_dom, dst_dom)``.
    """
    b_dom, b_node = before[0].T, before[1].T
    a_dom, a_node = after[0].T, after[1].T
    moved, src, dst, src_slot = _align_replica_sets(
        b_node, a_node, n_replicas=n_replicas
    )
    src_dom = jnp.take_along_axis(b_dom.astype(jnp.int32), src_slot, axis=1)
    dst_dom = a_dom.astype(jnp.int32)
    src_dom = jnp.where(moved, src_dom, dst_dom)
    return moved, src, dst, src_slot, src_dom, dst_dom


def hier_diff_replicas_on_tables_device(
    datum_ids,
    tables_a,
    tables_b,
    *,
    statics_a: tuple,
    statics_b: tuple,
    n_replicas: int,
    params: AsuraParams = DEFAULT_PARAMS,
    use_pallas: bool = True,
    interpret: bool | None = None,
    rows_per_block: int = DEFAULT_ROWS,
):
    """Two-level replica-set version diff, both levels under both versions.

    ``statics_*`` are ``(top_level, max_top, s_pad)`` per version (the two
    artifacts' static shape keys).  Places every id's full (domain, node)
    R-set under v and v+1 with the fused two-level pass each, then aligns
    on the node plane -- ``(moved, src, dst, src_slot, src_dom, dst_dom)``,
    all (batch, R) device arrays, zero host syncs.
    """
    ids = jnp.asarray(datum_ids).astype(jnp.uint32)
    if ids.shape[0] == 0:
        empty = jnp.zeros((0, n_replicas), dtype=jnp.int32)
        return (
            jnp.zeros((0, n_replicas), dtype=bool),
            empty, empty, empty, empty, empty,
        )
    kw = dict(
        n_replicas=n_replicas,
        params=params,
        use_pallas=use_pallas,
        interpret=interpret,
        rows_per_block=rows_per_block,
    )
    top_a, max_a, pad_a = statics_a
    top_b, max_b, pad_b = statics_b
    before = hier_place_replicas_on_tables_device(
        ids, tables_a, top_level=top_a, max_top=max_a, s_pad=pad_a, **kw
    )
    after = hier_place_replicas_on_tables_device(
        ids, tables_b, top_level=top_b, max_top=max_b, s_pad=pad_b, **kw
    )
    return _hier_align(before, after, n_replicas=n_replicas)


def asura_place(
    datum_ids,
    seg_lengths,
    params: AsuraParams = DEFAULT_PARAMS,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
    rows_per_block: int = DEFAULT_ROWS,
) -> jax.Array:
    """Place a batch of datum ids -> int32 segment numbers (device array).

    use_pallas=False routes through the pure-jnp reference (place_ref) --
    the path the engine runs on a TPU; the Pallas path is validated
    bit-identical in interpret mode (tests/test_kernels.py).
    The result is total (on-device tail) and stays on device -- no host
    round trip, no result re-upload.
    """
    len32, top_level = table_prep(seg_lengths, params)
    cum_hi, cum_lo = tail_prep(len32)
    return place_on_table_device(
        datum_ids,
        len32,
        cum_hi,
        cum_lo,
        top_level=top_level,
        params=params,
        use_pallas=use_pallas,
        interpret=interpret,
        rows_per_block=rows_per_block,
    )


def asura_place_nodes(
    datum_ids,
    seg_lengths,
    seg_to_node,
    params: AsuraParams = DEFAULT_PARAMS,
    **kwargs,
) -> jax.Array:
    """Batch placement straight to node ids (fused gather, device array)."""
    len32, top_level = table_prep(seg_lengths, params)
    cum_hi, cum_lo = tail_prep(len32)
    node_of = node_table_prep(seg_to_node)
    return place_nodes_on_table_device(
        datum_ids,
        len32,
        cum_hi,
        cum_lo,
        node_of,
        top_level=top_level,
        params=params,
        **kwargs,
    )


def asura_place_replicas(
    datum_ids,
    seg_lengths,
    seg_to_node,
    n_replicas: int,
    params: AsuraParams = DEFAULT_PARAMS,
    *,
    use_pallas: bool = True,
    interpret: bool | None = None,
    rows_per_block: int = DEFAULT_ROWS,
) -> jax.Array:
    """Replica placement -> (batch, R) int32 segment numbers, primary first."""
    len32, top_level = table_prep(seg_lengths, params)
    node_of = node_table_prep(seg_to_node)
    segs = place_replicas_on_table(
        datum_ids,
        len32,
        node_of,
        n_replicas,
        top_level=top_level,
        params=params,
        use_pallas=use_pallas,
        interpret=interpret,
        rows_per_block=rows_per_block,
    )
    return jnp.asarray(segs.astype(np.int32))
