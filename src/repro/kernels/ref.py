"""Pure-jnp oracle for the batched ASURA placement kernel.

Bit-identical to ``repro.core.asura.place_batch`` (NumPy) and to the Pallas
kernel in ``asura_place.py`` -- all three use the exact integer formulation
(uint32 draws, MSB descend test, shift-based floor/fraction).  Tested against
both in tests/test_kernels.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

GOLDEN = 0x9E3779B9
KMULT = 0x85EBCA77
# Ladder-depth histogram width (obs device plane): a draw's depth is
# ``top_level - exit_level + 1`` in [1, top_level + 1], and the shift
# construction bounds top_level <= 32 - s_log2, so 34 bins (clipped)
# cover every reachable depth at any parameterization.
DEPTH_BINS = 34
# Ladder-draws histogram width (obs device plane): bin d counts the lanes
# that needed d draws to find their replicas; the last bin holds every
# lane that needed DRAW_BINS - 1 or more.
DRAW_BINS = 64
# NOTE: no module-level jnp constants here -- this module's helpers run
# inside Pallas kernels, which reject captured device arrays.


def fmix32(h: jax.Array) -> jax.Array:
    """MurmurHash3 finalizer on uint32 lanes."""
    h = h.astype(jnp.uint32)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def draw_u32(ids: jax.Array, level, counters: jax.Array) -> jax.Array:
    """k-th raw draw of the level-``level`` generator (counter-based).

    ``level`` may be a static int or a traced scalar (the lazy-depth ladder
    walks levels inside a ``lax.while_loop``); uint32 wrap-around
    multiplication matches the static ``(GOLDEN * (level+1)) & 0xFFFFFFFF``.
    """
    lvl = jnp.asarray(level).astype(jnp.uint32)
    lvl_term = jnp.uint32(GOLDEN) * (lvl + jnp.uint32(1))
    seed = fmix32(ids.astype(jnp.uint32) + lvl_term)
    return fmix32(seed ^ (counters.astype(jnp.uint32) * jnp.uint32(KMULT)))


def next_asura(
    ids,
    counters,
    top_level: int,
    s_log2: int,
    emit_depth: bool = False,
    active=None,
):
    """One ASURA number per lane as (k:int32, frac32:uint32, new_counters).

    counters: (top_level + 1, ...) uint32; row r is the counter of level
    ``top_level - r`` (row 0 = top).  ``ids`` may be any shape (1-D batch
    here, (rows, 128) tiles in the Pallas kernels); counters carry one
    leading level axis over it.

    Lazy-depth ladder (DESIGN.md section 3.4): every lane starts at
    ``top_level`` and lanes descend in lockstep one level per iteration, so
    all still-consulting lanes sit at the SAME level and the ladder is a
    ``lax.while_loop`` over a scalar level that exits as soon as no lane is
    still consulting -- expected 2 iterations (the descend test is a coin
    flip), not ``top_level + 1``.  Counter rows are read/updated through
    dynamic indexing at the one consulted level, so the loop-carried state
    is the counter array plus O(1) scalars instead of one rebuilt counter
    tensor per unrolled level.  Draw order and counter ticks are
    bit-identical to the unrolled ladder and the scalar oracle (tested).

    ``emit_depth=True`` additionally returns the per-lane consulted depth
    (``top_level - exit_level + 1``, int32) as a fourth output -- the obs
    device plane's ladder-depth histogram source.  The k/frac/counter
    stream is bit-identical either way (the extra ``where`` only feeds
    the depth output; tested in tests/test_obs.py).

    ``active`` (optional bool mask over ``ids``) gates the counter TICK
    only: inactive lanes still draw (their k/f outputs are garbage the
    caller ignores) but leave their counters frozen.  The replica loop
    uses this to keep satisfied lanes' lockstep dead draws out of the
    derived depth histogram -- the gate rides the existing one-row
    counter update, so it costs O(batch) per consulted level instead of
    an O(levels x batch) select per draw.  Active lanes' streams are
    unaffected (lanes never read each other's counters).
    """
    shape = ids.shape
    # NOTE: constants below are created inside the traced function (not
    # module-level jnp arrays) so this helper can run inside Pallas kernels.

    def cond(state):
        consult = state[1]
        return jnp.any(consult)

    def body(state):
        if emit_depth:
            level, consult, out_k, out_f, out_d, ctrs = state
        else:
            level, consult, out_k, out_f, ctrs = state
        row = top_level - level
        ctr = jax.lax.dynamic_index_in_dim(ctrs, row, 0, keepdims=False)
        h = draw_u32(ids, level, ctr)
        tick = consult if active is None else consult & active
        ctrs = jax.lax.dynamic_update_index_in_dim(
            ctrs, ctr + tick.astype(jnp.uint32), row, 0
        )
        descend = consult & (level > 0) & ((h & jnp.uint32(0x80000000)) == 0)
        emit = consult & ~descend
        lvl = level.astype(jnp.uint32)
        k = (h >> (jnp.uint32(32 - s_log2) - lvl)).astype(jnp.int32)
        f = h << (jnp.uint32(s_log2) + lvl)
        out_k = jnp.where(emit, k, out_k)
        out_f = jnp.where(emit, f, out_f)
        if emit_depth:
            out_d = jnp.where(emit, jnp.int32(top_level) - level + 1, out_d)
            return level - 1, descend, out_k, out_f, out_d, ctrs
        return level - 1, descend, out_k, out_f, ctrs

    state = (
        jnp.int32(top_level),
        jnp.ones(shape, dtype=bool),
        jnp.zeros(shape, dtype=jnp.int32),
        jnp.zeros(shape, dtype=jnp.uint32),
        *((jnp.zeros(shape, dtype=jnp.int32),) if emit_depth else ()),
        counters,
    )
    out = jax.lax.while_loop(cond, body, state)
    if emit_depth:
        _, _, out_k, out_f, out_d, counters = out
        return out_k, out_f, counters, out_d
    _, _, out_k, out_f, counters = out
    return out_k, out_f, counters


def mul32_wide(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Full 32x32 -> 64 bit product as (hi, lo) uint32 pairs.

    TPUs have no native u64, so the 64-bit product is assembled from 16-bit
    limbs; ``t`` (the carry column) fits uint32 by construction.
    """
    a = a.astype(jnp.uint32)
    b = b.astype(jnp.uint32)
    m16 = jnp.uint32(0xFFFF)
    a_lo, a_hi = a & m16, a >> jnp.uint32(16)
    b_lo, b_hi = b & m16, b >> jnp.uint32(16)
    ll = a_lo * b_lo
    lh = a_lo * b_hi
    hl = a_hi * b_lo
    hh = a_hi * b_hi
    t = (ll >> jnp.uint32(16)) + (lh & m16) + (hl & m16)
    lo = (t << jnp.uint32(16)) | (ll & m16)
    hi = hh + (lh >> jnp.uint32(16)) + (hl >> jnp.uint32(16)) + (t >> jnp.uint32(16))
    return hi, lo


def resolve_tail_dev(
    ids: jax.Array,
    segs: jax.Array,
    cum_hi: jax.Array,
    cum_lo: jax.Array,
    top_level: int,
) -> jax.Array:
    """Device-resident non-converged-tail fallback (DESIGN.md section 3.2).

    Bit-identical to ``repro.core.asura.resolve_tail_np``: lanes with
    ``segs < 0`` get one raw draw h at level ``top_level + 1`` (counter 0),
    scaled by the exact total occupied mass T via
    ``u = h*(T>>32) + ((h*(T&0xFFFFFFFF))>>32)`` (the 95-bit product split
    through ``mul32_wide``), then mapped to the segment whose inclusive u64
    cumsum first exceeds u -- a branchless per-lane binary search over the
    (cum_hi, cum_lo) halves, so no u64 and no host round trip.  Trailing
    zero-length padding (cumsum == T > u) never wins.  The whole fallback is
    gated behind ``lax.cond`` on any lane missing, so the p < 2**-53 common
    case pays one reduction only.  Runs in plain jit and inside Pallas
    kernels (all constants are trace-time).
    """
    n_pad = cum_hi.shape[0]
    shape = ids.shape
    miss = segs < 0

    def tail(_):
        h = draw_u32(ids, top_level + 1, jnp.zeros(shape, dtype=jnp.uint32))
        t_hi = cum_hi[n_pad - 1]
        t_lo = cum_lo[n_pad - 1]
        p1_hi, p1_lo = mul32_wide(h, t_hi)
        p2_hi, _ = mul32_wide(h, t_lo)
        u_lo = p1_lo + p2_hi
        u_hi = p1_hi + (u_lo < p1_lo).astype(jnp.uint32)
        # searchsorted(cum, u, side="right"): first index with cum[idx] > u.
        lo = jnp.zeros(shape, dtype=jnp.int32)
        hi = jnp.full(shape, n_pad, dtype=jnp.int32)
        for _step in range(max(1, int(n_pad).bit_length())):
            active = lo < hi
            mid = jnp.minimum((lo + hi) >> 1, n_pad - 1)
            c_hi = jnp.take(cum_hi, mid.reshape(-1), axis=0).reshape(shape)
            c_lo = jnp.take(cum_lo, mid.reshape(-1), axis=0).reshape(shape)
            le = (c_hi < u_hi) | ((c_hi == u_hi) & (c_lo <= u_lo))  # cum<=u
            lo = jnp.where(active & le, mid + 1, lo)
            hi = jnp.where(active & ~le, mid, hi)
        return lo

    tail_seg = jax.lax.cond(
        jnp.any(miss), tail, lambda _: jnp.zeros(shape, dtype=jnp.int32), None
    )
    return jnp.where(miss, tail_seg, segs)


# Row width of ``_prefix_count``'s blocked scan.
_SCAN_ROW = 1024


def _prefix_count(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a 1-D int32 array, as a blocked scan: rows
    of ``_SCAN_ROW``, then the row totals, recursively.  One ``cumsum``
    over the whole array lowers on the TPU to a reduce-window as wide as
    the array, whose compile time grows with it (~20 s at 2^20 lanes,
    against ~2 s blocked)."""
    n = x.shape[0]
    if n <= _SCAN_ROW:
        return jnp.cumsum(x)
    rows = jnp.cumsum(jnp.pad(x, (0, -n % _SCAN_ROW)).reshape(-1, _SCAN_ROW), axis=1)
    before = _prefix_count(rows[:, -1]) - rows[:, -1]
    return (rows + before[:, None]).reshape(-1)[:n]


def _live_slots(live: jax.Array, w: int) -> jax.Array:
    """(w,) int32 lane indices of the live lanes, in lane order (prefix
    count scatter).  Slots past the live count hold lane 0: a duplicate
    lane recomputes lane 0's exact draws.  Live lanes past ``w`` are
    dropped, so callers compact only once the live lanes fit."""
    pos = _prefix_count(live.astype(jnp.int32)) - 1
    slot = jnp.where(live, pos, w)  # settled lanes -> out of range, dropped
    return (
        jnp.zeros((w,), dtype=jnp.int32)
        .at[slot]
        .set(jnp.arange(live.shape[0], dtype=jnp.int32), mode="drop")
    )


# Full-width draws before the bulk place loop compacts its stragglers
# (below).  After p draws a lane survives with probability ~(1-fill)^p,
# so 4 leaves ~6% of lanes at the half-full tables every post-add
# version has -- inside the batch/8 straggler block with 2x margin.
_PREFIX_DRAWS = 4


@functools.partial(jax.jit, static_argnames=("top_level", "s_log2", "max_draws"))
def place_ref(
    ids: jax.Array,
    len32: jax.Array,
    *,
    top_level: int,
    s_log2: int = 1,
    max_draws: int = 128,
) -> jax.Array:
    """Batched STEP 2 -> int32 segment numbers (-1 if not converged).

    ids: (batch,) uint32 datum ids.
    len32: (n_segs,) uint32 canonical segment lengths (round(len * 2**32)).

    Draw-loop schedule: a lockstep while_loop pays every draw over the
    FULL batch even though per-lane draw counts are geometric (E[draws]
    = 1/fill); on a half-full table (every post-add/remove version) the
    all-lanes-converged exit trails the typical lane by ~10 draws, so
    the naive loop does ~9x the useful hash work.  After
    ``_PREFIX_DRAWS`` full-width draws the surviving lanes are compacted
    (cumsum scatter) into a ``batch/8`` straggler block that finishes
    narrow; a guard falls back to the full-width loop if the stragglers
    ever overflow the block (pathologically sparse tables).  Per-lane
    draw sequences are pure functions of the lane's id, so compaction
    changes nothing a lane computes -- results are bit-identical to the
    uncompacted loop (tested against the scalar oracle).
    """
    ids = ids.astype(jnp.uint32)
    n_segs = len32.shape[0]
    batch = ids.shape[0]

    def cond(state):
        i, _, _, done = state
        return (i < max_draws) & ~jnp.all(done)

    def mk_body(lane_ids):
        def body(state):
            i, counters, result, done = state
            k, f, counters = next_asura(lane_ids, counters, top_level, s_log2)
            k_safe = jnp.minimum(k, n_segs - 1)
            hit = (~done) & (k < n_segs) & (f < len32[k_safe])
            result = jnp.where(hit, k, result)
            return i + 1, counters, result, done | hit

        return body

    body = mk_body(ids)
    state = (
        0,
        jnp.zeros((top_level + 1, batch), dtype=jnp.uint32),
        jnp.full((batch,), -1, dtype=jnp.int32),
        jnp.zeros((batch,), dtype=bool),
    )
    w = batch >> 3
    if w < 64 or max_draws <= _PREFIX_DRAWS:
        # small batches: compaction overhead beats the tail waste
        _, _, result, _ = jax.lax.while_loop(cond, body, state)
        return result

    def prefix_cond(state):
        i, _, _, done = state
        return (i < _PREFIX_DRAWS) & ~jnp.all(done)

    state = jax.lax.while_loop(prefix_cond, body, state)
    n_live = jnp.sum((~state[3]).astype(jnp.int32))

    def narrow(state):
        i, counters, result, done = state
        idx = _live_slots(~done, w)
        # duplicates of lane 0 write lane 0's own result: value-unique
        sub = (i, counters[:, idx], result[idx], done[idx])
        _, _, sub_result, _ = jax.lax.while_loop(cond, mk_body(ids[idx]), sub)
        return result.at[idx].set(sub_result)

    def full(state):
        _, _, result, _ = jax.lax.while_loop(cond, body, state)
        return result

    return jax.lax.cond(n_live <= w, narrow, full, state)


@functools.partial(
    jax.jit, static_argnames=("top_level", "s_log2", "max_draws", "n_replicas")
)
def addition_numbers_ref(
    ids: jax.Array,
    len32: jax.Array,
    node_of: jax.Array,
    *,
    top_level: int,
    s_log2: int = 1,
    max_draws: int = 128,
    n_replicas: int = 1,
) -> jax.Array:
    """Device-resident section 2.D ADDITION NUMBER -> (batch,) int32.

    The device twin of ``repro.core.asura.addition_numbers_batch``: every
    lane runs the bounded replica trace on device, tracking the minimum
    *unused* anterior ASURA number as an exact ``(k, frac32)``
    lexicographic pair (no u64 needed, so it runs on TPUs).  Where the NumPy batch falls back to the exact scalar
    oracle (non-convergence, or the rare range-extension case where every
    anterior number was used), this returns ``-1`` -- checking would force a
    host sync.  ``-1`` means "unknown: treat as a candidate", which keeps
    the AN <= f prefilter sound (DESIGN.md sections 7, 8); lanes with a
    definite result are bit-identical to the NumPy batch (tested).
    """
    ids = ids.astype(jnp.uint32)
    n_segs = len32.shape[0]
    batch = ids.shape[0]
    R = n_replicas
    NO_K = jnp.int32(0x7FFFFFFF)  # above any reachable k (k < 2**(s+top))

    def cond(state):
        i, _, _, found, _, _ = state
        return (i < max_draws * max(1, R)) & ~jnp.all(found >= R)

    def body(state):
        i, counters, nodes, found, min_k, min_f = state
        k, f, counters = next_asura(ids, counters, top_level, s_log2)
        k_safe = jnp.minimum(k, n_segs - 1)
        hit = (k < n_segs) & (f < len32[k_safe])
        node_k = node_of[k_safe]
        dup = jnp.zeros((batch,), dtype=bool)
        for r in range(R):
            dup |= (nodes[r] >= 0) & (nodes[r] == node_k)
        active = found < R
        used = active & hit & ~dup
        unused = active & ~used
        better = unused & ((k < min_k) | ((k == min_k) & (f < min_f)))
        min_k = jnp.where(better, k, min_k)
        min_f = jnp.where(better, f, min_f)
        nodes = jnp.stack(
            [jnp.where(used & (found == r), node_k, nodes[r]) for r in range(R)]
        )
        return i + 1, counters, nodes, found + used.astype(jnp.int32), min_k, min_f

    counters0 = jnp.zeros((top_level + 1, batch), dtype=jnp.uint32)
    nodes0 = jnp.full((R, batch), -1, dtype=jnp.int32)
    found0 = jnp.zeros((batch,), dtype=jnp.int32)
    min_k0 = jnp.full((batch,), NO_K, dtype=jnp.int32)
    min_f0 = jnp.zeros((batch,), dtype=jnp.uint32)
    _, _, _, found, min_k, _ = jax.lax.while_loop(
        cond, body, (0, counters0, nodes0, found0, min_k0, min_f0)
    )
    return jnp.where((found >= R) & (min_k != NO_K), min_k, jnp.int32(-1))


def _u32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _i32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def replica_ladder(
    ids: jax.Array,
    len32: jax.Array,
    node_of: jax.Array,
    *,
    top_level: int,
    s_log2: int,
    max_draws: int,
    n_replicas: int,
    emit_stats: bool = False,
):
    """The section 5.A replica draw loop -> ``(segs, nodes, counters)``.

    ``segs`` and ``nodes`` are (R, batch) int32 (-1 where a lane did not
    converge within ``max_draws * R`` draws); ``nodes`` is the carried
    ``node_of[segs]``, so callers that want node ids read it and gather
    nothing.  ``counters`` is the (top_level + 1, batch) draw-counter
    array; with ``emit_stats`` on, satisfied lanes stop ticking, so row
    ``r`` counts the draws of depth >= r + 1 each lane issued while still
    seeking replicas, and row 0 (every draw consults the top level) is the
    number of draws the lane needed.  Without stats the counters of
    satisfied lanes keep ticking and mean nothing.

    Straggler block (DESIGN.md section 15.3): per-lane draw counts are
    geometric, so a lockstep loop keeps drawing -- and gathering both
    tables -- over the full batch until its slowest lane is done.  The
    full-width loop runs only while the live lanes (``found < R``)
    outnumber ``w = batch // 8``; the live lanes are then compacted
    (``_live_slots``, unused slots repeat lane 0) into a ``w``-wide block
    that finishes narrow, and the block's results are scattered back.
    The block starts only once the live lanes fit in it, so it cannot
    overflow (when the draw cap ends the wide loop first, the block runs
    no draw and writes back what it read; under 8 lanes the block is
    empty and the wide loop is the whole ladder).  A lane's draws are a
    function of its id and its own counters alone, so the results (and,
    with stats on, the counters) are bit-identical to the plain loop.
    """
    ids = ids.astype(jnp.uint32)
    n_segs = len32.shape[0]
    batch = ids.shape[0]
    R = n_replicas
    cap = max_draws * max(1, R)

    def mk_body(lane_ids):
        def body(state):
            i, counters, segs, nodes, found = state
            # With stats on, satisfied lanes stop ticking their counters:
            # the lockstep dead draws they keep issuing depend on the
            # slowest lane sharing their loop, so counting them would make
            # the derived histograms depend on how a stream is sharded or
            # compacted.  A frozen lane is inert for placement either way
            # (``take`` requires ``found < R``).
            k, f, counters = next_asura(
                lane_ids,
                counters,
                top_level,
                s_log2,
                active=(found < R) if emit_stats else None,
            )
            k_safe = jnp.minimum(k, n_segs - 1)
            hit = (found < R) & (k < n_segs) & (f < len32[k_safe])
            node_k = node_of[k_safe]
            dup = jnp.zeros(lane_ids.shape, dtype=bool)
            for r in range(R):
                dup |= (nodes[r] >= 0) & (nodes[r] == node_k)
            take = hit & ~dup
            segs = jnp.stack(
                [jnp.where(take & (found == r), k, segs[r]) for r in range(R)]
            )
            nodes = jnp.stack(
                [jnp.where(take & (found == r), node_k, nodes[r]) for r in range(R)]
            )
            found = found + take.astype(jnp.int32)
            return i + 1, counters, segs, nodes, found

        return body

    def cond(state):
        i, found = state[0], state[4]
        return (i < cap) & ~jnp.all(found >= R)

    state = (
        0,
        jnp.zeros((top_level + 1, batch), dtype=jnp.uint32),
        jnp.full((R, batch), -1, dtype=jnp.int32),
        jnp.full((R, batch), -1, dtype=jnp.int32),
        jnp.zeros((batch,), dtype=jnp.int32),
    )
    w = batch >> 3

    def wide_cond(state):
        i, found = state[0], state[4]
        return (i < cap) & (jnp.sum((found < R).astype(jnp.int32)) > w)

    i, counters, segs, nodes, found = jax.lax.while_loop(
        wide_cond, mk_body(ids), state
    )
    live = found < R
    n_live = jnp.sum(live.astype(jnp.int32))
    idx = _live_slots(live, w)
    # one gather of every lane's whole state, as columns of one stack
    rows = jnp.concatenate(
        [ids[None], counters, _u32(segs), _u32(nodes), _u32(found)[None]]
    )[:, idx]
    c1 = top_level + 2
    sub_ids, sub_found = rows[0], _i32(rows[-1])
    sub = (i, rows[1:c1], _i32(rows[c1 : c1 + R]), _i32(rows[c1 + R : -1]), sub_found)
    _, sub_ctr, sub_segs, sub_nodes, _ = jax.lax.while_loop(
        cond, mk_body(sub_ids), sub
    )
    # unused slots (lane 0's copies) write nothing back
    dst = jnp.where(jnp.arange(w, dtype=jnp.int32) < n_live, idx, batch)
    back = [_u32(segs), _u32(nodes)]
    sub_back = [_u32(sub_segs), _u32(sub_nodes)]
    if emit_stats:
        back.append(counters)
        sub_back.append(sub_ctr)
    merged = (
        jnp.concatenate(back)
        .at[:, dst]
        .set(jnp.concatenate(sub_back), mode="drop")
    )
    segs, nodes = _i32(merged[:R]), _i32(merged[R : 2 * R])
    if emit_stats:
        counters = merged[2 * R :]
    return segs, nodes, counters


def depth_histogram(counters: jax.Array, top_level: int) -> jax.Array:
    """(DEPTH_BINS,) uint32 consulted-ladder-depth histogram, derived from
    gated draw counters: row ``r`` ticks once for every draw of depth >=
    r + 1, so the histogram is the first difference of the row sums."""
    # cnt[r] = draws of depth >= r + 1; hist[d] = cnt[d-1] - cnt[d]
    cnt = jnp.sum(counters, axis=1, dtype=jnp.uint32)
    cnt = jnp.concatenate([cnt, jnp.zeros((1,), dtype=jnp.uint32)])
    dh = jnp.zeros((DEPTH_BINS,), dtype=jnp.uint32)
    return dh.at[1 : top_level + 2].set(cnt[:-1] - cnt[1:])


def draws_histogram(counters: jax.Array) -> jax.Array:
    """(DRAW_BINS,) uint32 histogram of the draws each lane needed, from
    gated draw counters (row 0: every draw consults the top level)."""
    d = jnp.minimum(counters[0], DRAW_BINS - 1).astype(jnp.int32)
    return jnp.bincount(d, length=DRAW_BINS).astype(jnp.uint32)


@functools.partial(
    jax.jit,
    static_argnames=("top_level", "s_log2", "max_draws", "n_replicas", "emit_stats"),
)
def place_replicas_ref(
    ids: jax.Array,
    len32: jax.Array,
    node_of: jax.Array,
    *,
    top_level: int,
    s_log2: int = 1,
    max_draws: int = 128,
    n_replicas: int = 1,
    emit_stats: bool = False,
):
    """Batched section 5.A replication -> (batch, R) int32 segment numbers.

    First column is the primary; the R draws hit distinct *nodes* (checked
    against the nodes of already-picked replicas, carried in-register so the
    dup test costs no extra table gather).  -1 marks lanes that did not
    converge (the wrapper raises).  Bit-identical to
    ``repro.core.asura.place_replicas_scalar`` lane-by-lane (tested).  The
    loop itself is ``replica_ladder``.

    ``emit_stats=True`` returns ``(segs, depth_hist)`` where ``depth_hist``
    is the (DEPTH_BINS,) uint32 consulted-ladder-depth histogram over every
    draw each lane issued while still seeking replicas -- the obs device
    plane's view of how much ladder work the batch cost.  It is DERIVED
    from the final draw counters (``depth_histogram``) rather than
    accumulated per draw: one reduction after the loop plus a lane-liveness
    gate folded into the existing one-row counter tick (the <= 1.05x
    overhead ceiling rules out both an in-loop scatter and a full-array
    counter select).  Satisfied lanes' counters freeze so the histogram is
    a function of each lane's id alone -- summing per-shard histograms of
    any partition of a batch is bit-identical to the unsharded histogram
    (the sharded snapshot merge relies on this).  The placement stream is
    bit-identical either way.
    """
    segs, _, counters = replica_ladder(
        ids,
        len32,
        node_of,
        top_level=top_level,
        s_log2=s_log2,
        max_draws=max_draws,
        n_replicas=n_replicas,
        emit_stats=emit_stats,
    )
    if emit_stats:
        return segs.T, depth_histogram(counters, top_level)
    return segs.T
