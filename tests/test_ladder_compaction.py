"""The replica ladder's straggler block (DESIGN.md section 15.3).

``replica_ladder`` runs its full-width draw loop only while the lanes still
seeking replicas outnumber ``batch // 8``, then compacts them into a block
that finishes narrow.  A lane's draws are a function of its id and its own
counters, so the contract is BIT-IDENTITY with the plain lockstep loop:

  * segments, carried nodes and (with ``emit_stats``) draw counters equal
    a plain lockstep copy of the loop kept in this file, and the scalar
    oracle ``place_replicas_scalar`` lane by lane; across R, top levels
    0-19, batches from 1 lane (an empty block) to 65,536, a half-full
    table, and a draw cap small enough that lanes return -1;
  * the serving superstep, the migrating route and the replica diff give
    what they gave with the plain loop;
  * the ``asura.ladder_draws`` histogram sums to the batch, equals the
    draws of a NumPy loop per lane, merges exactly over any partition,
    and tells where a 65,536-lane batch's block engages.
"""

from __future__ import annotations

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.kernels.ops as ops
from repro.core.asura import (
    DEFAULT_PARAMS,
    _next_asura_batch,
    lengths_to_u32,
    place_replicas_scalar,
)
from repro.kernels.ops import _place_replicas_fused_ref, node_table_prep, table_prep
from repro.kernels.ref import (
    DEPTH_BINS,
    DRAW_BINS,
    _prefix_count,
    depth_histogram,
    draws_histogram,
    next_asura,
    place_replicas_ref,
    replica_ladder,
)

S_LOG2 = DEFAULT_PARAMS.s_log2
MAX_DRAWS = DEFAULT_PARAMS.max_draws


def plain_ladder(
    ids, len32, node_of, *, top_level, s_log2, max_draws, n_replicas,
    emit_stats=False,
):
    """The lockstep replica loop as it stood before the straggler block:
    every draw over the full batch until the slowest lane is done, and the
    node ids gathered from the segments afterwards."""
    ids = ids.astype(jnp.uint32)
    n_segs = len32.shape[0]
    batch = ids.shape[0]
    R = n_replicas

    def cond(state):
        i, found = state[0], state[4]
        return (i < max_draws * max(1, R)) & ~jnp.all(found >= R)

    def body(state):
        i, counters, segs, nodes, found = state
        k, f, counters = next_asura(
            ids, counters, top_level, s_log2,
            active=(found < R) if emit_stats else None,
        )
        k_safe = jnp.minimum(k, n_segs - 1)
        hit = (found < R) & (k < n_segs) & (f < len32[k_safe])
        node_k = node_of[k_safe]
        dup = jnp.zeros((batch,), dtype=bool)
        for r in range(R):
            dup |= (nodes[r] >= 0) & (nodes[r] == node_k)
        take = hit & ~dup
        segs = jnp.stack(
            [jnp.where(take & (found == r), k, segs[r]) for r in range(R)]
        )
        nodes = jnp.stack(
            [jnp.where(take & (found == r), node_k, nodes[r]) for r in range(R)]
        )
        return i + 1, counters, segs, nodes, found + take.astype(jnp.int32)

    _, counters, segs, _, _ = jax.lax.while_loop(
        cond,
        body,
        (
            0,
            jnp.zeros((top_level + 1, batch), dtype=jnp.uint32),
            jnp.full((R, batch), -1, dtype=jnp.int32),
            jnp.full((R, batch), -1, dtype=jnp.int32),
            jnp.zeros((batch,), dtype=jnp.int32),
        ),
    )
    nodes = jnp.where(segs >= 0, jnp.take(node_of, jnp.maximum(segs, 0)), -1)
    return segs, nodes, counters


_STATICS = ("top_level", "s_log2", "max_draws", "n_replicas", "emit_stats")
_compact = jax.jit(replica_ladder, static_argnames=_STATICS)
_plain = jax.jit(plain_ladder, static_argnames=_STATICS)


# ---------------------------------------------------------------------------
# Tables: a given top level, full or half full
# ---------------------------------------------------------------------------

# number of segments whose upper bound sets each top level (s_log2 = 1)
N_SEGS = {0: 2, 5: 40, 10: 1_792, 19: 600_000}


def _table(top_level: int, fill: float = 0.625, n_nodes: int = 1_024):
    """Segment lengths averaging ``fill`` (every other segment a hole when
    ``fill`` is 0.5), nodes dealt round-robin, and their device tables."""
    n = N_SEGS[top_level]
    if fill == 0.5:
        lengths = np.where(np.arange(n) % 2 == 0, 0.999, 0.0)
        lengths[-1] = 0.999
    else:
        lengths = 0.5 + 0.25 * ((np.arange(n) * 7) % 5) / 4
    node = np.arange(n) % min(n_nodes, n)
    len32, top = table_prep(lengths, DEFAULT_PARAMS)
    assert top == top_level
    return lengths, node, len32, node_table_prep(node)


def _ids(batch: int, salt: int = 0):
    x = (np.arange(batch, dtype=np.uint64) + salt) * np.uint64(2654435761)
    return jnp.asarray((x % np.uint64(2**32)).astype(np.uint32))


def _both(ids, len32, node_of, **kw):
    a = [np.asarray(x) for x in _compact(ids, len32, node_of, **kw)]
    b = [np.asarray(x) for x in _plain(ids, len32, node_of, **kw)]
    return a, b


def _assert_scalar(segs, ids, lengths, node, R, lanes):
    ids = np.asarray(ids)
    for b in lanes:
        want = place_replicas_scalar(int(ids[b]), lengths, node, R)
        assert list(segs[:, b]) == want, f"lane {b}"


# ---------------------------------------------------------------------------
# Bit-identity of the compacted ladder
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("top_level", [0, 5, 10, 19])
@pytest.mark.parametrize("R", [1, 2, 3])
@pytest.mark.parametrize("batch", [100, 511, 512, 65_536])
def test_compacted_matches_plain_and_scalar(top_level, R, batch):
    lengths, node, len32, node_of = _table(top_level)
    ids = _ids(batch)
    kw = dict(top_level=top_level, s_log2=S_LOG2, max_draws=MAX_DRAWS, n_replicas=R)
    (segs, nodes, _), (p_segs, p_nodes, _) = _both(ids, len32, node_of, **kw)
    assert np.array_equal(segs, p_segs)
    assert np.array_equal(nodes, p_nodes)
    if top_level == 0 and R == 3:
        # two segments, two nodes: a third distinct node never turns up
        assert (segs[2] == -1).all()
        return
    assert (segs >= 0).all()
    _assert_scalar(segs, ids, lengths, node, R, range(0, batch, max(1, batch // 24)))


@pytest.mark.parametrize("n", [1, 1_024, 1_025, 70_001, (1 << 20) + 5])
def test_blocked_prefix_count_is_cumsum(n):
    """The compaction's prefix count scans rows of 1,024 and then the row
    totals, recursively: padding, one level and two levels of rows."""
    x = (np.arange(n) * 2654435761 % 7 < 3).astype(np.int32)
    got = np.asarray(jax.jit(_prefix_count)(jnp.asarray(x)))
    assert np.array_equal(got, np.cumsum(x))


@pytest.mark.parametrize("batch", [1, 7, 9])
def test_empty_and_one_lane_blocks(batch):
    """Under 8 lanes the block is empty and the wide loop runs to the end;
    at 9 lanes it is one lane wide.  Both match the plain loop, counters
    included."""
    top_level, R = 10, 3
    lengths, node, len32, node_of = _table(top_level)
    ids = _ids(batch, salt=29)
    kw = dict(
        top_level=top_level, s_log2=S_LOG2, max_draws=MAX_DRAWS, n_replicas=R,
        emit_stats=True,
    )
    (segs, nodes, ctr), (p_segs, p_nodes, p_ctr) = _both(ids, len32, node_of, **kw)
    assert np.array_equal(segs, p_segs)
    assert np.array_equal(nodes, p_nodes)
    assert np.array_equal(ctr, p_ctr)
    assert (segs >= 0).all()
    _assert_scalar(segs, ids, lengths, node, R, range(batch))


@pytest.mark.parametrize("top_level", [5, 10])
@pytest.mark.parametrize("max_draws", [2, 4, MAX_DRAWS])
def test_half_full_table_and_draw_cap(top_level, max_draws):
    """At caps of 6 and 12 draws some lanes return -1.  At 6 the wide loop
    meets the cap before the block engages; at 12 on the top-10 table the
    block engages after 10 draws and 308 of its lanes meet the cap inside
    it.  Counters and the depth histogram match with stats on."""
    R, batch = 3, 4_096
    lengths, node, len32, node_of = _table(top_level, fill=0.5)
    ids = _ids(batch, salt=17)
    kw = dict(
        top_level=top_level, s_log2=S_LOG2, max_draws=max_draws, n_replicas=R,
        emit_stats=True,
    )
    (segs, nodes, ctr), (p_segs, p_nodes, p_ctr) = _both(ids, len32, node_of, **kw)
    assert np.array_equal(segs, p_segs)
    assert np.array_equal(nodes, p_nodes)
    assert np.array_equal(ctr, p_ctr)
    live = (segs < 0).any(axis=0)
    if max_draws < MAX_DRAWS:
        assert live.any(), "cap too loose: no lane returned -1"
        assert (ctr[0][live] == max_draws * R).all()
    else:
        assert not live.any()
    done = np.nonzero(~live)[0]
    _assert_scalar(segs, ids, lengths, node, R, done[:: max(1, len(done) // 24)])
    want = np.asarray(depth_histogram(jnp.asarray(p_ctr), top_level))
    got = np.asarray(
        place_replicas_ref(ids, len32, node_of, **kw)[1]
    )
    assert np.array_equal(got, want)


@pytest.mark.parametrize("batch", [511, 65_536])
def test_emit_stats_depth_histogram_identical(batch):
    top_level, R = 10, 3
    _, _, len32, node_of = _table(top_level)
    ids = _ids(batch, salt=5)
    kw = dict(
        top_level=top_level, s_log2=S_LOG2, max_draws=MAX_DRAWS, n_replicas=R,
        emit_stats=True,
    )
    (segs, _, ctr), (p_segs, _, p_ctr) = _both(ids, len32, node_of, **kw)
    assert np.array_equal(segs, p_segs)
    assert np.array_equal(ctr, p_ctr)
    got = place_replicas_ref(ids, len32, node_of, **kw)
    plain = place_replicas_ref(
        ids, len32, node_of, **{**kw, "emit_stats": False}
    )
    assert np.array_equal(np.asarray(got[0]), np.asarray(plain))
    assert np.array_equal(
        np.asarray(got[1]),
        np.asarray(depth_histogram(jnp.asarray(p_ctr), top_level)),
    )
    assert np.asarray(got[1]).shape == (DEPTH_BINS,)


def test_fused_emit_nodes_reads_the_carried_nodes():
    """``emit_nodes=True`` returns the ladder's carried nodes: the same as
    gathering ``node_of`` at the segments, -1 where a lane did not
    converge."""
    top_level, R = 5, 3
    _, _, len32, node_of = _table(top_level, fill=0.5)
    ids = _ids(2_048, salt=3)
    kw = dict(top_level=top_level, s_log2=S_LOG2, n_replicas=R)
    for max_draws in (2, MAX_DRAWS):
        segs = _place_replicas_fused_ref(
            ids, len32, node_of, max_draws=max_draws, emit_nodes=False, **kw
        )
        nodes = _place_replicas_fused_ref(
            ids, len32, node_of, max_draws=max_draws, emit_nodes=True, **kw
        )
        segs, nodes = np.asarray(segs), np.asarray(nodes)
        want = np.where(segs >= 0, np.asarray(node_of)[np.maximum(segs, 0)], -1)
        assert np.array_equal(nodes, want)


# ---------------------------------------------------------------------------
# The callers give what they gave with the plain loop
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _plain_loop():
    """Route every fused replica placement through ``plain_ladder``;
    yields the list of its traces, so a test can see that it ran."""
    traced = []

    def ladder(*args, **kw):
        traced.append(kw["top_level"])
        return plain_ladder(*args, **kw)

    jax.clear_caches()
    saved = ops.replica_ladder
    ops.replica_ladder = ladder
    try:
        yield traced
    finally:
        ops.replica_ladder = saved
        jax.clear_caches()


def _superstep_chosen():
    from repro.core import PlacementEngine, make_uniform_cluster
    from repro.serve import RequestStreamDriver

    eng = PlacementEngine(make_uniform_cluster(24), backend="ref")
    d = RequestStreamDriver(
        eng, batch=256, n_keys=1 << 12, n_replicas=3, policy="pow2", seed=9
    )
    return np.asarray(d.superstep(4)), np.asarray(d.counts)


def _migrating_chosen():
    from repro.core import PlacementEngine, make_cluster
    from repro.migrate import LiveMigration, MigrationPlanner
    from repro.serve import RequestStreamDriver

    cluster = make_cluster([0.5 + (i % 5) / 4 for i in range(32)])
    engine = PlacementEngine(cluster, backend="ref")
    records = np.arange(1 << 11, dtype=np.uint32)
    engine.artifact()
    v0 = cluster.version
    segs = cluster.add_node(32, 1.0)
    plan = MigrationPlanner(engine).plan_replicas(
        records, v0, cluster.version, 3, max_new_seg=max(segs)
    )
    mig = LiveMigration.from_plan(engine, plan, egress=8, ingress=8)
    driver = RequestStreamDriver(
        engine, batch=512, n_keys=1, law="uniform", n_replicas=3,
        policy="pow2", seed=2, n_bins=33,
    )
    rng = np.random.default_rng(8)
    out = []
    for _ in range(3):
        keys = rng.integers(0, len(records), 700).astype(np.uint32)
        out.append(np.asarray(driver.route_batch(keys, migration=mig)))
        mig.round_block(1)
    return np.concatenate(out)


def _replica_diff():
    from repro.core import make_cluster
    from repro.kernels.ops import diff_replicas_on_tables_device

    cluster = make_cluster([0.5 + (i % 3) / 2 for i in range(40)])
    a = table_prep(cluster.seg_lengths(), DEFAULT_PARAMS)
    na = node_table_prep(cluster.seg_to_node())
    cluster.remove_node(7)
    b = table_prep(cluster.seg_lengths(), DEFAULT_PARAMS)
    nb = node_table_prep(cluster.seg_to_node())
    out = diff_replicas_on_tables_device(
        np.arange(5_000, dtype=np.uint32), a[0], na, b[0], nb,
        top_a=a[1], top_b=b[1], n_replicas=3, use_pallas=False,
    )
    return [np.asarray(x) for x in out]


@pytest.mark.parametrize(
    "run", [_superstep_chosen, _migrating_chosen, _replica_diff],
    ids=["superstep", "route_migrating", "replica_diff"],
)
def test_callers_identical_to_plain_loop(run):
    got = run()
    with _plain_loop() as traced:
        want = run()
    assert traced, "the plain loop never ran"
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# asura.ladder_draws
# ---------------------------------------------------------------------------


def numpy_draws(ids, lengths, node, top_level, R, max_draws):
    """Per-lane draws until R distinct nodes (capped), by a NumPy loop
    over the live lanes (``_next_asura_batch`` draws)."""
    ids = np.asarray(ids, dtype=np.uint32)
    len32 = lengths_to_u32(lengths)
    n_segs = len(len32)
    batch = len(ids)
    counters = np.zeros((top_level + 1, batch), dtype=np.uint32)
    nodes = np.full((R, batch), -1, dtype=np.int64)
    found = np.zeros(batch, dtype=np.int64)
    draws = np.zeros(batch, dtype=np.int64)
    for _ in range(max_draws * R):
        live = np.nonzero(found < R)[0]
        if live.size == 0:
            break
        c = counters[:, live]
        k, f = _next_asura_batch(ids[live], c, top_level, DEFAULT_PARAMS)
        counters[:, live] = c
        draws[live] += 1
        k_safe = np.minimum(k, n_segs - 1)
        hit = (k < n_segs) & (f < len32[k_safe])
        node_k = node[k_safe]
        dup = (nodes[:, live] == node_k[None, :]).any(axis=0)
        take = live[hit & ~dup]
        nodes[found[take], take] = node[k_safe[hit & ~dup]]
        found[take] += 1
    return draws


def _stats(ids, len32, node_of, top_level, R, max_draws=MAX_DRAWS):
    _, stats = _place_replicas_fused_ref(
        ids, len32, node_of, top_level=top_level, s_log2=S_LOG2,
        max_draws=max_draws, n_replicas=R, emit_nodes=True, emit_stats=True,
    )
    stats = np.asarray(stats).astype(np.int64)
    assert stats.shape == (DEPTH_BINS + 1 + DRAW_BINS,)
    return stats[DEPTH_BINS + 1 :]


@pytest.mark.parametrize("max_draws", [4, MAX_DRAWS])
def test_ladder_draws_equals_numpy_loop(max_draws):
    top_level, R, batch = 10, 3, 4_096
    lengths, node, len32, node_of = _table(top_level, fill=0.5)
    ids = _ids(batch, salt=29)
    hist = _stats(ids, len32, node_of, top_level, R, max_draws)
    assert hist.sum() == batch
    draws = numpy_draws(ids, lengths, node, top_level, R, max_draws)
    assert np.array_equal(
        hist, np.bincount(np.minimum(draws, DRAW_BINS - 1), minlength=DRAW_BINS)
    )
    # per lane: counter row 0 of the gated ladder is the lane's draws
    _, _, ctr = _compact(
        ids, len32, node_of, top_level=top_level, s_log2=S_LOG2,
        max_draws=max_draws, n_replicas=R, emit_stats=True,
    )
    assert np.array_equal(np.asarray(ctr)[0].astype(np.int64), draws)


def test_ladder_draws_last_bin_holds_the_tail():
    counters = jnp.asarray(np.array([[0, 5, 62, 63, 64, 384]], dtype=np.uint32))
    hist = np.asarray(draws_histogram(counters))
    assert hist[0] == hist[5] == hist[62] == 1
    assert hist[DRAW_BINS - 1] == 3
    assert hist.sum() == 6


def test_ladder_draws_partition_invariant():
    """Summing the histograms of any partition of a batch gives the whole
    batch's histogram, whether or not each part compacts."""
    top_level, R = 10, 3
    _, _, len32, node_of = _table(top_level)
    ids = _ids(6_000, salt=41)
    whole = _stats(ids, len32, node_of, top_level, R)
    parts = sum(
        _stats(ids[a:b], len32, node_of, top_level, R)
        for a, b in ((0, 300), (300, 2_348), (2_348, 6_000))
    )
    assert np.array_equal(whole, parts)


def test_ladder_draws_tells_where_the_block_engages():
    """From the histogram alone: lanes live after d draws are the lanes in
    bins above d, so the block of a 65,536-lane batch engages at the first
    d with at most 8,192 live lanes and takes that many.  Checked against a
    direct count of the wide loop's draws and the block's lanes."""
    top_level, R, batch = 10, 3, 65_536
    _, _, len32, node_of = _table(top_level)
    ids = _ids(batch, salt=53)
    hist = _stats(ids, len32, node_of, top_level, R)
    assert hist.sum() == batch
    live_after = batch - np.cumsum(hist)  # live_after[d]: lanes needing > d
    engage = int(np.argmax(live_after <= batch // 8))
    occupancy = int(live_after[engage])
    assert 0 < occupancy <= batch // 8

    # direct count: run the wide loop by hand
    w = batch // 8
    counters = jnp.zeros((top_level + 1, batch), jnp.uint32)
    found = np.zeros(batch, dtype=np.int64)
    nodes = np.full((R, batch), -1, dtype=np.int64)
    node_np, len_np = np.asarray(node_of), np.asarray(len32)
    d = 0
    while (found < R).sum() > w:
        k, f, counters = next_asura(
            ids, counters, top_level, S_LOG2, active=jnp.asarray(found < R)
        )
        k, f = np.asarray(k).astype(np.int64), np.asarray(f)
        k_safe = np.minimum(k, len(len_np) - 1)
        hit = (found < R) & (k < len(len_np)) & (f < len_np[k_safe])
        node_k = node_np[k_safe]
        dup = ((nodes >= 0) & (nodes == node_k[None, :])).any(axis=0)
        take = hit & ~dup
        for r in range(R):
            nodes[r] = np.where(take & (found == r), node_k, nodes[r])
        found += take
        d += 1
    assert d == engage
    assert int((found < R).sum()) == occupancy
