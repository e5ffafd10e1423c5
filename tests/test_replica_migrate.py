"""ISSUE-5 coverage: replica-set migration (DESIGN.md section 10).

  * the fused dual-table replica-diff kernel vs an INDEPENDENT scalar
    set-diff oracle -- bit-identical per-slot (moved, src, dst, src_slot)
    for R in {1, 2, 3} at top_level in {0, 5, 19}, ref and pallas, and the
    numpy host path through ``plan_replicas``,
  * a transfer-guard + np.asarray-tripwire proof that the replica
    streaming sweep performs ZERO host syncs,
  * minimal replica mass: an add/remove event moves exactly
    ``|after \\ before|`` replicas per id, with no wrong-direction moves,
  * a churn property test (hypothesis): replica sets stay pairwise
    distinct and planned movement matches the brute-force minimal set
    diff across add/remove/resize sequences,
  * dual-version replica serving: every served set is R pairwise-distinct
    holders at every round, host and device paths agreeing, including
    through a mid-drain rollback (slot re-indexing),
  * consumers: the replica coordinator's owner tracking, the failure
    driver's replica repair, the checkpoint store's per-slot live
    add/repair with bit-identical restores every round,
  * ``remove_numbers_batch`` row-identical to the scalar trace,
  * the owner prefilter on additions: plans bit-identical to the full
    diff (fractional capacities, a top-level raise, a grown existing
    node), kept ids exactly the moved ids, the fallback on a shrink, and
    no ADDITION-NUMBER trace on the path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import AsuraCheckpointStore, CheckpointManager
from repro.core import Cluster, PlacementEngine, make_uniform_cluster
from repro.core.asura import (
    DEFAULT_PARAMS,
    align_replica_sets,
    place_replicas_batch,
    remove_numbers,
    remove_numbers_batch,
)
from repro.migrate import MigrationPlanner
from repro.runtime import ElasticCoordinator, HeartbeatTracker, MigrationDriver
from repro.serve.router import ReplicaRouter

from test_migrate import TOP_CASES, TableCluster, _mutations


def _oracle_slot_moves(before_row, after_row):
    """Independent scalar oracle: slot -> (src, dst, src_slot) via explicit
    set differences (k-th new after-slot pairs with k-th lost before-slot)."""
    before = [int(x) for x in before_row]
    after = [int(x) for x in after_row]
    lost = [(q, n) for q, n in enumerate(before) if n not in after]
    moves = {}
    k = 0
    for r, n in enumerate(after):
        if n not in before:
            q, src = lost[k]
            k += 1
            moves[r] = (src, n, q)
    assert k == len(lost)  # set differences have equal size
    return moves


def _check_against_oracle(before, after, moved, src, dst, src_slot):
    n, R = before.shape
    for b in range(n):
        moves = _oracle_slot_moves(before[b], after[b])
        for r in range(R):
            assert dst[b, r] == after[b, r]
            if r in moves:
                o_src, o_dst, o_slot = moves[r]
                assert moved[b, r]
                assert src[b, r] == o_src
                assert dst[b, r] == o_dst
                assert src_slot[b, r] == o_slot
            else:
                assert not moved[b, r]
                assert src[b, r] == after[b, r]


# ---------------------------------------------------------------------------
# Replica diff == independent scalar set-diff oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("top_level", sorted(TOP_CASES))
def test_diff_replicas_matches_oracle(backend, top_level):
    lengths, nodes = TOP_CASES[top_level]
    slow = backend == "pallas" and top_level == 19
    n_ids = 128 if slow else 512
    replica_counts = (2,) if slow else (1, 2, 3)
    ids = (np.arange(n_ids, dtype=np.uint64) * 2654435761 % (2**32)).astype(
        np.uint32
    )
    for name, new_l, new_n in _mutations(top_level):
        # R-way replication needs R distinct live nodes under BOTH versions
        live = lambda l, n: len(  # noqa: E731
            set(np.asarray(n)[np.asarray(l) > 0].tolist())
        )
        max_r = min(live(lengths, nodes), live(new_l, new_n))
        for R in replica_counts:
            if R > max_r:
                continue
            cluster = TableCluster(lengths, nodes)
            eng = PlacementEngine(cluster, backend=backend)
            eng.artifact()
            v_from = cluster.version
            cluster.mutate(new_l, new_n)
            moved, src, dst, src_slot = (
                np.asarray(a)
                for a in eng.diff_replicas_device(ids, v_from, cluster.version, R)
            )
            before = np.asarray(nodes)[place_replicas_batch(ids, lengths, nodes, R)]
            after = np.asarray(new_n)[place_replicas_batch(ids, new_l, new_n, R)]
            _check_against_oracle(
                before, after, moved, src, dst, src_slot
            )


@pytest.mark.parametrize("R", [1, 2, 3])
def test_plan_replicas_host_path_matches_oracle(R):
    """The numpy host path (place twice + align) through plan_replicas."""
    cluster = make_uniform_cluster(7)
    eng = PlacementEngine(cluster, backend="numpy")
    ids = np.arange(1200, dtype=np.uint32)
    before = eng.place_replica_nodes(ids, R)
    eng.artifact()
    v_from = cluster.version
    cluster.remove_node(3)
    cluster.add_node(40, 1.3)
    after = eng.place_replica_nodes(ids, R)
    plan = MigrationPlanner(eng).plan_replicas(ids, v_from, cluster.version, R)
    assert plan.n_replicas == R
    # reassemble per-slot rows into dense arrays and compare to the oracle
    moved = np.zeros((len(ids), R), dtype=bool)
    src = np.where(moved, 0, after).astype(np.int64)
    src_slot = np.tile(np.arange(R), (len(ids), 1))
    moved[plan.index, plan.slot] = True
    src[plan.index, plan.slot] = plan.src
    src_slot[plan.index, plan.slot] = plan.src_slot
    dst = after.copy()
    dst[plan.index, plan.slot] = plan.dst
    _check_against_oracle(before, after, moved, src, dst, src_slot)
    # minimal replica mass: exactly the set difference, id by id
    minimal = (~(after[:, :, None] == before[:, None, :]).any(axis=2)).sum()
    assert plan.n_moves == int(minimal)


@pytest.mark.parametrize("backend", ["numpy", "ref"])
def test_plan_replicas_backends_agree_and_chunking_invisible(backend):
    cluster = make_uniform_cluster(6)
    eng = PlacementEngine(cluster, backend=backend)
    ids = np.arange(2000, dtype=np.uint32)
    eng.artifact()
    v_from = cluster.version
    cluster.add_node(9, 0.8)
    planner = MigrationPlanner(eng)
    whole = planner.plan_replicas(ids, v_from, cluster.version, 3)
    chunked = planner.plan_replicas(ids, v_from, cluster.version, 3, chunk=701)
    for field in ("ids", "src", "dst", "index", "slot", "src_slot"):
        assert np.array_equal(getattr(whole, field), getattr(chunked, field))


def test_plan_replicas_prefilter_is_plan_preserving():
    cluster = make_uniform_cluster(8)
    eng = PlacementEngine(cluster, backend="ref")
    ids = np.arange(3000, dtype=np.uint32)
    eng.place_replica_nodes(ids, 3)
    v_from = cluster.version
    new_segs = cluster.add_node(50, 1.0)
    planner = MigrationPlanner(eng)
    full = planner.plan_replicas(ids, v_from, cluster.version, 3)
    pre = planner.plan_replicas(
        ids, v_from, cluster.version, 3, max_new_seg=max(new_segs)
    )
    assert full.n_moves > 0
    for field in ("ids", "src", "dst", "index", "slot", "src_slot"):
        assert np.array_equal(getattr(full, field), getattr(pre, field))


# ---------------------------------------------------------------------------
# Zero host syncs in the replica streaming sweep
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_plan_replicas_stream_zero_host_transfers(backend, monkeypatch):
    cluster = make_uniform_cluster(5)
    eng = PlacementEngine(cluster, backend=backend)
    eng.artifact()
    v_from = cluster.version
    cluster.add_node(9, 1.2)
    v_to = cluster.version
    planner = MigrationPlanner(eng)
    chunks = [jnp.arange(s, s + 512, dtype=jnp.uint32) for s in (0, 512, 1024)]
    for _, m, s, d, ss in planner.plan_replicas_stream(chunks, v_from, v_to, 3):
        m.block_until_ready()  # warm-up: device tables + jit compile
    uploads = eng.uploads

    real_asarray = np.asarray
    host_reads: list = []

    def tripwire(*args, **kwargs):
        host_reads.append(args)
        return real_asarray(*args, **kwargs)

    monkeypatch.setattr(np, "asarray", tripwire)
    with jax.transfer_guard("disallow"):
        for _, moved, src, dst, src_slot in planner.plan_replicas_stream(
            chunks, v_from, v_to, 3
        ):
            moved.block_until_ready()
            src.block_until_ready()
            dst.block_until_ready()
            src_slot.block_until_ready()
    monkeypatch.undo()
    assert isinstance(src, jax.Array) and isinstance(src_slot, jax.Array)
    assert not host_reads, f"replica sweep touched the host: {len(host_reads)}"
    assert eng.uploads == uploads == 2  # one per version, ever


# ---------------------------------------------------------------------------
# Minimal replica mass / direction constraints
# ---------------------------------------------------------------------------


def test_add_remove_move_exactly_the_minimal_replica_mass():
    cluster = make_uniform_cluster(10)
    eng = cluster.engine
    ids = np.arange(4000, dtype=np.uint32)
    R = 3
    planner = MigrationPlanner(eng)

    before = eng.place_replica_nodes(ids, R)
    v0 = cluster.version
    cluster.add_node(10, 1.0)
    plan = planner.plan_replicas(ids, v0, cluster.version, R)
    after = eng.place_replica_nodes(ids, R)
    minimal = int((~(after[:, :, None] == before[:, None, :]).any(axis=2)).sum())
    assert plan.n_moves == minimal > 0
    assert np.all(plan.dst == 10)  # additions pull ONLY toward the new node
    assert plan.n_moves <= len(ids)  # at most one slot per id on a single add

    before = after
    v1 = cluster.version
    cluster.remove_node(4)
    plan = planner.plan_replicas(ids, v1, cluster.version, R)
    after = eng.place_replica_nodes(ids, R)
    minimal = int((~(after[:, :, None] == before[:, None, :]).any(axis=2)).sum())
    assert plan.n_moves == minimal > 0
    assert np.all(plan.src == 4)  # removals push ONLY off the victim
    victims = (before == 4).any(axis=1)
    assert np.array_equal(np.unique(plan.index), np.nonzero(victims)[0])


def test_replica_sets_pairwise_distinct_under_churn():
    """Property test: across an add/remove/resize churn sequence, replica
    sets stay pairwise distinct and every planned movement equals the
    brute-force minimal set diff."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    ops = st.lists(
        st.tuples(st.sampled_from(["add", "remove", "resize"]),
                  st.floats(0.5, 2.0)),
        min_size=1,
        max_size=4,
    )

    @settings(max_examples=15, deadline=None)
    @given(ops=ops, seed=st.integers(0, 2**16))
    def run(ops, seed):
        rng = np.random.default_rng(seed)
        cluster = make_uniform_cluster(6)
        eng = cluster.engine
        ids = rng.integers(0, 2**32, 300, dtype=np.uint32)
        planner = MigrationPlanner(eng)
        next_node = 100
        R = 3
        for op, cap in ops:
            before = eng.place_replica_nodes(ids, R)
            v_from = cluster.version
            live = list(cluster.nodes)
            if op == "add" or len(live) <= R + 1:
                cluster.add_node(next_node, float(cap))
                next_node += 1
            elif op == "remove":
                cluster.remove_node(live[int(cap * 7) % len(live)])
            else:
                cluster.resize_node(live[int(cap * 5) % len(live)], float(cap))
            after = eng.place_replica_nodes(ids, R)
            # pairwise distinct under every membership state
            for row in after:
                assert len(set(row.tolist())) == R
            plan = planner.plan_replicas(ids, v_from, cluster.version, R)
            minimal = int(
                (~(after[:, :, None] == before[:, None, :]).any(axis=2)).sum()
            )
            assert plan.n_moves == minimal
            # every moved slot's destination really is its v+1 owner
            assert np.array_equal(plan.dst, after[plan.index, plan.slot])
            # and its source really was a v member that vacated
            assert np.array_equal(
                plan.src, before[plan.index, plan.src_slot]
            )

    run()


# ---------------------------------------------------------------------------
# Dual-version replica serving: invariant at every round, incl. rollback
# ---------------------------------------------------------------------------


def _assert_served_sets_valid(served, holdings, ids, R):
    for i, row in zip(ids, served):
        s = set(int(x) for x in row)
        assert len(s) == R  # pairwise distinct
        assert s <= holdings[int(i)], (
            f"id {int(i)}: served {s} not all holders {holdings[int(i)]}"
        )


@pytest.mark.parametrize("backend", ["numpy", "ref"])
def test_replica_window_routing_and_rollback(backend):
    """Every replica read returns R pairwise-distinct nodes that all hold
    the datum, at every round, through an add-node migration rolled back
    at half-drain; host and device read rules agree throughout."""
    R = 3
    cluster = make_uniform_cluster(6)
    eng = PlacementEngine(cluster, backend=backend)
    cluster._engine = eng
    ids = np.arange(1500, dtype=np.uint32)
    coord = ElasticCoordinator(cluster, ids, n_replicas=R)
    sets_v = coord.owners()
    holdings = {int(i): set(map(int, row)) for i, row in zip(ids, sets_v)}

    mig = coord.add_node_live(6, 1.0, egress=25)
    plan = mig.state.plan
    assert plan.n_replicas == R and plan.n_moves > 30
    uploads = eng.uploads

    def land_and_check(m):
        before = m.state.landed.copy()
        m.round()
        p = m.state.plan
        for r in np.nonzero(m.state.landed & ~before)[0]:
            k = int(p.ids[r])
            holdings[k].discard(int(p.src[r]))
            holdings[k].add(int(p.dst[r]))
        served = m.route_replicas(ids)
        _assert_served_sets_valid(served, holdings, ids, R)
        served_dev = np.asarray(m.route_replicas_device(jnp.asarray(ids)))
        assert np.array_equal(served, served_dev)

    while mig.state.n_pending > plan.n_moves // 2:
        land_and_check(mig)
    assert not mig.done

    rev = coord.rollback_live(mig)
    assert 6 not in cluster.nodes
    assert rev.state.plan.n_replicas == R
    # reverse slots are re-indexed into the reverse destination (= v) set
    assert np.array_equal(
        rev.state.plan.slot, mig.state.plan.src_slot[mig.state.landed]
    )
    while not rev.done:
        land_and_check(rev)

    for i in ids:
        assert holdings[int(i)] == set(map(int, sets_v[int(i)]))
    assert np.array_equal(coord.owners(), sets_v)
    assert eng.uploads == uploads  # the flap re-materialized NOTHING


def test_replica_live_plan_equals_atomic():
    ids = np.arange(1800, dtype=np.uint32)
    atomic = ElasticCoordinator(
        make_uniform_cluster(5), ids, n_replicas=2
    )
    a_plan = atomic.add_node(5, 1.0)
    live_coord = ElasticCoordinator(
        make_uniform_cluster(5), ids, n_replicas=2
    )
    live = live_coord.add_node_live(5, 1.0)
    assert live.state.plan.moves_dict() == a_plan.moves
    live.run()
    assert np.array_equal(atomic.owners(), live_coord.owners())
    # the owner table tracks the post-drain truth
    assert np.array_equal(
        live_coord.owners(), live_coord.engine.place_replica_nodes(ids, 2)
    )


def test_replica_coordinator_owner_tracking_through_events():
    cluster = make_uniform_cluster(6)
    ids = np.arange(1000, dtype=np.uint32)
    coord = ElasticCoordinator(cluster, ids, n_replicas=3)
    coord.add_node(7, 1.5)
    assert np.array_equal(coord.owners(), cluster.engine.place_replica_nodes(ids, 3))
    coord.remove_node(2)
    assert np.array_equal(coord.owners(), cluster.engine.place_replica_nodes(ids, 3))
    mig = coord.remove_node_live(3, ingress=50)
    assert np.all(mig.state.plan.src == 3)
    mig.run()
    assert np.array_equal(coord.owners(), cluster.engine.place_replica_nodes(ids, 3))


def test_driver_runs_replica_repairs_to_completion():
    """Failure detector -> throttled replica repair; DrainDriver.run()
    drains every queued repair."""
    cluster = make_uniform_cluster(6)
    ids = np.arange(900, dtype=np.uint32)
    coord = ElasticCoordinator(cluster, ids, n_replicas=2)
    t = {"now": 0.0}
    tracker = HeartbeatTracker(timeout=1.0, clock=lambda: t["now"])
    for nid in range(6):
        tracker.beat(nid)
    driver = MigrationDriver(
        tracker, lambda node: coord.remove_node_live(node, ingress=30)
    )
    t["now"] = 5.0
    for nid in range(4):
        tracker.beat(nid)
    t["now"] = 5.5
    assert set(driver.poll()) == {4, 5}
    assert not driver.done
    driver.run()  # the shared drain loop retires BOTH queued repairs
    assert driver.done and len(driver.completed) == 2
    assert all(m.done for m in driver.completed)
    assert np.array_equal(coord.owners(), cluster.engine.place_replica_nodes(ids, 2))


def test_router_replica_scale_migration():
    router = ReplicaRouter({i: 1.0 for i in range(5)})
    sessions = np.arange(1200, dtype=np.uint32)
    before = router.route_replicas(sessions, 2)
    mig = router.begin_scale_migration(
        sessions, add=(9, 1.0), n_replicas=2, egress=30
    )
    served = router.route_replicas_migrating(sessions, mig)
    # nothing landed yet: every served SET is exactly the v-side holders
    # (slot order follows the v+1 set, so compare as sets)
    assert np.array_equal(np.sort(served, axis=1), np.sort(before, axis=1))
    while not mig.done:
        mig.round()
        served = router.route_replicas_migrating(sessions, mig)
        dev = np.asarray(
            router.route_replicas_migrating_device(jnp.asarray(sessions), mig)
        )
        assert np.array_equal(served, dev)
        for row in served:
            assert len(set(row.tolist())) == 2
    assert np.array_equal(served, router.route_replicas(sessions, 2))


# ---------------------------------------------------------------------------
# Checkpoint store: per-slot live add + live repair
# ---------------------------------------------------------------------------


def test_store_live_repair_restores_at_every_round():
    store = AsuraCheckpointStore({i: 1.0 for i in range(6)}, n_replicas=3)
    mgr = CheckpointManager(store)
    rng = np.random.default_rng(13)
    tree = {"w": rng.standard_normal((2048, 2048)).astype(np.float32)}
    mgr.save(2, tree)
    store.fail_node(1)  # CRASH: no drain possible, sources are gone
    sm = store.begin_remove_node(1, ingress=2)
    plan = sm.live.state.plan
    assert plan.n_moves > 0 and np.all(plan.src == 1)
    rounds = 0
    while not sm.done:
        matrix = sm.round()
        for (_, d), c in matrix.items():
            assert c <= 2  # repair ingress budget per node per round
        out = mgr.restore(2, tree)  # degraded window: replicas fall back
        assert np.array_equal(out["w"], tree["w"])
        rounds += 1
        assert rounds < 500
    assert rounds > 1
    assert store._migration is None
    # repaired copies match the atomic placement exactly
    keys = np.fromiter(
        {k for n in store.nodes.values() for k in n.blobs}, dtype=np.uint32
    )
    for key, row in zip(keys, store.replicas_for(keys)):
        for nid in row:
            assert int(key) in store.nodes[int(nid)].blobs
    assert np.array_equal(mgr.restore(2, tree)["w"], tree["w"])


def test_store_live_add_accounts_every_replica_copy():
    """The per-slot plan accounts each replica copy as its own flow: the
    drained matrices sum to exactly the copies moved."""
    store = AsuraCheckpointStore({i: 1.0 for i in range(5)}, n_replicas=2)
    mgr = CheckpointManager(store)
    rng = np.random.default_rng(2)
    mgr.save(1, {"w": rng.standard_normal((2048, 2048)).astype(np.float32)})
    sm = store.begin_add_node(20, capacity=2.0, ingress=3)
    plan = sm.live.state.plan
    assert plan.n_replicas == 2
    matrices = sm.run()
    assert sum(sum(m.values()) for m in matrices) == plan.n_moves
    assert sm.copies_moved == plan.n_moves  # every row landed one copy
    assert np.all(plan.dst == 20)


def test_remove_numbers_batch_matches_scalar():
    cluster = make_uniform_cluster(9)
    ids = np.arange(120, dtype=np.uint32)
    for R in (1, 2, 3):
        batch = remove_numbers_batch(
            ids, cluster.seg_lengths(), cluster.seg_to_node(), R
        )
        engine_batch = cluster.engine.remove_numbers_batch(ids, R)
        assert np.array_equal(batch, engine_batch)
        for i in ids[:40]:
            want = remove_numbers(
                int(i), cluster.seg_lengths(), cluster.seg_to_node(), R
            )
            assert batch[int(i)].tolist() == want


def test_align_replica_sets_host_vs_device_twin():
    """The two alignment implementations (numpy spec and the jitted jnp
    twin) are bit-identical on random distinct-node sets."""
    from repro.kernels.ops import _align_replica_sets

    rng = np.random.default_rng(0)
    for R in (1, 2, 3):
        rows = []
        for _ in range(400):
            rows.append(
                (
                    rng.choice(12, size=R, replace=False),
                    rng.choice(12, size=R, replace=False),
                )
            )
        before = np.stack([b for b, _ in rows]).astype(np.int64)
        after = np.stack([a for _, a in rows]).astype(np.int64)
        moved, src, src_slot = align_replica_sets(before, after)
        m2, s2, d2, ss2 = (
            np.asarray(x)
            for x in _align_replica_sets(
                jnp.asarray(before, dtype=jnp.int32),
                jnp.asarray(after, dtype=jnp.int32),
                n_replicas=R,
            )
        )
        assert np.array_equal(moved, m2)
        assert np.array_equal(src, s2)
        assert np.array_equal(after, d2)
        assert np.array_equal(src_slot, ss2)


# ---------------------------------------------------------------------------
# Owner prefilter: an add keeps exactly the ids whose v+1 set holds a grown node
# ---------------------------------------------------------------------------

_PLAN_FIELDS = ("ids", "src", "dst", "index", "slot", "src_slot", "n_scanned")


def _owner_cluster(case):
    """The cluster a case starts from: fractional capacities, or 64 full
    nodes (one more raises the table's top level)."""
    from repro.core import make_cluster

    if case == "top_level":  # 64 full segments plus one: top level 5 -> 6
        return make_uniform_cluster(64)
    caps = np.random.default_rng(7).uniform(0.5, 2.0, 30)
    return make_cluster(caps.tolist())


def _apply_owner_change(cluster, case):
    """Grow the cluster -> the ``max_new_seg`` a caller would pass."""
    if case == "resize":  # an existing node grows: its fractional tail too
        cluster.resize_node(3, cluster.nodes[3].capacity + 1.7)
        return len(cluster.seg_lengths()) - 1
    cap = 1.0 if case == "top_level" else 1.3
    return max(cluster.add_node(100, cap))


@pytest.mark.parametrize("case", ["fractional", "top_level", "resize"])
@pytest.mark.parametrize("R", [1, 3])
@pytest.mark.parametrize("backend", ["ref", "numpy"])
def test_owner_prefilter_plan_equals_full_diff(backend, R, case):
    cluster = _owner_cluster(case)
    eng = PlacementEngine(cluster, backend=backend)
    ids = np.arange(6000, dtype=np.uint32) * np.uint32(2654435761)
    top_from = eng.artifact().top_level
    v_from = cluster.version
    max_new_seg = _apply_owner_change(cluster, case)
    if case == "top_level":
        assert eng.artifact().top_level == top_from + 1
    planner = MigrationPlanner(eng)
    full = planner.plan_replicas(ids, v_from, cluster.version, R)
    pre = planner.plan_replicas(
        ids, v_from, cluster.version, R, chunk=2500, max_new_seg=max_new_seg
    )
    assert full.n_moves > 0
    for field in _PLAN_FIELDS:
        assert np.array_equal(getattr(full, field), getattr(pre, field)), field
    if R == 1:  # the single-owner planner takes the same filter
        one = planner.plan(
            ids, v_from, cluster.version, chunk=2500, max_new_seg=max_new_seg
        )
        for field in ("ids", "src", "dst", "index"):
            assert np.array_equal(getattr(one, field), getattr(full, field))


@pytest.mark.parametrize("backend", ["ref", "numpy"])
def test_owner_prefilter_keeps_exactly_the_moved_ids(backend):
    from repro.obs import TraceLedger

    cluster = _owner_cluster("fractional")
    eng = PlacementEngine(cluster, backend=backend)
    ids = np.arange(8000, dtype=np.uint32)
    eng.artifact()
    v_from = cluster.version
    max_new_seg = _apply_owner_change(cluster, "fractional")
    led = TraceLedger(clock=lambda: 0.0)
    plan = MigrationPlanner(eng, ledger=led).plan_replicas(
        ids, v_from, cluster.version, 3, chunk=3000, max_new_seg=max_new_seg
    )
    assert led.counter("planner.prefilter_scanned") == len(ids)
    assert led.counter("planner.prefilter_kept") == len(np.unique(plan.ids)) > 0
    [ev] = led.events("span")
    assert (ev["filter"], ev["grown_nodes"]) == ("owner", 1)


@pytest.mark.parametrize("backend", ["ref", "numpy"])
def test_owner_prefilter_falls_back_when_a_segment_shrinks(backend):
    from repro.obs import TraceLedger

    cluster = _owner_cluster("fractional")
    eng = PlacementEngine(cluster, backend=backend)
    ids = np.arange(5000, dtype=np.uint32)
    eng.artifact()
    v_from = cluster.version
    cluster.resize_node(4, cluster.nodes[4].capacity - 0.4)  # shrinks
    max_new_seg = max(cluster.add_node(100, 1.3))
    led = TraceLedger(clock=lambda: 0.0)
    planner = MigrationPlanner(eng, ledger=led)
    pre = planner.plan_replicas(
        ids, v_from, cluster.version, 3, max_new_seg=max_new_seg
    )
    assert led.counter("planner.prefilter_kept") == len(ids)
    [ev] = led.events("span")
    assert (ev["filter"], ev["grown_nodes"]) == ("fallback", 0)
    full = planner.plan_replicas(ids, v_from, cluster.version, 3)
    assert full.n_moves > 0
    for field in _PLAN_FIELDS:
        assert np.array_equal(getattr(full, field), getattr(pre, field)), field


@pytest.mark.parametrize("backend", ["ref", "numpy"])
def test_owner_prefilter_never_runs_the_addition_number_trace(
    backend, monkeypatch
):
    import repro.core.asura as asura

    def refuse(*a, **k):
        raise AssertionError("the planner ran the ADDITION-NUMBER trace")

    cluster = make_uniform_cluster(8)
    eng = PlacementEngine(cluster, backend=backend)
    monkeypatch.setattr(eng, "addition_numbers_device", refuse)
    monkeypatch.setattr(asura, "addition_numbers_batch", refuse)
    ids = np.arange(3000, dtype=np.uint32)
    eng.artifact()
    v_from = cluster.version
    max_new_seg = max(cluster.add_node(50, 1.0))
    plan = MigrationPlanner(eng).plan_replicas(
        ids, v_from, cluster.version, 3, max_new_seg=max_new_seg
    )
    assert plan.n_moves > 0 and (plan.dst == 50).all()


def test_owner_prefilter_refuses_a_max_new_seg_below_the_grown_segments():
    cluster = make_uniform_cluster(8)
    eng = PlacementEngine(cluster, backend="numpy")
    eng.artifact()
    v_from = cluster.version
    new_segs = cluster.add_node(50, 1.5)
    with pytest.raises(ValueError, match="grew segment"):
        MigrationPlanner(eng).plan_replicas(
            np.arange(100, dtype=np.uint32), v_from, cluster.version, 3,
            max_new_seg=min(new_segs),
        )
