"""ISSUE-7 coverage: the batched stream served THROUGH a live migration.

Dual-version serving must keep working under the batched driver: a
generated request stream routed mid-drain via
``LiveMigration.route_replicas_device`` (the cached fused probe) must, at
EVERY batch of every round,

  * match the host ``route_replicas`` rule bit for bit,
  * return pairwise-distinct holder sets (every served set is R live
    copies),
  * serve each slot from the v or v+1 replica set of its id -- never a
    node on neither side of the window,
  * pick the chosen node from the served set,

with stable probe trace counts across batches within a round (the fused
probe caches per routing config, not per call) and zero host syncs after
the per-round pending-view refresh.
"""

import numpy as np
import pytest

import jax

import repro.migrate.live as live
from repro.serve import Router

N_NODES = 8
R = 3
SESSIONS = 20_000


def _window():
    router = Router({i: 1.0 for i in range(N_NODES)})
    sessions = np.arange(SESSIONS, dtype=np.uint32)
    mig = router.begin_scale_migration(
        sessions,
        add=(N_NODES, 1.0),
        n_replicas=R,
        egress={n: 60 for n in range(N_NODES + 1)},
    )
    assert mig.state.plan.n_moves > 120, "plan too small to span rounds"
    driver = router.stream_driver(
        batch=1024, n_keys=1 << 14, n_replicas=R, policy="pow2",
        seed=5, n_bins=N_NODES + 1,
    )
    return router, mig, driver


def test_batched_stream_through_mid_drain_window():
    router, mig, driver = _window()
    engine = router.engine
    v0, v1 = mig.v_from, mig.v_to
    rounds = 0
    while not mig.done and rounds < 6:
        mig.round()
        rounds += 1
        for _ in range(2):  # two batches per round
            ids_dev, chosen_dev = driver.serve_migrating(mig)
            ids = np.asarray(ids_dev)
            chosen = np.asarray(chosen_dev)
            served = np.asarray(mig.route_replicas_device(ids_dev))
            # device rule == host rule, bit for bit
            assert np.array_equal(served, mig.route_replicas(ids))
            # holder sets stay pairwise-distinct mid-drain
            for a in range(R):
                for b in range(a + 1, R):
                    assert (served[:, a] != served[:, b]).all()
            # every served slot is on one side of the version window
            v_set = engine.place_replica_nodes_at(ids, v0, R)
            v1_set = engine.place_replica_nodes_at(ids, v1, R)
            union_hit = (served[:, :, None] == v_set[:, None, :]).any(-1) | (
                served[:, :, None] == v1_set[:, None, :]
            ).any(-1)
            assert union_hit.all(), "served a node on neither side of the window"
            # the selected node comes from the served set
            assert (chosen[:, None] == served).any(axis=1).all()
    assert rounds > 1, "window drained in one round; nothing mid-drain tested"
    if not mig.done:
        mig.run()
    assert driver.load_counts().sum() == driver.steps_done * driver.batch


def test_window_probe_trace_stable_within_round(monkeypatch):
    _router, mig, driver = _window()
    mig.round()
    driver.serve_migrating(mig)  # warm: probe compile + pending-view upload
    traces = live.probe_trace_count()
    real_asarray = np.asarray
    host_reads: list = []

    def tripwire(*args, **kwargs):
        host_reads.append(args)
        return real_asarray(*args, **kwargs)

    monkeypatch.setattr(np, "asarray", tripwire)
    with jax.transfer_guard("disallow"):
        for _ in range(3):
            _ids, chosen = driver.serve_migrating(mig)
        chosen.block_until_ready()
    monkeypatch.undo()
    assert not host_reads, f"mid-round serving touched the host: {len(host_reads)}"
    assert live.probe_trace_count() == traces, "repeated batches retraced the probe"


def test_serve_migrating_requires_matching_replication():
    _router, mig, driver = _window()
    bad = _window()[0].stream_driver(
        batch=256, n_keys=1 << 12, n_replicas=2, n_bins=N_NODES + 1
    )
    with pytest.raises(ValueError, match="R=2"):
        bad.serve_migrating(mig)
    mig.run()
    # a drained window still serves (pending sets empty, all v+1)
    ids_dev, chosen = driver.serve_migrating(mig)
    served = np.asarray(mig.route_replicas_device(ids_dev))
    assert np.array_equal(
        served,
        driver.engine.place_replica_nodes_at(np.asarray(ids_dev), mig.v_to, R),
    )
    assert (np.asarray(chosen)[:, None] == served).any(axis=1).all()


# ---------------------------------------------------------------------------
# Host-fed batches through a live migration: route_batch(ids, migration=m)
# ---------------------------------------------------------------------------

CAPS = 0.5 + 1.5 * (np.arange(64) % 7) / 6  # 64 nodes of mixed capacity
RACK = [(64 + i, 1.0) for i in range(4)]
RECORDS = np.arange(1 << 12, dtype=np.uint32)


def _reference():
    """The benchmark's plain NumPy reference of reads through a rack change."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir, "bench",
                        "references", "asura_elastic.py")
    spec = importlib.util.spec_from_file_location("asura_elastic_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rack_plans():
    """A 64-node R=3 engine, the plans of 4 nodes joining and then leaving
    over 2^12 records, and a pow2 driver with room for the rack."""
    from repro.core import PlacementEngine, make_cluster
    from repro.migrate import MigrationPlanner
    from repro.serve import RequestStreamDriver

    cluster = make_cluster(CAPS)
    engine = PlacementEngine(cluster)
    planner = MigrationPlanner(engine)
    engine.artifact()
    v0 = cluster.version
    segs = [cluster.add_node(n, c) for n, c in RACK]
    v1 = cluster.version
    join = planner.plan_replicas(RECORDS, v0, v1, R, max_new_seg=max(max(s) for s in segs))
    engine.artifact()
    for n, _ in RACK:
        cluster.remove_node(n)
    leave = planner.plan_replicas(RECORDS, v1, cluster.version, R)
    driver = RequestStreamDriver(engine, batch=512, n_keys=1, law="uniform", n_replicas=R,
                                 policy="pow2", seed=11, n_bins=64 + len(RACK))
    return engine, join, leave, driver


def _drain_serving(driver, mig, keys, *, batches_per_round=2):
    """Serve batches through ``mig`` until it drains, a mover round after
    every ``batches_per_round``; returns per batch ``(keys, chosen,
    landed-before, counter growth)``."""
    log = []
    i = 0
    while not mig.done:
        k = keys[i % len(keys)]
        landed = mig.state.landed.copy()
        before = np.asarray(driver.counts)
        chosen = np.asarray(driver.route_batch(k, migration=mig))
        log.append((k, chosen, landed, np.asarray(driver.counts) - before))
        i += 1
        if i % batches_per_round == 0:
            mig.round_block(1)
    return log


def test_route_batch_through_rack_join_and_leave():
    """Every host-fed read lands on a node that holds its datum at its
    round (the NumPy reference), the counters grow by exactly the routes,
    and each drain traces ``route_migrating`` once."""
    from repro.migrate import LiveMigration

    ref = _reference()
    engine, join, leave, driver = _rack_plans()
    rng = np.random.default_rng(4)
    keys = [rng.integers(0, len(RECORDS), 500).astype(np.uint32) for _ in range(6)]
    base, grown = ref.rack_sets(RECORDS, CAPS, RACK, R)
    for plan, before, after in ((join, base, grown), (leave, grown, base)):
        assert plan.n_moves > 100
        budget = max(1, -(-int(np.bincount(plan.dst).max()) // 6))
        mig = LiveMigration.from_plan(engine, plan, egress=budget, ingress=budget)
        traces = driver.step_traces
        log = _drain_serving(driver, mig, keys)
        assert driver.step_traces == traces + 1, "one drain, one trace (fixed pad)"
        assert len(log) > 6, "drained too fast to test mid-drain reads"
        for k, chosen, landed, grew in log:
            pending = np.zeros((len(k), R), dtype=bool)
            for row in np.nonzero(~landed)[0]:
                pending[k == plan.ids[row], plan.slot[row]] = True
            assert ref.non_holder_reads(chosen, before[k], after[k], pending) == 0
            assert np.array_equal(grew, np.bincount(chosen, minlength=driver.n_bins))


def test_route_batch_without_a_live_migration_is_the_flat_body():
    """``migration=None`` and a finished migration route exactly as
    ``route_batch(ids)``, through the flat jit (``body``)."""
    from repro.migrate import LiveMigration

    engine, _join, leave, a = _rack_plans()
    *_, b = _rack_plans()
    done = LiveMigration.from_plan(engine, leave)
    done.run()
    keys = np.random.default_rng(5).integers(0, 2**32, 700, dtype=np.uint32)
    for mig in (None, done):
        assert np.array_equal(np.asarray(a.route_batch(keys, migration=mig)),
                              np.asarray(b.route_batch(keys)))
    assert np.array_equal(np.asarray(a.counts), np.asarray(b.counts))
    assert {k[0] for k in a._fns} == {"route_batch"}
    assert all(fn.__name__ == "body" for fn in a._fns.values())


def test_route_batch_after_the_drain_equals_the_flat_v1_route():
    """Once every row has landed the read rule is the v+1 placement: the
    drained window routes as the flat body of the cluster at v+1."""
    from repro.migrate import LiveMigration

    engine, _join, leave, driver = _rack_plans()
    *_, twin = _rack_plans()
    mig = LiveMigration.from_plan(engine, leave, egress=50, ingress=50)
    keys = np.random.default_rng(6).integers(0, len(RECORDS), 512, dtype=np.uint32)
    _drain_serving(driver, mig, [keys])
    # the drained rule: nothing pending, the v+1 sets exactly
    assert np.array_equal(np.asarray(mig.route_replicas_device(keys)),
                          engine.place_replica_nodes_at(keys, mig.v_to, R))
    for name in ("counts", "queue", "qhist", "_step"):
        setattr(twin, name, getattr(driver, name))
    assert np.array_equal(np.asarray(driver.route_batch(keys, migration=mig)),
                          np.asarray(twin.route_batch(keys)))


def test_route_batch_refuses_a_window_of_another_r():
    from repro.migrate import LiveMigration
    from repro.serve import RequestStreamDriver

    engine, join, _leave, _driver = _rack_plans()
    mig = LiveMigration.from_plan(engine, join)
    two = RequestStreamDriver(engine, batch=256, n_keys=1, law="uniform", n_replicas=2,
                              n_bins=64 + len(RACK))
    with pytest.raises(ValueError, match="R=3"):
        two.route_batch(RECORDS[:100], migration=mig)


def test_pending_view_keeps_its_pad_through_the_drain():
    """The per-slot pending view's P is pow2 of the plan's largest slot,
    fixed while rows land: the sentinel tail grows, the shape does not; each
    refresh bumps the ledger's refresh and live-row counters."""
    from repro.migrate import MigrationState, ThrottledMover
    from repro.obs import TraceLedger, get_ledger, set_ledger

    _engine, join, _leave, _driver = _rack_plans()
    state = MigrationState(join)
    P = 1 << (int(np.bincount(join.slot).max()) - 1).bit_length()
    mover = ThrottledMover(state, egress=40, ingress=40)
    prev = set_ledger(TraceLedger())
    try:
        refreshes = rows = 0
        while True:
            ids_pad, src_pad, counts = map(np.asarray, state.pending_replicas_device())
            refreshes += 1
            assert ids_pad.shape == src_pad.shape == (R, P)
            live = [int((~state.landed & (join.slot == r)).sum()) for r in range(R)]
            assert counts.tolist() == live
            rows += sum(live)
            for r in range(R):
                assert np.all(np.diff(ids_pad[r, : live[r]].astype(np.int64)) > 0)
                assert np.all(ids_pad[r, live[r]:] == 0xFFFFFFFF)
            if state.done:
                break
            mover.round()
        assert refreshes > 3
        assert get_ledger().counter("migrate.pending_refreshes") == refreshes
        assert get_ledger().counter("migrate.pending_rows") == rows
    finally:
        set_ledger(prev)


def test_serve_migrating_drains_a_whole_plan_with_one_trace():
    """The generated stream through a whole drain: the fused probe traces
    once (the pending view's pad is fixed) and matches the host rule at
    every round."""
    router, mig, driver = _window()
    prev = live.probe_trace_count()
    driver.serve_migrating(mig)
    first = live.probe_trace_count()
    assert first <= prev + 1
    rounds = 0
    while not mig.done:
        mig.round()
        rounds += 1
        ids_dev, _chosen = driver.serve_migrating(mig)
        served = np.asarray(mig.route_replicas_device(ids_dev))
        assert np.array_equal(served, mig.route_replicas(np.asarray(ids_dev)))
    assert rounds > 3
    assert live.probe_trace_count() == first, "the drain retraced the probe"


@pytest.mark.parametrize("P, counts", [
    (1, (0, 1, 0)),
    (8, (8, 3, 0)),
    (4096, (4096, 1, 2049)),  # rows of live.PROBE_ROW: full, one id, a part row
])
def test_probe_pending_matches_a_sorted_search(P, counts):
    """The two-level probe finds exactly the live ids of each slot (row
    heads, the last live id, 0xFFFFFFFF as a real id beside the sentinel
    tail) and their sources, and nothing in the tail."""
    rng = np.random.default_rng(P)
    ids_pad = np.full((R, P), 0xFFFFFFFF, dtype=np.uint32)
    src_pad = np.full((R, P), -1, dtype=np.int32)
    for r, n in enumerate(counts):
        ids = np.sort(rng.choice(2**32 - 1, n, replace=False)).astype(np.uint32)
        if n and r == 1:
            ids[-1] = 0xFFFFFFFF  # a real pending id equal to the sentinel
        ids_pad[r, :n] = ids
        src_pad[r, :n] = rng.integers(0, 100, n)
    queries = np.concatenate([ids_pad[:, :P].ravel(), [0, 0xFFFFFFFF],
                              rng.integers(0, 2**32, 500)]).astype(np.uint32)
    hit, src = map(np.asarray, live_probe(queries, ids_pad, src_pad, counts))
    for r, n in enumerate(counts):
        want = np.isin(queries, ids_pad[r, :n])
        assert np.array_equal(hit[:, r], want)
        at = np.searchsorted(ids_pad[r, :n], queries[want])
        assert np.array_equal(src[want, r], src_pad[r, at])


def live_probe(queries, ids_pad, src_pad, counts):
    import jax.numpy as jnp

    return jax.jit(live.probe_pending)(
        jnp.asarray(queries), jnp.asarray(ids_pad), jnp.asarray(src_pad),
        jnp.asarray(np.asarray(counts, dtype=np.int32)))
