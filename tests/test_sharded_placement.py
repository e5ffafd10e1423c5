"""The sharded bulk-placement layer (DESIGN.md section 11).

Two halves:

  * FORCED-8-DEVICE bit-identity: ``--xla_force_host_platform_device_count``
    must be set before the first jax init, and this test process has long
    since initialized jax on one device -- so the 8-way mesh runs in a
    SUBPROCESS (``repro.launch.placement_mesh --selftest``, the same entry
    CI smokes at 4 devices), which asserts sharded placement / histogram /
    diff / replica-diff / planner results equal the single-device engine
    path for ASURA and all three baselines, R in {1, 3}, odd-sized
    streams.

  * IN-PROCESS semantics on a 1-device mesh (partition + psum plumbing is
    device-count-independent; the subprocess covers >1): pad-lane
    weighting, histogram/matrix exactness, ``engine.sharded()``, the
    planner's ``mesh=`` threading, and the pow2 tail bucketing of the
    streaming planner (ragged chunks share a bucket compile and pad lanes
    can never produce phantom moves).
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import PlacementEngine, make_uniform_cluster
from repro.launch.placement_mesh import ShardedSweep, make_data_mesh
from repro.migrate import MigrationPlanner

N_NODES = 16
N_IDS = 4_099  # odd: does not divide any mesh


@pytest.fixture(scope="module")
def mesh():
    return make_data_mesh()


@pytest.fixture(scope="module")
def versions():
    """(engine, sweep, ids, v0, v1): a ref-backend engine with two cached
    table versions (one add-node event)."""
    cluster = make_uniform_cluster(N_NODES)
    engine = PlacementEngine(cluster, backend="ref")
    sweep = engine.sharded()
    ids = np.arange(N_IDS, dtype=np.uint32)
    engine.artifact()
    v0 = cluster.version
    cluster.add_node(N_NODES, 1.0)
    return engine, sweep, ids, v0, cluster.version


# ---------------------------------------------------------------------------
# Forced 8 host devices (subprocess: device count locks at first jax init)
# ---------------------------------------------------------------------------


def test_selftest_on_8_forced_host_devices():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.join(root, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    env.pop("XLA_FLAGS", None)  # the selftest sets the device count itself
    proc = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.placement_mesh",
            "--selftest", "--devices", "8", "--ids", "20011",
        ],
        capture_output=True, text=True, env=env, cwd=root, timeout=600,
    )
    assert proc.returncode == 0, f"selftest failed:\n{proc.stderr[-3000:]}"
    assert "OK on 8 devices" in proc.stdout


# ---------------------------------------------------------------------------
# In-process semantics (1-device mesh)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alg", ["asura", "ch", "wrh", "rs"])
def test_sharded_owners_and_histogram_match_engine(alg, mesh):
    cluster = make_uniform_cluster(N_NODES)
    engine = PlacementEngine(cluster, backend="ref", algorithm=alg)
    sweep = ShardedSweep(engine, mesh)
    ids = np.arange(N_IDS, dtype=np.uint32)
    ref = engine.place_nodes(ids)
    assert np.array_equal(sweep.place_nodes(ids), ref)
    hist = sweep.histogram(ids, N_NODES)
    assert hist.sum() == N_IDS  # pad lanes carry weight 0
    assert np.array_equal(hist, np.bincount(ref, minlength=N_NODES))


@pytest.mark.parametrize("n_replicas", [1, 3])
def test_sharded_replica_histogram(n_replicas, versions):
    engine, sweep, ids, _, _ = versions
    nodes = engine.place_replica_nodes(ids, n_replicas)
    hist = sweep.histogram(ids, N_NODES + 1, n_replicas=n_replicas)
    assert hist.sum() == n_replicas * N_IDS
    assert np.array_equal(hist, np.bincount(nodes.ravel(), minlength=N_NODES + 1))


def test_engine_sharded_accessor_caches_default(versions):
    engine, sweep, _, _, _ = versions
    assert engine.sharded() is sweep  # default-mesh sweep is cached
    other = engine.sharded(make_data_mesh())
    assert other is not sweep  # explicit meshes get fresh sweeps


def test_movement_matrix_matches_plan(versions):
    engine, sweep, ids, v0, v1 = versions
    plan = MigrationPlanner(engine).plan(ids, v0, v1)
    n_moved, mat = sweep.movement_matrix(ids, v0, v1, N_NODES + 1)
    assert n_moved == plan.n_moves
    ref = np.zeros((N_NODES + 1, N_NODES + 1), dtype=np.int64)
    np.add.at(ref, (plan.src, plan.dst), 1)
    assert np.array_equal(mat, ref)
    rplan = MigrationPlanner(engine).plan_replicas(ids, v0, v1, 3)
    rn, rmat = sweep.movement_matrix(ids, v0, v1, N_NODES + 1, n_replicas=3)
    assert rn == rplan.n_moves == rmat.sum()


def test_planner_mesh_kwarg_is_bit_identical(versions):
    engine, sweep, ids, v0, v1 = versions
    planner = MigrationPlanner(engine)
    plan = planner.plan(ids, v0, v1)
    for mesh_arg in (sweep, sweep.mesh):
        splan = planner.plan(ids, v0, v1, mesh=mesh_arg)
        for f in ("ids", "src", "dst", "index", "slot", "src_slot"):
            assert np.array_equal(getattr(plan, f), getattr(splan, f))
    rplan = planner.plan_replicas(ids, v0, v1, 3)
    srplan = planner.plan_replicas(ids, v0, v1, 3, mesh=sweep)
    for f in ("ids", "src", "dst", "index", "slot", "src_slot"):
        assert np.array_equal(getattr(rplan, f), getattr(srplan, f))


def test_rejects_non_data_mesh(versions):
    import jax

    engine = versions[0]
    bad = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("model",))
    with pytest.raises(ValueError, match="must be 1-D"):
        ShardedSweep(engine, bad)


def test_forced_host_devices_refused_off_cpu(monkeypatch):
    """On a chip, neither the selftest CLI nor the CPU scaling workers may
    force host devices: both raise before any child or compile starts."""
    import jax

    from benchmarks import scaling
    from repro.launch.placement_mesh import force_host_devices

    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="needs the CPU platform"):
        force_host_devices(4)
    monkeypatch.setattr(scaling, "_CACHE", {})
    monkeypatch.setattr(
        scaling, "_run_worker", lambda *a: pytest.fail("worker started")
    )
    with pytest.raises(RuntimeError, match="needs the CPU platform"):
        scaling.measure(quick=True)


# ---------------------------------------------------------------------------
# pow2 tail bucketing of the streaming planner (satellite: no phantom moves)
# ---------------------------------------------------------------------------


def test_pad_pow2_buckets_and_passthrough():
    full = np.arange(1024, dtype=np.uint32)
    padded, n = MigrationPlanner._pad_pow2(full)
    assert padded is full and n == 1024  # pow2 chunks: untouched fast path
    for ragged in (1000, 900, 513):
        padded, n = MigrationPlanner._pad_pow2(
            np.arange(ragged, dtype=np.uint32)
        )
        assert n == ragged
        assert padded.shape[0] == 1024  # same bucket -> same diff compile
        assert not np.any(padded[ragged:])
    padded, _ = MigrationPlanner._pad_pow2(np.arange(6, dtype=np.uint32), 4)
    assert padded.shape[0] == 8  # pow2 already divisible by the mesh


def test_ragged_stream_chunks_produce_no_phantom_moves(versions):
    """Streamed moved-count must equal the assembled plan's n_moves for
    chunkings whose tails are ragged: the pad lanes (zero-filled ids)
    MUST be masked out of ``moved``, not trusted to place identically
    under both table versions."""
    engine, sweep, ids, v0, v1 = versions
    planner = MigrationPlanner(engine)
    want = planner.plan(ids, v0, v1).n_moves
    for chunk, mesh_arg in ((1000, None), (1 << 10, None), (777, sweep)):
        total = 0
        for padded, moved, _, _ in planner.plan_stream(
            planner.chunked(ids, chunk), v0, v1, mesh=mesh_arg
        ):
            m = np.asarray(moved)
            assert m.shape[0] == padded.shape[0]
            total += int(m.sum())
        assert total == want, f"phantom/lost moves at chunk={chunk}"


def test_ragged_replica_stream_no_phantom_moves(versions):
    engine, sweep, ids, v0, v1 = versions
    planner = MigrationPlanner(engine)
    want = planner.plan_replicas(ids, v0, v1, 3).n_moves
    for chunk, mesh_arg in ((1000, None), (777, sweep)):
        total = 0
        for _, moved, _, _, _ in planner.plan_replicas_stream(
            planner.chunked(ids, chunk), v0, v1, 3, mesh=mesh_arg
        ):
            total += int(np.asarray(moved).sum())
        assert total == want, f"phantom/lost replica moves at chunk={chunk}"


def test_device_chunk_tail_pads_on_device(versions):
    """A ragged DEVICE-array chunk must pad on device (no silent host
    round-trip) and still mask its tail."""
    import jax.numpy as jnp

    engine, _, _, v0, v1 = versions
    planner = MigrationPlanner(engine)
    chunk = jnp.arange(900, dtype=jnp.uint32)
    [(padded, moved, _, _)] = list(planner.plan_stream([chunk], v0, v1))
    assert padded.shape[0] == 1024
    assert np.asarray(moved)[900:].sum() == 0
    want = planner.plan(np.arange(900, dtype=np.uint32), v0, v1).n_moves
    assert int(np.asarray(moved).sum()) == want
