"""ISSUE-7 coverage: the batched device-resident serving pipeline.

  * TrafficModel statistics: chi-square goodness-of-fit of the sampled
    ranks against each law's exact pmf at a fixed seed (hand-rolled, no
    scipy: tail bins merged to keep expected counts honest, critical value
    via the Wilson-Hilferty approximation), determinism, and the exact-u32
    threshold quantization,
  * ref-vs-pallas engine backends driving bit-identical streams (ids,
    chosen nodes, counters),
  * zero host syncs per batch step: transfer guard + np.asarray tripwire +
    one table upload + a stable ``step_traces`` trace count,
  * the fused step's accounting: counters == bincount of every chosen
    node, the queue recurrence replayed on the host, ragged external
    batches through the pow2 buckets without phantom counts,
  * power-of-two-choices beating random-of-R under Zipf(1.1) at R=3,
  * the baselines' salted replica fan-out: device == numpy oracle bit for
    bit, pairwise-distinct rows, primary-first, host dispatch,
  * the cached replica probes' trace-count tripwires (router + window).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import PlacementEngine, make_uniform_cluster
from repro.kernels.baselines import (
    REPLICA_MAX_TRIES,
    baseline_place_replicas_np,
)
from repro.serve import RequestStreamDriver, Router, TrafficModel
from repro.serve.stream import select_replica

BASELINES = ("ch", "wrh", "rs")


# ---------------------------------------------------------------------------
# TrafficModel: exact thresholds, determinism, chi-square fit
# ---------------------------------------------------------------------------


def test_uniform_thresholds_are_exact_u32():
    # 2**32 divisible by n_keys: every rank gets exactly 2**32 / n draws
    n = 1 << 8
    tm = TrafficModel(n, law="uniform")
    width = 1 << 24
    expect = np.arange(1, n + 1, dtype=np.uint64) * width - 1
    assert np.array_equal(tm.thresholds.astype(np.uint64), expect)
    # boundary draws map to the right ranks
    ranks = np.asarray(
        TrafficModel.ranks_from_words(
            jnp.asarray([0, width - 1, width, 2**32 - 1], dtype=jnp.uint32),
            tm.thresholds_dev,
        )
    )
    assert list(ranks) == [0, 0, 1, n - 1]


def test_thresholds_monotone_and_total():
    for law in ("uniform", "zipf", "hotset"):
        tm = TrafficModel(1000, law=law)
        thr = tm.thresholds.astype(np.int64)
        assert thr[-1] == 2**32 - 1  # the CDF must cover every u32 draw
        assert (np.diff(thr) >= 0).all()


def test_sample_ranks_deterministic_and_id_bijection():
    tm = TrafficModel(4096, law="zipf", seed=3)
    a = tm.sample_ranks(17, 5000)
    b = tm.sample_ranks(17, 5000)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, tm.sample_ranks(18, 5000))
    # rank -> id is the salted fmix32 bijection, numpy twin == device
    ids_dev = np.asarray(
        TrafficModel.ids_from_ranks(jnp.asarray(a, dtype=jnp.uint32), tm.id_salt)
    )
    assert np.array_equal(ids_dev, tm.rank_to_id_np(a))
    ranks = np.arange(4096, dtype=np.uint32)
    assert len(np.unique(tm.rank_to_id_np(ranks))) == 4096


def _chi_square_crit(df: int, z: float = 3.09) -> float:
    """Upper-tail chi-square critical value (Wilson-Hilferty), z=3.09 is
    the ~0.1% normal quantile -- loose enough to keep a fixed seed stable."""
    h = 2.0 / (9.0 * df)
    return df * (1.0 - h + z * np.sqrt(h)) ** 3


@pytest.mark.parametrize("law", ["uniform", "zipf", "hotset"])
def test_generator_chi_square_fit(law):
    n_keys, n_draws = 512, 1 << 17
    tm = TrafficModel(n_keys, law=law, alpha=1.1, hot_keys=16, seed=0)
    ranks = tm.sample_ranks(5, n_draws)
    obs = np.bincount(ranks, minlength=n_keys).astype(np.float64)
    exp = tm.pmf * n_draws
    # merge the tail into bins with expected count >= 8 (chi-square needs
    # non-starved cells; zipf's tail ranks are individually tiny)
    order = np.argsort(-exp)
    obs_b, exp_b, o_acc, e_acc = [], [], 0.0, 0.0
    for i in order:
        o_acc += obs[i]
        e_acc += exp[i]
        if e_acc >= 8.0:
            obs_b.append(o_acc)
            exp_b.append(e_acc)
            o_acc = e_acc = 0.0
    if e_acc > 0:
        obs_b[-1] += o_acc
        exp_b[-1] += e_acc
    obs_b, exp_b = np.asarray(obs_b), np.asarray(exp_b)
    chi2 = float(((obs_b - exp_b) ** 2 / exp_b).sum())
    crit = _chi_square_crit(len(exp_b) - 1)
    assert chi2 < crit, f"{law}: chi2 {chi2:.1f} >= crit {crit:.1f}"


# ---------------------------------------------------------------------------
# The fused batch step
# ---------------------------------------------------------------------------


def _driver(engine, **kw):
    kw.setdefault("batch", 2048)
    kw.setdefault("n_keys", 1 << 14)
    kw.setdefault("n_replicas", 3)
    kw.setdefault("policy", "pow2")
    kw.setdefault("seed", 0)
    return RequestStreamDriver(engine, **kw)


def test_ref_vs_pallas_streams_bit_identical():
    cluster = make_uniform_cluster(12)
    drivers = [
        _driver(PlacementEngine(cluster, backend=b)) for b in ("ref", "pallas")
    ]
    for _ in range(3):
        a, b = (np.asarray(d.step()) for d in drivers)
        assert np.array_equal(a, b)
    assert np.array_equal(drivers[0].load_counts(), drivers[1].load_counts())


def test_step_zero_host_syncs(monkeypatch):
    cluster = make_uniform_cluster(12)
    eng = PlacementEngine(cluster, backend="ref")
    d = _driver(eng)
    d.step().block_until_ready()  # warm: table upload + fused-step compile
    assert eng.uploads == 1
    traces = d.step_traces
    real_asarray = np.asarray
    host_reads: list = []

    def tripwire(*args, **kwargs):
        host_reads.append(args)
        return real_asarray(*args, **kwargs)

    monkeypatch.setattr(np, "asarray", tripwire)
    with jax.transfer_guard("disallow"):
        for _ in range(3):
            chosen = d.step()
        chosen.block_until_ready()
    monkeypatch.undo()
    assert isinstance(chosen, jax.Array)
    assert not host_reads, f"batch step touched the host: {len(host_reads)} reads"
    assert eng.uploads == 1
    assert d.step_traces == traces, "repeated steps retraced the fused step"


def test_counts_match_chosen_and_queue_recurrence():
    cluster = make_uniform_cluster(10)
    d = _driver(PlacementEngine(cluster, backend="ref"), batch=1024)
    hist_total = np.zeros(d.n_bins, dtype=np.int64)
    q = np.zeros(d.n_bins, dtype=np.int64)
    service = d.service_rate
    for step in range(5):
        chosen = np.asarray(d.step())
        h = np.bincount(chosen, minlength=d.n_bins)
        hist_total += h
        q = np.maximum(q + h - service, 0)
        assert np.array_equal(np.asarray(d.queue), q)
        assert np.array_equal(np.asarray(d.qhist)[step], q)
    assert np.array_equal(d.load_counts(), hist_total)
    # reset rewinds the stream: the replay is bit-identical
    first = np.asarray(d.qhist)[0]
    d.reset()
    d.step()
    assert np.array_equal(np.asarray(d.qhist)[0], first)


def test_route_batch_pow2_buckets_no_phantom_counts():
    cluster = make_uniform_cluster(10)
    d = _driver(PlacementEngine(cluster, backend="ref"))
    ids = np.arange(1000, dtype=np.uint32)
    out = np.asarray(d.route_batch(ids))
    assert out.shape == (1000,)
    assert d.load_counts().sum() == 1000  # pad lanes never counted
    # chosen nodes come from each id's replica set
    sets_ = d.engine.place_replica_nodes(ids, d.n_replicas)
    assert (out[:, None] == sets_).any(axis=1).all()
    traces = d.step_traces
    out2 = np.asarray(d.route_batch(np.arange(700, dtype=np.uint32)))
    assert out2.shape == (700,)
    assert d.load_counts().sum() == 1700
    assert d.step_traces == traces, "same pow2 bucket must share one compile"


def test_pow2_beats_random_under_zipf():
    cluster = make_uniform_cluster(16)
    eng = PlacementEngine(cluster, backend="ref")
    skews = {}
    for policy in ("random", "pow2"):
        d = _driver(eng, batch=4096, law="zipf", alpha=1.1, policy=policy)
        for _ in range(8):
            d.step()
        skews[policy] = d.load_skew()
    assert skews["pow2"] < skews["random"], skews


def test_select_replica_policies():
    owners = jnp.asarray([[3, 1, 2], [5, -1, -1], [-1, -1, -1]], dtype=jnp.int32)
    counts = jnp.asarray([0, 9, 1, 4, 0, 2, 0, 0], dtype=jnp.int32)
    sel = jnp.zeros(3, dtype=jnp.uint32)  # slots i=0, j=1 everywhere
    prim = np.asarray(
        select_replica(owners, sel, counts, policy="primary", n_replicas=3)
    )
    assert list(prim) == [3, 5, 0]  # fully-invalid row clamps to 0
    p2 = np.asarray(
        select_replica(owners, sel, counts, policy="pow2", n_replicas=3)
    )
    # row 0: counts[3]=4 vs counts[1]=9 -> keep 3; row 1: -1 candidate
    # loses to the valid 5; row 2: all invalid -> clamped primary
    assert list(p2) == [3, 5, 0]
    rnd = np.asarray(
        select_replica(owners, sel, counts, policy="random", n_replicas=3)
    )
    assert list(rnd) == [3, 5, 0]


# ---------------------------------------------------------------------------
# Baseline replica fan-out (the salted rejection re-probe)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alg", BASELINES)
@pytest.mark.parametrize("R", [1, 3])
def test_baseline_fanout_device_matches_numpy_oracle(alg, R):
    cluster = make_uniform_cluster(9)
    eng = PlacementEngine(cluster, algorithm=alg, backend="ref")
    ids = np.arange(3000, dtype=np.uint32)
    art = eng.artifact()
    oracle = baseline_place_replicas_np(
        alg, ids, art.keys, art.vals, R, max_tries=REPLICA_MAX_TRIES
    )
    dev = np.asarray(eng.place_replica_nodes_device(ids, R))
    assert np.array_equal(dev, oracle)
    host = eng.place_replica_nodes(ids, R)
    assert np.array_equal(host, oracle)
    # primary-first, pairwise-distinct, converged
    assert np.array_equal(host[:, 0], eng.place_nodes(ids))
    assert (host >= 0).all()
    for r in range(R):
        for s in range(r + 1, R):
            assert (host[:, r] != host[:, s]).all()


def test_baseline_fanout_r_exceeding_nodes_raises():
    cluster = make_uniform_cluster(3)
    eng = PlacementEngine(cluster, algorithm="ch", backend="ref")
    with pytest.raises(ValueError, match="fan-out"):
        eng.place_replica_nodes(np.arange(10, dtype=np.uint32), 4)


# ---------------------------------------------------------------------------
# Cached probes: trace-count tripwires
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alg", ["asura", "ch"])
def test_router_replica_probe_trace_tripwire(alg):
    r = Router({i: 1.0 for i in range(8)}, algorithm=alg)
    ids = np.arange(512, dtype=np.uint32)
    first = np.asarray(r.route_replicas_device(ids, 3))
    assert r.probe_traces == 1
    for _ in range(3):
        out = np.asarray(r.route_replicas_device(ids, 3))
    assert r.probe_traces == 1, "repeated replica routing retraced the probe"
    assert np.array_equal(out, first)
    assert np.array_equal(out, r.route_replicas(ids, 3))
    r.route_replicas_device(ids, 2)
    assert r.probe_traces == 2  # a different R is a different probe


def test_stream_driver_factory_binds_router_algorithm():
    r = Router({i: 1.0 for i in range(6)}, algorithm="wrh")
    d = r.stream_driver(batch=512, n_keys=1 << 12, n_replicas=2, seed=1)
    assert d.algorithm == "wrh"
    chosen = np.asarray(d.step())
    assert chosen.shape == (512,)
    assert d.load_counts().sum() == 512


# ---------------------------------------------------------------------------
# The shared tail: every serving call, instrumented vs plain
# ---------------------------------------------------------------------------

_KEYS = np.random.default_rng(9).integers(0, 1 << 14, 700, dtype=np.uint32)

# Each call serves two batches and returns the chosen nodes, flattened.
_SERVING_CALLS = {
    "step": lambda d, m: [d.step() for _ in range(2)],
    "superstep": lambda d, m: [d.superstep(2)],
    "route_batch": lambda d, m: [d.route_batch(_KEYS), d.route_batch(_KEYS[:300])],
    "route_batch_migrating": lambda d, m: [
        d.route_batch(_KEYS, migration=m), d.route_batch(_KEYS[:300], migration=m)
    ],
    "serve_migrating": lambda d, m: [d.serve_migrating(m)[1] for _ in range(2)],
    "superstep_migrating": lambda d, m: [d.superstep_migrating(m, 2)[1]],
}


@pytest.mark.parametrize("call", sorted(_SERVING_CALLS))
def test_instrumented_serving_matches_plain_and_counts_what_it_routes(call):
    """Every serving entry point runs the same tail with and without the
    metrics slab: an instrumented driver and its plain twin choose the
    same nodes and keep the same counters, queue and queue ring, and the
    counters, the slab's served histogram and its routed counter count
    the valid lanes only (a ragged external batch counts no pad)."""
    from repro.obs import MetricsRegistry

    router = Router({i: 1.0 for i in range(8)})
    mig = router.begin_scale_migration(
        np.arange(20_000, dtype=np.uint32), add=(8, 1.0), n_replicas=3,
        egress={n: 60 for n in range(9)},
    )
    mig.round()
    reg = MetricsRegistry()
    kw = dict(batch=512, n_keys=1 << 12, n_replicas=3, policy="pow2", seed=5, n_bins=9)
    plain, metered = router.stream_driver(**kw), router.stream_driver(metrics=reg, **kw)
    chosen = [
        np.concatenate([np.asarray(c).ravel() for c in _SERVING_CALLS[call](d, mig)])
        for d in (plain, metered)
    ]
    assert not mig.done, "window drained; the migrating calls would serve flat"
    assert np.array_equal(chosen[0], chosen[1])
    for attr in ("counts", "queue", "qhist"):
        assert np.array_equal(np.asarray(getattr(plain, attr)),
                              np.asarray(getattr(metered, attr))), attr
    assert np.array_equal(np.asarray(metered.counts), np.bincount(chosen[1], minlength=9))
    snap = reg.snapshot()
    assert np.array_equal(snap["serve.served"], np.asarray(metered.counts))
    assert int(snap[f"serve.routed.{metered.algorithm}.pow2"]) == chosen[1].size
    assert chosen[1].size == (1000 if call.startswith("route_batch") else 1024)
