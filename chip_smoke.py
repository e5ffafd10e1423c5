"""On-chip smoke of the serve -> plan -> move path.

    python chip_smoke.py               # one TPU chip: serve, node add, node remove
    python chip_smoke.py --four-chips  # four chips: the mesh path vs one device

One chip.  A 1,024-node cluster with seeded mixed capacities (0.5-2.0)
serves YCSB-style Zipf traffic (constant 0.99) over 2^22 keys through ASURA
R=3 replica routing with power-of-two-choices selection, in scan-fused
supersteps of 8 x 65,536 requests.  Then 2^24 tracked object ids go through
one node addition and one node removal: each change is planned by the
streaming replica planner and drained by the throttled mover in device
round blocks.  Every result is checked against an oracle -- the scalar
replica placement, the host NumPy engine, the plan itself -- and a failed
check raises, so the process exits non-zero.

Four chips.  ``placement_mesh.selftest`` at 1,024 nodes, 2^22+1 ids and a
65,536-request serving batch: sharded placement, histograms, planner,
serving stream and supersteps must equal the single-device results bit for
bit, with the ids partitioned and the tables replicated over the mesh.

Data comes from ``--seed``.  The times printed are wall times of this one
run, compile included where so labelled; they are not benchmark numbers.
The last line of stdout is one JSON status object.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

N_REPLICAS = 3
ZIPF_CONSTANT = 0.99  # YCSB's default zipfian constant


class CheckFailed(RuntimeError):
    """An oracle comparison of the smoke run did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def _object_ids_fn():
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.ref import fmix32

    @functools.partial(jax.jit, static_argnames=("n",))
    def object_ids(start, salt, *, n: int):
        # fmix32 is a bijection on u32, so distinct counters give distinct ids
        return fmix32(start + jnp.arange(n, dtype=jnp.uint32) + salt)

    return object_ids


def serve_phase(engine, cluster, *, n_keys, batch, k, supersteps, sample_lanes,
                seed, log) -> dict:
    """Warm up, then ``supersteps`` scan-fused supersteps of ``k`` batches;
    check served holders against the scalar replica oracle and the load
    counters against the request count."""
    import jax
    import jax.numpy as jnp

    from repro.core.asura import place_replicas_scalar
    from repro.serve import RequestStreamDriver, TrafficModel

    driver = RequestStreamDriver(
        engine, batch=batch, n_keys=n_keys, law="zipf", alpha=ZIPF_CONSTANT,
        n_replicas=N_REPLICAS, policy="pow2", seed=seed,
    )
    t0 = time.perf_counter()
    driver.superstep(k).block_until_ready()
    compile_s = time.perf_counter() - t0
    driver.reset()
    t0 = time.perf_counter()
    for _ in range(supersteps):
        chosen = driver.superstep(k)
    chosen.block_until_ready()
    steady_s = time.perf_counter() - t0
    requests = supersteps * k * batch

    counts = driver.load_counts()
    check(int(counts.sum()) == requests,
          f"load counters sum to {int(counts.sum())}, not {requests} requests")
    check(driver.superstep_traces == 1, "superstep retraced after warm-up")

    # served holders of sampled lanes, spread over the last superstep's k
    # sub-batches, must each lie in the scalar oracle's replica set
    chosen_np = np.asarray(chosen)
    lengths, seg_node = cluster.seg_lengths(), cluster.seg_to_node()
    rng = np.random.default_rng(seed)
    per_row = -(-sample_lanes // k)
    key = jax.random.PRNGKey(seed)
    n_checked = 0
    for row in range(k):
        lanes = np.sort(rng.choice(batch, size=min(per_row, batch), replace=False))
        step = driver.steps_done - k + row
        ids, _ = TrafficModel.draw(
            key, jnp.int32(step), jnp.asarray(lanes, dtype=jnp.uint32),
            driver.traffic.thresholds_dev, driver.traffic.id_salt,
        )
        for datum, node in zip(np.asarray(ids).tolist(), chosen_np[row, lanes].tolist()):
            segs = place_replicas_scalar(
                datum, lengths, seg_node, N_REPLICAS, cluster.params
            )
            check(node in {int(seg_node[s]) for s in segs},
                  f"id {datum} served by node {node}, outside its replica set")
            n_checked += 1
    log(f"serve: {requests} requests in {supersteps} supersteps of "
        f"{k} x {batch}; compile+first superstep {compile_s:.3f} s, "
        f"steady {steady_s:.3f} s (wall, one run); skew "
        f"{driver.load_skew():.4f}, queue p99 {driver.queue_p99()}; "
        f"{n_checked} served holders in the oracle replica sets")
    return {"requests": requests, "compile_s": compile_s, "steady_s": steady_s,
            "sampled": n_checked}


def _collect_plan(stream, sizes, v_from, v_to, *, np_engine, oracle_ids):
    """Assemble a ``MigrationPlan`` from ``plan_replicas_stream`` chunks of
    ``sizes`` ids each (a ragged chunk comes back pow2-padded), checking
    the first ``oracle_ids`` lanes against the host NumPy engine."""
    from repro.migrate import MigrationPlan

    parts = {k: [] for k in ("ids", "src", "dst", "idx", "slot", "src_slot")}
    base = 0
    n_oracle = 0
    oracle_s = 0.0
    for n, (ids_d, moved_d, src_d, dst_d, slot_d) in zip(sizes, stream, strict=True):
        check(not np.asarray(moved_d)[n:].any(), "a pad lane of the plan moved")
        ids, moved = np.asarray(ids_d)[:n], np.asarray(moved_d)[:n]
        src, dst = np.asarray(src_d)[:n], np.asarray(dst_d)[:n]
        src_slot = np.asarray(slot_d)[:n]
        if n_oracle < oracle_ids:
            take = min(oracle_ids - n_oracle, n)
            t0 = time.perf_counter()
            want = np_engine.diff_replicas_at(ids[:take], v_from, v_to, N_REPLICAS)
            oracle_s += time.perf_counter() - t0
            for name, got, ref in zip(("moved", "src", "dst", "src_slot"),
                                      (moved, src, dst, src_slot), want):
                check(np.array_equal(got[:take], ref),
                      f"plan {name} differs from the NumPy engine")
            n_oracle += take
        b, r = np.nonzero(moved)
        parts["ids"].append(ids[b])
        parts["src"].append(src[b, r].astype(np.int64))
        parts["dst"].append(dst[b, r].astype(np.int64))
        parts["idx"].append(base + b.astype(np.int64))
        parts["slot"].append(r.astype(np.int32))
        parts["src_slot"].append(src_slot[b, r].astype(np.int32))
        base += n
    cat = {k: np.concatenate(v) for k, v in parts.items()}
    plan = MigrationPlan(
        v_from=v_from, v_to=v_to, ids=cat["ids"], src=cat["src"],
        dst=cat["dst"], index=cat["idx"], n_scanned=base,
        n_replicas=N_REPLICAS, slot=cat["slot"], src_slot=cat["src_slot"],
    )
    return plan, n_oracle, oracle_s


def _drain(plan, *, blocks, k, log, label) -> dict:
    """Drain ``plan`` with ``blocks`` round blocks of ``k`` rounds under a
    per-node budget sized to finish exactly then; check that every admitted
    row landed, no budget was exceeded, and the moves match the plan."""
    from repro.migrate import MigrationState, ThrottledMover

    budget = max(1, math.ceil(plan.n_moves / (blocks * k)))
    state = MigrationState(plan)
    mover = ThrottledMover(state, egress=budget, ingress=budget)
    times = []
    for _ in range(blocks):
        t0 = time.perf_counter()
        matrices = mover.round_block(k)
        times.append(time.perf_counter() - t0)
        for m in matrices:
            out_of, into = {}, {}
            for (s, d), c in m.items():
                out_of[s] = out_of.get(s, 0) + c
                into[d] = into.get(d, 0) + c
            check(max(out_of.values(), default=0) <= budget, "egress budget exceeded")
            check(max(into.values(), default=0) <= budget, "ingress budget exceeded")
    admitted = sum(sum(m.values()) for m in mover.history)
    landed = int(state.landed.sum())
    check(admitted == landed, f"{label}: {admitted} rows admitted, {landed} landed")
    check(mover.done and landed == plan.n_moves,
          f"{label}: {landed} of {plan.n_moves} rows landed")
    pairs, counts = np.unique(np.stack([plan.src, plan.dst], 1), axis=0,
                              return_counts=True)
    want = {(int(s), int(d)): int(c) for (s, d), c in zip(pairs, counts)}
    check(mover.movement_matrix() == want, f"{label}: moves differ from the plan")
    log(f"{label} mover: {landed} rows in {blocks} blocks of {k} rounds "
        f"(budget {budget} rows/node/round); compile+first block "
        f"{times[0]:.3f} s, later blocks {sum(times[1:]):.3f} s (wall, one run)")
    return {"rows": landed, "first_block_s": times[0], "later_blocks_s": sum(times[1:])}


def membership_phase(engine, np_engine, cluster, *, n_objects, chunk,
                     oracle_ids, mover_blocks, mover_k, seed, log) -> dict:
    """Add one node, then remove one; plan each change over ``n_objects``
    device-generated ids and drain it with the throttled mover."""
    import jax.numpy as jnp

    from repro.migrate import MigrationPlanner

    rng = np.random.default_rng(seed + 1)
    planner = MigrationPlanner(engine)
    object_ids = _object_ids_fn()
    salt = jnp.uint32(int(rng.integers(0, 2**32)))

    sizes = [min(chunk, n_objects - start) for start in range(0, n_objects, chunk)]

    def chunks():
        for i, n in enumerate(sizes):
            yield object_ids(jnp.uint32(i * chunk), salt, n=n)

    new_node = max(cluster.nodes) + 1
    events = (
        ("add", lambda: cluster.add_node(new_node, float(rng.uniform(0.5, 2.0)))),
        ("remove", lambda: cluster.remove_node(int(rng.choice(sorted(cluster.nodes))))),
    )
    out = {}
    for label, mutate in events:
        engine.artifact()  # pin v in both engines' LRUs before mutating
        np_engine.artifact()
        before = set(cluster.nodes)
        v_from = cluster.version
        mutate()
        v_to = cluster.version
        t0 = time.perf_counter()
        plan, n_oracle, oracle_s = _collect_plan(
            planner.plan_replicas_stream(chunks(), v_from, v_to, N_REPLICAS),
            sizes, v_from, v_to, np_engine=np_engine, oracle_ids=oracle_ids,
        )
        plan_s = time.perf_counter() - t0 - oracle_s
        check(plan.n_scanned == n_objects, f"{label}: scanned {plan.n_scanned} ids")
        check(plan.n_moves > 0, f"{label}: nothing to move")
        # minimal movement: only the changed node's replicas move
        if label == "add":
            (changed,) = set(cluster.nodes) - before
            check(bool(np.all(plan.dst == changed)), "add: a row moves elsewhere")
        else:
            (changed,) = before - set(cluster.nodes)
            check(bool(np.all(plan.src == changed)), "remove: a row leaves elsewhere")
        log(f"{label} node {changed}: planned {n_objects} ids -> {plan.n_moves} "
            f"rows in {plan_s:.3f} s (wall, one run; host assembly included, "
            f"compile too on the first change); rows on {n_oracle} ids equal "
            f"the NumPy engine ({oracle_s:.3f} s, not in the plan time)")
        out[label] = {"rows": plan.n_moves, "plan_s": plan_s,
                      **_drain(plan, blocks=mover_blocks, k=mover_k, log=log,
                               label=label)}
    return out


def run_phases(
    *,
    n_nodes: int = 1024,
    n_keys: int = 1 << 22,
    batch: int = 1 << 16,
    k: int = 8,
    supersteps: int = 3,
    sample_lanes: int = 4096,
    n_objects: int = 1 << 24,
    chunk: int = 1 << 20,
    oracle_ids: int = 1 << 20,
    mover_blocks: int = 3,
    mover_k: int = 8,
    seed: int = 0,
    backend: str = "auto",
    log=print,
) -> dict:
    """The one-chip smoke at the given sizes: build the cluster, then the
    serve phase and the membership phase.  Raises ``CheckFailed`` on the
    first oracle mismatch."""
    from repro.core import PlacementEngine, make_cluster

    rng = np.random.default_rng(seed)
    cluster = make_cluster(rng.uniform(0.5, 2.0, n_nodes))
    engine = PlacementEngine(cluster, backend=backend)
    check(engine.backend != "numpy", "the engine resolved to the host NumPy backend")
    np_engine = PlacementEngine(cluster, backend="numpy")
    log(f"backend: {engine.backend} (requested {backend!r}); {n_nodes} nodes, "
        f"{len(cluster.seg_lengths())} segments, R={N_REPLICAS}")
    serve = serve_phase(
        engine, cluster, n_keys=n_keys, batch=batch, k=k, supersteps=supersteps,
        sample_lanes=sample_lanes, seed=seed, log=log,
    )
    membership = membership_phase(
        engine, np_engine, cluster, n_objects=n_objects, chunk=chunk,
        oracle_ids=oracle_ids, mover_blocks=mover_blocks, mover_k=mover_k,
        seed=seed, log=log,
    )
    return {"backend": engine.backend, "serve": serve, **membership}


def run_four_chips(log=print) -> int:
    """The mesh selftest at deployment size over the first four devices."""
    from repro.launch.placement_mesh import selftest

    t0 = time.perf_counter()
    n = selftest(4, n_ids=(1 << 22) + 1, n_nodes=1024, serve_nodes=1024,
                 serve_batch=1 << 16, n_keys=1 << 22)
    log(f"mesh selftest: bit-identical to one device on {n} devices, ids "
        f"partitioned and tables replicated; {time.perf_counter() - t0:.3f} s "
        "(wall, one run, compile included)")
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh path and its comparator")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache = enable_compile_cache(ROOT)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              "this smoke runs only on the chip", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found {len(devices)}",
              file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    print(f"device: {kind}, {len(devices)} visible; compile cache: {cache}",
          flush=True)
    log = lambda msg: print(msg, flush=True)  # noqa: E731
    if args.four_chips:
        run_four_chips(log)
    else:
        run_phases(seed=args.seed, log=log)
    for d in devices[: 4 if args.four_chips else 1]:
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use", "not reported")
        print(f"peak_bytes_in_use {d}: {peak}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
