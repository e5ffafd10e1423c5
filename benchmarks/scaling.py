"""Weak/strong scaling of the sharded placement/diff sweeps (DESIGN.md 11).

The device count is locked at first jax init, so every mesh size gets its
own SUBPROCESS worker: the parent calls ``measure(quick)`` which launches

    python -m benchmarks.scaling --worker --devices N [--quick]

once per device count (``--xla_force_host_platform_device_count=N`` set in
the worker's env before its first jax import -- the ``launch/dryrun.py``
trick).  One worker measures all three sweep families -- uniformity
histogram (``ShardedSweep.histogram``), single-owner planner stream and
R=3 replica planner stream (``MigrationPlanner.plan*_stream(mesh=...)``)
-- at both a FIXED total population (strong scaling) and a FIXED
per-device population (weak scaling), and prints one JSON line.

Results are cached per process, so the head_to_head / movement / migrate
suites emitting scaling entries in one ``benchmarks.run`` invocation share
a single worker sweep (4 subprocesses quick, not 12).

Forced host devices time-slice the host's real cores: speedups track the
physical core count, not the forced device count (a single-core runner
measures ~1x -- the committed baselines record what the baseline machine
saw, and the perf gate's calibration normalization absorbs machine
differences).

This is CPU-only by construction: the workers run with
``JAX_PLATFORMS=cpu``, and a parent on any other platform refuses to start
them (``require_cpu``) -- it holds the chip, and a child reaching for it
would hang or measure the wrong device.  Scaling across real chips is
one-process mesh work, not this worker sweep.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from repro.launch.placement_mesh import force_host_devices, require_cpu

N_NODES = 128

# strong: fixed total ids; weak: fixed ids PER DEVICE.
STRONG_IDS = 1 << 22
WEAK_IDS_PER_DEV = 1 << 20
CHUNK = 1 << 20
DEVICE_COUNTS = (1, 2, 4, 8)

QUICK_STRONG_IDS = 1 << 19
QUICK_WEAK_IDS_PER_DEV = 1 << 17
QUICK_CHUNK = 1 << 16
QUICK_DEVICE_COUNTS = (1, 2, 4)

N_REPLICAS = 3

METRICS = ("uniformity", "planner", "replica_planner")

_CACHE: dict[bool, dict[int, dict]] = {}


def device_counts(quick: bool) -> tuple[int, ...]:
    return QUICK_DEVICE_COUNTS if quick else DEVICE_COUNTS


def measure(quick: bool) -> dict[int, dict]:
    """{device_count: worker result dict}, one subprocess per count,
    cached for the life of the benchmark process."""
    quick = bool(quick)
    if quick not in _CACHE:
        # the workers are CPU-only; a parent on a chip would hold it while
        # they run and report CPU numbers as if they were the chip's
        require_cpu("benchmarks.scaling")
        _CACHE[quick] = {n: _run_worker(n, quick) for n in device_counts(quick)}
    return _CACHE[quick]


def _run_worker(n_devices: int, quick: bool) -> dict:
    cmd = [sys.executable, "-m", "benchmarks.scaling", "--worker",
           "--devices", str(n_devices)]
    if quick:
        cmd.append("--quick")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices} "
        + env.get("XLA_FLAGS", "")
    ).strip()
    proc = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"scaling worker ({n_devices} devices) failed:\n{proc.stderr[-2000:]}"
        )
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(
        f"scaling worker ({n_devices} devices) printed no JSON:\n"
        f"{proc.stdout[-2000:]}"
    )


def emit(csv_print, quick: bool, prefix: str, metric: str) -> None:
    """Emit one sweep family's scaling entries into a suite's BENCH JSON:
    per-device-count throughputs plus the 4-device strong/weak speedup
    ratios the acceptance gate watches (unit ``x_speedup`` -- higher is
    better, compared raw: machine speed cancels in the ratio)."""
    if metric not in METRICS:
        raise ValueError(f"metric must be one of {METRICS}, got {metric!r}")
    res = measure(quick)
    for n, r in sorted(res.items()):
        csv_print(
            f"{prefix}_strong_{n}dev_ids_per_s",
            int(r[f"{metric}_strong_ids_per_s"]),
            "ids_per_s",
        )
        csv_print(
            f"{prefix}_weak_{n}dev_ids_per_s",
            int(r[f"{metric}_weak_ids_per_s"]),
            "ids_per_s",
        )
    base = res[min(res)]
    top = 4 if 4 in res else max(res)
    for kind in ("strong", "weak"):
        ratio = (
            res[top][f"{metric}_{kind}_ids_per_s"]
            / max(base[f"{metric}_{kind}_ids_per_s"], 1e-9)
        )
        csv_print(f"{prefix}_{kind}_{top}dev_x_speedup", ratio, "x_speedup")


# ---------------------------------------------------------------------------
# Worker (runs under --xla_force_host_platform_device_count=N)
# ---------------------------------------------------------------------------


def _best_of(fn, repeats: int = 3) -> float:
    fn()  # warm: compile + artifact upload
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _worker(n_devices: int, quick: bool) -> dict:
    import numpy as np

    from repro.core import PlacementEngine, make_uniform_cluster
    from repro.launch.placement_mesh import ShardedSweep, make_data_mesh
    from repro.migrate import MigrationPlanner

    strong = QUICK_STRONG_IDS if quick else STRONG_IDS
    weak = (QUICK_WEAK_IDS_PER_DEV if quick else WEAK_IDS_PER_DEV) * n_devices
    chunk = QUICK_CHUNK if quick else CHUNK

    cluster = make_uniform_cluster(N_NODES)
    engine = PlacementEngine(cluster, backend="ref")
    mesh = make_data_mesh(n_devices)
    sweep = ShardedSweep(engine, mesh)
    engine.artifact()
    v0 = cluster.version
    cluster.add_node(N_NODES, 1.0)
    v1 = cluster.version
    planner = MigrationPlanner(engine)

    out: dict = {"devices": n_devices, "quick": quick}
    for kind, n_ids in (("strong", strong), ("weak", weak)):
        ids = np.arange(n_ids, dtype=np.uint32)

        out[f"uniformity_{kind}_ids_per_s"] = n_ids / _best_of(
            lambda: sweep.histogram(ids, N_NODES + 1)
        )

        def drain_plan():
            for _, moved, _, _ in planner.plan_stream(
                planner.chunked(ids, chunk), v0, v1, mesh=sweep
            ):
                moved.block_until_ready()

        out[f"planner_{kind}_ids_per_s"] = n_ids / _best_of(drain_plan)

        def drain_replicas():
            for _, moved, _, _, _ in planner.plan_replicas_stream(
                planner.chunked(ids, chunk), v0, v1, N_REPLICAS, mesh=sweep
            ):
                moved.block_until_ready()

        out[f"replica_planner_{kind}_ids_per_s"] = n_ids / _best_of(
            drain_replicas
        )
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)
    if not args.worker:
        # standalone: print the full scaling table (parent mode)
        for n, r in measure(args.quick).items():
            print(json.dumps(r))
        return 0
    force_host_devices(args.devices)
    print(json.dumps(_worker(args.devices, args.quick)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
