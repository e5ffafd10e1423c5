"""Sweep the offered rate of an open-loop routing cell to find its knee.

    python bench/knee.py --workload kv.zipf.open --seed 7 --seconds 10 \
        --rates 400000 800000 1200000

Sets the cell up once, then runs its window at each rate in turn and prints
one line per rate: the requests due, how long after the window's close the
last of them was served, the mean batch and the 95th-percentile latency.
The knee is the highest rate whose backlog does not grow over the window:
the last request is served within about one batch service time of the
close.  The cell's traffic file then states 80% of that rate as a number;
the benchmark's own runs never search for a rate.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import generate
    import harness
    from repro.compile_cache import enable_compile_cache

    spec = harness.resolve(harness.load_manifest(ROOT), args.workload)
    enable_compile_cache(ROOT)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("knee: JAX found no TPU", file=sys.stderr)
        return 2
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    cell = harness.entry_module(spec).Cell(
        config=spec["config"], traffic=spec["traffic"], seed=args.seed, seconds=args.seconds,
        chips=1, rec=harness.Recorder(), reference=harness.reference_module(spec),
        system=None, backend="auto", log=log,
    )
    cell.setup()
    for rate in args.rates:
        cell.due = generate.poisson_arrivals(rate, args.seconds, args.seed, cell.min_batch)
        cell._open(args.seconds)
        sizes = [b for _, b, _, _ in cell.batch_log]
        print(json.dumps({
            "rate_per_s": rate, "requests": int(len(cell.due)),
            "drain_after_close_s": float(cell.elapsed - args.seconds),
            "mean_batch": float(np.mean(sizes)), "batches": len(sizes),
            "p95_ms": 1e3 * float(np.percentile(cell.latency, 95)),
            "service_ms": cell.rec.facts["service_ms"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
