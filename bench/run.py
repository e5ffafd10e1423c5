"""Run one benchmark cell once on the chip.

    python bench/run.py --workload kv.zipf.closed --seed 7 --seconds 10 --trace 0

Reads ``BENCHMARK.json`` at the checkout's root, sets the cell up from
``--seed``, measures for ``--seconds`` and checks what the timed path
produced against the configuration's plain reference.  With ``--trace 0``
the result holds the cell's end-to-end metrics; with ``--trace 1`` the
window runs under JAX's profiler and the result holds the per-layer
metrics.  The numbers compared are the last lines on standard error, and
the last line on standard output is the result as one JSON object.

Refuses to run (exit 2, no result) where JAX finds no TPU or fewer chips
than the cell asks for.
"""

import time

T_START = time.perf_counter()  # set-up runs from here to the window's start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    from repro.compile_cache import enable_compile_cache

    spec = harness.resolve(harness.load_manifest(ROOT), args.workload)
    chips = int(spec["workload"]["chips"])
    cache = enable_compile_cache(ROOT)
    import jax

    # every program goes to the persistent cache, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r}); "
              "this benchmark measures only on the chip", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    print(f"bench: {args.workload} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}; compile cache {cache}", file=sys.stderr, flush=True)
    out = harness.run_cell(spec, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), t_start=T_START)
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
