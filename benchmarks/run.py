"""Benchmark harness: one module per paper table/figure.

Prints ``name,value,derived`` CSV lines AND writes one machine-readable
``BENCH_<suite>.json`` per suite run (the perf trajectory the ROADMAP
tracks; CI uploads them as workflow artifacts so every PR records a perf
point).  Usage:

    PYTHONPATH=src python -m benchmarks.run [--only fig5,table2,...]
        [--quick] [--out-dir DIR]

``--quick`` asks suites that support it for a reduced-size run (the CI
smoke configuration).
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

from repro.compile_cache import enable_compile_cache

from . import (
    actual_usage,
    calc_time,
    capacity,
    durability,
    head_to_head,
    memory,
    migrate,
    movement,
    obs,
    replicas,
    roofline,
    serve,
    uniformity,
)

SUITES = {
    "fig5_calc_time": calc_time,
    "table2_memory": memory,
    "fig67_uniformity": uniformity,
    "movement": movement,
    "migrate": migrate,
    "replicas": replicas,
    "head_to_head": head_to_head,
    "serve": serve,
    "obs": obs,
    "table3_actual_usage": actual_usage,
    "capacity": capacity,
    "roofline": roofline,
    "durability": durability,
}


def csv_print(name: str, value, derived="") -> None:
    print(f"{name},{value},{derived}", flush=True)


def _json_path(mod, out_dir: str) -> str:
    short = mod.__name__.rsplit(".", 1)[-1]
    return os.path.join(out_dir, f"BENCH_{short}.json")


def run_suite(name: str, mod, *, quick: bool, out_dir: str) -> None:
    """Run one suite, teeing every entry to CSV stdout and BENCH_*.json."""
    entries: dict[str, dict] = {}

    def record(entry_name: str, value, derived="") -> None:
        csv_print(entry_name, value, derived)
        entries[entry_name] = {"value": value, "unit": str(derived)}

    kwargs = {}
    if "quick" in inspect.signature(mod.run).parameters:
        kwargs["quick"] = quick
    t0 = time.time()
    mod.run(record, **kwargs)
    payload = {
        "suite": name,
        "quick": quick,
        "elapsed_s": round(time.time() - t0, 3),
        "entries": entries,
    }
    path = _json_path(mod, out_dir)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path} ({len(entries)} entries)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated suite substrings")
    ap.add_argument(
        "--quick",
        action="store_true",
        help="reduced-size run (CI smoke) for suites that support it",
    )
    ap.add_argument(
        "--out-dir", default=".", help="directory for the BENCH_*.json files"
    )
    args = ap.parse_args(argv)
    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    os.makedirs(args.out_dir, exist_ok=True)
    picks = args.only.split(",") if args.only else None
    for name, mod in SUITES.items():
        if picks and not any(p in name for p in picks):
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            run_suite(name, mod, quick=args.quick, out_dir=args.out_dir)
        except Exception as e:  # pragma: no cover
            print(f"{name},ERROR,{e}", file=sys.stderr)
            return 1
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
