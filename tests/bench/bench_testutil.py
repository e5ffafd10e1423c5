"""Shared set-up of the benchmark's tests: runs a cell through the whole
harness on the CPU at a size a test can hold, skipping only the look for a
chip."""

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

# configuration and traffic sizes per cell for CPU tests
TINY = {
    "kv.zipf.closed": ({"nodes": 64}, {"batch": 1024, "pool_batches": 4, "check_sample": 4096}),
    "kv.zipf.open": ({"nodes": 64}, {"batch": 1024, "min_batch": 64, "pool_batches": 4,
                                     "rate_per_s": 20000,
                                     "check_sample": 4096}),
    "rebal.add-remove": ({"nodes": 64, "tracked_objects": 1 << 14, "planner_chunk": 1 << 12},
                         {"check_sample": 8192}),
}
SEED = 2**31 + 977  # above 32 signed bits: run seeds may be that large


# The open-loop mix (bench/traffic/zipf.open.json) has no cell yet: its
# 95th-percentile latency spread too widely on the chip to hold a bound.
# The tests still drive its path through a cell of their own.
OPEN_CELL = {"name": "kv.zipf.open", "config": "ycsb-c-1024", "traffic": "zipf.open",
             "chips": 1, "why": "open loop"}


def manifest(root=ROOT):
    m = harness.load_manifest(root)
    if all(w["name"] != OPEN_CELL["name"] for w in m["workloads"]):
        m["workloads"].append(OPEN_CELL)
        m["end_to_end"].append({"name": "route_p95_ms", "unit": "ms", "better": "lower",
                                "bound": 0.25, "source": "host_clock",
                                "workloads": [OPEN_CELL["name"]]})
        for e in m["end_to_end"]:
            if e["name"] == "load_skew":
                e["workloads"].append(OPEN_CELL["name"])
    return m


def spec_for(workload, root=ROOT):
    spec = harness.resolve(manifest(root), workload, root=root)
    base = workload if workload in TINY else workload.rsplit(".", 1)[0]
    sizes, traffic = TINY[base]
    spec["traffic"] = {**spec["traffic"], **traffic}
    return spec, sizes


def run(workload, *, system=None, seed=SEED, seconds=0.5, root=ROOT):
    spec, sizes = spec_for(workload, root)
    return harness.run_cell(
        spec, seed=seed, seconds=seconds, trace=False, t_start=time.perf_counter(),
        system=system, backend="ref", sizes=sizes, log=lambda _msg: None,
    )


def entry(workload):
    spec, _ = spec_for(workload)
    return harness.entry_module(spec)
