"""The benchmark's harness: finds a cell's files by name and runs it once.

``BENCHMARK.json`` names every cell (``workloads``), its configuration and
its traffic mix.  Everything that belongs to one of them sits in a file of
its own, found by that name:

* ``configs/<config>.json``     -- the deployment (sizes, guarantees);
* ``references/<reference>.py`` -- the configuration's plain reference,
  named by the configuration's ``reference`` key;
* ``traffic/<traffic>.json``    -- the traffic mix, read by ``generate.py``,
  whose ``entry`` key names the driver in ``entries/<entry>.py``;
* ``metrics/<metric>.py``       -- one reader per per-layer metric.

A later cell adds files and manifest entries; no existing file changes.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import re
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT_DIR = ".bench_out"  # run outputs inside the checkout (gitignored)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file by path (metric names carry dots, so no package)."""
    path = os.path.abspath(path)
    name = "bench_" + re.sub(r"\W", "_", path)
    mod = sys.modules.get(name)
    if mod is not None:
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(manifest: dict, workload: str, root: str = ROOT) -> dict:
    """Everything one cell needs: its manifest entry, configuration,
    traffic, entry module and the metrics it reports."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg_entry = configs[cell["config"]]
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    bench = os.path.join(root, os.path.basename(BENCH))
    traffic = _read_json(os.path.join(bench, "traffic", cell["traffic"] + ".json"))
    e2e = [m for m in manifest["end_to_end"] if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in manifest["per_layer"]
        if (workload in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
    ]
    return {
        "bench": bench,
        "workload": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def entry_module(spec: dict):
    return load_module(os.path.join(spec["bench"], "entries", spec["traffic"]["entry"] + ".py"))


def reference_module(spec: dict):
    return load_module(os.path.join(spec["bench"], "references", spec["config"]["reference"] + ".py"))


def metric_reader(spec: dict, name: str):
    return load_module(os.path.join(spec["bench"], "metrics", name + ".py"))


class Recorder:
    """The benchmark's own host-clock spans and facts for one run.

    ``span`` records ``(name, t0, t1)`` on ``time.perf_counter`` and, while
    a profiler trace is on, also writes the span into the trace as a
    ``TraceAnnotation``, so device idle gaps can be attributed to what the
    host was doing."""

    def __init__(self, tracing: bool = False):
        self.tracing = tracing
        self.spans: list[tuple[str, float, float]] = []
        self.facts: dict = {}

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def spans_named(self, name: str) -> list[tuple[float, float]]:
        return [(a, b) for n, a, b in self.spans if n == name]


class CompileCounter:
    """Counts compiles and persistent-cache loads while armed: the window
    must have none."""

    EVENTS = (
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_hits",
    )

    def __init__(self):
        self.armed = False
        self.count = 0
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def close(self) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        self._on_event(event)

    def _on_event(self, event, **_kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


def run_cell(spec: dict, *, seed: int, seconds: float, trace: bool, t_start: float,
             system=None, backend: str = "auto", sizes: dict | None = None,
             log=None) -> dict:
    """Set up, measure and check one cell; return the result object.

    ``system`` replaces the timed path (the control and the fault tests use
    it); ``sizes`` overrides configuration sizes (tests only)."""
    import jax

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    config = dict(spec["config"], **(sizes or {}))
    rec = Recorder(tracing=trace)
    compiles = CompileCounter()
    cell = entry_module(spec).Cell(
        config=config, traffic=spec["traffic"], seed=seed, seconds=seconds,
        chips=spec["workload"]["chips"],
        rec=rec, reference=reference_module(spec), system=system, backend=backend, log=log,
    )
    cell.setup()
    setup_s = time.perf_counter() - t_start
    phases = ", ".join(f"{n} {b - a:.3f} s" for n, a, b in rec.spans if n.startswith("setup."))
    log(f"set-up: {setup_s:.3f} s from process start: {phases}")
    rec.spans = []  # per-layer readers see the window's spans only
    trace_dir = None
    if trace:
        trace_dir = os.path.join(ROOT, OUT_DIR, "trace", spec["workload"]["name"])
        _empty_dir(trace_dir)
        jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
    compiles.armed = True
    try:
        with rec.span("bench.window"):
            cell.window(seconds)
    finally:
        compiles.close()
        if trace:
            jax.profiler.stop_trace()
    devices = jax.devices()[: spec["workload"]["chips"]]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    e2e = cell.end_to_end()
    e2e["setup_s"] = setup_s
    cell.release()
    compared = cell.check()
    log(f"window: {compiles.count} compiles or cache loads inside the window")
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    metrics = {}
    breakdown = None
    if trace:
        metrics, breakdown, busy = _per_layer(spec, rec, trace_dir, devices, log)
        device.update(busy)
    else:
        for m in spec["end_to_end"]:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    correct = all(v <= lim for v, lim in compared.values()) and cell.failed == 0
    out = {
        "correct": bool(correct),
        "attempted": int(cell.attempted),
        "failed": int(cell.failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
    return out


def profile_options():
    """Device and host trace events, with the Python call tracer off: the
    benchmark's own spans are ``TraceAnnotation``s, which the host tracer
    keeps, and tracing every Python call would slow the host loop."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def _empty_dir(path: str) -> None:
    import shutil

    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)


def _per_layer(spec, rec, trace_dir, devices, log):
    """Reduce the window's trace and run each per-layer reader."""
    import trace_reduce

    n_devices = len(devices)
    window = rec.spans_named("bench.window")[0]
    summary = trace_reduce.summarize(trace_reduce.find_xplane(trace_dir), n_devices,
                                     window_name="bench.window")
    log(f"trace: busy {summary.busy_s:.6f} s of a {summary.window_s:.6f} s window, "
        f"mean over {n_devices} device(s); worst device idle "
        f"{100 * summary.worst_idle_share:.4f}%; host window {window[1] - window[0]:.6f} s")
    peaks = load_peaks(devices[0].device_kind)
    view = {"rec": rec, "trace": summary, "peaks": peaks, "facts": rec.facts}
    metrics = {}
    for m in spec["per_layer"]:
        value = metric_reader(spec, m["name"]).read(view)
        if value is None:
            log(f"per-layer {m['name']}: nothing to read, left out")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    breakdown = {
        "device_ops": [[n, s] for n, s in summary.top_ops(10)],
        "idle_gaps": [[n, s] for n, s in summary.top_gaps(10)],
    }
    busy = {"busy_s": summary.busy_s, "window_s": summary.window_s}
    return metrics, breakdown, busy


def load_peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an error."""
    table = _read_json(os.path.join(BENCH, "peaks.json"))
    kinds = table["devices"]
    if device_kind not in kinds:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json (have {sorted(kinds)})")
    return kinds[device_kind]
