"""Reduce a JAX profiler trace of one window to device metrics.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.  Its
device planes (``/device:TPU:<i>``) carry an ``XLA Modules`` line, one event
per run of a compiled program, named after the jitted function
(``jit_<name>(<id>)``), and an ``XLA Ops`` line, one event per operation.
The host planes carry the benchmark's own ``TraceAnnotation`` spans on the
Python thread, on the same clock.

* busy: the union of a device's operation intervals inside the window;
  idle is the rest of the window.  Averaged over the devices used.
* program time: the summed duration of a program's module events.
* gaps: the idle intervals of device 0, each named after the innermost
  event of the window's host thread that covers its midpoint.

JAX's profiler is imported inside functions only, so importing this file
never loads an accelerator library.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MODULE_NAME = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_planes(path: str) -> dict:
    """``{plane name: {line name: [(event name, start ns, duration ns)]}}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: dict = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns)) for ev in line.events
            )
    return planes


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def overlap(merged, lo: float, hi: float) -> float:
    """Length of the part of ``[lo, hi)`` that ``merged`` covers."""
    return total(clip(merged, lo, hi))


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi)`` that ``merged`` does not cover."""
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def module_name(event_name: str) -> str:
    """``jit_body(1234)`` -> ``body``."""
    return MODULE_NAME.match(event_name).group(1)


def device_planes(planes: dict) -> list[str]:
    names = [p for p in planes if p.startswith("/device:") and OPS_LINE in planes[p]]
    return sorted(names, key=lambda p: int(re.sub(r"\D", "", p.split(":")[-1]) or 0))


def host_spans(planes: dict, window_name: str) -> list[tuple[str, float, float]]:
    """The events of the host thread that ran the window (its spans, and
    JAX's own dispatch and transfer events), as ``(name, start, end)``."""
    for name, lines in planes.items():
        if not name.startswith("/host:"):
            continue
        for events in lines.values():
            if any(n == window_name for n, _, _ in events):
                return [(n, s, s + d) for n, s, d in events]
    raise ValueError(f"the trace has no host span {window_name!r}")


@dataclasses.dataclass
class Summary:
    window: tuple[float, float]  # ns, on the trace's clock
    busy: list[list[tuple[float, float]]]  # merged busy intervals per device, in the window
    ops: list[list[tuple[str, float, float]]]  # per device
    modules: list[list[tuple[str, float, float]]]  # per device
    spans: list[tuple[str, float, float]]  # host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return sum(total(b) for b in self.busy) / len(self.busy) * 1e-9

    @property
    def idle_shares(self) -> list[float]:
        w = self.window[1] - self.window[0]
        return [1.0 - total(b) / w for b in self.busy]

    @property
    def worst_idle_share(self) -> float:
        return max(self.idle_shares)

    def program(self, name: str) -> tuple[float, int]:
        """(seconds, runs) of the program ``name`` on device 0, in the window."""
        lo, hi = self.window
        hits = [(s, d) for n, s, d in self.modules[0]
                if module_name(n) == name and s >= lo and s < hi]
        return sum(d for _, d in hits) * 1e-9, len(hits)

    def busy_in(self, span_name: str) -> tuple[float, float]:
        """(seconds spanned, seconds device 0 was busy) over the host spans
        named ``span_name`` inside the window."""
        lo, hi = self.window
        spans = [(s, e) for n, s, e in self.spans if n == span_name and s >= lo and e <= hi]
        return total(spans) * 1e-9, sum(overlap(self.busy[0], s, e) for s, e in spans) * 1e-9

    def top_ops(self, n: int) -> list[tuple[str, float]]:
        """The device operations that took most time on device 0, by HLO
        name (an op's text up to `` = ``; a loop's time includes its body's)."""
        acc: dict[str, float] = {}
        for name, _s, d in self.ops[0]:
            name = name.split(" = ", 1)[0]
            acc[name] = acc.get(name, 0.0) + d
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v * 1e-9) for k, v in ranked]

    def top_gaps(self, n: int) -> list[tuple[str, float]]:
        """The longest idle gaps of device 0, each named after the innermost
        host span covering its midpoint (``idle`` where none does)."""
        out = []
        for s, e in gaps(self.busy[0], *self.window):
            mid = 0.5 * (s + e)
            cover = [(b - a, name) for name, a, b in self.spans
                     if a <= mid < b and name != "bench.window"]
            out.append((min(cover)[1] if cover else "idle", (e - s) * 1e-9))
        return sorted(out, key=lambda kv: -kv[1])[:n]


def summarize(path: str, n_devices: int, window_name: str = "bench.window") -> Summary:
    planes = read_planes(path)
    spans = host_spans(planes, window_name)
    window = next((s, e) for n, s, e in spans if n == window_name)
    devs = device_planes(planes)[:n_devices]
    if len(devs) < n_devices:
        raise ValueError(f"the trace has {len(devs)} device planes, {n_devices} were used")
    busy, ops, modules = [], [], []
    for dev in devs:
        lines = planes[dev]
        dev_ops = [(n, s, d) for n, s, d in lines[OPS_LINE] if d > 0]
        ops.append([(n, s, d) for n, s, d in dev_ops if window[0] <= s < window[1]])
        busy.append(merge(clip([(s, s + d) for _n, s, d in dev_ops], *window)))
        modules.append(lines.get(MODULES_LINE, []))
    return Summary(window=window, busy=busy, ops=ops, modules=modules, spans=spans)
