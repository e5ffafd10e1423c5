"""Host-plane telemetry: timestamped structured events + counters.

``TraceLedger`` is the host half of the two-plane design (DESIGN.md
section 13): a bounded ring of structured events (span timings, artifact
uploads, LRU evictions, jit traces, migration rounds) plus a dict of
monotonically-increasing counters.  The three ad-hoc trace tripwires
that grew across PRs 2-7 (``engine.uploads``,
``RequestStreamDriver.step_traces``, the router/window probe counters)
are all ledger counters now, with the old attributes kept as read-only
aliases so every existing tripwire test reads the same way.

Counters are cheap (one dict update -- safe inside traced-body Python
side effects, which fire once per TRACE); events carry a timestamp from
an injectable clock (tests pass a fake) and export as JSONL (one object
per line) or Prometheus-style text exposition, optionally merged with a
``MetricsRegistry``'s drained device totals.  Spans (``span``, and
``TraceLedger.span`` through it) are also ``jax.profiler.TraceAnnotation``s,
so a profiler trace shows each one on the host thread beside the device's
work; their names are the program's API.

A module-level ledger (``get_ledger()``) serves call sites with no
instance to hang state on (the migration window's module-level probe
cache); everything else defaults to instance-scoped ledgers so exact
tripwire counts never alias across objects.
"""

from __future__ import annotations

import collections
import contextlib
import json
import re
import time

import numpy as np

DEFAULT_CAPACITY = 65536

_PROM_BAD = re.compile(r"[^a-zA-Z0-9_]")


def _jsonable(v):
    """Coerce an event field into something json.dumps accepts."""
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, np.ndarray):
        return v.tolist()
    return str(v)


class TraceLedger:
    """Bounded event ring + counter dict with JSONL/Prometheus export."""

    def __init__(self, *, clock=None, capacity: int = DEFAULT_CAPACITY):
        self._clock = clock if clock is not None else time.perf_counter
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self._counters: dict[str, int] = {}

    # -- counters (the tripwire plane) ----------------------------------------

    def incr(self, name: str, n: int = 1) -> int:
        """Bump counter ``name`` by ``n``; returns the new value.  Cheap
        enough for traced-body side effects (fires once per jit TRACE)."""
        self._counters[name] = c = self._counters.get(name, 0) + int(n)
        return c

    def counter(self, name: str) -> int:
        return self._counters.get(name, 0)

    @property
    def counters(self) -> dict[str, int]:
        return dict(self._counters)

    # -- events ----------------------------------------------------------------

    def event(self, kind: str, name: str = "", **fields) -> dict:
        ev = {"ts": float(self._clock()), "kind": str(kind), "name": str(name)}
        for k, v in fields.items():
            ev[str(k)] = _jsonable(v)
        self._events.append(ev)
        return ev

    def events(self, kind: str | None = None) -> list[dict]:
        evs = list(self._events)
        if kind is None:
            return evs
        return [e for e in evs if e["kind"] == kind]

    def span(self, name: str, **fields):
        """Time a block; emits one ``kind="span"`` event with ``dur_s``
        (the module-level ``span`` with this ledger)."""
        return span(name, self, **fields)

    def clear(self) -> None:
        self._events.clear()

    # -- exporters --------------------------------------------------------------

    def to_jsonl(self) -> str:
        """One JSON object per line: every buffered event, then one
        ``kind="counters"`` summary line."""
        lines = [json.dumps(e, sort_keys=True) for e in self._events]
        if self._counters:
            lines.append(
                json.dumps(
                    {"kind": "counters", "counters": dict(self._counters)},
                    sort_keys=True,
                )
            )
        return "\n".join(lines) + ("\n" if lines else "")

    def export_jsonl(self, path: str) -> int:
        """Write ``to_jsonl()`` to ``path``; returns the event count."""
        with open(path, "w") as f:
            f.write(self.to_jsonl())
        return len(self._events)

    def prometheus_text(self, registry=None, *, prefix: str = "repro") -> str:
        """Prometheus-style text exposition of the counters (and, given a
        ``MetricsRegistry``, its drained device totals -- call
        ``registry.snapshot()`` first; this reads host totals only)."""

        def metric(name: str) -> str:
            return f"{prefix}_{_PROM_BAD.sub('_', name)}"

        lines: list[str] = []
        for name in sorted(self._counters):
            m = metric(name)
            lines.append(f"# TYPE {m} counter")
            lines.append(f"{m} {self._counters[name]}")
        if registry is not None:
            for name, v in sorted(registry.totals().items()):
                m = metric(name)
                if np.ndim(v) == 0:
                    lines.append(f"# TYPE {m} counter")
                    lines.append(f"{m} {int(v)}")
                else:
                    lines.append(f"# TYPE {m} histogram")
                    for i, c in enumerate(np.asarray(v).tolist()):
                        lines.append(f'{m}_bucket{{bin="{i}"}} {int(c)}')
        return "\n".join(lines) + ("\n" if lines else "")


# -- the module-level ledger (for module-level call sites) ---------------------

_GLOBAL: TraceLedger | None = None


def get_ledger() -> TraceLedger:
    """The process-wide default ledger (lazily created)."""
    global _GLOBAL
    if _GLOBAL is None:
        _GLOBAL = TraceLedger()
    return _GLOBAL


def set_ledger(ledger: TraceLedger) -> TraceLedger:
    """Swap the process-wide ledger (tests inject a fresh one); returns
    the previous ledger."""
    global _GLOBAL
    prev = get_ledger()
    _GLOBAL = ledger
    return prev


@contextlib.contextmanager
def span(name: str, ledger: TraceLedger | None = None, **fields):
    """The program's one span: a ``jax.profiler.TraceAnnotation`` named
    ``name`` around the block, so a profiler trace shows it on the host
    thread that ran it, on the device trace's clock (with no profiler
    running the annotation costs about a microsecond).

    Given a ``ledger``, the block also appends one ``kind="span"`` ring
    event with ``dur_s`` on the ledger's clock.  The context yields the
    event's ``fields`` dict, so the block can add what it learns (a
    plan's row count) before the event is written."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        if ledger is None:
            yield fields
            return
        t0 = ledger._clock()
        try:
            yield fields
        finally:
            ledger.event("span", name, dur_s=float(ledger._clock() - t0), **fields)
