"""Two-plane telemetry for the placement/serving/migration stack.

Device plane (``obs.metrics``): ``MetricsRegistry`` owns one u32 device
slab that the fused jits accumulate into in-register -- routed counts,
per-node served histograms, ladder-depth histograms, re-probe and
non-convergence counts -- drained by ONE explicit ``snapshot()`` transfer
into host uint64 totals (DESIGN.md section 13).

Host plane (``obs.trace``): ``TraceLedger`` records timestamped
structured events (spans, uploads, jit traces, migration rounds) plus
monotonically-increasing host counters, with JSONL and Prometheus-style
text exporters; ``span`` also writes each span into a running profiler
trace.  The three ad-hoc trace tripwires (``engine.uploads``,
``RequestStreamDriver.step_traces``, the window/router probe counters)
are ledger counters behind back-compat aliases.
"""

from .metrics import MetricsRegistry
from .trace import TraceLedger, get_ledger, set_ledger, span

__all__ = ["MetricsRegistry", "TraceLedger", "get_ledger", "set_ledger", "span"]
