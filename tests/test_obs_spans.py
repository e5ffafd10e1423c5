"""The program's spans on the profiler's clock (``obs.trace.span``).

A tiny ``route_batch``, a prefiltered ``plan_replicas`` and a
``round_block(2)`` run under ``jax.profiler`` on the CPU; the trace is read
back with the benchmark's own reduction (``bench/trace_reduce.py``), so
every span name the benchmark's readers look for is checked where they
find it: on the host thread that ran the work, nested as the code nests.
"""

import importlib.util
import math
import os
import sys

import numpy as np
import pytest

import jax

from repro.core import PlacementEngine, make_uniform_cluster
from repro.migrate import MigrationPlanner, MigrationState, ThrottledMover
from repro.obs import TraceLedger, span
from repro.serve import RequestStreamDriver

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW = "test.window"
PLANNER = ("planner.prefilter", "planner.diff", "planner.assemble")
MOVER = ("mover.prepare", "mover.scan", "mover.matrices")
NAMES = ("serve.route_batch", "planner.plan_replicas", *PLANNER, "mover.round_block", *MOVER)


def _trace_reduce():
    path = os.path.join(ROOT, "bench", "trace_reduce.py")
    spec = importlib.util.spec_from_file_location("trace_reduce_for_spans", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclass looks its module up by name
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Serve, plan an add and move two rounds under the profiler; return the
    window thread's host events and what the run left behind."""
    cluster = make_uniform_cluster(16)
    eng = PlacementEngine(cluster, backend="ref")
    driver = RequestStreamDriver(eng, batch=256, n_keys=1, law="uniform", seed=3)
    keys = np.arange(256, dtype=np.uint32) * np.uint32(2654435761)
    ids = np.arange(4096, dtype=np.uint32)
    eng.artifact()
    v0 = cluster.version
    max_new_seg = max(cluster.add_node(16, 1.0))
    ledger = TraceLedger()
    planner = MigrationPlanner(eng, ledger=ledger)
    serve_events = len(driver.ledger.events())
    out_dir = str(tmp_path_factory.mktemp("spans"))
    jax.profiler.start_trace(out_dir)
    try:
        with span(WINDOW):
            np.asarray(driver.route_batch(keys))
            plan = planner.plan_replicas(
                ids, v0, cluster.version, 3, chunk=1024, max_new_seg=max_new_seg
            )
            budget = math.ceil(plan.n_moves / 4)
            mover = ThrottledMover(MigrationState(plan), egress=budget, ingress=budget)
            mover.round_block(2)
    finally:
        jax.profiler.stop_trace()
    tr = _trace_reduce()
    spans = tr.host_spans(tr.read_planes(tr.find_xplane(out_dir)), WINDOW)
    return {
        "spans": spans, "plan": plan, "ledger": ledger,
        "serve_new_events": len(driver.ledger.events()) - serve_events,
    }


def _named(spans, name):
    return [(s, e) for n, s, e in spans if n == name]


def _inside(inner, outer) -> bool:
    return any(a <= s and e <= b for a, b in outer for s, e in [inner])


def test_every_layer_span_is_on_the_window_thread(traced):
    names = {n for n, _, _ in traced["spans"]}
    missing = [n for n in NAMES if n not in names]
    assert not missing, f"spans missing from the trace: {missing}"
    assert len(_named(traced["spans"], "serve.route_batch")) == 1
    assert len(_named(traced["spans"], "mover.round_block")) == 1
    # 4,096 ids in 1,024-id chunks: one prefilter per chunk
    assert len(_named(traced["spans"], "planner.prefilter")) == 4


@pytest.mark.parametrize(
    "inner, outer",
    [(n, "planner.plan_replicas") for n in PLANNER]
    + [(n, "mover.round_block") for n in MOVER],
)
def test_spans_nest_as_the_code_does(traced, inner, outer):
    outer_iv = _named(traced["spans"], outer)
    inner_iv = _named(traced["spans"], inner)
    assert inner_iv and all(_inside(iv, outer_iv) for iv in inner_iv)


def test_planner_sub_spans_do_not_overlap(traced):
    ivs = sorted(iv for n in PLANNER for iv in _named(traced["spans"], n))
    assert all(a[1] <= b[0] for a, b in zip(ivs, ivs[1:]))


def test_ring_events_unchanged_by_the_annotations(traced):
    """``plan_replicas`` keeps its one ring event; the layer spans inside it
    and ``route_batch``'s span write to the profiler only."""
    evs = traced["ledger"].events("span")
    assert [e["name"] for e in evs] == ["planner.plan_replicas"]
    [ev] = evs
    plan = traced["plan"]
    assert list(ev) == ["ts", "kind", "name", "dur_s", "filter", "grown_nodes",
                        "n_scanned", "n_moves", "v_from", "v_to"]
    assert (ev["filter"], ev["grown_nodes"]) == ("owner", 1)
    assert ev["n_moves"] == plan.n_moves and ev["n_scanned"] == 4096
    assert (ev["v_from"], ev["v_to"]) == (plan.v_from, plan.v_to)
    assert traced["serve_new_events"] == 0


def test_span_records_dur_and_late_fields_on_the_injected_clock():
    t = {"now": 5.0}
    led = TraceLedger(clock=lambda: t["now"])
    with span("outer", led, tag="a") as fields:
        t["now"] = 7.5
        fields["rows"] = 3
    with led.span("inner"):
        t["now"] = 8.0
    with span("no.ledger") as fields:
        fields["ignored"] = 1
    outer, inner = led.events("span")
    assert outer["name"] == "outer" and outer["dur_s"] == 2.5
    assert outer["tag"] == "a" and outer["rows"] == 3 and outer["ts"] == 7.5
    assert inner["name"] == "inner" and inner["dur_s"] == 0.5
    assert len(led.events()) == 2


def test_span_records_its_event_when_the_block_raises():
    led = TraceLedger(clock=lambda: 1.0)
    with pytest.raises(KeyError):
        with span("fails", led):
            raise KeyError("x")
    [ev] = led.events("span")
    assert ev["name"] == "fails" and ev["dur_s"] == 0.0
