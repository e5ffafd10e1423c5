"""The trace reduction (``bench/trace_reduce.py``): busy union, program
time and gap attribution, on hand-built intervals and on a small trace of
three ``route_batch`` calls recorded on a TPU v5e (``data/``)."""

import os

import pytest

import bench_testutil  # noqa: F401  (puts the benchmark on the path)
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small_route.xplane.pb")


def test_merge_overlap_and_gaps_by_hand():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (6, 9), (9, 10)])
    assert merged == [(0, 3), (5, 10)]
    assert tr.total(merged) == 8
    assert tr.overlap(merged, 2, 6) == 2  # [2, 3) and [5, 6)
    assert tr.gaps(merged, -1, 12) == [(-1, 0), (3, 5), (10, 12)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def _summary():
    ops = [("fusion.1", 100.0, 50.0), ("fusion.2", 120.0, 60.0), ("while.3", 300.0, 100.0)]
    modules = [("jit_body(12)", 100.0, 80.0), ("jit_body(12)", 300.0, 100.0),
               ("jit__head(3)", 500.0, 10.0)]
    spans = [("bench.window", 0.0, 1000.0), ("route.batch", 90.0, 450.0),
             ("host.wait", 600.0, 900.0), ("route.batch", 950.0, 1000.0)]
    busy = [tr.merge(tr.clip([(s, s + d) for _, s, d in ops + [("x", 500.0, 10.0)]], 0, 1000))]
    return tr.Summary(window=(0.0, 1000.0), busy=busy, ops=[ops], modules=[modules], spans=spans)


def test_summary_by_hand():
    s = _summary()
    assert s.busy_s == pytest.approx(190e-9)  # [100, 180) + [300, 400) + [500, 510)
    assert s.window_s == pytest.approx(1e-6)
    assert s.idle_shares == [pytest.approx(0.81)]
    assert s.program("body") == (pytest.approx(180e-9), 2)
    assert s.program("_head") == (pytest.approx(10e-9), 1)
    assert s.busy_in("route.batch") == (pytest.approx(410e-9), pytest.approx(180e-9))
    assert s.top_ops(2) == [("while.3", pytest.approx(100e-9)), ("fusion.2", pytest.approx(60e-9))]
    gaps = s.top_gaps(10)
    # [510, 1000) is the longest gap; its midpoint 755 lies in host.wait
    assert gaps[0] == ("host.wait", pytest.approx(490e-9))
    assert ("route.batch", pytest.approx(120e-9)) in gaps  # [180, 300)
    assert ("idle", pytest.approx(100e-9)) in gaps  # [0, 100): before the first span


def test_module_names():
    assert tr.module_name("jit_body(1234)") == "body"
    assert tr.module_name("jit__diff_replicas_fused_ref(7)") == "_diff_replicas_fused_ref"
    assert tr.module_name("run") == "run"


def test_recorded_chip_trace():
    s = tr.summarize(DATA, 1)
    assert 0 < s.busy_s < s.window_s
    # busy is the union of the operations: no longer than their sum, no
    # shorter than the longest one, and equal to a brute-force sweep
    ops = [(st, st + d) for _, st, d in s.ops[0]]
    assert max(e - b for b, e in ops) * 1e-9 <= s.busy_s + 1e-12
    points = sorted({p for iv in ops for p in iv})
    brute = sum(b - a for a, b in zip(points, points[1:])
                if any(x <= a and b <= y for x, y in ops))
    assert s.busy_s == pytest.approx(tr.total(tr.clip(tr.merge(ops), *s.window)) * 1e-9)
    assert s.busy_s == pytest.approx(brute * 1e-9, rel=1e-6)
    seconds, runs = s.program("body")
    assert runs == 3 and 0 < seconds < s.window_s
    # a program's span also holds the short bubbles between its operations
    runs_iv = [(st, st + d) for n, st, d in s.modules[0] if tr.module_name(n) == "body"]
    covered = sum(tr.overlap(s.busy[0], a, b) for a, b in runs_iv) * 1e-9
    assert 0.9 * seconds < covered <= seconds
    names = {n for n, _ in s.top_gaps(10)}
    assert "host.sleep" in names
