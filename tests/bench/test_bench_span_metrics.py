"""The per-layer readers of the program's own spans (``serve.*``,
``planner.*``, ``mover.*``), on hand-built trace summaries: values computed
by hand, nothing read where the spans are absent (a program without them)
or where the count they divide by is 0."""

import os

import pytest

import bench_testutil as bt
import harness
import trace_reduce as tr

MS = 1e6  # ns


def reader(name):
    return harness.load_module(os.path.join(bt.BENCH, "metrics", name + ".py"))


def _summary(spans, busy):
    window = (0.0, 200 * MS)
    spans = [("bench.window", *window)] + spans
    return tr.Summary(window=window, busy=[tr.merge(busy)], ops=[[]], modules=[[]], spans=spans)


def _route():
    spans = [
        ("serve.route_batch", -2 * MS, -1 * MS),  # before the window: not counted
        ("route.batch", 1 * MS, 10 * MS),
        ("serve.route_batch", 1 * MS, 3 * MS),
        ("route.batch", 11 * MS, 20 * MS),
        ("serve.route_batch", 11 * MS, 12 * MS),
    ]
    busy = [(2 * MS, 10 * MS), (11.5 * MS, 20 * MS)]
    return _summary(spans, busy)


def _rebalance():
    spans = [
        ("rebalance.plan", 0.5 * MS, 50 * MS),
        ("planner.plan_replicas", 0.6 * MS, 45 * MS),
        ("planner.prefilter", 1 * MS, 21 * MS),
        ("planner.diff", 21 * MS, 24 * MS),
        ("planner.assemble", 24 * MS, 25 * MS),
        ("planner.prefilter", 25 * MS, 35 * MS),
        ("planner.assemble", 40 * MS, 42 * MS),
        ("rebalance.drain", 59 * MS, 100 * MS),
        ("mover.round_block", 60 * MS, 80 * MS),
        ("mover.prepare", 60 * MS, 61 * MS),
        ("mover.scan", 61 * MS, 70 * MS),
        ("mover.matrices", 70 * MS, 73 * MS),
        ("mover.round_block", 90 * MS, 99 * MS),
        ("mover.scan", 90 * MS, 95 * MS),
        ("mover.matrices", 95 * MS, 99 * MS),
    ]
    busy = [(2 * MS, 20 * MS), (22 * MS, 23 * MS), (62 * MS, 69 * MS), (91 * MS, 94 * MS)]
    return _summary(spans, busy)


FACTS = {"batches": 2, "prefilter_scanned": 2_000_000, "diff_ids": 1_500_000,
         "ids_planned": 4_000_000, "rounds": 16}

CASES = [
    # spans 2 + 1 ms, device busy in them 1 + 0.5 ms: 1.5 ms host over 2 batches
    ("route.host_ms_per_batch", _route, 0.75),
    # 20 + 10 ms over 2 M ids scanned
    ("planner.prefilter_ms_per_mid", _rebalance, 15.0),
    # 3 ms over 1.5 M ids diffed
    ("planner.diff_ms_per_mid", _rebalance, 2.0),
    # 1 + 2 ms over 4 M ids planned
    ("planner.assemble_ms_per_mid", _rebalance, 0.75),
    # 9 + 5 ms over 16 rounds
    ("mover.scan_ms_per_round", _rebalance, 0.875),
    # prepare 1 ms + matrices 3 + 4 ms over 16 rounds
    ("mover.host_ms_per_round", _rebalance, 0.5),
]
NAMES = [c[0] for c in CASES]


@pytest.mark.parametrize("name, summary, want", CASES)
def test_reader_by_hand(name, summary, want):
    view = {"trace": summary(), "facts": dict(FACTS), "rec": None, "peaks": {}}
    assert reader(name).read(view) == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_nothing_without_program_spans(name):
    """A program without the spans (only the benchmark's own around it)."""
    spans = [("route.batch", 1 * MS, 10 * MS), ("rebalance.plan", 11 * MS, 50 * MS),
             ("rebalance.drain", 51 * MS, 90 * MS)]
    view = {"trace": _summary(spans, [(2 * MS, 9 * MS)]), "facts": dict(FACTS),
            "rec": None, "peaks": {}}
    assert reader(name).read(view) is None


@pytest.mark.parametrize("name, summary, want", CASES)
def test_reader_reads_nothing_over_a_zero_base(name, summary, want):
    view = {"trace": summary(), "facts": {k: 0 for k in FACTS}, "rec": None, "peaks": {}}
    assert reader(name).read(view) is None


def test_the_split_fits_inside_its_parents():
    """The planner's three parts lie inside ``planner.plan_replicas``, which
    lies inside the entry's ``rebalance.plan``; the mover's scan and host
    parts add up to no more than the entry's drain."""
    s = _rebalance()
    parts = sum(s.busy_in(n)[0] for n in ("planner.prefilter", "planner.diff",
                                          "planner.assemble"))
    assert parts <= s.busy_in("planner.plan_replicas")[0] <= s.busy_in("rebalance.plan")[0]
    rounds = FACTS["rounds"]
    view = {"trace": s, "facts": dict(FACTS)}
    split = reader("mover.scan_ms_per_round").read(view) + reader(
        "mover.host_ms_per_round").read(view)
    assert split <= 1e3 * s.busy_in("rebalance.drain")[0] / rounds


def test_manifest_lists_each_reader_as_a_program_span():
    m = {x["name"]: x for x in harness.load_manifest(bt.ROOT)["per_layer"]}
    for name in NAMES:
        assert m[name]["source"] == "program_span" and m[name]["workloads"], name
