"""The ``kv.migrating-read`` cell (YCSB reads through a live rack
scale-out) on the CPU at a small size: the sound program comes out correct,
each fault of the read rule or of the mover's landed log comes out not
correct, the per-layer readers compute by hand, and a program whose
``route_batch`` serves at one version only is refused at set-up."""

import time

import numpy as np
import pytest

import bench_testutil as bt
import harness
import migrating_bytes
import trace_reduce as tr
from bytes_model import route_bytes

CELL = "kv.migrating-read"
SIZES = {"nodes": 64, "rack_nodes": 4, "recordcount": 1 << 12, "planner_chunk": 1 << 12,
         "mover_rounds_per_drain": 8, "batches_per_round": 2}
TRAFFIC = {"batch": 256, "pool_batches": 4, "check_sample": 4096}
MS = 1e6  # ns


def spec():
    s = harness.resolve(harness.load_manifest(bt.ROOT), CELL, root=bt.ROOT)
    s["traffic"] = {**s["traffic"], **TRAFFIC}
    return s


def run(system=None, seconds=0.5):
    return harness.run_cell(
        spec(), seed=bt.SEED, seconds=seconds, trace=False, t_start=time.perf_counter(),
        system=system, backend="ref", sizes=SIZES, log=lambda _msg: None,
    )


def test_cell_is_correct_and_alternates_drains():
    logs = []
    out = harness.run_cell(
        spec(), seed=bt.SEED, seconds=0.5, trace=False, t_start=time.perf_counter(),
        backend="ref", sizes=SIZES, log=logs.append,
    )
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["compared"]) == {"read_from_non_holder", "counter_gap", "unrouted",
                                    "plan_vs_reference", "rows_not_landed", "moves_vs_plan",
                                    "over_budget"}
    assert all(c["value"] == 0 and c["limit"] == 0 for c in out["compared"].values())
    assert set(out["metrics"]) == {"routed_per_s", "setup_s"}
    assert any("0 compiles" in m for m in logs)
    window = next(m for m in logs if m.startswith("window: ") and "drains" in m)
    assert "('join', 8, 16, True), ('leave', 8, 16, True)" in window  # 8 rounds a drain


class Faulty:
    """The program with its read rule or its mover's landed log broken."""

    def __init__(self, program, fault):
        self.p, self.fault = program, fault
        self.shadows: dict = {}

    def route(self, keys, migration):
        if self.fault in ("v1_while_pending", "v_after_landed"):
            migration = self._shadow(migration)
        return self.p.route(keys, migration)

    def _shadow(self, migration):
        """A window over the same plan whose rows land all at once but one
        (the v+1 holder while pending) or never (the v holder once landed)."""
        from repro.migrate import LiveMigration

        shadow = self.shadows.get(id(migration))
        if shadow is None:
            shadow = LiveMigration.from_plan(self.p.engine, migration.state.plan)
            if self.fault == "v1_while_pending":
                shadow.state.mark_landed(np.arange(migration.state.plan.n_moves - 1))
            self.shadows[id(migration)] = shadow
        return shadow

    def round(self, migration):
        matrix, landed = self.p.round(migration)
        if self.fault == "landed_marks_dropped" and migration.mover.rounds_done == 3:
            migration.state.landed[landed] = False
            migration.state.mark_landed(landed[:0])  # drop the stale views
            landed = landed[:0]
        return matrix, landed

    def __getattr__(self, name):
        return getattr(self.p, name)


def fault(name):
    mod = harness.entry_module(spec())

    def factory(cell):
        program = mod.MigratingProgram(cell.capacities, cell.config, cell.seed, "ref",
                                       cell.max_batch)
        return Faulty(program, name)

    return factory


@pytest.mark.parametrize("name, number", [
    ("v1_while_pending", "read_from_non_holder"),
    ("v_after_landed", "read_from_non_holder"),
    ("landed_marks_dropped", "moves_vs_plan"),
])
def test_fault_is_caught(name, number):
    out = run(system=fault(name))
    assert not out["correct"]
    assert out["compared"][number]["value"] > 0, out["compared"]


def test_single_version_program_is_refused_at_setup(monkeypatch):
    """A ``route_batch`` with no ``migration=`` (a program before the
    host-fed read rule) stops set-up at once with the reason."""
    from repro.serve import RequestStreamDriver

    def route_batch(self, datum_ids):
        raise AssertionError("never reached")

    monkeypatch.setattr(RequestStreamDriver, "route_batch", route_batch)
    with pytest.raises(RuntimeError, match="one version only"):
        run()


def reader(name):
    return harness.metric_reader(spec(), name)


FACTS = {"batches": 3, "batch": 65536, "n_bins": 1056, "replicas": 3, "table_len": 1792,
         "rounds": 4, "route_shapes": [[2, 1792, 1 << 17], [1, 1792, 1 << 19]]}


def _summary():
    window = (0.0, 100 * MS)
    spans = [("bench.window", *window),
             ("migrate.pending_refresh", -3 * MS, -1 * MS),  # before the window
             ("serve.route_batch", 1 * MS, 30 * MS),
             ("migrate.pending_refresh", 1 * MS, 3 * MS),
             ("serve.route_batch", 40 * MS, 70 * MS),
             ("migrate.pending_refresh", 40 * MS, 46 * MS)]
    modules = [("jit_route_migrating(7)", 5 * MS, 20 * MS),
               ("jit_route_migrating(7)", 48 * MS, 20 * MS),
               ("jit_body(3)", 80 * MS, 5 * MS)]
    busy = [(5 * MS, 25 * MS), (48 * MS, 68 * MS), (80 * MS, 85 * MS)]
    return tr.Summary(window=window, busy=[tr.merge(busy)], ops=[[]], modules=[modules],
                      spans=spans)


def test_readers_by_hand():
    view = {"trace": _summary(), "facts": dict(FACTS), "rec": None,
            "peaks": {"hbm_bytes_per_s": 819e9}}
    # two runs of route_migrating, 20 ms each
    assert reader("route.migrating_device_ms_per_batch").read(view) == pytest.approx(20.0)
    # refresh spans 2 + 6 ms in the window, over 4 rounds
    assert reader("migrate.pending_refresh_ms_per_round").read(view) == pytest.approx(2.0)
    # busy 20 + 20 + 5 ms of a 100 ms window
    assert reader("device_idle.migrating").read(view) == pytest.approx(55.0)
    # two runs at the batch-weighted mean of the two shapes' bytes, over 40 ms
    per = (2 * migrating_bytes.migrating_route_bytes(65536, 1056, 1792, 3, 1 << 17)
           + migrating_bytes.migrating_route_bytes(65536, 1056, 1792, 3, 1 << 19)) / 3
    want = 100 * 2 * per / 819e9 / 0.040
    assert reader("route.migrating_roofline").read(view) == pytest.approx(want)
    assert 0 < want < 1


@pytest.mark.parametrize("name", ["route.migrating_device_ms_per_batch",
                                  "route.migrating_roofline",
                                  "migrate.pending_refresh_ms_per_round"])
def test_readers_read_nothing_from_a_program_without_them(name):
    """A program with no ``route_migrating`` jit and no refresh span (the
    flat route body only)."""
    window = (0.0, 100 * MS)
    s = tr.Summary(window=window, busy=[[(1 * MS, 9 * MS)]], ops=[[]],
                   modules=[[("jit_body(3)", 1 * MS, 8 * MS)]],
                   spans=[("bench.window", *window), ("serve.route_batch", 1 * MS, 9 * MS)])
    view = {"trace": s, "facts": dict(FACTS), "rec": None, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert reader(name).read(view) is None


def test_migrating_route_bytes_by_hand():
    """At the cell's shape: the flat body's bytes, then per lane and slot
    17 probe reads and one source gather of 4 bytes, and 3 live counts."""
    flat = route_bytes(65536, 1056, 1792)
    assert migrating_bytes.migrating_route_bytes(65536, 1056, 1792, 3, 1 << 17) == (
        flat + 65536 * 3 * 18 * 4 + 12)
    assert migrating_bytes.migrating_route_bytes(65536, 1056, 1792, 3, 1) == (
        flat + 65536 * 3 * 4 + 12)
