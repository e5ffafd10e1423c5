"""The timed path broken underneath, the rest of a run as it is: each fault
a cell can have must make ``correct`` come out false.  (No cell runs on
several chips, so the fault of a left-out exchange between chips has no
cell to break.)"""

import dataclasses

import numpy as np
import pytest

import bench_testutil as bt


class FaultyRoute:
    def __init__(self, program, fault, n_bins):
        self.p, self.fault, self.n_bins = program, fault, n_bins

    def route(self, keys):
        if self.fault == "state_unchanged":
            before = self.p.driver.counts
            chosen = self.p.route(keys)
            self.p.driver.counts = before
            return chosen
        if self.fault == "half_batch":
            half = self.p.route(keys[: max(1, len(keys) // 2)])
            return np.concatenate([half, half])[: len(keys)]
        chosen = self.p.route(keys).copy()  # "altered"
        chosen[0] = (chosen[0] + 1) % self.n_bins
        return chosen

    def __getattr__(self, name):
        return getattr(self.p, name)


class FrozenMover:
    """A mover whose rounds admit nothing: the drain's state never moves."""

    def __init__(self, mover):
        self.m = mover

    def round_block(self, k):
        return [{} for _ in range(k)]

    def __getattr__(self, name):
        return getattr(self.m, name)


class FaultyPlan:
    def __init__(self, program, fault):
        self.p, self.fault = program, fault

    def plan(self, ids, *args):
        if self.fault == "half_batch":
            return self.p.plan(ids[: len(ids) // 2], *args)
        plan = self.p.plan(ids, *args)
        if self.fault == "altered" and plan.n_moves:
            dst = plan.dst.copy()
            dst[0] = (dst[0] + 1) % 64
            plan = dataclasses.replace(plan, dst=dst)
        return plan

    def mover(self, plan, budget):
        m = self.p.mover(plan, budget)
        return FrozenMover(m) if self.fault == "state_unchanged" else m

    def __getattr__(self, name):
        return getattr(self.p, name)


def route_fault(fault):
    mod = bt.entry("kv.zipf.closed")

    def factory(cell):
        program = mod.RouteProgram(cell.capacities, cell.config, cell.seed, "ref", cell.max_batch)
        return FaultyRoute(program, fault, cell.n_bins)

    return factory


def plan_fault(fault):
    mod = bt.entry("rebal.add-remove")

    def factory(cell):
        return FaultyPlan(mod.PlanProgram(cell.capacities, cell.config, "ref", 1), fault)

    return factory


FAULTS = ("state_unchanged", "half_batch", "altered")


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", ["kv.zipf.closed", "kv.zipf.open"])
def test_route_fault_is_caught(workload, fault):
    out = bt.run(workload, system=route_fault(fault))
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("fault", FAULTS)
def test_rebalance_fault_is_caught(fault):
    out = bt.run("rebal.add-remove", system=plan_fault(fault))
    assert not out["correct"], out["compared"]
