"""Entry ``rebalance``: an object store absorbing membership changes.

Each event changes the cluster by one node and moves the replicas that the
change displaces: ``MigrationPlanner.plan_replicas`` over every tracked id
(with the ADDITION-NUMBER prefilter on an add, through ``max_new_seg`` as
``Router.begin_scale_migration`` computes it), then the plan is drained by
``ThrottledMover(MigrationState(plan)).round_block(k)`` under a per-node
budget of ``ceil(rows / (blocks * k))`` rows a round, until every row has
landed.  Events alternate: add node ``n`` with a capacity from the traffic
file's list, then remove it again, so the cluster returns to its starting
layout after every pair.

The window runs whole add/remove pairs and closes at the first pair
boundary at or after ``--seconds``, so every window holds as many adds as
removes.  Set-up runs one add/remove pair, which compiles every
shape the window meets: the traffic file's capacities are chosen so that
their plans fall in one power-of-two bucket of rows.

The check, once the window has closed, for every event: on a seeded sample
of the tracked ids the plan's rows equal the reference's minimal movement
exactly; every row of the plan moves to the added node (add) or off the
removed node (remove); every row landed; the mover's moves equal the plan,
pair by pair; and no round exceeded a node's budget.
"""

from __future__ import annotations

import math
import time

import numpy as np

import generate


class PlanProgram:
    """The timed path: the program's planner and mover on one engine."""

    def __init__(self, capacities, config: dict, backend: str, chips: int):
        from repro.core import PlacementEngine, make_cluster
        from repro.migrate import MigrationPlanner
        from repro.obs import TraceLedger

        self.cluster = make_cluster(capacities)
        self.engine = PlacementEngine(self.cluster, backend=backend)
        self.ledger = TraceLedger()
        self.planner = MigrationPlanner(self.engine, ledger=self.ledger)
        self.mesh = self.engine.sharded() if chips > 1 else None
        self.chunk = int(config["planner_chunk"])

    def change(self, add=None, remove=None):
        """Apply one membership change -> ``(v_from, v_to, max_new_seg)``."""
        self.engine.artifact()  # pin v in the engine's LRU before mutating
        v_from = self.cluster.version
        max_new_seg = None
        if add is not None:
            max_new_seg = max(self.cluster.add_node(*add))
        else:
            self.cluster.remove_node(remove)
        return v_from, self.cluster.version, max_new_seg

    def plan(self, ids, v_from, v_to, n_replicas, max_new_seg):
        return self.planner.plan_replicas(
            ids, v_from, v_to, n_replicas, chunk=self.chunk, max_new_seg=max_new_seg,
            mesh=self.mesh,
        )

    def mover(self, plan, budget: int):
        from repro.migrate import MigrationState, ThrottledMover

        return ThrottledMover(MigrationState(plan), egress=budget, ingress=budget)

    def counters(self) -> dict:
        return self.ledger.counters

    def release(self) -> None:
        self.planner = self.engine = self.cluster = self.mesh = None


class Cell:
    def __init__(self, *, config, traffic, seed, seconds, chips, rec, reference, system,
                 backend, log):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.chips, self.rec, self.ref, self.log = int(chips), rec, reference, log
        self.system_factory, self.backend = system, backend
        self.attempted = self.failed = 0

    # -- set-up ----------------------------------------------------------------

    def setup(self) -> None:
        cfg, tr, rec = self.config, self.traffic, self.rec
        with rec.span("setup.generate"):
            self.capacities = generate.capacities(cfg)
            self.new_node = int(cfg["nodes"])
            self.R = int(cfg["replicas"])
            self.ids = generate.object_ids(cfg, self.seed)
            self.event_caps = generate.event_capacities(tr, self.seed)
            self.k = int(tr["mover_block_rounds"])
            self.blocks = int(tr["mover_blocks"])
            grown = self.ref.SegmentTable(self.capacities)
            grown.add(self.new_node, max(self.event_caps))
            self.table_len = -(-len(grown.len32) // 128) * 128  # the program lane-pads to 128
        with rec.span("setup.system"):
            if self.system_factory is None:
                self.system = PlanProgram(self.capacities, cfg, self.backend, self.chips)
            else:
                self.system = self.system_factory(self)
        self.events: list[dict] = []
        with rec.span("setup.warm"):
            for i in range(2):  # one add/remove pair
                self._event(i, record=False)

    # -- one event -------------------------------------------------------------

    def _event(self, i: int, record: bool = True) -> dict:
        rec, sys_ = self.rec, self.system
        cap = self.event_caps[(i // 2) % len(self.event_caps)]
        kind = "add" if i % 2 == 0 else "remove"
        c0 = dict(sys_.counters())
        with rec.span("rebalance.plan"):
            if kind == "add":
                v_from, v_to, max_new = sys_.change(add=(self.new_node, cap))
            else:
                v_from, v_to, max_new = sys_.change(remove=self.new_node)
            plan = sys_.plan(self.ids, v_from, v_to, self.R, max_new)
        c1 = dict(sys_.counters())
        budget = max(1, math.ceil(plan.n_moves / (self.blocks * self.k)))
        mover = sys_.mover(plan, budget)
        over_budget = 0
        rounds = 0
        with rec.span("rebalance.drain"):
            while not mover.done and rounds < 4 * self.blocks * self.k:
                for m in mover.round_block(self.k):
                    over_budget += _over_budget(m, budget)
                rounds += self.k
        ev = {
            "kind": kind, "cap": cap, "plan": plan, "rounds": rounds,
            "landed": int(mover.state.landed.sum()), "done": bool(mover.done),
            "admitted": sum(sum(m.values()) for m in mover.history),
            "matrix": mover.movement_matrix(), "over_budget": over_budget,
            "scanned": c1.get("planner.prefilter_scanned", 0) - c0.get("planner.prefilter_scanned", 0),
            "kept": c1.get("planner.prefilter_kept", 0) - c0.get("planner.prefilter_kept", 0),
        }
        if record:
            self.events.append(ev)
        return ev

    # -- the window ------------------------------------------------------------

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        i = 0
        while True:
            ev = self._event(i)
            i += 1
            ev["end"] = time.perf_counter() - t0
            if i % 2 == 0 and ev["end"] >= seconds:
                break
        self.elapsed = ev["end"]
        self.attempted = len(self.events)
        self.failed = sum(not e["done"] for e in self.events)
        plans = [e["plan"] for e in self.events]
        self.rec.facts.update(
            ids_planned=len(self.ids) * len(self.events),
            diff_ids=sum(len(self.ids) if e["kind"] == "remove" else e["kept"] for e in self.events),
            replicas=self.R,
            table_len=self.table_len,
            rounds=sum(e["rounds"] for e in self.events),
            prefilter_scanned=sum(e["scanned"] for e in self.events),
            prefilter_kept=sum(e["kept"] for e in self.events),
            rows=sum(p.n_moves for p in plans),
        )

    # -- results -----------------------------------------------------------------

    def end_to_end(self) -> dict:
        done = sum(e["done"] for e in self.events)
        rate = done * len(self.ids) / self.elapsed
        per = [(e["kind"], round(e["cap"], 4), e["plan"].n_moves, e["kept"]) for e in self.events]
        self.log(f"window: {len(self.events)} events in {self.elapsed:.6f} s "
                 f"(kind, capacity, rows, prefilter kept): {per}")
        return {"rebalance_ids_per_s": rate}

    def release(self) -> None:
        self.system.release()
        self.system = None

    def check(self) -> dict:
        """Numbers compared, each ``(value, limit)``."""
        pos = generate.sample(len(self.ids), int(self.traffic["check_sample"]), self.seed, "check")
        sample = self.ids[pos]
        table = self.ref.SegmentTable(self.capacities)
        base = self.ref.place_replicas(sample, table, self.R)
        added: dict[float, np.ndarray] = {}
        mismatch = minimality = unlanded = matrix_gap = over_budget = 0
        for ev in self.events:
            cap, plan = ev["cap"], ev["plan"]
            if cap not in added:
                table.add(self.new_node, cap)
                added[cap] = self.ref.place_replicas(sample, table, self.R)
                table.remove(self.new_node)
            before, after = (base, added[cap]) if ev["kind"] == "add" else (added[cap], base)
            mismatch += _plan_gap(plan, pos, before, after, self.ref)
            if ev["kind"] == "add":
                minimality += int((plan.dst != self.new_node).sum())
            else:
                minimality += int((plan.src != self.new_node).sum())
            unlanded += plan.n_moves - ev["landed"] + abs(ev["admitted"] - ev["landed"])
            matrix_gap += _matrix_gap(plan, ev["matrix"])
            over_budget += ev["over_budget"]
        self.log(f"check: {len(self.events)} events; plan rows of {len(pos)} sampled ids "
                 f"against the reference ({sum(int(e['plan'].n_moves) for e in self.events)} rows in all)")
        return {
            "plan_vs_reference": (mismatch, 0),
            "rows_off_changed_node": (minimality, 0),
            "rows_not_landed": (unlanded, 0),
            "moves_vs_plan": (matrix_gap, 0),
            "over_budget": (over_budget, 0),
        }


def _over_budget(matrix: dict, budget: int) -> int:
    out_of: dict = {}
    into: dict = {}
    for (s, d), c in matrix.items():
        out_of[s] = out_of.get(s, 0) + c
        into[d] = into.get(d, 0) + c
    return sum(max(0, v - budget) for v in out_of.values()) + sum(
        max(0, v - budget) for v in into.values())


def _plan_gap(plan, pos, before, after, ref) -> int:
    """Rows that differ between the plan and the reference on the sample:
    rows of sampled ids missing from either side, or with other nodes."""
    moved, src = ref.align(before, after)
    b, r = np.nonzero(moved)
    want = set(zip(pos[b].tolist(), r.tolist(), src[b, r].tolist(), after[b, r].tolist()))
    keep = np.isin(plan.index, pos)
    got = set(zip(plan.index[keep].tolist(), plan.slot[keep].tolist(),
                  plan.src[keep].tolist(), plan.dst[keep].tolist()))
    return len(want ^ got)


def _matrix_gap(plan, matrix: dict) -> int:
    want: dict = {}
    for s, d in zip(plan.src.tolist(), plan.dst.tolist()):
        want[(s, d)] = want.get((s, d), 0) + 1
    keys = set(want) | set(matrix)
    return sum(abs(want.get(k, 0) - matrix.get(k, 0)) for k in keys)
