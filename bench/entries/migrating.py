"""Entry ``migrating``: host-fed YCSB reads served through a live rack
scale-out (YCSB's Tier 2 elastic speedup).

A storage front end hands the router batches of keys while the cluster
grows by a rack and later shrinks back.  The timed path is
``RequestStreamDriver.route_batch(keys, migration=m)``: ASURA's v+1
replica sets, the per-slot pending probe of the live migration, the merge
(a slot whose row is pending goes to its v-side source), power-of-two
choices and the served counters, in one jit; every batch's routes are
copied back to the host before the next is sent (one client, closed
loop).  After every ``batches_per_round`` batches the program's mover runs
one budgeted round (``LiveMigration.round_block(1)``), and the next batch
serves through the refreshed pending view.

Both plans are made at set-up with ``MigrationPlanner.plan_replicas``
over every record: the rack joining through the owner filter
(``max_new_seg``), the rack leaving through the full diff.  The window
opens with the join's migration live and alternates the two drains; a
drain ends when its last row lands, and the next begins with the next
batch.  The per-node budget, the same both ways, is
``ceil(largest per-node rows / mover_rounds_per_drain)``.  Set-up serves
one whole drain of each direction (one batch a round), which compiles
every shape the window meets.

The check, once the window has closed: for a seeded sample of the served
requests, the chosen node holds the datum at the round its batch was
served in, by the reference's replica sets and the mover's landed log;
the counters' growth equals the routes received; every request got a
route; on a seeded sample of the records both plans equal the reference's
minimal movement; every drain that ended landed every row; each drain's
moves equal its plan, and each round's moves the rows it landed; and no
round exceeded a node's budget.
"""

from __future__ import annotations

import inspect
import math
import os
import time

import numpy as np

import generate
import harness

_rebalance = harness.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                              "rebalance.py"))


class MigratingProgram:
    """The timed path: the program's serving driver, planner and live
    migration on one engine."""

    def __init__(self, capacities, config: dict, seed: int, backend: str, max_batch: int):
        from repro.core import PlacementEngine, make_cluster
        from repro.migrate import MigrationPlanner
        from repro.serve import RequestStreamDriver

        self.cluster = make_cluster(capacities)
        self.engine = PlacementEngine(self.cluster, backend=backend)
        self.planner = MigrationPlanner(self.engine)
        self.R = int(config["replicas"])
        self.chunk = int(config["planner_chunk"])
        # route_batch serves external keys; the stream driver's own generated
        # stream is never drawn, so it gets the smallest law there is.
        self.driver = RequestStreamDriver(
            self.engine, batch=max_batch, n_keys=1, law="uniform",
            n_replicas=self.R, policy=config["selection"], seed=seed % 2**31,
            n_bins=int(config["nodes"]) + int(config["rack_nodes"]),
        )
        if "migration" not in inspect.signature(self.driver.route_batch).parameters:
            raise RuntimeError(
                "RequestStreamDriver.route_batch takes no migration=: this program "
                "serves host-fed batches at one version only, so it cannot serve "
                "reads through a live migration")

    def plan_rack(self, ids, rack):
        """Join the rack, then retire it -> (join plan, leave plan)."""
        self.engine.artifact()  # keep v in the engine's LRU before mutating
        v0 = self.cluster.version
        segs = [self.cluster.add_node(node, cap) for node, cap in rack]
        v1 = self.cluster.version
        join = self.planner.plan_replicas(ids, v0, v1, self.R, chunk=self.chunk,
                                          max_new_seg=max(max(s) for s in segs))
        self.engine.artifact()
        for node, _ in rack:
            self.cluster.remove_node(node)
        leave = self.planner.plan_replicas(ids, v1, self.cluster.version, self.R,
                                           chunk=self.chunk)
        return join, leave

    def migration(self, plan, budget: int):
        from repro.migrate import LiveMigration

        return LiveMigration.from_plan(self.engine, plan, egress=budget, ingress=budget)

    def route(self, keys: np.ndarray, migration) -> np.ndarray:
        return np.asarray(self.driver.route_batch(keys, migration=migration))

    def round(self, migration):
        """One budgeted mover round -> (its movement matrix, the plan rows
        that landed in it)."""
        before = migration.state.landed.copy()
        (matrix,) = migration.round_block(1)
        return matrix, np.nonzero(migration.state.landed & ~before)[0]

    def served(self) -> np.ndarray:
        return np.asarray(self.driver.counts)

    def release(self) -> None:
        self.driver = self.planner = self.engine = self.cluster = None


def _budget(plan, rounds: int) -> int:
    """Rows a node may send and receive a round, so that the busiest node's
    rows take ``rounds`` rounds."""
    busiest = max(np.bincount(plan.src).max(), np.bincount(plan.dst).max())
    return max(1, math.ceil(int(busiest) / rounds))


class Cell:
    def __init__(self, *, config, traffic, seed, seconds, chips, rec, reference, system,
                 backend, log):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.rec, self.ref, self.log = rec, reference, log
        self.system_factory, self.backend = system, backend
        self.attempted = self.failed = 0

    # -- set-up ----------------------------------------------------------------

    def setup(self) -> None:
        cfg, tr, rec = self.config, self.traffic, self.rec
        with rec.span("setup.generate"):
            self.capacities = generate.capacities(cfg)
            self.R = int(cfg["replicas"])
            nodes = int(cfg["nodes"])
            self.n_bins = nodes + int(cfg["rack_nodes"])
            self.rack = [(nodes + i, float(cfg["rack_capacity"]))
                         for i in range(int(cfg["rack_nodes"]))]
            self.ids = np.arange(int(cfg["recordcount"]), dtype=np.uint32)
            self.pool = generate.key_pool(cfg, tr, self.seed)
            self.max_batch = int(tr["batch"])
            self.per_round = int(cfg["batches_per_round"])
            self.drain_rounds = int(cfg["mover_rounds_per_drain"])
            grown = self.ref.SegmentTable(self.capacities)
            for node, cap in self.rack:
                grown.add(node, cap)
            self.table_len = -(-len(grown.len32) // 128) * 128  # the program lane-pads to 128
        with rec.span("setup.system"):
            if self.system_factory is None:
                self.system = MigratingProgram(self.capacities, cfg, self.seed, self.backend,
                                               self.max_batch)
            else:
                self.system = self.system_factory(self)
        with rec.span("setup.plan"):
            self.plans = self.system.plan_rack(self.ids, self.rack)
            self.budgets = [_budget(p, self.drain_rounds) for p in self.plans]
            # the per-slot pending view's fixed pad: pow2 of the largest slot's rows
            self.pads = [1 << max(0, int(np.bincount(p.slot).max()) - 1).bit_length()
                         for p in self.plans]
        with rec.span("setup.warm"):
            for k in (0, 1):  # one whole drain each way, one batch a round
                m = self.system.migration(self.plans[k], self.budgets[k])
                rounds = 0
                while not m.done and rounds < self._round_cap():
                    self.system.route(self.pool[rounds % len(self.pool)], m)
                    self.system.round(m)
                    rounds += 1
        self.log(f"set-up: {self.plans[0].n_moves} rows to join the rack, "
                 f"{self.plans[1].n_moves} to retire it; budgets {self.budgets} rows a "
                 f"node a round; pending-view pads {self.pads}")

    def _round_cap(self) -> int:
        return 4 * self.drain_rounds

    # -- the window ------------------------------------------------------------

    def _open_drain(self, k: int) -> dict:
        drain = {
            "k": k, "m": self.system.migration(self.plans[k], self.budgets[k]),
            "rounds": 0, "landed_round": np.zeros(self.plans[k].n_moves, dtype=np.int32),
            "matrices": [], "landed_n": [], "ended": False, "batches": 0,
        }
        self.drains.append(drain)
        return drain

    def window(self, seconds: float) -> None:
        rec, sys_, pool = self.rec, self.system, self.pool
        n_pool = pool.shape[0]
        self.counts0 = sys_.served()
        self.routes: list[np.ndarray] = []
        self.batch_at: list[tuple[int, int]] = []  # (drain, rounds done in it)
        self.drains: list[dict] = []
        drain = self._open_drain(0)
        t0 = time.perf_counter()
        i = 0
        while True:
            with rec.span("route.batch"):
                self.routes.append(sys_.route(pool[i % n_pool], drain["m"]))
            self.batch_at.append((len(self.drains) - 1, drain["rounds"]))
            drain["batches"] += 1
            i += 1
            if i % self.per_round == 0:
                with rec.span("mover.round"):
                    matrix, landed = sys_.round(drain["m"])
                drain["rounds"] += 1
                drain["landed_round"][landed] = drain["rounds"]
                drain["matrices"].append(matrix)
                drain["landed_n"].append(len(landed))
                if drain["m"].done or drain["rounds"] >= self._round_cap():
                    drain["ended"] = True
                    drain = self._open_drain(1 - drain["k"])
            t = time.perf_counter()
            if t - t0 >= seconds:
                break
        self.elapsed = t - t0
        self.counts1 = sys_.served()
        self.batches = i
        self.attempted = i * self.max_batch
        shapes: dict[int, list] = {}
        for d in self.drains:
            s = shapes.setdefault(d["k"], [0, self.table_len, self.pads[d["k"]]])
            s[0] += d["batches"]
        self.rec.facts.update(
            batches=i, batch=self.max_batch, n_bins=self.n_bins, replicas=self.R,
            table_len=self.table_len, rounds=sum(d["rounds"] for d in self.drains),
            route_shapes=[s for s in shapes.values() if s[0]],
        )

    # -- results -----------------------------------------------------------------

    def _served_counts(self) -> np.ndarray:
        """Routes received per node; a route to no node counts nowhere."""
        counts = np.zeros(self.n_bins, dtype=np.int64)
        for r in self.routes:
            ok = (r >= 0) & (r < self.n_bins)
            counts += np.bincount(r[ok], minlength=self.n_bins)
        return counts

    def end_to_end(self) -> dict:
        per = [("join" if d["k"] == 0 else "leave", d["rounds"], d["batches"], d["ended"])
               for d in self.drains]
        self.log(f"window: {self.attempted} requests in {self.elapsed:.6f} s; "
                 f"{self.batches} batches; drains (direction, rounds, batches, ended): {per}")
        return {"routed_per_s": self.attempted / self.elapsed}

    def release(self) -> None:
        self.system.release()
        self.system = None

    def check(self) -> dict:
        """Numbers compared, each ``(value, limit)``."""
        ref, R = self.ref, self.R
        served = self._served_counts()
        counter_gap = int(np.abs((self.counts1 - self.counts0).astype(np.int64) - served).sum())
        unrouted = self.attempted - int(served.sum())

        pos = generate.sample(len(self.ids), int(self.traffic["check_sample"]), self.seed,
                              "plan-check")
        base, grown = ref.rack_sets(self.ids[pos], self.capacities, self.rack, R)
        plan_gap = (_plan_gap(self.plans[0], pos, base, grown, ref)
                    + _plan_gap(self.plans[1], pos, grown, base, ref))

        keys, chosen, drain_of, rounds_at = self._sampled(int(self.traffic["check_sample"]))
        uniq, inv = np.unique(keys, return_inverse=True)
        base, grown = ref.rack_sets(uniq, self.capacities, self.rack, R)
        base, grown = base[inv], grown[inv]
        lookups = [_SlotLookup(p) for p in self.plans]
        non_holder = 0
        for d, drain in enumerate(self.drains):
            sel = drain_of == d
            if not sel.any():
                continue
            k = drain["k"]
            rows = lookups[k].rows(keys[sel], R)
            landed = np.where(rows >= 0, drain["landed_round"][np.maximum(rows, 0)], 0)
            pending = (landed == 0) | (landed > rounds_at[sel][:, None])
            before, after = (base[sel], grown[sel]) if k == 0 else (grown[sel], base[sel])
            non_holder += ref.non_holder_reads(chosen[sel], before, after, pending)

        unlanded = moves_gap = over_budget = 0
        for drain in self.drains:
            plan, budget = self.plans[drain["k"]], self.budgets[drain["k"]]
            if drain["ended"]:
                unlanded += plan.n_moves - int((drain["landed_round"] > 0).sum())
                total: dict = {}
                for m in drain["matrices"]:
                    for pair, c in m.items():
                        total[pair] = total.get(pair, 0) + c
                moves_gap += _rebalance._matrix_gap(plan, total)
            moves_gap += sum(abs(sum(m.values()) - n)
                             for m, n in zip(drain["matrices"], drain["landed_n"]))
            over_budget += sum(_rebalance._over_budget(m, budget) for m in drain["matrices"])
        self.log(f"check: {len(keys)} sampled requests ({len(uniq)} distinct keys) against "
                 f"the reference's holders at their rounds; plans on {len(pos)} sampled "
                 f"records; {sum(d['ended'] for d in self.drains)} ended drains")
        return {
            "read_from_non_holder": (non_holder, 0),
            "counter_gap": (counter_gap, 0),
            "unrouted": (unrouted, 0),
            "plan_vs_reference": (plan_gap, 0),
            "rows_not_landed": (unlanded, 0),
            "moves_vs_plan": (moves_gap, 0),
            "over_budget": (over_budget, 0),
        }

    def _sampled(self, k: int):
        """Keys, chosen nodes, drains and rounds done of a seeded sample of
        the routed requests."""
        sizes = np.asarray([len(r) for r in self.routes], dtype=np.int64)
        starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        pos = generate.sample(int(sizes.sum()), k, self.seed, "check")
        bi = np.searchsorted(starts, pos, side="right") - 1
        lane = pos - starts[bi]
        chosen = np.asarray([self.routes[b][l] for b, l in zip(bi, lane)], dtype=np.int64)
        keys = self.pool[bi % self.pool.shape[0], lane]
        at = np.asarray(self.batch_at, dtype=np.int64)[bi]
        return keys, chosen, at[:, 0], at[:, 1]

class _SlotLookup:
    """Plan rows by (id, slot): per slot, the slot's ids sorted."""

    def __init__(self, plan):
        self.slots = []
        for r in range(plan.n_replicas):
            rows = np.nonzero(plan.slot == r)[0]
            rows = rows[np.argsort(plan.ids[rows], kind="stable")]
            self.slots.append((plan.ids[rows], rows))

    def rows(self, keys: np.ndarray, n_replicas: int) -> np.ndarray:
        """(n, R) plan row of each key's slot, -1 where the slot has none."""
        out = np.full((len(keys), n_replicas), -1, dtype=np.int64)
        keys = keys.astype(np.uint32)
        for r, (ids, rows) in enumerate(self.slots):
            if ids.size == 0:
                continue
            at = np.minimum(np.searchsorted(ids, keys), ids.size - 1)
            hit = ids[at] == keys
            out[hit, r] = rows[at[hit]]
        return out


def _plan_gap(plan, pos, before, after, ref) -> int:
    """Rows that differ between the plan and the reference's minimal
    movement on the sampled records (``plan.index`` positions ids)."""
    b, r, src, dst = ref.minimal_rows(before, after)
    want = set(zip(pos[b].tolist(), r.tolist(), src.tolist(), dst.tolist()))
    keep = np.isin(plan.index, pos)
    got = set(zip(plan.index[keep].tolist(), plan.slot[keep].tolist(),
                  plan.src[keep].tolist(), plan.dst[keep].tolist()))
    return len(want ^ got)
