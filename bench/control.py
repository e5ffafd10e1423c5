"""The controls: the plain reference put in the program's place with one
stated guarantee broken, run through the whole harness, whose check must
then come out not correct.  The benchmark's own runs never run these.

    python bench/control.py --workload kv.zipf.closed --seed 7 --seconds 5

* ``route`` cells: keys are placed by the reference with every occupied
  segment counted as full length, so replica sets no longer follow node
  capacity (the guarantee of capacity-weighted ASURA placement), and a
  holder is chosen by power of two choices against the control's own
  counters.
* ``rebalance`` cells: the reference plans a change by comparing replica
  sets slot by slot instead of as sets, so a holder that only changed
  position also moves (the guarantee of minimal movement); the program's
  mover drains the plan.

Prints the check's numbers beside their limits, and the result line.
Exits 2 without a TPU, like ``run.py``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))


class RouteControl:
    def __init__(self, cell):
        self.ref, self.R = cell.ref, int(cell.config["replicas"])
        self.table = cell.ref.SegmentTable(cell.capacities)
        self.counts = np.zeros(cell.n_bins, dtype=np.int64)
        self.rng = np.random.default_rng(cell.seed)

    def route(self, keys):
        sets = self.ref.place_replicas(keys, self.table, self.R, weighted=False)
        n = len(keys)
        i = self.rng.integers(0, self.R, n)
        j = (i + 1 + self.rng.integers(0, self.R - 1, n)) % self.R
        a, b = sets[np.arange(n), i], sets[np.arange(n), j]
        chosen = np.where(self.counts[b] < self.counts[a], b, a)
        self.counts += np.bincount(chosen, minlength=len(self.counts))
        return chosen.astype(np.int32)

    def served(self):
        return self.counts.copy()

    def release(self):
        pass


class PlanControl:
    def __init__(self, cell):
        self.ref, self.R = cell.ref, int(cell.config["replicas"])
        self.table = cell.ref.SegmentTable(cell.capacities)
        self.version = 0
        self.sets = self.ref.place_replicas(cell.ids, self.table, self.R)

    def change(self, add=None, remove=None):
        if add is not None:
            self.table.add(*add)
        else:
            self.table.remove(remove)
        self.version += 1
        return self.version - 1, self.version, None

    def plan(self, ids, v_from, v_to, n_replicas, max_new_seg):
        from repro.migrate import MigrationPlan

        before, after = self.sets, self.ref.place_replicas(ids, self.table, self.R)
        self.sets = after
        b, r = np.nonzero(after != before)  # slot by slot: not minimal
        return MigrationPlan(
            v_from=v_from, v_to=v_to, ids=ids[b], src=before[b, r], dst=after[b, r],
            index=b.astype(np.int64), n_scanned=len(ids), n_replicas=n_replicas,
            slot=r.astype(np.int32), src_slot=r.astype(np.int32),
        )

    def mover(self, plan, budget):
        from repro.migrate import MigrationState, ThrottledMover

        return ThrottledMover(MigrationState(plan), egress=budget, ingress=budget)

    def counters(self):
        return {}

    def release(self):
        pass


CONTROLS = {"route": RouteControl, "rebalance": PlanControl}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import harness
    from repro.compile_cache import enable_compile_cache

    spec = harness.resolve(harness.load_manifest(ROOT), args.workload)
    enable_compile_cache(ROOT)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    out = harness.run_cell(spec, seed=args.seed, seconds=args.seconds, trace=False,
                           t_start=T_START, system=CONTROLS[spec["traffic"]["entry"]])
    for name, c in out["compared"].items():
        print(f"control {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
