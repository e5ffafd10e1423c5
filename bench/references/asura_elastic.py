"""Plain NumPy reference of reads served while a rack joins and leaves.

It reuses ``asura.py``'s placement (STEP 1, STEP 2 and replication, exact
integer arithmetic) and imports nothing of the program.  It adds three
things, from the ASURA paper (sections 5.A and 6.D) and the read rule a
cluster must keep while it migrates:

* The per-slot minimal-movement plan of a change, in either direction.
  Slot ``r`` of the new set moves iff its node was not in the old set;
  the k-th such slot takes its bytes from the k-th node of the old set
  that left it (``asura.align``).  A rack joining moves rows only onto
  the rack; the rack leaving moves rows only off it.
* Who holds a datum at a given round.  A slot that does not move is held
  by its node throughout.  A slot that moves is held by its source until
  its row lands and by its destination from then on.
* The check: a read is sound iff the node it was served by holds the
  datum at the round the read was served.
"""

from __future__ import annotations

import importlib.util
import os
import re
import sys

import numpy as np


def _asura():
    """``asura.py``, beside this file (references are loaded by path)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "asura.py")
    name = "bench_reference_" + re.sub(r"\W", "_", path)
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


asura = _asura()
SegmentTable = asura.SegmentTable
place_replicas = asura.place_replicas
align = asura.align


def rack_sets(ids, capacities, rack: list[tuple[int, float]], n_replicas: int):
    """(base, grown): the (n, R) replica sets of ``ids`` on the cluster, and
    on the cluster once the ``rack``'s ``(node, capacity)`` pairs have
    joined in order.  The rack leaving returns the cluster to ``base``:
    its segment numbers are freed and every other node keeps its own."""
    table = SegmentTable(capacities)
    base = place_replicas(ids, table, n_replicas)
    for node, cap in rack:
        table.add(node, cap)
    grown = place_replicas(ids, table, n_replicas)
    return base, grown


def minimal_rows(before: np.ndarray, after: np.ndarray):
    """The per-slot minimal-movement plan from ``before`` to ``after`` (both
    (n, R)): ``(b, r, src, dst)`` arrays, one entry per moving slot, ``b``
    indexing the rows of the two sets and ``r`` the slot of ``after``."""
    moved, src = align(before, after)
    b, r = np.nonzero(moved)
    return b, r, src[b, r], after[b, r]


def holders(before: np.ndarray, after: np.ndarray, pending: np.ndarray) -> np.ndarray:
    """(n, R) nodes that hold each datum while the change drains:
    ``pending[b, r]`` says slot ``r``'s row has not landed yet (it is read
    only where the slot moves)."""
    moved, src = align(before, after)
    return np.where(moved & pending, src, after)


def non_holder_reads(chosen: np.ndarray, before: np.ndarray, after: np.ndarray,
                     pending: np.ndarray) -> int:
    """Reads served by a node that did not hold the datum at the read's
    round."""
    held = holders(before, after, pending)
    return int((~(held == np.asarray(chosen)[:, None]).any(axis=1)).sum())
