"""Plain NumPy reference of ASURA replica placement and minimal movement.

Written from the ASURA paper (Ishikawa 2013) and the repository's stated
arithmetic, and importing nothing of the program:

* STEP 1, segments.  Nodes join in order.  A node of capacity ``c`` takes
  ``floor(c)`` full segments of length ``(2**32 - 1) / 2**32`` and one
  segment of the remainder, each at the smallest free segment number
  (freed numbers first, else a new one at the end).  A removed node's
  numbers become free.  Lengths are held as ``round(length * 2**32)``.
* STEP 2, the ASURA random number.  Draw ``l`` of the level-``l``
  generator for id ``x`` is ``fmix32(fmix32(x + GOLDEN * (l + 1)) ^ (n *
  KMULT))`` (MurmurHash3's finalizer).  From the top level ``L`` (the
  least with ``2**(1 + L)`` covering the last occupied segment's end) a
  number descends one level while its draw is below ``2**31``; the level
  it stops at gives segment ``h >> (31 - l)`` and fraction ``h << (1 + l)``.
  A number hits when its segment exists and the fraction lies below the
  segment's length.
* Replication (paper section 5.A): the first ``R`` hits on distinct nodes,
  primary first.
* Movement on a change (sections 5.A, 6.D): a slot of the new set moves
  iff its node was not in the old set; the k-th such slot takes its bytes
  from the k-th node of the old set that left it.

Every step is exact integer arithmetic, so the program must agree bit for
bit.  ``weighted=False`` treats every occupied segment as full length, so
placement no longer follows capacity: that is the control, which breaks a
guarantee the configurations state.
"""

from __future__ import annotations

import heapq
import math

import numpy as np

GOLDEN = 0x9E3779B9
KMULT = 0x85EBCA77
S_LOG2 = 1
FULL_LEN32 = 2**32 - 1


def fmix32(h: np.ndarray) -> np.ndarray:
    h = h.astype(np.uint32)
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    return h


class SegmentTable:
    """STEP 1: the node <-> segment table of a cluster, mutated in place."""

    def __init__(self, capacities):
        self.len32: list[int] = []
        self.node_of: list[int] = []
        self.free: list[int] = []
        self.segments: dict[int, list[int]] = {}
        for node, cap in enumerate(capacities):
            self.add(node, float(cap))

    def _alloc(self) -> int:
        if self.free:
            return heapq.heappop(self.free)
        self.len32.append(0)
        self.node_of.append(-1)
        return len(self.len32) - 1

    def add(self, node: int, capacity: float) -> list[int]:
        whole = math.floor(capacity)
        lengths = [FULL_LEN32] * whole
        rest = capacity - whole
        if rest > 1e-12:
            lengths.append(min(round(rest * 2**32), FULL_LEN32))
        segs = []
        for length in lengths:
            seg = self._alloc()
            self.len32[seg] = length
            self.node_of[seg] = node
            segs.append(seg)
        self.segments[node] = segs
        return segs

    def remove(self, node: int) -> list[int]:
        segs = self.segments.pop(node)
        for seg in segs:
            self.len32[seg] = 0
            self.node_of[seg] = -1
            heapq.heappush(self.free, seg)
        return segs

    def capacity_share(self, n_bins: int) -> np.ndarray:
        """Each node's share of the total segment mass, indexed by node."""
        share = np.zeros(n_bins, dtype=np.float64)
        for node, segs in self.segments.items():
            share[node] = sum(self.len32[s] for s in segs)
        return share / share.sum()

    def arrays(self) -> tuple[np.ndarray, np.ndarray, int]:
        len32 = np.asarray(self.len32, dtype=np.uint32)
        node_of = np.asarray(self.node_of, dtype=np.int64)
        last = int(np.nonzero(len32)[0][-1])
        upper = last + float(len32[last]) / 2**32
        top = max(0, math.ceil(math.log2(max(upper, 1.0))) - S_LOG2)
        return len32, node_of, top


def place_replicas(ids, table: SegmentTable, n_replicas: int, *, weighted: bool = True,
                   max_rounds: int = 100_000) -> np.ndarray:
    """(n, R) replica nodes of ``ids``, primary first."""
    len32, node_of, top = table.arrays()
    if not weighted:
        len32 = np.where(len32 > 0, np.uint32(FULL_LEN32), np.uint32(0))
    n_segs = len(len32)
    ids = np.asarray(ids, dtype=np.uint32)
    n = ids.shape[0]
    out = np.full((n, n_replicas), -1, dtype=np.int64)
    found = np.zeros(n, dtype=np.int64)
    counters = np.zeros((top + 1, n), dtype=np.uint32)
    lane = np.arange(n)
    with np.errstate(over="ignore"):
        seeds = [fmix32(ids + np.uint32((GOLDEN * (lv + 1)) & 0xFFFFFFFF)) for lv in range(top + 1)]
        for _ in range(max_rounds):
            if lane.size == 0:
                return out
            # one ASURA number per live lane: descend while the draw is < 2**31
            level = np.full(lane.size, top, dtype=np.int64)
            h = np.zeros(lane.size, dtype=np.uint32)
            consult = np.ones(lane.size, dtype=bool)
            for lv in range(top, -1, -1):
                rows = lane[consult]
                hv = fmix32(seeds[lv][rows] ^ (counters[lv, rows] * np.uint32(KMULT)))
                counters[lv, rows] += np.uint32(1)
                h[consult] = hv
                level[consult] = lv
                stay = np.zeros(lane.size, dtype=bool)
                if lv > 0:
                    stay[consult] = hv < np.uint32(2**31)
                consult = stay
                if not consult.any():
                    break
            seg = (h.astype(np.int64) >> (32 - S_LOG2 - level)).astype(np.int64)
            frac = (h.astype(np.uint64) << (S_LOG2 + level).astype(np.uint64)) & np.uint64(0xFFFFFFFF)
            ok = seg < n_segs
            seg_c = np.minimum(seg, n_segs - 1)
            hit = ok & (frac < len32[seg_c].astype(np.uint64))
            node = node_of[seg_c]
            hit &= ~(out[lane] == node[:, None]).any(axis=1)
            rows = lane[hit]
            out[rows, found[rows]] = node[hit]
            found[rows] += 1
            lane = lane[found[lane] < n_replicas]
    raise RuntimeError("replica placement did not converge")


def align(before: np.ndarray, after: np.ndarray):
    """Minimal per-slot movement between two (n, R) replica sets.

    Returns ``(moved, src)``: ``moved[b, r]`` when ``after[b, r]`` is not in
    ``before[b]``, and for such a slot ``src[b, r]`` is the old holder that
    left the set, matched in slot order (``after[b, r]`` elsewhere)."""
    n, R = after.shape
    moved = ~(after[:, :, None] == before[:, None, :]).any(axis=2)
    lost = ~(before[:, :, None] == after[:, None, :]).any(axis=2)
    src = after.copy()
    for b in np.nonzero(moved.any(axis=1))[0]:
        gone = before[b][lost[b]]
        src[b, np.nonzero(moved[b])[0]] = gone
    return moved, src
