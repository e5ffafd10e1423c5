"""Migration layer 2: the bandwidth-throttled mover.

Draining a ``MigrationPlan`` all at once would saturate the cluster
network exactly when it is already degraded (the scenario Sequential
Checking, arXiv:1707.00904, and the mean-field repair analysis,
arXiv:1701.00335, treat as the scarce resource).  The mover drains the
plan in ROUNDS under per-node ingress/egress budgets:

  * ``MigrationState`` -- the plan plus a landed bitmap (which moves have
    physically completed) and a device view of the still-pending id set
    for the dual-version read rule (``live.py``),
  * ``ThrottledMover``  -- each round picks pending rows in plan order,
    admitting a row only while both its source's egress budget and its
    destination's ingress budget have headroom, and returns the round's
    per-(src, dst) movement matrix.  The clock is injected (simulated,
    like ``runtime/failures.py``) so ``pump()`` advances exactly the
    rounds the wall time allows and tests stay deterministic.

Budget admission is conservative: ranks are computed per src group and
per dst group up front (vectorized), and a row is admitted iff BOTH ranks
are within budget -- a row blocked on one side may leave a slot of the
other side unused for a round, but neither budget is ever exceeded.
"""

from __future__ import annotations

import math
from typing import Callable

import numpy as np

from repro.obs.trace import span

from .drain import DrainDriver
from .planner import MigrationPlan


def _group_ranks(keys: np.ndarray) -> np.ndarray:
    """Rank of each element within its value group, preserving order.

    ``keys = [7, 3, 7, 7, 2]`` -> ``[0, 0, 1, 2, 0]``: the cumcount the
    budget admission is defined on (see ``_GroupIndex`` for the per-round
    sort-free evaluation).
    """
    if keys.size == 0:
        return np.zeros(0, dtype=np.int64)
    return _GroupIndex(keys).ranks(np.ones(len(keys), dtype=bool))


class _GroupIndex:
    """Per-round group-rank evaluation without per-round sorting.

    The plan's row order never changes -- only the pending mask does -- so
    the stable sort by node and the group boundaries are computed ONCE;
    each round the rank of every pending row within its group's pending
    rows is a segmented cumsum over the precomputed order: O(n) arithmetic,
    no sort, and bit-identical to ranking the compacted pending set.
    """

    def __init__(self, keys: np.ndarray):
        self.order = np.argsort(keys, kind="stable")
        sorted_keys = keys[self.order]
        self.is_start = np.empty(len(keys), dtype=bool)
        if len(keys):
            self.is_start[0] = True
            np.not_equal(sorted_keys[1:], sorted_keys[:-1], out=self.is_start[1:])

    def ranks(self, flags: np.ndarray) -> np.ndarray:
        """Rank of each row among the FLAGGED rows of its group (row order);
        meaningful only where ``flags`` is True."""
        if flags.size == 0:
            return np.zeros(0, dtype=np.int64)
        f = flags[self.order].astype(np.int64)
        cum = np.cumsum(f)
        before = cum - f  # flagged rows anywhere before this position
        base = np.maximum.accumulate(np.where(self.is_start, before, 0))
        ranks = np.empty(len(f), dtype=np.int64)
        ranks[self.order] = before - base
        return ranks


def _budget_of(budget, nodes: np.ndarray) -> np.ndarray:
    """Per-row budget array from None (unlimited), a scalar, or a dict.

    The dict path pays one Python lookup per DISTINCT node, not per
    pending row -- rounds over multi-million-row plans stay NumPy-bound.
    """
    no_limit = np.iinfo(np.int64).max
    if budget is None:
        return np.full(len(nodes), no_limit, dtype=np.int64)
    if isinstance(budget, dict):
        uniq, inverse = np.unique(nodes, return_inverse=True)
        caps = np.array(
            [budget.get(int(n), no_limit) for n in uniq], dtype=np.int64
        )
        return caps[inverse]
    return np.full(len(nodes), int(budget), dtype=np.int64)


def _scan_rounds(landed, *, src, dst, valid, src_c, dst_c, n_bins, k):
    """k throttled admission rounds in ONE jit over the pow2-padded plan.

    Module-level so jax's jit cache (keyed on array shapes + the static
    ``(n_bins, k)``) is shared by every mover in the process.  Each round
    recomputes the per-group admission ranks with the ``_GroupIndex``
    recurrence -- ``lax.cummax`` standing in for ``np.maximum.accumulate``
    -- and scatter-adds the admitted rows into a dense (n_bins, n_bins)
    movement matrix.
    """
    return _get_scan_rounds_jit()(
        landed, *src, *dst, valid, src_c, dst_c, n_bins=n_bins, k=k
    )


def _scan_rounds_impl(
    landed,
    order_s, start_s, cap_s,
    order_d, start_d, cap_d,
    valid, src_c, dst_c,
    *, n_bins, k,
):
    import jax
    import jax.numpy as jnp

    P = landed.shape[0]

    def ranks(order, is_start, pend):
        f = pend[order].astype(jnp.int32)
        cum = jnp.cumsum(f)
        before = cum - f
        base = jax.lax.cummax(jnp.where(is_start, before, 0))
        return jnp.zeros((P,), jnp.int32).at[order].set(before - base)

    def one(landed, _):
        pend = valid & ~landed
        take = (
            pend
            & (ranks(order_s, start_s, pend) < cap_s)
            & (ranks(order_d, start_d, pend) < cap_d)
        )
        mat = jnp.zeros((n_bins, n_bins), jnp.int32).at[src_c, dst_c].add(
            take.astype(jnp.int32)
        )
        return landed | take, mat

    return jax.lax.scan(one, landed, None, length=k)


_scan_rounds_jit = None  # jitted lazily: keep jax imports off the host path


def _get_scan_rounds_jit():
    global _scan_rounds_jit
    if _scan_rounds_jit is None:
        import jax

        _scan_rounds_jit = jax.jit(
            _scan_rounds_impl, static_argnames=("n_bins", "k")
        )
    return _scan_rounds_jit


class MigrationState:
    """A plan plus its landed bitmap -- the single source of truth for the
    dual-version read rule.

    Rows are per (id, replica_slot) -- the PER-SLOT LANDED BITMAP of
    DESIGN.md section 10; single-owner plans are the R=1 case.
    ``landed[i]`` flips True when row i's replica has physically arrived at
    ``dst[i]`` (and left ``src[i]``); until then readers of that slot must
    be routed to its v-side source.  ``pending_device()`` exposes the
    still-pending id set as a sorted, sentinel-padded device array so the
    single-owner serving hot path tests membership with zero host syncs
    (padding to the next power of two bounds recompiles at O(log n)
    distinct shapes); ``pending_replicas_device()`` is the per-slot twin:
    one sorted (ids, src) pair per replica slot, stacked (R, P), so the
    replica read rule probes all R slots in one jitted vmap.  Its P is
    fixed for the state's life, so a whole drain serves at one shape.
    """

    _SENTINEL = np.uint32(0xFFFFFFFF)

    def __init__(self, plan: MigrationPlan):
        self.plan = plan
        self.landed = np.zeros(plan.n_moves, dtype=bool)
        self._sorted_pending = None  # host cache for the serving hot path
        self._dev_view = None  # (padded sorted pending ids, count) device pair
        self._slot_host = None  # per-slot (sorted ids, src) host cache
        self._slot_dev = None  # per-slot device view (ids, src, counts)
        self._slot_order = None  # per-slot plan rows in id order (plan-constant)

    # -- host views ----------------------------------------------------------

    @property
    def n_pending(self) -> int:
        return int((~self.landed).sum())

    @property
    def done(self) -> bool:
        return self.n_pending == 0

    def pending_ids(self) -> np.ndarray:
        return self.plan.ids[~self.landed]

    def landed_ids(self) -> np.ndarray:
        return self.plan.ids[self.landed]

    def is_pending(self, datum_ids) -> np.ndarray:
        """Vectorized membership of ids in the still-pending move set.

        Probes a sorted pending array cached per round (invalidated by
        ``mark_landed``), so a serving read batch costs O(batch log
        pending), not a fresh sort of the pending set per call."""
        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        if self._sorted_pending is None:
            self._sorted_pending = np.sort(self.pending_ids())
        pending = self._sorted_pending
        if pending.size == 0:
            return np.zeros(ids.shape, dtype=bool)
        pos = np.searchsorted(pending, ids)
        return (pos < pending.size) & (pending[np.minimum(pos, pending.size - 1)] == ids)

    def mark_landed(self, rows: np.ndarray) -> None:
        """Flip plan rows to landed (the mover calls this per round)."""
        self.landed[rows] = True
        self._sorted_pending = None  # host and device views are stale
        self._dev_view = None
        self._slot_host = None
        self._slot_dev = None

    # -- per-slot views (replica read rule) ------------------------------------

    def _slot_rows(self) -> list[np.ndarray]:
        """Per slot, the plan's rows of that slot sorted by id: fixed for
        the plan, so a round's refresh filters them and never sorts."""
        if self._slot_order is None:
            plan = self.plan
            self._slot_order = []
            for r in range(plan.n_replicas):
                rows = np.nonzero(plan.slot == r)[0]
                self._slot_order.append(rows[np.argsort(plan.ids[rows], kind="stable")])
        return self._slot_order

    def _slot_tables(self) -> list[tuple[np.ndarray, np.ndarray]]:
        """Per-slot sorted pending ``(ids, src)`` pairs, cached per round.

        Within one slot each id appears at most once (a plan row is a
        unique (id, slot)), so a sorted array per slot supports the same
        O(batch log pending) probe ``is_pending`` uses."""
        if self._slot_host is None:
            plan = self.plan
            tables = []
            for rows in self._slot_rows():
                rows = rows[~self.landed[rows]]
                tables.append((plan.ids[rows], plan.src[rows]))
            self._slot_host = tables
        return self._slot_host

    def pending_replicas(self, datum_ids) -> tuple[np.ndarray, np.ndarray]:
        """(batch, R) pending mask + aligned v-side sources (host path).

        ``pending[b, r]`` says slot r of id b still awaits its copy;
        ``src[b, r]`` is then the node that holds that replica's bytes
        right now (meaningful only where pending)."""
        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        R = self.plan.n_replicas
        pending = np.zeros((len(ids), R), dtype=bool)
        src = np.zeros((len(ids), R), dtype=np.int64)
        for r, (p_ids, p_src) in enumerate(self._slot_tables()):
            if p_ids.size == 0:
                continue
            pos = np.searchsorted(p_ids, ids)
            pos_c = np.minimum(pos, p_ids.size - 1)
            hit = (pos < p_ids.size) & (p_ids[pos_c] == ids)
            pending[:, r] = hit
            src[hit, r] = p_src[pos_c[hit]]
        return pending, src

    def pending_replicas_device(self):
        """Per-slot device view: ``(ids_pad, src_pad, counts)``.

        ``ids_pad`` (R, P) sorted sentinel-padded pending ids per slot,
        ``src_pad`` (R, P) their aligned v-side sources, ``counts`` (R,)
        live lengths.  P is the next power of two of the plan's largest
        per-slot row count, fixed for this state's life: as rows land the
        sentinel tail grows and the shape does not, so serving through a
        whole drain compiles the read rule once.  Rebuilt lazily after
        ``mark_landed`` -- one host rebuild and upload per round on the
        control path (span ``migrate.pending_refresh``; ledger counters
        ``migrate.pending_refreshes`` and ``migrate.pending_rows``, the
        live rows summed over the slots); call outside any transfer guard.
        """
        if self._slot_dev is None:
            import jax.numpy as jnp

            from repro.obs import get_ledger

            with span("migrate.pending_refresh"):
                rows = self._slot_rows()
                n_max = max((len(r) for r in rows), default=0)
                padded_len = 1 << max(0, n_max - 1).bit_length()
                R = self.plan.n_replicas
                ids_pad = np.full((R, padded_len), self._SENTINEL, dtype=np.uint32)
                src_pad = np.full((R, padded_len), -1, dtype=np.int32)
                counts = np.zeros(R, dtype=np.int32)
                for r, (p_ids, p_src) in enumerate(self._slot_tables()):
                    ids_pad[r, : len(p_ids)] = p_ids
                    src_pad[r, : len(p_ids)] = p_src
                    counts[r] = len(p_ids)
                self._slot_dev = (
                    jnp.asarray(ids_pad),
                    jnp.asarray(src_pad),
                    jnp.asarray(counts),
                )
            ledger = get_ledger()
            ledger.incr("migrate.pending_refreshes")
            ledger.incr("migrate.pending_rows", int(counts.sum()))
        return self._slot_dev

    # -- device view ----------------------------------------------------------

    def pending_device(self):
        """(sorted_padded_ids, count) device pair for sync-free membership.

        Rebuilt lazily after ``mark_landed`` -- ONE upload per round on the
        control path, so the serving path (``live.route_device``) stays
        guarded-transfer clean.  Call this outside any transfer guard.
        """
        if self._dev_view is None:
            import jax.numpy as jnp

            pending = np.sort(self.pending_ids())
            n = len(pending)
            padded_len = max(1, 1 << (n - 1).bit_length()) if n else 1
            padded = np.full(padded_len, self._SENTINEL, dtype=np.uint32)
            padded[:n] = pending
            self._dev_view = (jnp.asarray(padded), jnp.asarray(np.int32(n)))
        return self._dev_view


class ThrottledMover(DrainDriver):
    """Drains a ``MigrationState`` in budgeted rounds.

    ``egress`` / ``ingress``: max rows (replica copies) a node may send /
    receive per round -- ``None`` (unlimited), a scalar applied to every
    node, or a ``{node_id: limit}`` dict (missing nodes unlimited).  Rows
    are per (id, replica_slot), so budgets and movement matrices account
    every replica copy individually.  ``clock`` is an injected time
    source; ``pump()`` runs however many whole ``round_seconds`` periods
    have elapsed since the last call, so a simulated clock drives
    deterministic tests and a real clock drives a real drain loop.  The
    round/pump/run verbs come from the shared ``DrainDriver`` loop.
    """

    def __init__(
        self,
        state: MigrationState,
        *,
        egress=None,
        ingress=None,
        clock: Callable[[], float] | None = None,
        round_seconds: float = 1.0,
        ledger=None,
        metrics=None,
        bytes_per_row: int = 0,
    ):
        self.state = state
        self.egress = egress
        self.ingress = ingress
        self.clock = clock
        self.round_seconds = float(round_seconds)
        # observability (optional): a TraceLedger gets one structured
        # event per round via the DrainDriver hook; ``bytes_per_row``
        # prices each (id, slot) row so the events/counters carry bytes.
        self.ledger = ledger
        self.metrics = metrics
        self.bytes_per_row = int(bytes_per_row)
        self.rounds_done = 0
        self._pumped = 0  # clock-paced rounds only (manual round()s excluded)
        self.history: list[dict[tuple[int, int], int]] = []
        self._t0 = clock() if clock is not None else 0.0
        # Row order and budgets never change; precompute so each round is
        # pure O(n) arithmetic (no sort, no Python per-row lookups).
        self._by_src = _GroupIndex(state.plan.src)
        self._by_dst = _GroupIndex(state.plan.dst)
        self._cap_src = _budget_of(egress, state.plan.src)
        self._cap_dst = _budget_of(ingress, state.plan.dst)
        # Device round engine (lazy): built on the first round_block().
        self._dev_rounds = None
        self._block_fns: dict[int, object] = {}

    @property
    def done(self) -> bool:
        return self.state.done

    @property
    def next_round_at(self) -> float | None:
        """Clock time the next paced round becomes due (None: no clock or
        already drained).  Event-driven callers (the durability simulator)
        use this to jump virtual time straight to the next thing that can
        happen instead of polling round by round."""
        if self.clock is None or self.done:
            return None
        return self._t0 + (self._pumped + 1) * self.round_seconds

    def _pending_desc(self) -> str:
        return f"{self.state.n_pending} rows pending"

    def _round(self) -> dict[tuple[int, int], int]:
        """One throttled round -> the per-(src, dst) movement matrix."""
        state = self.state
        pending = ~state.landed
        take = (
            pending
            & (self._by_src.ranks(pending) < self._cap_src)
            & (self._by_dst.ranks(pending) < self._cap_dst)
        )
        moved_rows = np.nonzero(take)[0]
        state.mark_landed(moved_rows)
        matrix: dict[tuple[int, int], int] = {}
        if moved_rows.size:
            pairs, counts = np.unique(
                np.stack([state.plan.src[take], state.plan.dst[take]], axis=1),
                axis=0,
                return_counts=True,
            )
            matrix = {
                (int(s), int(d)): int(c) for (s, d), c in zip(pairs, counts)
            }
        self.rounds_done += 1
        self.history.append(matrix)
        return matrix

    def _pump_rounds(self) -> list[dict[tuple[int, int], int]]:
        """The injected-clock pacing (0 rounds if none are due).

        Clock-paced rounds are accounted separately from manual ``round()``
        calls, so mixing an eager kick-off round with ``pump()`` never
        skips periods the clock has earned."""
        if self.clock is None:
            return [] if self.done else [self._round()]
        due = int(math.floor((self.clock() - self._t0) / self.round_seconds))
        out = []
        while self._pumped < due and not self.done:
            out.append(self._round())
            self._pumped += 1
        return out

    # -- device-resident round blocks (DESIGN.md section 15) ------------------

    def _device_rounds(self):
        """Lazy device round engine over the pow2-padded plan view.

        Everything the admission rule needs is plan-constant -- the stable
        group orders, group-start flags, per-row budget caps, scatter
        coordinates -- so it uploads ONCE per mover and each round becomes
        pure on-device arithmetic: a segmented cumsum per group axis (the
        ``_GroupIndex.ranks`` recurrence, with ``lax.cummax`` standing in
        for ``np.maximum.accumulate``) and one landed-bitmap OR.  Budget
        caps clamp to int32 max: ranks are < P <= 2^31, so the comparison
        is unchanged.  Returns None for an empty plan."""
        if self._dev_rounds is None:
            plan = self.state.plan
            n = plan.n_moves
            if n == 0:
                self._dev_rounds = False
            else:
                with span("mover.prepare"):
                    import jax.numpy as jnp

                    P = 1 << max(0, n - 1).bit_length()
                    no_key = np.iinfo(np.int64).max  # pads sort last
                    i32max = np.iinfo(np.int32).max

                    def axis(keys, caps):
                        kp = np.full(P, no_key, dtype=np.int64)
                        kp[:n] = keys
                        order = np.argsort(kp, kind="stable")
                        sk = kp[order]
                        is_start = np.empty(P, dtype=bool)
                        is_start[0] = True
                        np.not_equal(sk[1:], sk[:-1], out=is_start[1:])
                        cp = np.zeros(P, dtype=np.int64)
                        cp[:n] = np.minimum(caps, i32max)
                        return (
                            jnp.asarray(order.astype(np.int32)),
                            jnp.asarray(is_start),
                            jnp.asarray(cp.astype(np.int32)),
                        )

                    n_bins = int(max(plan.src.max(), plan.dst.max())) + 1
                    coord = np.zeros((2, P), dtype=np.int32)
                    coord[0, :n] = plan.src
                    coord[1, :n] = plan.dst
                    self._dev_rounds = {
                        "src": axis(plan.src, self._cap_src),
                        "dst": axis(plan.dst, self._cap_dst),
                        "valid": jnp.asarray(np.arange(P) < n),
                        "src_c": jnp.asarray(coord[0]),
                        "dst_c": jnp.asarray(coord[1]),
                        "n_bins": n_bins,
                        "P": P,
                    }
        return self._dev_rounds or None

    def _block_fn(self, k: int):
        """k-round scan, bound to this mover's plan-constant arrays.

        The jit itself is the MODULE-LEVEL ``_scan_rounds`` (static over
        (k, n_bins) and cached by jax on array shapes), so two movers with
        same-shape plans share one compile -- a fresh migration pays no
        retrace for its round blocks."""
        fn = self._block_fns.get(k)
        if fn is not None:
            return fn
        import functools

        dv = self._device_rounds()
        fn = functools.partial(
            _scan_rounds,
            src=dv["src"],
            dst=dv["dst"],
            valid=dv["valid"],
            src_c=dv["src_c"],
            dst_c=dv["dst_c"],
            n_bins=dv["n_bins"],
            k=k,
        )
        self._block_fns[k] = fn
        return fn

    def _round_block(self, k: int) -> list[dict[tuple[int, int], int]]:
        """k throttled rounds on device -- ONE dispatch, one sync back.

        Bit-identical to k sequential ``_round()`` calls: the scan carries
        the landed bitmap so each round's admission sees the previous
        round's landings, and the per-round matrices aggregate the same
        (src, dst) pair counts ``np.unique`` produces on the host path.
        Runs exactly k rounds even once drained (trailing rounds move
        nothing and record empty matrices, like the host loop)."""
        state = self.state
        if self._device_rounds() is None:  # empty plan: host loop is exact
            return [self._round() for _ in range(k)]
        import jax.numpy as jnp

        dv = self._device_rounds()
        P, n = dv["P"], state.plan.n_moves
        landed = state.landed if n == P else np.pad(state.landed, (0, P - n))
        with span("mover.scan"):
            landed_out, mats = self._block_fn(k)(jnp.asarray(landed))
            landed_np = np.asarray(landed_out)[:n]
            mats_np = np.asarray(mats)
        with span("mover.matrices"):
            newly = landed_np & ~state.landed
            state.mark_landed(np.nonzero(newly)[0])
            matrices: list[dict[tuple[int, int], int]] = []
            for r in range(k):
                s_idx, d_idx = np.nonzero(mats_np[r])
                matrices.append(
                    {
                        (int(s), int(d)): int(mats_np[r, s, d])
                        for s, d in zip(s_idx, d_idx)
                    }
                )
        self.rounds_done += k
        self.history.extend(matrices)
        return matrices

    def round_block(self, k: int) -> list[dict[tuple[int, int], int]]:
        """Run k budgeted rounds in ONE device dispatch; returns the k
        per-round movement matrices (ledger-emitted like any other round).
        Counts as manual rounds: clock pacing (``pump``) is unaffected."""
        k = int(k)
        if k < 1:
            raise ValueError(f"round_block needs k >= 1, got {k}")
        with span("mover.round_block"):
            return self._emit_rounds(
                self._advance(lambda: self._round_block(k))
            )

    def movement_matrix(self) -> dict[tuple[int, int], int]:
        """Accumulated (src, dst) -> rows moved so far, across all rounds."""
        total: dict[tuple[int, int], int] = {}
        for matrix in self.history:
            for pair, count in matrix.items():
                total[pair] = total.get(pair, 0) + count
        return total
