"""Migration layer 3: the dual-version serving window.

While a plan drains, the system is BETWEEN versions: some data already
sits at its v+1 owner, the rest still at its v owner.  ``LiveMigration``
owns that window and gives readers one total rule (DESIGN.md section 8):

    route(id) = v   owner  if id's move is still pending,
                v+1 owner  otherwise (landed, or never had to move)

Equivalently: route to the v+1 owner iff the id's move has landed.  The
"pending" formulation is what makes ROLLBACK free: reversing a
half-landed migration is just a new LiveMigration whose plan is the
landed rows with src/dst swapped and v_from/v_to swapped -- unlanded
rows of the original never moved, so under the reversed rule they fall
into the "not in plan -> v_to(reverse) = v(original) owner" case, which
is exactly where they physically are.

``route_replicas[_device]`` is the per-slot REPLICA generalization
(DESIGN.md section 10): each slot of an id's R-replica set is
independently v or v+1 by its own landed bit --

    route_replicas(id)[r] = plan.src of (id, r)  while that slot's copy
                            is pending (the vacated v-side node still
                            holding the bytes),
                            v+1 set's slot r     otherwise

-- so every served set is R pairwise-distinct nodes that all physically
hold the datum at every round.  Rollback stays free: the reverse plan
swaps src/dst AND slot/src_slot, re-indexing slots into the reverse
destination (= original v) set.

Both versions' placements come from the engine's artifact LRU (no table
re-upload during the window, no matter how often the router flaps) and
the device paths keep the whole rule on device: the fused dual-table
diff kernels supply the owners, sorted-membership probes against the
(per-slot) pending sets supply the landed bits, and one ``where`` merges
them -- zero host syncs after the per-round control-path update.
"""

from __future__ import annotations

import functools

import numpy as np

from .drain import DrainDriver
from .mover import MigrationState, ThrottledMover


@functools.cache
def _member_fn():
    """Jitted sorted-set membership (lazy: no jax import on the host path)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def member(ids, sorted_pad, n):
        pos = jnp.searchsorted(sorted_pad, ids.astype(jnp.uint32), side="left")
        pos_c = jnp.minimum(pos, sorted_pad.shape[0] - 1)
        return (pos < n) & (sorted_pad[pos_c] == ids)

    return member


_ROUTE_CACHE: dict = {}


def probe_trace_count(kind: str = "replica_route") -> int:
    """Total jit traces of the fused window probes so far (the tests'
    tripwire that repeated serving batches stop retracing).  The count
    lives on the process-wide ``obs`` ledger now (the probe cache is
    module-level, so its counter is too); this alias keeps the PR-7
    call sites reading the same way."""
    from repro.obs import get_ledger

    return get_ledger().counter(f"migrate.live.{kind}_traces")


# Pending ids per row of the two-level probe.  On a TPU v5e, at 65,536
# lanes and P of 2^17 and 2^19, rows of 128 probed fastest of 32-2,048
# (4.2-4.8 ms for three slots; 512: 8.7 ms; a binary search: 44-81 ms).
PROBE_ROW = 128


def probe_pending(u, ids_pad, src_pad, counts):
    """The per-slot pending probe, traced: a membership search of ``u`` in
    each slot of the ``pending_replicas_device`` view, vmapped over the
    static R slots.

    ``u`` (batch,) uint32 ids -> ``(hit, src)``, each (batch, R): whether
    slot r of the id still awaits its copy, and the aligned v-side source
    (meaningful only where ``hit``).  Every traced read rule calls this
    one body.

    Two levels instead of a binary search: each slot's sorted P ids are
    viewed as rows of ``PROBE_ROW``; a lane counts the live rows whose
    first id is <= its id (a compare against every row head, VPU work),
    then gathers that one row and compares across it.  On a TPU a binary
    search is ``log2 P`` dependent gathers of single words, each a pass
    over the batch; this is one row gather.  Ids within a slot are
    unique, so the live entries equal to an id sit in one row; the
    sentinel tail is never live (``pos < n``)."""
    import jax
    import jax.numpy as jnp

    P = ids_pad.shape[1]
    width = min(PROBE_ROW, P)
    n_rows = P // width

    def per_slot(sorted_pad, src_vals, n):
        rows = sorted_pad.reshape(n_rows, width)
        live = jnp.arange(n_rows, dtype=jnp.int32) * width < n
        heads = (rows[:, 0][None, :] <= u[:, None]) & live[None, :]
        row = jnp.maximum(jnp.sum(heads, axis=1, dtype=jnp.int32) - 1, 0)
        eq = rows[row] == u[:, None]
        pos = row * width + jnp.argmax(eq, axis=1).astype(jnp.int32)
        hit = eq.any(axis=1) & (pos < n)
        return hit, src_vals[pos]

    hit, src = jax.vmap(per_slot)(ids_pad, src_pad, counts)
    return hit.T, src.T


def migrating_owners(statics: tuple):
    """The traced per-slot read rule of DESIGN.md section 10.2 for one
    static routing configuration ``(top_level, s_log2, max_draws,
    n_replicas)``: ``owners(u, len32, node_of, ids_pad, src_pad, counts)``
    -> (batch, R) int32 holders -- the v+1 replica sets (``len32`` /
    ``node_of`` are ``v_to``'s device tables), with each pending slot
    replaced by its v-side source."""
    import jax.numpy as jnp

    from repro.kernels.ops import _place_replicas_fused_ref

    top_level, s_log2, max_draws, n_replicas = statics

    def owners(u, len32, node_of, ids_pad, src_pad, counts):
        dst = _place_replicas_fused_ref(
            u,
            len32,
            node_of,
            top_level=top_level,
            s_log2=s_log2,
            max_draws=max_draws,
            n_replicas=n_replicas,
            emit_nodes=True,
        )
        hit, src = probe_pending(u, ids_pad, src_pad, counts)
        return jnp.where(hit, src, dst)

    return owners


def _fused_replica_route(statics: tuple):
    """ONE jit for the whole replica read rule, cached per
    ``(top_level, s_log2, max_draws, n_replicas)``.

    The batched serving driver calls ``route_replicas_device`` every
    batch; dispatching three separate jits (dst placement, membership
    probe, merge) per batch is measurable overhead and three chances to
    leak an eager op.  This fuses dst = v+1 replica sets, the per-slot
    pending probe and the ``where`` merge (``migrating_owners``) into one
    traced body.  The cache key is exactly the static routing
    configuration -- re-begun windows, rollbacks and fresh
    ``LiveMigration`` objects at the same config all reuse the same
    compiled probe.
    """
    fn = _ROUTE_CACHE.get(statics)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp

    from repro.obs import get_ledger

    owners = migrating_owners(statics)

    @jax.jit
    def route(ids, len32, node_of, ids_pad, src_pad, counts):
        get_ledger().incr("migrate.live.replica_route_traces")  # per TRACE
        return owners(ids.astype(jnp.uint32), len32, node_of, ids_pad, src_pad, counts)

    _ROUTE_CACHE[statics] = route
    return route


class LiveMigration(DrainDriver):
    """One membership change served THROUGH its throttled drain.

    Wraps the three layers: the assembled plan (in ``state.plan``), the
    landed bitmap (``state``), and the budgeted scheduler (``mover``).
    The cluster table is already at v+1 when this object exists; readers
    must go through ``route``/``route_device`` until ``done``.
    """

    def __init__(self, engine, state: MigrationState, mover: ThrottledMover):
        self.engine = engine
        self.state = state
        self.mover = mover
        self.aborted = False
        # NO window-level ledger: this wrapper's _round/_pump_rounds call
        # the inner mover's PUBLIC verbs, whose DrainDriver hook already
        # emits each round exactly once.

    @classmethod
    def from_plan(
        cls,
        engine,
        plan,
        *,
        egress=None,
        ingress=None,
        clock=None,
        round_seconds: float = 1.0,
        ledger=None,
        metrics=None,
        bytes_per_row: int = 0,
    ) -> "LiveMigration":
        """Assemble the standard state + throttled mover around a plan (the
        one construction path every consumer shares)."""
        state = MigrationState(plan)
        mover = ThrottledMover(
            state,
            egress=egress,
            ingress=ingress,
            clock=clock,
            round_seconds=round_seconds,
            ledger=ledger,
            metrics=metrics,
            bytes_per_row=bytes_per_row,
        )
        return cls(engine, state, mover)

    # -- window state ---------------------------------------------------------

    @property
    def v_from(self) -> int:
        return self.state.plan.v_from

    @property
    def v_to(self) -> int:
        return self.state.plan.v_to

    @property
    def done(self) -> bool:
        return self.state.done

    def _check_live(self) -> None:
        if self.aborted:
            raise RuntimeError("migration was rolled back; drive the reverse one")

    # -- dual-version read rule ----------------------------------------------

    def route(self, datum_ids) -> np.ndarray:
        """ids -> the node that HOLDS each datum right now (host path).

        Only the (typically shrinking) pending subset pays the second
        placement under v; everything else is one placement under v+1.
        """
        self._check_live()
        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        owner = self.engine.place_nodes_at(ids, self.v_to)
        pending = self.state.is_pending(ids)
        if pending.any():
            owner[pending] = self.engine.place_nodes_at(
                ids[pending], self.v_from
            )
        return owner

    def route_device(self, datum_ids):
        """Device-resident read rule: int32 node ids, zero host syncs.

        The pending-set device view is refreshed on the control path
        (``round``/``pump`` mark rows landed; the first ``route_device``
        after that pays the one upload) -- call once outside any transfer
        guard after each round, then serve freely."""
        self._check_live()
        import jax.numpy as jnp

        _, src, dst = self.engine.diff_nodes_device(
            datum_ids, self.v_from, self.v_to
        )
        sorted_pad, n = self.state.pending_device()
        pending = _member_fn()(jnp.asarray(datum_ids), sorted_pad, n)
        return jnp.where(pending, src, dst)

    # -- per-slot replica read rule (DESIGN.md section 10) --------------------

    @property
    def n_replicas(self) -> int:
        return self.state.plan.n_replicas

    def route_replicas(self, datum_ids) -> np.ndarray:
        """ids -> the (batch, R) replica sets that HOLD each datum now.

        Slot r serves its vacated v-side source while its copy is pending
        and the v+1 owner after; non-moving slots hold the datum
        throughout.  Every returned set is pairwise-distinct: pending
        sources are vacated (lost) nodes, which by construction are not
        members of the v+1 set, and distinct slots pair with distinct
        sources (the rank-matched alignment).
        """
        self._check_live()
        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        owner = self.engine.place_replica_nodes_at(ids, self.v_to, self.n_replicas)
        pending, src = self.state.pending_replicas(ids)
        return np.where(pending, src, owner)

    def route_operands(self):
        """``(statics, operands)`` of the traced read rule
        (``migrating_owners``): the static routing configuration, and
        ``v_to``'s device tables followed by the per-slot pending view
        (refreshed here after a round; call outside any transfer guard)."""
        self._check_live()
        art = self.engine._device_artifact_for(self.v_to, "asura")
        params = self.engine.params
        statics = (art.top_level, params.s_log2, params.max_draws, self.n_replicas)
        ids_pad, src_pad, counts = self.state.pending_replicas_device()
        return statics, (art.len32_dev, art.node_of_dev, ids_pad, src_pad, counts)

    def route_replicas_device(self, datum_ids):
        """Device-resident ``route_replicas``: (batch, R) int32, zero host
        syncs after the per-round control-path refresh (the per-slot
        pending view uploads once per round, like ``route_device``).

        The whole rule -- v+1 replica placement, per-slot pending probe,
        merge -- runs as ONE cached jit (``_fused_replica_route``), so the
        batched serving driver pays a single dispatch per batch and
        repeated batches never retrace (``probe_trace_count`` tripwire)."""
        import jax.numpy as jnp

        statics, operands = self.route_operands()
        return _fused_replica_route(statics)(jnp.asarray(datum_ids), *operands)

    # -- drain control (round/pump/run from the shared DrainDriver loop) ------

    def _advance(self, fn):
        self._check_live()
        return fn()

    def _round(self) -> dict[tuple[int, int], int]:
        return self.mover.round()

    def _pump_rounds(self) -> list[dict[tuple[int, int], int]]:
        # delegate so clock accounting lives in the mover alone (mixing
        # mover.pump() and migration.pump() must not double-run periods)
        return self.mover.pump()

    def round_block(self, k: int) -> list[dict[tuple[int, int], int]]:
        """k budgeted rounds in ONE device dispatch (the mover's
        scan-fused round block); returns the k per-round matrices.  The
        mover's public verb already ledger-emits each round exactly once,
        so this wrapper only adds the liveness guard."""
        self._check_live()
        return self.mover.round_block(k)

    def _pending_desc(self) -> str:
        return f"{self.state.n_pending} rows pending"

    # -- rollback -------------------------------------------------------------

    def rollback(self) -> "LiveMigration":
        """Reverse a half-landed migration; returns the reverse migration.

        The reverse plan is the LANDED rows with src/dst and v_from/v_to
        swapped (unlanded rows never moved -- nothing to reverse).  This
        object becomes inert; drive and route through the returned one.
        Budgets swap roles with the flow direction: the forward drain's
        per-node ingress caps bind the reverse drain's egress and vice
        versa, so the node the throttle was protecting stays protected.
        Both versions stay in the artifact LRU, so the flap re-uploads
        nothing.  Once the reverse drain completes, all data is back at
        its v owner and the caller may revert the membership change
        itself (e.g. ``cluster.remove_node`` of the just-added node) --
        segment correspondences never change (paper rule 2), so the
        reverted table places identically to v.  Consumers that maintain
        side state per owner should roll it back too
        (``ElasticCoordinator.rollback_live`` does).
        """
        self._check_live()
        if getattr(self, "membership_event", None) is not None and not getattr(
            self, "_coordinator_rollback", False
        ):
            # A coordinator-owned migration carries side state (owner table,
            # membership) that a bare reversal would silently desync.
            raise RuntimeError(
                "this migration belongs to an ElasticCoordinator; use "
                "coordinator.rollback_live(migration)"
            )
        from .planner import MigrationPlan

        plan, landed = self.state.plan, self.state.landed
        reverse_plan = MigrationPlan(
            v_from=plan.v_to,
            v_to=plan.v_from,
            ids=plan.ids[landed],
            src=plan.dst[landed],
            dst=plan.src[landed],
            index=plan.index[landed],
            n_scanned=plan.n_scanned,
            n_replicas=plan.n_replicas,
            # slots index the plan's DESTINATION set; the reverse drains
            # back into the original v set, so slot/src_slot swap along
            # with src/dst (DESIGN.md section 10).
            slot=plan.src_slot[landed],
            src_slot=plan.slot[landed],
        )
        self.aborted = True
        mover = self.mover
        reverse = LiveMigration.from_plan(
            self.engine,
            reverse_plan,
            egress=mover.ingress,  # reversed flows: receive caps now bind sends
            ingress=mover.egress,
            clock=mover.clock,
            round_seconds=mover.round_seconds,
            ledger=mover.ledger,
            metrics=mover.metrics,
            bytes_per_row=mover.bytes_per_row,
        )
        tracked = getattr(self, "tracked_rows", None)
        if tracked is not None:
            # consumer side-state mapping rides along (plan rows = landed)
            reverse.tracked_rows = tracked[landed]
        return reverse
