"""ASURA session routing across serving replicas.

Sessions (request streams with KV caches) are sticky: a session's cache
lives on one replica, so re-routing a session is expensive (cache refill =
a full prefill). ASURA gives exactly the right trade:

  * any frontend computes the owner locally from the O(N) table — no
    routing service, no consistent-hashing ring to sync,
  * replica loss re-routes ONLY its sessions (everyone else's caches stay
    hot) — the paper's removal-optimality theorem,
  * capacity-weighted replicas (heterogeneous hardware generations) get
    proportional load via segment lengths,
  * scale-out steals the minimal set of sessions from existing replicas.

``plan_scale_event`` returns the exact session moves so the serving layer
can schedule cache re-prefill for just those sessions.

Routing goes through the cluster's ``PlacementEngine``: the segment table is
canonicalized (and, on accelerator backends, uploaded) once per membership
version, so the per-request hot path is pure placement -- no table prep.

``Router(algorithm=...)`` swaps the placement algorithm under the SAME
interface: ``"asura"`` (default), ``"ch"``, ``"wrh"`` or ``"rs"`` route
through the engine's baseline device backends (DESIGN.md section 9), so the
paper's head-to-head comparison runs on the serving path too -- including
R-way replica fan-out (the baselines use the salted rejection re-probe,
DESIGN.md section 12).  Live scale migrations remain ASURA-only (they ride
on its dual-version table artifacts) and raise a clear error otherwise.

The replica hot path is a CACHED fused probe: ``route_replicas_device``
compiles once per ``(algorithm statics, n_replicas, table shapes)`` and
every later batch is a single dispatch (``probe_traces`` is the tests'
retrace tripwire).  ``stream_driver()`` hands the same engine to the
batched serving pipeline (``serve.stream``).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import Cluster, PlacementEngine
from repro.core.engine import DEFAULT_VIRTUAL_NODES


@dataclasses.dataclass
class ScalePlan:
    moved_sessions: dict[int, tuple[int, int]]  # session -> (src, dst)

    @property
    def n_reprefills(self) -> int:
        return len(self.moved_sessions)


class ReplicaRouter:
    def __init__(
        self,
        replica_capacities: dict[int, float],
        *,
        algorithm: str = "asura",
        virtual_nodes: int = DEFAULT_VIRTUAL_NODES,
        ledger=None,
    ):
        from repro.obs import TraceLedger

        self.hierarchical = any(
            isinstance(v, dict) for v in replica_capacities.values()
        )
        if self.hierarchical:
            # {domain: {replica: capacity}} -> failure-domain-aware routing
            # (two-level ASURA; replica sets span R distinct domains).
            if algorithm != "asura":
                raise ValueError(
                    "hierarchical routing is ASURA-only (two-level segment "
                    f"tables); got algorithm={algorithm!r}"
                )
            from repro.core.hierarchy import HierarchicalCluster

            self.cluster = HierarchicalCluster()
            for did, members in replica_capacities.items():
                for rid, cap in members.items():
                    self.cluster.add_node(did, rid, cap)
        else:
            self.cluster = Cluster()
            for rid, cap in replica_capacities.items():
                self.cluster.add_node(rid, cap)
        self.algorithm = algorithm
        if algorithm == "asura":
            self.engine = self.cluster.engine
        else:
            # dedicated engine whose DEFAULT algorithm is the baseline, so
            # every route call dispatches to the baseline device backend.
            self.engine = PlacementEngine(
                self.cluster, algorithm=algorithm, virtual_nodes=virtual_nodes
            )
        self._scale_migration = None  # at most one live window at a time
        self._probe_cache: dict = {}  # (statics, R, table shapes) -> jitted probe
        # instance-scoped unless a shared ledger is injected -- the exact
        # probe-trace tripwire counts must never alias across routers
        self.ledger = ledger if ledger is not None else TraceLedger()

    @property
    def probe_traces(self) -> int:
        """Replica-probe jit traces (retrace tripwire) -- a ledger counter
        behind the PR-7 attribute name."""
        return self.ledger.counter("serve.probe_traces")

    def route(self, session_ids) -> np.ndarray:
        """session ids -> replica ids (vectorized, table-local)."""
        return self.engine.place_nodes(np.asarray(session_ids, dtype=np.uint32))

    def route_device(self, session_ids):
        """session ids -> replica ids as a DEVICE array, zero host syncs.

        The request hot path for device-chained frontends: pass
        device-resident session ids and the placement, tail resolution and
        replica-id gather all stay on device (the routing result feeds
        device-side batching/dispatch without a round trip)."""
        return self.engine.place_nodes_device(session_ids)

    def route_replicas(self, session_ids, n_replicas: int) -> np.ndarray:
        """(sessions, R) replica ids on distinct replicas, primary first --
        for read fan-out / warm-standby session caches (section 5.A; the
        baselines fan out via the salted rejection re-probe).  Hierarchical
        routers return the replica ids of pairwise-DISTINCT domains (use
        ``route_replica_pairs`` for the (domain, replica) view)."""
        out = self.engine.place_replica_nodes(
            np.asarray(session_ids, dtype=np.uint32), n_replicas
        )
        return out[:, :, 1] if self.hierarchical else out

    def route_replica_pairs(self, session_ids, n_replicas: int) -> np.ndarray:
        """(sessions, R, 2) ``(domain, replica)`` pairs, hierarchical
        routers only: every session's R cache holders live in R distinct
        failure domains, so a whole-domain outage re-prefills at most one
        warm copy per session."""
        if not self.hierarchical:
            raise ValueError(
                "route_replica_pairs needs a hierarchical router (pass "
                "{domain: {replica: capacity}} capacities)"
            )
        return self.engine.place_replica_pairs(
            np.asarray(session_ids, dtype=np.uint32), n_replicas
        )

    def _replica_probe(self, n_replicas: int):
        """The cached fused replica probe + its table operands.

        One jit per ``(algorithm statics, n_replicas, table shapes)``:
        membership changes (new table shapes) or a different R compile a
        new probe; steady-state serving always hits the cache.  The trace
        counter increments inside the traced body, so it ticks per TRACE,
        not per call -- the tripwire tests pin it across repeated batches.
        """
        from .stream import replica_owners_body, route_statics

        tables, statics = route_statics(self.engine, self.algorithm)
        key = (statics, n_replicas, tuple(t.shape for t in tables))
        fn = self._probe_cache.get(key)
        if fn is None:
            import jax

            owners_fn = replica_owners_body(statics, n_replicas)
            router = self

            @jax.jit
            def probe(ids, *tabs):
                router.ledger.incr("serve.probe_traces")  # per TRACE only
                return owners_fn(ids, *tabs)

            fn = self._probe_cache[key] = probe
        return fn, tables

    def route_replicas_device(self, session_ids, n_replicas: int):
        """Device-resident ``route_replicas`` (fused node gather; -1 marks
        the practically-impossible non-converged entries).  One cached-jit
        dispatch per call -- the serving hot path."""
        import jax.numpy as jnp

        fn, tables = self._replica_probe(n_replicas)
        return fn(jnp.asarray(session_ids), *tables)

    def stream_driver(self, **kwargs):
        """A batched ``RequestStreamDriver`` bound to this router's engine
        and algorithm (DESIGN.md section 12) -- the serving-at-scale entry
        point: device-resident traffic generation, fused route+select,
        on-device load counters."""
        from .stream import RequestStreamDriver

        return RequestStreamDriver(self.engine, algorithm=self.algorithm, **kwargs)

    @property
    def table_uploads(self) -> int:
        """Table materializations so far (1 per membership version used)."""
        return self.engine.uploads

    def my_sessions(self, replica_id: int, session_ids) -> np.ndarray:
        ids = np.asarray(session_ids, dtype=np.uint32)
        return ids[self.route(ids) == replica_id]

    def plan_scale_event(self, session_ids, *, add=None, remove=None) -> ScalePlan:
        """Apply a membership change; return the minimal session moves.

        Hierarchical routers take ``add=(domain, replica, capacity)`` /
        ``remove=(domain, replica)``; flat routers the 2-/1-tuple forms."""
        ids = np.asarray(session_ids, dtype=np.uint32)
        before = self.route(ids)
        if remove is not None:
            if self.hierarchical:
                self.cluster.remove_node(*remove)
            else:
                self.cluster.remove_node(remove)
        if add is not None:
            self.cluster.add_node(*add)
        after = self.route(ids)
        moved = np.nonzero(before != after)[0]
        return ScalePlan(
            {int(ids[i]): (int(before[i]), int(after[i])) for i in moved}
        )

    # -- migration-window serving (DESIGN.md section 8) ----------------------

    def begin_scale_migration(
        self,
        session_ids,
        *,
        add=None,
        remove=None,
        n_replicas: int = 1,
        egress=None,
        ingress=None,
        clock=None,
        round_seconds: float = 1.0,
    ):
        """Apply a membership change as a LIVE migration.

        Instead of an instantaneous table swap, the minimal session moves
        (session cache re-prefills) drain under per-replica ingress/egress
        budgets while ``route_migrating`` keeps every request on a replica
        whose cache is actually warm: the v owner until the session's
        re-prefill lands, the v+1 owner after.  The add-node case uses the
        planner's owner prefilter, so only sessions the new node takes
        pay the dual-version diff.  With ``n_replicas > 1`` the plan is the
        per-slot REPLICA plan (DESIGN.md section 10) -- warm-standby
        session caches (section 5.A fan-out) migrate replica by replica,
        and ``route_replicas_migrating`` serves the mixed-version sets.
        Returns a ``LiveMigration``.
        """
        from repro.migrate import LiveMigration, MigrationPlanner

        if self.algorithm != "asura":
            raise ValueError(
                "live scale migrations ride on ASURA's dual-version table "
                f"artifacts; this router routes via {self.algorithm!r} -- "
                "use plan_scale_event for the instantaneous-swap plan"
            )
        if self.hierarchical:
            raise NotImplementedError(
                "live scale-migration windows are flat-router only for "
                "now; hierarchical routers plan instantaneous swaps via "
                "plan_scale_event (the engine's diff_replica_domains_device "
                "gives the per-slot moves for external drivers)"
            )
        live = self._scale_migration
        if live is not None and not (live.done or live.aborted):
            # overlapping windows' read rules do not compose (section 8.3)
            raise RuntimeError(
                "a scale migration is already in flight; drain it first"
            )
        ids = np.asarray(session_ids, dtype=np.uint32)
        self.engine.artifact()  # pin the v table in the LRU before mutating
        v_from = self.cluster.version
        max_new_seg = None
        if remove is not None:
            self.cluster.remove_node(remove)
        if add is not None:
            rid, cap = add
            new_segs = self.cluster.add_node(rid, cap)
            if remove is None:
                max_new_seg = max(new_segs)
        planner = MigrationPlanner(self.engine)
        if n_replicas > 1:
            plan = planner.plan_replicas(
                ids,
                v_from,
                self.cluster.version,
                n_replicas,
                max_new_seg=max_new_seg,
            )
        else:
            plan = planner.plan(
                ids, v_from, self.cluster.version, max_new_seg=max_new_seg
            )
        self._scale_migration = LiveMigration.from_plan(
            self.engine,
            plan,
            egress=egress,
            ingress=ingress,
            clock=clock,
            round_seconds=round_seconds,
        )
        return self._scale_migration

    def route_migrating(self, session_ids, migration) -> np.ndarray:
        """Migration-window routing: each session goes to the replica that
        holds its warm cache right now (v owner while its re-prefill is
        pending, v+1 owner once landed)."""
        return migration.route(np.asarray(session_ids, dtype=np.uint32))

    def route_migrating_device(self, session_ids, migration):
        """Device-resident migration-window routing (zero host syncs after
        the per-round pending-set refresh)."""
        return migration.route_device(session_ids)

    def route_replicas_migrating(self, session_ids, migration) -> np.ndarray:
        """Migration-window REPLICA routing: (sessions, R) replica sets,
        each slot independently on whichever side of the version window
        holds its warm cache (pending -> v-side source, landed -> v+1
        owner).  Sets stay pairwise-distinct every round."""
        return migration.route_replicas(np.asarray(session_ids, dtype=np.uint32))

    def route_replicas_migrating_device(self, session_ids, migration):
        """Device-resident ``route_replicas_migrating`` (zero host syncs
        after the per-round per-slot pending refresh)."""
        return migration.route_replicas_device(session_ids)

    def table_blob(self) -> str:
        """The only state frontends need to share (kilobytes).

        Valid for "asura" (the blob IS the placement state), "ch" and
        "wrh" (their tables derive deterministically from the blob's
        membership).  Random slicing is HISTORY-dependent -- its interval
        table lives in the engine shadow, not the cluster blob -- so a
        frontend rebuilt from the blob would route differently; sharing it
        would silently split ownership, so this raises instead.
        """
        if self.algorithm == "rs":
            raise ValueError(
                "random slicing's interval table is history-dependent and "
                "not captured by the cluster blob; rs frontends must share "
                "the router (or replay the same membership sequence), not "
                "table_blob()"
            )
        return self.cluster.to_json()


# the name the quickstart / head-to-head docs use
Router = ReplicaRouter
