"""Batched device-resident serving pipeline (DESIGN.md section 12).

The paper's headline claims (sub-microsecond calc time, <1% load
variability) are about SERVING a placement function under real traffic.
``RequestStreamDriver`` is the batched, stateful driver that replaces
per-call routing on the serving hot path.  It is built from three parts:

  * ONE counter-coupled tail (``_tail``): a replica-selection policy
    picks one of the R holders per request -- ``pow2`` is
    power-of-two-choices against the on-device per-node load counters
    (arXiv 2312.10360: redundancy level + selection policy jointly set the
    achievable balance) -- then the served histogram, the counters, a
    queue-depth recurrence ``q' = max(q + arrivals - service, 0)``, a
    queue-history ring for p99, the metrics slab delta and, on a mesh, the
    batch's one exact integer psum,
  * three bodies that trace it: ``step`` (one generated batch),
    ``superstep`` (K generated batches drawn and routed jointly, the tail
    scanned) and ``route_batch`` (one external, pow2-bucketed id batch);
    ``serve_migrating`` dispatches the tail alone after its probe,
  * three read rules that produce the holders: the flat replica placement
    (``replica_owners_body``: ASURA's section-5.A ladder, the baselines'
    salted fan-out, or the two-level node plane), a live migration
    window's per-slot rule traced in the body (``migrate.live
    .migrating_owners``: ``route_batch(ids, migration)``,
    ``superstep_migrating``), and the window's cached fused probe
    (``LiveMigration.route_replicas_device``: ``serve_migrating``).

Generation is a device-resident request generator (``serve.traffic``):
threefry fold-in streams per GLOBAL lane, exact-u32 CDF sampling -- no host
RNG anywhere in the loop, and zero host syncs per batch (transfer-guard
tested).  Every selection in a batch reads the START-of-batch counters and
the batch histogram merges once -- the standard batched approximation of
least-loaded-of-two, and the property that makes the mesh path exact:
``mesh=`` shards the generated stream over ``launch/placement_mesh``'s 1-D
``data`` mesh, each shard generating and routing ITS slice of the global
lane range against replicated tables and start-of-batch counters, so the
sharded stream is bit-identical to the single-device stream
(selftest-enforced at 8 forced host devices).
"""

from __future__ import annotations

import math

import numpy as np

from repro.migrate.planner import pad_pow2
from repro.obs.trace import span

from .traffic import TrafficModel

POLICIES = ("primary", "random", "pow2")

DEFAULT_BATCH = 1 << 16
DEFAULT_KEYS = 1 << 20
DEFAULT_HIST = 256  # queue-history ring rows (p99 window)


def route_statics(engine, algorithm: str | None = None):
    """(tables, statics) for a replica-routing body under ``algorithm``.

    ``tables`` are the replicated device operands; ``statics`` is a
    hashable key that fully determines the body (the compile-cache key the
    driver, router probe and mesh serving path all share)."""
    alg = engine._resolve_algorithm(algorithm)
    if getattr(engine, "hierarchical", False):
        art = engine.hier_artifact()
        tables = art.tables_dev
        statics = (
            "hier", art.top_level, art.max_top, art.s_pad,
            engine.params.s_log2, engine.params.max_draws,
        )
    elif alg == "asura":
        art = engine._device_artifact("asura")
        tables = (art.len32_dev, art.node_of_dev)
        statics = ("asura", art.top_level, engine.params.s_log2, engine.params.max_draws)
    else:
        art = engine._device_artifact(alg)
        tables = (art.keys_dev, art.vals_dev)
        statics = (alg,)
    return tables, statics


def replica_owners_body(statics: tuple, n_replicas: int, emit_stats: bool = False):
    """Per-shard replica owners: (ids, *tables) -> (batch, R) int32 -- the
    same jnp kernel bodies the single-device engine paths run (the
    ``ShardedSweep._owners_body`` idiom, R-way).

    ``emit_stats=True`` returns ``(owners, stats)`` instead, where
    ``stats`` is the algorithm's uint32 device-plane vector (ASURA:
    ``[ladder_depth_hist..., nonconverged, ladder_draws_hist...]`` of
    length ``DEPTH_BINS + 1 + DRAW_BINS``;
    baselines: ``[reprobes]``) -- owners are bit-identical either way.

    ``hier`` statics route the fused two-level kernel and emit the NODE
    plane (the request stream balances over node holders; the domains are
    a placement property, not a routing one).  Stats plumbing is flat-path
    only for now."""
    alg = statics[0]
    if alg == "hier":
        if emit_stats:
            raise NotImplementedError(
                "hierarchical serving has no stats plane yet; route with "
                "emit_stats=False"
            )
        from repro.kernels.hierarchy import hier_place_replicas_ref

        _, top_level, max_top, s_pad, s_log2, max_draws = statics

        def owners(ids, *tables):
            out = hier_place_replicas_ref(
                ids, *tables,
                top_level=top_level, max_top=max_top, s_log2=s_log2,
                max_draws=max_draws, s_pad=s_pad, n_replicas=n_replicas,
            )
            return out[1].T  # (batch, R) node plane

        return owners
    if alg == "asura":
        from repro.kernels.ops import _place_replicas_fused_ref

        _, top_level, s_log2, max_draws = statics

        def owners(ids, len32, node_of):
            return _place_replicas_fused_ref(
                ids, len32, node_of,
                top_level=top_level, s_log2=s_log2, max_draws=max_draws,
                n_replicas=n_replicas, emit_nodes=True, emit_stats=emit_stats,
            )

        return owners
    from repro.kernels.baselines import _LOOKUP, baseline_replicas_lookup

    lookup = _LOOKUP[alg]

    def owners(ids, keys, vals):
        return baseline_replicas_lookup(
            lookup, ids, keys, vals, n_replicas=n_replicas,
            emit_stats=emit_stats,
        )

    return owners


def select_replica(owners, sel, counts, *, policy: str, n_replicas: int):
    """Pick one holder per request -> (batch,) int32 chosen nodes.

    ``owners`` is (batch, R) with -1 marking non-converged slots (masked:
    an invalid candidate always loses, and a fully-invalid row falls back
    to a clamped primary).  ``pow2`` draws two DISTINCT slots from the
    selection word and takes the one with the smaller start-of-batch
    counter (strict <, first-slot tie-break); ``random`` takes one slot
    uniformly; ``primary`` (or R == 1) always slot 0.
    """
    import jax.numpy as jnp

    prim = jnp.maximum(owners[:, 0], 0)
    if policy == "primary" or n_replicas == 1:
        return prim
    R = n_replicas
    if policy == "random":
        slot = (sel % jnp.uint32(R)).astype(jnp.int32)
        chosen = jnp.take_along_axis(owners, slot[:, None], axis=1)[:, 0]
        return jnp.where(chosen >= 0, chosen, prim)
    if policy != "pow2":
        raise ValueError(f"policy must be one of {POLICIES}, got {policy!r}")
    i = (sel % jnp.uint32(R)).astype(jnp.int32)
    off = ((sel >> jnp.uint32(16)) % jnp.uint32(R - 1)).astype(jnp.int32)
    j = (i + 1 + off) % R
    a = jnp.take_along_axis(owners, i[:, None], axis=1)[:, 0]
    b = jnp.take_along_axis(owners, j[:, None], axis=1)[:, 0]
    big = jnp.iinfo(jnp.int32).max
    la = jnp.where(a >= 0, jnp.take(counts, jnp.maximum(a, 0)), big)
    lb = jnp.where(b >= 0, jnp.take(counts, jnp.maximum(b, 0)), big)
    chosen = jnp.where(lb < la, b, a)
    return jnp.where(chosen >= 0, chosen, prim)


class RequestStreamDriver:
    """Stateful batched serving simulator bound to one ``PlacementEngine``.

    Device state (all jax arrays; the host only ever reads them through
    the explicit metric accessors):

      * ``counts`` -- (n_bins,) int32 cumulative served requests per node,
      * ``queue``  -- (n_bins,) int32 current queue depth per node
        (``service_rate`` requests drain per node per step),
      * ``qhist``  -- (max_hist, n_bins) int32 queue-depth ring (p99),
      * ``_step``  -- int32 device scalar (the fold-in stream position).

    Every serving call runs ONE jitted program per host dispatch and
    returns the chosen nodes (device arrays; shard-partitioned on a mesh).
    ``step_traces`` counts jit traces of the one-batch programs (``step``,
    ``route_batch``) and ``superstep_traces`` those of the scan-fused ones
    -- the tripwires that repeated calls stop retracing.
    """

    def __init__(
        self,
        engine,
        *,
        batch: int = DEFAULT_BATCH,
        n_keys: int = DEFAULT_KEYS,
        law: str = "zipf",
        alpha: float = 1.1,
        hot_fraction: float = 0.9,
        hot_keys: int = 64,
        n_replicas: int = 3,
        policy: str = "pow2",
        seed: int = 0,
        service_rate: int | None = None,
        max_hist: int = DEFAULT_HIST,
        n_bins: int | None = None,
        mesh=None,
        algorithm: str | None = None,
        metrics=None,
        ledger=None,
    ):
        import jax
        import jax.numpy as jnp

        if policy not in POLICIES:
            raise ValueError(f"policy must be one of {POLICIES}, got {policy!r}")
        self.engine = engine
        self.algorithm = engine._resolve_algorithm(algorithm)
        self.batch = int(batch)
        self.n_replicas = int(n_replicas)
        self.policy = policy
        self.max_hist = int(max_hist)
        self.traffic = TrafficModel(
            n_keys, law=law, alpha=alpha,
            hot_fraction=hot_fraction, hot_keys=hot_keys, seed=seed,
        )
        self._sweep = None
        if mesh is not None:
            from repro.launch.placement_mesh import ShardedSweep

            self._sweep = (
                mesh if isinstance(mesh, ShardedSweep) else ShardedSweep(engine, mesh)
            )
            if self.batch % self._sweep.n_devices:
                raise ValueError(
                    f"batch ({self.batch}) must divide the mesh "
                    f"({self._sweep.n_devices} devices)"
                )
        nodes = getattr(engine.cluster, "nodes", None)
        if nodes is None and getattr(engine, "hierarchical", False):
            # two-level cluster: the artifact's node -> domain map is the
            # flat node-id space the load/queue planes index
            nodes = engine.hier_artifact().node_domain
        if n_bins is not None:
            self.n_bins = int(n_bins)
        elif nodes:
            self.n_bins = int(max(nodes)) + 1
        else:  # table-only cluster: size off the seg->node map
            self.n_bins = int(np.max(engine.artifact().node_of)) + 1
        n_active = len(nodes) if nodes else self.n_bins
        if service_rate is None:
            # 25% capacity headroom over the mean arrival rate: uniform
            # traffic keeps queues near zero, skew shows up as real depth.
            service_rate = max(1, math.ceil(1.25 * self.batch / max(1, n_active)))
        self.service_rate = int(service_rate)
        self._service = jnp.full((self.n_bins,), self.service_rate, jnp.int32)
        self._key = jax.random.PRNGKey(seed)
        from repro.obs import TraceLedger

        # Instance-scoped by default so the exact trace-count tripwires
        # never alias across drivers; pass a shared ledger to unify.
        self.ledger = ledger if ledger is not None else TraceLedger()
        self.metrics = metrics
        self._instrumented = metrics is not None and metrics.enabled
        if self._instrumented:
            self._register_metrics()
        self._fns: dict = {}
        self.reset()

    def _register_metrics(self) -> None:
        """Claim this driver's slab windows (append-only; idempotent)."""
        from repro.kernels.ref import DEPTH_BINS, DRAW_BINS

        reg = self.metrics
        self._routed_name = reg.counter(
            f"serve.routed.{self.algorithm}.{self.policy}"
        )
        reg.histogram("serve.served", self.n_bins)
        if self.algorithm == "asura":
            reg.histogram("asura.ladder_depth", DEPTH_BINS)
            reg.histogram("asura.ladder_draws", DRAW_BINS)
            reg.counter("asura.nonconverged")
        else:
            reg.counter("baseline.reprobes")

    @property
    def step_traces(self) -> int:
        """Fused-step jit traces (the retrace tripwire) -- a ledger
        counter behind the PR-7 attribute name."""
        return self.ledger.counter("serve.step_traces")

    @property
    def superstep_traces(self) -> int:
        """Scan-fused superstep jit traces (the superstep retrace
        tripwire; one trace per distinct (statics, k))."""
        return self.ledger.counter("serve.superstep_traces")

    def _accumulate(self, delta, hist, stats):
        """Fold one batch's device-plane contributions into a slab delta
        (build-time no-op chain when uninstrumented -- never traced)."""
        from repro.kernels.ref import DEPTH_BINS

        reg = self.metrics
        delta = reg.add_hist(delta, "serve.served", hist)
        if stats is not None:
            if self.algorithm == "asura":
                delta = reg.add_hist(delta, "asura.ladder_depth", stats[:DEPTH_BINS])
                delta = reg.add(delta, "asura.nonconverged", stats[DEPTH_BINS])
                delta = reg.add_hist(
                    delta, "asura.ladder_draws", stats[DEPTH_BINS + 1 :]
                )
            else:
                delta = reg.add(delta, "baseline.reprobes", stats[0])
        return delta

    # -- state ----------------------------------------------------------------

    def reset(self) -> None:
        """Zero the load/queue state and rewind the request stream."""
        import jax.numpy as jnp

        self.counts = jnp.zeros((self.n_bins,), jnp.int32)
        self.queue = jnp.zeros((self.n_bins,), jnp.int32)
        self.qhist = jnp.zeros((self.max_hist, self.n_bins), jnp.int32)
        self._step = jnp.zeros((), jnp.int32)
        self.steps_done = 0

    def _cached(self, key: tuple, build):
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = build()
        return fn

    def _fixed_operands(self):
        return (self._service, self.traffic.thresholds_dev)

    # -- the counter-coupled tail (traced once per batch by every body) -------

    def _split(self, rest):
        """A body's trailing arguments -> ``([slab], operands)``."""
        return (rest[:1], rest[1:]) if self._instrumented else ((), rest)

    def _tail(self, owners, sel, carry, service, *, n_routed, valid=None, stats=None):
        """select -> served histogram -> slab delta -> [mesh psum] ->
        counters, queue recurrence, queue-history ring.

        ``carry`` is ``(counts, queue, qhist, [slab,] step_idx)``; returns
        ``(carry', chosen)`` with the stream position advanced.  ``valid``
        masks pad lanes out of every count; ``n_routed`` and the routing
        kernel's ``stats`` feed the slab (instrumented only).  Under a mesh
        the histogram and the slab delta merge with ONE exact integer
        psum, which keeps the sharded stream bit-identical to one device.
        """
        import jax
        import jax.numpy as jnp

        n_bins = self.n_bins
        counts, queue, qhist, *slab, step_idx = carry
        chosen = select_replica(
            owners, sel, counts, policy=self.policy, n_replicas=self.n_replicas
        )
        hist = jnp.zeros((n_bins,), jnp.int32).at[chosen].add(
            1 if valid is None else valid.astype(jnp.int32)
        )
        if slab:
            delta = self.metrics.add(jnp.zeros_like(slab[0]), self._routed_name, n_routed)
            delta = self._accumulate(delta, hist, stats)
        if self._sweep is not None:
            from repro.launch.placement_mesh import DATA_AXIS

            if slab:
                merged = jax.lax.psum(
                    jnp.concatenate([hist, delta.astype(jnp.int32)]), DATA_AXIS
                )
                hist, delta = merged[:n_bins], merged[n_bins:].astype(jnp.uint32)
            else:
                hist = jax.lax.psum(hist, DATA_AXIS)
        counts = counts + hist
        queue = jnp.maximum(queue + hist - service, 0)
        qhist = jax.lax.dynamic_update_slice(
            qhist, queue[None], (step_idx % self.max_hist, jnp.int32(0))
        )
        if slab:
            slab = [slab[0] + delta]
        return (counts, queue, qhist, *slab, step_idx + 1), chosen

    def _dispatch(self, fn, lead, operands, n_steps: int = 1) -> list:
        """Call a serving jit ``fn(*lead, counts, queue, qhist, [slab,]
        *operands) -> (counts, queue, qhist, [slab,] step_idx, *outs)``,
        keep the new state and return ``outs``."""
        slab = [self.metrics.slab()] if self._instrumented else []
        out = fn(*lead, self.counts, self.queue, self.qhist, *slab, *operands)
        self.counts, self.queue, self.qhist = out[:3]
        if slab:
            self.metrics.set_slab(out[3])
        self._step, *outs = out[3 + len(slab) :]
        self.steps_done += n_steps
        return outs

    # -- the generated stream: one batch, or K scan-fused ---------------------

    def _lanes(self):
        """This shard's GLOBAL lane indices (traced; all of them off a mesh)."""
        import jax
        import jax.numpy as jnp

        if self._sweep is None:
            return jnp.arange(self.batch, dtype=jnp.uint32)
        from repro.launch.placement_mesh import DATA_AXIS

        local = self.batch // self._sweep.n_devices
        first = jax.lax.axis_index(DATA_AXIS).astype(jnp.uint32) * local
        return first + jnp.arange(local, dtype=jnp.uint32)

    def _jit(self, body, lead_axes: int = 0):
        """jit a generated-stream body; on a mesh, shard_map it first.
        Every operand is replicated (lanes derive from ``axis_index``, so
        there is no partitioned INPUT at all); only ``chosen`` comes back
        lane-partitioned, behind ``lead_axes`` replicated axes."""
        import jax

        if self._sweep is None:
            return jax.jit(body)
        from jax.sharding import PartitionSpec as P

        from repro.launch.placement_mesh import DATA_AXIS

        n_carry = 5 if self._instrumented else 4
        return jax.jit(
            jax.shard_map(
                body,
                mesh=self._sweep.mesh,
                in_specs=P(),
                out_specs=(P(),) * n_carry + (P(*(None,) * lead_axes, DATA_AXIS),),
                check_vma=False,  # while_loop ladders have no replication rule
            )
        )

    def _step_fn(self, statics: tuple):
        """ONE batch in one jit: generate -> route -> the tail, signature

            stepped(key, step_idx, counts, queue, qhist, [slab,] service,
                    thresholds, *tables)
              -> (counts, queue, qhist, [slab,] step_idx + 1, chosen)

        With a live ``MetricsRegistry`` the body also threads the u32
        metrics slab (DESIGN.md section 13) -- still zero host syncs."""
        instrumented, id_salt = self._instrumented, self.traffic.id_salt
        owners_fn = replica_owners_body(statics, self.n_replicas, emit_stats=instrumented)
        driver = self

        def stepped(key, step_idx, counts, queue, qhist, *rest):
            driver.ledger.incr("serve.step_traces")  # fires per TRACE only
            slab, (service, thresholds, *tables) = driver._split(rest)
            lanes = driver._lanes()
            ids, sel = TrafficModel.draw(key, step_idx, lanes, thresholds, id_salt)
            routed = owners_fn(ids, *tables)
            owners, stats = routed if instrumented else (routed, None)
            carry, chosen = driver._tail(
                owners, sel, (counts, queue, qhist, *slab, step_idx), service,
                n_routed=lanes.shape[0], stats=stats,
            )
            return (*carry, chosen)

        return self._jit(stepped)

    def _superstep_fn(self, statics: tuple, k: int, migrating: bool = False):
        """K fused batches in ONE jit, restructured around what actually
        needs to be sequential:

          1. generate ALL K sub-batches in one vectorized draw (every
             threefry word is a pure function of (key, step, lane)),
          2. route the joint (k*batch,) id block through ONE owners call
             -- one ladder while_loop instead of K,
          3. ``lax.scan`` only the counter-coupled tail with
             (counts, queue, qhist, [slab,] step_idx) as the carry.

        Bit-identical to K sequential one-batch calls: generation is
        counter-based, the owners functions are per-lane pure (a lane's
        holders and emitted stats never depend on which other lanes share
        the batch -- the partition invariance the sharded stream's psum
        merge relies on), and the scan reads counters fresh as of the
        previous sub-batch.  The joint route's kernel stats fold into the
        first sub-batch's slab delta (u32 sums: the same total).  On a mesh
        each sub-batch's exact psum stays INSIDE the scan.  ``chosen``
        comes back stacked (k, batch).

        ``migrating`` swaps the flat placement for the live window's read
        rule (``migrate.live.migrating_owners(statics)``, tables = the
        window's operands): it emits no stats, and the body also returns
        the drawn ids -- ``superstep_migrating``."""
        import jax
        import jax.numpy as jnp

        R, id_salt = self.n_replicas, self.traffic.id_salt
        emit_stats = self._instrumented and not migrating
        if migrating:
            from repro.migrate.live import migrating_owners

            owners_fn = migrating_owners(statics)
        else:
            owners_fn = replica_owners_body(statics, R, emit_stats=emit_stats)
        driver = self

        def super_body(key, step_idx, counts, queue, qhist, *rest):
            driver.ledger.incr("serve.superstep_traces")  # per TRACE only
            slab, (service, thresholds, *tables) = driver._split(rest)
            lanes = driver._lanes()
            local = lanes.shape[0]
            steps = step_idx + jnp.arange(k, dtype=step_idx.dtype)
            ids, sel = jax.vmap(
                lambda s: TrafficModel.draw(key, s, lanes, thresholds, id_salt)
            )(steps)  # (k, local) each
            routed = owners_fn(ids.reshape(k * local), *tables)
            owners, stats = routed if emit_stats else (routed, None)
            if stats is not None:
                stats = jnp.zeros((k, *stats.shape), stats.dtype).at[0].set(stats)

            def sub(carry, xs):
                owners_i, sel_i, stats_i = xs
                return driver._tail(
                    owners_i, sel_i, carry, service, n_routed=local, stats=stats_i
                )

            carry, chosen = jax.lax.scan(
                sub, (counts, queue, qhist, *slab, step_idx),
                (owners.reshape(k, local, R), sel, stats), length=k,
            )
            return (*carry, *([ids] if migrating else []), chosen)

        return self._jit(super_body, lead_axes=1)

    def step(self):
        """Serve one generated batch -> (batch,) int32 chosen nodes (device
        array; shard-partitioned over the mesh when sharded).  Zero host
        syncs: state stays on device, the stream position is a device
        scalar."""
        tables, statics = route_statics(self.engine, self.algorithm)
        fn = self._cached(("step", statics), lambda: self._step_fn(statics))
        (chosen,) = self._dispatch(
            fn, (self._key, self._step), (*self._fixed_operands(), *tables)
        )
        return chosen

    def superstep(self, k: int):
        """Serve K generated batches in ONE host dispatch -> (k, batch)
        int32 chosen nodes (device array; lane-partitioned over the mesh
        when sharded).

        Bit-identical to K sequential ``step()`` calls -- same counters,
        queue ring, metrics slab and chosen nodes: the superstep draws and
        routes all K sub-batches JOINTLY (one ladder while_loop instead of
        K) and scans only the counter-coupled tail (``_superstep_fn``).
        Amortizes both the host dispatch and the routing loop's
        per-iteration overhead ~k-fold; at most one slab transfer per
        superstep when instrumented.  Pick k so ``k * batch`` trails the
        metric-read cadence (README "Throughput tuning")."""
        (chosen,) = self._run_superstep(k)
        return chosen

    def _run_superstep(self, k, migration=None) -> list:
        k = int(k)
        if k < 1:
            raise ValueError(f"superstep needs k >= 1, got {k}")
        if migration is None:
            tables, statics = route_statics(self.engine, self.algorithm)
        else:
            statics, tables = migration.route_operands()
        migrating = migration is not None
        fn = self._cached(
            ("superstep", migrating, statics, k),
            lambda: self._superstep_fn(statics, k, migrating),
        )
        return self._dispatch(
            fn, (self._key, self._step), (*self._fixed_operands(), *tables), n_steps=k
        )

    # -- external batches (pow2 bucketing -- ragged tails share compiles) -----

    def _route_batch_fn(self, owners_fn, name: str = "body"):
        """The jitted external-batch body around ``owners_fn(ids, *tables)
        -> (batch, R)`` holders: the flat replica placement
        (``replica_owners_body``) or the live-migration read rule
        (``migrate.live.migrating_owners``).  ``name`` names the jit, and so
        the program in a device trace (``jit_body``,
        ``jit_route_migrating``)."""
        import jax
        import jax.numpy as jnp

        driver = self

        def body(ids, n_valid, key, step_idx, counts, queue, qhist, *rest):
            driver.ledger.incr("serve.step_traces")
            slab, (service, *tables) = driver._split(rest)
            lanes = jnp.arange(ids.shape[0], dtype=jnp.uint32)
            valid = lanes < n_valid.astype(jnp.uint32)
            sel = TrafficModel.lane_words(key, step_idx, lanes, 1)[:, 0]
            owners = owners_fn(ids.astype(jnp.uint32), *tables)
            carry, chosen = driver._tail(
                owners, sel, (counts, queue, qhist, *slab, step_idx), service,
                n_routed=n_valid, valid=valid,  # pad lanes never count
            )
            return (*carry, chosen)

        body.__name__ = body.__qualname__ = name
        return jax.jit(body)

    def route_batch(self, datum_ids, migration=None):
        """Serve one EXTERNAL id batch through the fused select+count pass
        -> (len(ids),) int32 chosen nodes (device array).

        Ids are pow2-bucketed (``migrate.planner.pad_pow2``) with the valid
        count traced, so ragged tails share one compile per bucket and pad
        lanes never touch a counter.  Single-device (the generated stream
        is the mesh path).

        With a live ``migration`` (a ``LiveMigration`` of the driver's R)
        the batch routes through the window's per-slot read rule (DESIGN.md
        section 10.2): slot r goes to its v-side source while its row is
        pending and to the v+1 set's slot r otherwise, then the same select
        and count -- one jit, ``route_migrating``, whose shape stays fixed
        through the whole drain.  ``None`` or a finished migration routes
        at the cluster's current version, as without one."""
        import jax.numpy as jnp

        from repro.kernels.ops import _head

        if self._sweep is not None:
            raise ValueError(
                "route_batch serves host-fed batches single-device; "
                "mesh-sharded serving goes through step()"
            )
        live = migration is not None and not migration.done
        if live:
            self._check_window(migration)
        with span("serve.route_batch"):
            ids = jnp.asarray(datum_ids)
            n = int(ids.shape[0])
            padded, n_valid = pad_pow2(ids)
            if live:
                from repro.migrate.live import migrating_owners

                statics, tables = migration.route_operands()
                fn = self._cached(
                    ("route_migrating", statics),
                    lambda: self._route_batch_fn(migrating_owners(statics), "route_migrating"),
                )
            else:
                # External batches carry pad lanes, whose kernel stats would
                # be phantom work -- only the valid-masked routed/served
                # metrics accumulate, so the body routes without emit_stats.
                tables, statics = route_statics(self.engine, self.algorithm)
                fn = self._cached(
                    ("route_batch", statics),
                    lambda: self._route_batch_fn(replica_owners_body(statics, self.n_replicas)),
                )
            (chosen,) = self._dispatch(
                fn, (padded, jnp.uint32(n_valid), self._key, self._step),
                (self._service, *tables),
            )
            return _head(chosen, n)

    def _check_window(self, migration) -> None:
        """A migration window serves single-device, at the driver's R."""
        if self._sweep is not None:
            raise ValueError(
                "migration windows are single-device (the pending views "
                "refresh per round); build the driver without mesh="
            )
        if migration.n_replicas != self.n_replicas:
            raise ValueError(
                f"driver serves R={self.n_replicas} but the migration plan "
                f"is R={migration.n_replicas}"
            )

    # -- serving through a live migration window ------------------------------

    def _gen_fn(self):
        import jax
        import jax.numpy as jnp

        batch, id_salt = self.batch, self.traffic.id_salt

        @jax.jit
        def gen(key, step_idx, thresholds):
            lanes = jnp.arange(batch, dtype=jnp.uint32)
            return TrafficModel.draw(key, step_idx, lanes, thresholds, id_salt)

        return gen

    def _tail_fn(self):
        """The tail alone, jitted: what ``serve_migrating`` dispatches
        after the window's probe has routed the batch."""
        import jax

        driver = self

        @jax.jit
        def select(owners, sel, step_idx, counts, queue, qhist, *rest):
            slab, (service,) = driver._split(rest)
            carry, chosen = driver._tail(
                owners, sel, (counts, queue, qhist, *slab, step_idx), service,
                n_routed=owners.shape[0],
            )
            return (*carry, chosen)

        return select

    def serve_migrating(self, migration):
        """Serve one generated batch THROUGH a live migration window ->
        (datum_ids, chosen) device arrays.

        Routing goes through the window's dual-version replica read rule
        (``LiveMigration.route_replicas_device`` -- the cached fused
        probe), so every request lands on a node that physically holds its
        datum mid-drain.  Three jitted dispatches (generate, route, the
        tail), zero host syncs after the per-round pending-view refresh.
        Single-device, like the window itself."""
        self._check_window(migration)
        gen = self._cached(("gen",), self._gen_fn)
        ids, sel = gen(self._key, self._step, self.traffic.thresholds_dev)
        owners = migration.route_replicas_device(ids)
        tail = self._cached(("tail",), self._tail_fn)
        (chosen,) = self._dispatch(tail, (owners, sel, self._step), (self._service,))
        return ids, chosen

    def superstep_migrating(self, migration, k: int):
        """Serve K generated batches THROUGH a live migration window in
        ONE host dispatch -> (datum_ids, chosen), each (k, batch).

        The superstep body of ``superstep`` with the window's read rule as
        its owners function: bit-identical to K sequential
        ``serve_migrating`` calls against the same pending view -- the
        pending snapshot is the one at call time (refresh per round, as
        with ``serve_migrating``).  Single-device, like the window."""
        self._check_window(migration)
        ids, chosen = self._run_superstep(k, migration)
        return ids, chosen

    # -- host-facing metrics (each accessor is ONE deliberate sync) -----------

    def _active_bins(self) -> np.ndarray:
        nodes = getattr(self.engine.cluster, "nodes", None)
        if nodes:
            return np.asarray(sorted(int(n) for n in nodes), dtype=np.int64)
        return np.arange(self.n_bins, dtype=np.int64)

    def load_counts(self) -> np.ndarray:
        return np.asarray(self.counts)

    def load_skew(self) -> float:
        """max/mean served load over the live nodes (1.0 = perfectly
        even; the paper's uniformity story, measured under traffic)."""
        c = self.load_counts()[self._active_bins()].astype(np.float64)
        mean = c.mean()
        return float(c.max() / mean) if mean > 0 else 0.0

    def queue_p99(self) -> float:
        """p99 queue depth over (recorded step, live node) samples."""
        rows = min(self.steps_done, self.max_hist)
        if rows == 0:
            return 0.0
        q = np.asarray(self.qhist)[:rows][:, self._active_bins()]
        return float(np.percentile(q, 99))

    def snapshot(self) -> dict:
        snap = {
            "counts": self.load_counts(),
            "queue": np.asarray(self.queue),
            "steps": self.steps_done,
            "skew": self.load_skew(),
            "q_p99": self.queue_p99(),
        }
        self.ledger.event(
            "serve.snapshot", self.algorithm,
            steps=self.steps_done, skew=snap["skew"], q_p99=snap["q_p99"],
        )
        return snap
