"""Batched device-resident serving pipeline (DESIGN.md section 12).

The paper's headline claims (sub-microsecond calc time, <1% load
variability) are about SERVING a placement function under real traffic.
``RequestStreamDriver`` is the batched, stateful driver that replaces
per-call routing on the serving hot path:

  * a device-resident request generator (``serve.traffic``): threefry
    fold-in streams per GLOBAL lane, exact-u32 CDF sampling -- no host RNG
    anywhere in the loop,
  * a fused route+select pass: the batch routes through the replica
    placement (ASURA's section-5.A kernel body, or the baselines' salted
    fan-out), then a replica-selection policy picks one of the R holders
    per request -- ``pow2`` is power-of-two-choices against the on-device
    per-node load counters (arXiv 2312.10360: redundancy level + selection
    policy jointly set the achievable balance),
  * on-device load state: per-node served counters, a queue-depth
    recurrence ``q' = max(q + arrivals - service, 0)`` and a queue-history
    ring for p99 -- scatter-updated in the same jit,

all inside ONE jit per step with zero host syncs (transfer-guard tested).
Every selection in a batch reads the START-of-batch counters and the batch
histogram merges once -- the standard batched approximation of
least-loaded-of-two, and the property that makes the mesh path exact:

``mesh=`` shards the request stream over ``launch/placement_mesh``'s 1-D
``data`` mesh (the PR-6 follow-up): each shard generates ITS slice of the
global lane range (bit-identical words by the counter-based construction),
routes and selects against the replicated kilobyte tables and replicated
start-of-batch counters, and the per-node load histogram merges with ONE
exact integer psum per batch -- so the sharded stream is bit-identical to
the single-device stream (selftest-enforced at 8 forced host devices).

External id batches (``route_batch``) reuse the migration planner's pow2
bucketing so ragged tails share one compile per bucket.  Given a live
migration, ``route_batch`` routes host-fed batches through the window's
per-slot read rule in one jit (``route_migrating``), and
``serve_migrating`` drives the generated stream through it via the cached
fused ``route_replicas_device`` probe -- dual-version serving keeps
working under the batched driver.
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

    ``step()`` runs one fused generate+route+select+count batch and
    returns the chosen nodes (device array; shard-partitioned on a mesh).
    ``step_traces`` counts jit traces of the fused step -- the tripwire
    that repeated steps stop retracing.
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

    # -- the fused step -------------------------------------------------------

    def _batch_body(self, statics: tuple):
        """The traced ONE-BATCH body ``step()`` and ``superstep()`` share:
        generate -> route -> select -> count, signature

            body(key, step_idx, counts, queue, qhist, *rest)
              -> (counts, queue, qhist, [slab,] step_idx + 1, chosen)

        where ``rest = [slab,] service, thresholds, *tables``.  Both
        drivers trace EXACTLY this function (step jits it directly, the
        superstep scans it), which is what makes ``superstep(k)``
        bit-identical to K sequential ``step()`` calls by construction.

        With a live ``MetricsRegistry`` the body also threads the u32
        metrics slab: routed/served/kernel-stats accumulate into a zeros
        DELTA slab in-register, and under a mesh the delta rides the
        batch's single exact integer psum alongside the per-node histogram
        (DESIGN.md section 13) -- still zero host syncs per batch.
        """
        import jax
        import jax.numpy as jnp

        batch, R = self.batch, self.n_replicas
        policy, n_bins, max_hist = self.policy, self.n_bins, self.max_hist
        id_salt = self.traffic.id_salt
        instrumented = self._instrumented
        owners_fn = replica_owners_body(statics, R, emit_stats=instrumented)
        sweep = self._sweep
        driver = self

        def body(key, step_idx, counts, queue, qhist, *rest):
            if instrumented:
                slab, service, thresholds, *tables = rest
            else:
                service, thresholds, *tables = rest
            if sweep is None:
                lanes = jnp.arange(batch, dtype=jnp.uint32)
            else:
                from repro.launch.placement_mesh import DATA_AXIS

                local = batch // sweep.n_devices
                first = jax.lax.axis_index(DATA_AXIS).astype(jnp.uint32) * local
                lanes = first + jnp.arange(local, dtype=jnp.uint32)
            ids, sel = TrafficModel.draw(key, step_idx, lanes, thresholds, id_salt)
            if instrumented:
                owners, stats = owners_fn(ids, *tables)
            else:
                owners = owners_fn(ids, *tables)
            chosen = select_replica(
                owners, sel, counts, policy=policy, n_replicas=R
            )
            hist = jnp.zeros((n_bins,), jnp.int32).at[chosen].add(1)
            if instrumented:
                delta = jnp.zeros_like(slab)
                delta = driver.metrics.add(
                    delta, driver._routed_name, lanes.shape[0]
                )
                delta = driver._accumulate(delta, hist, stats)
            if sweep is not None:
                from repro.launch.placement_mesh import DATA_AXIS

                if instrumented:
                    # the slab delta rides the step's ONE exact psum
                    merged = jax.lax.psum(
                        jnp.concatenate([hist, delta.astype(jnp.int32)]),
                        DATA_AXIS,
                    )
                    hist = merged[:n_bins]
                    delta = merged[n_bins:].astype(jnp.uint32)
                else:
                    hist = jax.lax.psum(hist, DATA_AXIS)
            counts = counts + hist
            queue = jnp.maximum(queue + hist - service, 0)
            qhist = jax.lax.dynamic_update_slice(
                qhist, queue[None], (step_idx % max_hist, jnp.int32(0))
            )
            if instrumented:
                return counts, queue, qhist, slab + delta, step_idx + 1, chosen
            return counts, queue, qhist, step_idx + 1, chosen

        return body

    def _spec_counts(self, statics: tuple) -> tuple[int, int]:
        """(n_in, n_rep_out) for the mesh shard_map wrap of a batch body."""
        # flat routing carries 2 table operands; the two-level path carries
        # the 8-array stacked hierarchy artifact (kernels/hierarchy.py)
        n_tables = (8 if statics[0] == "hier" else 2) + len(self._fixed_operands())
        n_in = (6 if self._instrumented else 5) + n_tables
        n_rep_out = 4 if self._instrumented else 3
        return n_in, n_rep_out

    def _step_fn(self, statics: tuple):
        """One-jit batch step: the shared batch body, jitted (shard_mapped
        on a mesh), plus the per-TRACE retrace tripwire."""
        import jax

        body = self._batch_body(statics)
        sweep = self._sweep
        driver = self

        def stepped(*args):
            driver.ledger.incr("serve.step_traces")  # fires per TRACE only
            return body(*args)

        if sweep is None:
            return jax.jit(stepped)
        from jax.sharding import PartitionSpec as P

        from repro.launch.placement_mesh import DATA_AXIS

        n_in, n_rep_out = self._spec_counts(statics)
        return jax.jit(
            jax.shard_map(
                stepped,
                mesh=sweep.mesh,
                # everything replicated: lanes derive from axis_index, so
                # there is no partitioned INPUT at all -- only the chosen
                # lanes come back shard-partitioned.
                in_specs=(P(),) * n_in,
                out_specs=(P(),) * (n_rep_out + 1) + (P(DATA_AXIS),),
                check_vma=False,  # while_loop ladders have no replication rule
            )
        )

    def _superstep_fn(self, statics: tuple, k: int):
        """K fused batches in ONE jit, restructured around what actually
        needs to be sequential:

          1. generate ALL K sub-batches in one vectorized draw (every
             threefry word is a pure function of (key, step, lane)),
          2. route the joint (k*batch,) id block through ONE ladder
             while_loop -- amortizing the loop's per-iteration dispatch
             overhead k-fold instead of paying it per sub-batch,
          3. ``lax.scan`` only the counter-COUPLED tail (pow2 select,
             count, queue ring) with (counts, queue, qhist, [slab,]
             step_idx) as the carry.

        This is still bit-identical to K sequential ``step()`` calls:
        generation is counter-based (stateless), the routing loops are
        per-lane pure (a lane's result and its emitted stats never depend
        on which other lanes share the batch -- the same partition
        invariance the sharded stream's psum merge already relies on,
        selftest-enforced), and the selection scan reads counters fresh
        as of the previous sub-batch exactly as ``step()`` does.  The
        once-per-batch slab contributions (routed counter, kernel stats)
        fold in once per SUPERSTEP with the same u32 modular sum.  On a
        mesh the per-sub-batch exact psum stays INSIDE the scan (K+1
        psums fused into one dispatch), so sharded supersteps remain
        bit-identical to single-device.  ``chosen`` comes back stacked
        (k, batch).
        """
        import jax
        import jax.numpy as jnp

        batch, R = self.batch, self.n_replicas
        policy, n_bins, max_hist = self.policy, self.n_bins, self.max_hist
        id_salt = self.traffic.id_salt
        instrumented = self._instrumented
        owners_fn = replica_owners_body(statics, R, emit_stats=instrumented)
        sweep = self._sweep
        driver = self

        def super_body(key, step_idx, counts, queue, qhist, *rest):
            driver.ledger.incr("serve.superstep_traces")  # per TRACE only
            if instrumented:
                slab, service, thresholds, *tables = rest
            else:
                service, thresholds, *tables = rest
            if sweep is None:
                local = batch
                lanes = jnp.arange(batch, dtype=jnp.uint32)
            else:
                from repro.launch.placement_mesh import DATA_AXIS

                local = batch // sweep.n_devices
                first = jax.lax.axis_index(DATA_AXIS).astype(jnp.uint32) * local
                lanes = first + jnp.arange(local, dtype=jnp.uint32)

            # stage 1+2: all K sub-batches drawn and routed jointly
            steps = step_idx + jnp.arange(k, dtype=step_idx.dtype)
            ids, sel = jax.vmap(
                lambda s: TrafficModel.draw(key, s, lanes, thresholds, id_salt)
            )(steps)  # (k, local) each
            if instrumented:
                owners, stats = owners_fn(ids.reshape(k * local), *tables)
            else:
                owners = owners_fn(ids.reshape(k * local), *tables)
            owners = owners.reshape(k, local, R)

            # stage 3: the counter-coupled tail, scanned
            def sub(carry, xs):
                if instrumented:
                    counts, queue, qhist, slab, si = carry
                else:
                    counts, queue, qhist, si = carry
                owners_i, sel_i = xs
                chosen = select_replica(
                    owners_i, sel_i, counts, policy=policy, n_replicas=R
                )
                hist = jnp.zeros((n_bins,), jnp.int32).at[chosen].add(1)
                if instrumented:
                    delta = jnp.zeros_like(slab)
                    delta = driver._accumulate(delta, hist, None)
                if sweep is not None:
                    from repro.launch.placement_mesh import DATA_AXIS

                    if instrumented:
                        merged = jax.lax.psum(
                            jnp.concatenate([hist, delta.astype(jnp.int32)]),
                            DATA_AXIS,
                        )
                        hist = merged[:n_bins]
                        delta = merged[n_bins:].astype(jnp.uint32)
                    else:
                        hist = jax.lax.psum(hist, DATA_AXIS)
                counts = counts + hist
                queue = jnp.maximum(queue + hist - service, 0)
                qhist = jax.lax.dynamic_update_slice(
                    qhist, queue[None], (si % max_hist, jnp.int32(0))
                )
                if instrumented:
                    return (counts, queue, qhist, slab + delta, si + 1), chosen
                return (counts, queue, qhist, si + 1), chosen

            if instrumented:
                carry0 = (counts, queue, qhist, slab, step_idx)
            else:
                carry0 = (counts, queue, qhist, step_idx)
            carry, chosen = jax.lax.scan(sub, carry0, (owners, sel), length=k)
            if instrumented:
                # once-per-superstep slab contributions: the routed counter
                # and the joint route's kernel stats (their per-sub-batch
                # sums are the same u32 total -- partition invariance)
                counts, queue, qhist, slab, si = carry
                delta = jnp.zeros_like(slab)
                delta = driver.metrics.add(
                    delta, driver._routed_name, k * local
                )
                delta = driver._accumulate(delta, jnp.zeros((n_bins,), jnp.int32), stats)
                if sweep is not None:
                    from repro.launch.placement_mesh import DATA_AXIS

                    delta = jax.lax.psum(
                        delta.astype(jnp.int32), DATA_AXIS
                    ).astype(jnp.uint32)
                carry = (counts, queue, qhist, slab + delta, si)
            return (*carry, chosen)

        if sweep is None:
            return jax.jit(super_body)
        from jax.sharding import PartitionSpec as P

        from repro.launch.placement_mesh import DATA_AXIS

        n_in, n_rep_out = self._spec_counts(statics)
        return jax.jit(
            jax.shard_map(
                super_body,
                mesh=sweep.mesh,
                in_specs=(P(),) * n_in,
                # stacked chosen is (k, local): partitioned on the LANE
                # axis, replicated over the scan axis.
                out_specs=(P(),) * (n_rep_out + 1) + (P(None, DATA_AXIS),),
                check_vma=False,
            )
        )

    def _fixed_operands(self):
        return (self._service, self.traffic.thresholds_dev)

    def step(self):
        """Serve one generated batch -> (batch,) int32 chosen nodes (device
        array; shard-partitioned over the mesh when sharded).  Zero host
        syncs: state stays on device, the stream position is a device
        scalar."""
        tables, statics = route_statics(self.engine, self.algorithm)
        fn = self._cached(("step", statics), lambda: self._step_fn(statics))
        if self._instrumented:
            (self.counts, self.queue, self.qhist, slab, self._step,
             chosen) = fn(
                self._key, self._step, self.counts, self.queue, self.qhist,
                self.metrics.slab(), *self._fixed_operands(), *tables,
            )
            self.metrics.set_slab(slab)
        else:
            self.counts, self.queue, self.qhist, self._step, chosen = fn(
                self._key, self._step, self.counts, self.queue, self.qhist,
                *self._fixed_operands(), *tables,
            )
        self.steps_done += 1
        return chosen

    def superstep(self, k: int):
        """Serve K generated batches in ONE host dispatch -> (k, batch)
        int32 chosen nodes (device array; lane-partitioned over the mesh
        when sharded).

        Bit-identical to K sequential ``step()`` calls -- same counters,
        queue ring, metrics slab and chosen nodes: generation and routing
        are per-lane pure, so the superstep draws and routes all K
        sub-batches JOINTLY (one ladder while_loop instead of K) and scans
        only the counter-coupled select/count tail (``_superstep_fn``).
        Amortizes both the host dispatch and the routing loop's
        per-iteration overhead ~k-fold; at most one slab transfer per
        superstep when instrumented.  Pick k so ``k * batch`` trails the
        metric-read cadence (README "Throughput tuning")."""
        k = int(k)
        if k < 1:
            raise ValueError(f"superstep needs k >= 1, got {k}")
        tables, statics = route_statics(self.engine, self.algorithm)
        fn = self._cached(
            ("superstep", statics, k), lambda: self._superstep_fn(statics, k)
        )
        if self._instrumented:
            (self.counts, self.queue, self.qhist, slab, self._step,
             chosen) = fn(
                self._key, self._step, self.counts, self.queue, self.qhist,
                self.metrics.slab(), *self._fixed_operands(), *tables,
            )
            self.metrics.set_slab(slab)
        else:
            self.counts, self.queue, self.qhist, self._step, chosen = fn(
                self._key, self._step, self.counts, self.queue, self.qhist,
                *self._fixed_operands(), *tables,
            )
        self.steps_done += k
        return chosen

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

        R, policy = self.n_replicas, self.policy
        n_bins, max_hist = self.n_bins, self.max_hist
        instrumented = self._instrumented
        driver = self

        def body(ids, n_valid, key, step_idx, counts, queue, qhist, *rest):
            driver.ledger.incr("serve.step_traces")
            if instrumented:
                slab, service, *tables = rest
            else:
                service, *tables = rest
            lanes = jnp.arange(ids.shape[0], dtype=jnp.uint32)
            valid = lanes < n_valid.astype(jnp.uint32)
            sel = TrafficModel.lane_words(key, step_idx, lanes, 1)[:, 0]
            owners = owners_fn(ids.astype(jnp.uint32), *tables)
            chosen = select_replica(
                owners, sel, counts, policy=policy, n_replicas=R
            )
            hist = jnp.zeros((n_bins,), jnp.int32).at[chosen].add(
                valid.astype(jnp.int32)  # pad lanes never count
            )
            counts = counts + hist
            queue = jnp.maximum(queue + hist - service, 0)
            qhist = jax.lax.dynamic_update_slice(
                qhist, queue[None], (step_idx % max_hist, jnp.int32(0))
            )
            if instrumented:
                delta = jnp.zeros_like(slab)
                delta = driver.metrics.add(delta, driver._routed_name, n_valid)
                delta = driver._accumulate(delta, hist, None)
                return counts, queue, qhist, slab + delta, step_idx + 1, chosen
            return counts, queue, qhist, step_idx + 1, chosen

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
            if self._instrumented:
                (self.counts, self.queue, self.qhist, slab, self._step,
                 chosen) = fn(
                    padded, jnp.uint32(n_valid), self._key, self._step,
                    self.counts, self.queue, self.qhist, self.metrics.slab(),
                    self._service, *tables,
                )
                self.metrics.set_slab(slab)
            else:
                self.counts, self.queue, self.qhist, self._step, chosen = fn(
                    padded, jnp.uint32(n_valid), self._key, self._step,
                    self.counts, self.queue, self.qhist, self._service, *tables,
                )
            self.steps_done += 1
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

    def _mig_select_fn(self):
        import jax
        import jax.numpy as jnp

        policy, R = self.policy, self.n_replicas
        n_bins, max_hist = self.n_bins, self.max_hist
        instrumented = self._instrumented
        driver = self

        @jax.jit
        def select(owners, sel, step_idx, counts, queue, qhist, *rest):
            if instrumented:
                slab, service = rest
            else:
                (service,) = rest
            chosen = select_replica(
                owners, sel, counts, policy=policy, n_replicas=R
            )
            hist = jnp.zeros((n_bins,), jnp.int32).at[chosen].add(1)
            counts = counts + hist
            queue = jnp.maximum(queue + hist - service, 0)
            qhist = jax.lax.dynamic_update_slice(
                qhist, queue[None], (step_idx % max_hist, jnp.int32(0))
            )
            if instrumented:
                delta = jnp.zeros_like(slab)
                delta = driver.metrics.add(
                    delta, driver._routed_name, owners.shape[0]
                )
                delta = driver._accumulate(delta, hist, None)
                return counts, queue, qhist, slab + delta, step_idx + 1, chosen
            return counts, queue, qhist, step_idx + 1, chosen

        return select

    def serve_migrating(self, migration):
        """Serve one generated batch THROUGH a live migration window ->
        (datum_ids, chosen) device arrays.

        Routing goes through the window's dual-version replica read rule
        (``LiveMigration.route_replicas_device`` -- the cached fused
        probe), so every request lands on a node that physically holds its
        datum mid-drain.  Three jitted dispatches (generate, route,
        select+count), zero host syncs after the per-round pending-view
        refresh.  Single-device, like the window itself."""
        self._check_window(migration)
        gen = self._cached(("gen",), self._gen_fn)
        ids, sel = gen(self._key, self._step, self.traffic.thresholds_dev)
        owners = migration.route_replicas_device(ids)
        select = self._cached(("mig_select",), self._mig_select_fn)
        if self._instrumented:
            (self.counts, self.queue, self.qhist, slab, self._step,
             chosen) = select(
                owners, sel, self._step, self.counts, self.queue, self.qhist,
                self.metrics.slab(), self._service,
            )
            self.metrics.set_slab(slab)
        else:
            self.counts, self.queue, self.qhist, self._step, chosen = select(
                owners, sel, self._step, self.counts, self.queue, self.qhist,
                self._service,
            )
        self.steps_done += 1
        return ids, chosen

    def _mig_superstep_fn(self, statics: tuple, k: int):
        """K migration-window batches in ONE jit: generate, the fused
        dual-version replica read rule (the ``migrate.live``
        ``migrating_owners`` body) and select+count, scanned with the
        serving state as the carry -- the superstep twin of
        ``serve_migrating``'s three dispatches."""
        import jax
        import jax.numpy as jnp

        from repro.migrate.live import migrating_owners

        R = statics[3]
        batch, id_salt = self.batch, self.traffic.id_salt
        policy, n_bins, max_hist = self.policy, self.n_bins, self.max_hist
        instrumented = self._instrumented
        owners_fn = migrating_owners(statics)
        driver = self

        @jax.jit
        def super_body(key, step_idx, counts, queue, qhist, *rest):
            driver.ledger.incr("serve.superstep_traces")  # per TRACE only
            if instrumented:
                slab, service, thresholds, *tables = rest
                carry0 = (counts, queue, qhist, slab, step_idx)
            else:
                service, thresholds, *tables = rest
                carry0 = (counts, queue, qhist, step_idx)

            def sub(carry, _):
                if instrumented:
                    c, q, qh, sl, si = carry
                else:
                    c, q, qh, si = carry
                lanes = jnp.arange(batch, dtype=jnp.uint32)
                ids, sel = TrafficModel.draw(key, si, lanes, thresholds, id_salt)
                owners = owners_fn(ids.astype(jnp.uint32), *tables)
                chosen = select_replica(
                    owners, sel, c, policy=policy, n_replicas=R
                )
                hist = jnp.zeros((n_bins,), jnp.int32).at[chosen].add(1)
                c = c + hist
                q = jnp.maximum(q + hist - service, 0)
                qh = jax.lax.dynamic_update_slice(
                    qh, q[None], (si % max_hist, jnp.int32(0))
                )
                if instrumented:
                    delta = jnp.zeros_like(sl)
                    delta = driver.metrics.add(
                        delta, driver._routed_name, owners.shape[0]
                    )
                    delta = driver._accumulate(delta, hist, None)
                    return (c, q, qh, sl + delta, si + 1), (ids, chosen)
                return (c, q, qh, si + 1), (ids, chosen)

            carry, (ids, chosen) = jax.lax.scan(sub, carry0, None, length=k)
            return (*carry, ids, chosen)

        return super_body

    def superstep_migrating(self, migration, k: int):
        """Serve K generated batches THROUGH a live migration window in
        ONE host dispatch -> (datum_ids, chosen), each (k, batch).

        Bit-identical to K sequential ``serve_migrating`` calls against
        the same pending view: the whole dual-version read rule runs
        inside the scan, counters stay fresh between sub-batches, and the
        pending snapshot is the one at call time (refresh per round, as
        with ``serve_migrating``).  Single-device, like the window."""
        self._check_window(migration)
        k = int(k)
        if k < 1:
            raise ValueError(f"superstep needs k >= 1, got {k}")
        statics, tables = migration.route_operands()
        fn = self._cached(
            ("mig_superstep", statics, k),
            lambda: self._mig_superstep_fn(statics, k),
        )
        operands = (self._service, self.traffic.thresholds_dev, *tables)
        if self._instrumented:
            (self.counts, self.queue, self.qhist, slab, self._step,
             ids, chosen) = fn(
                self._key, self._step, self.counts, self.queue, self.qhist,
                self.metrics.slab(), *operands,
            )
            self.metrics.set_slab(slab)
        else:
            (self.counts, self.queue, self.qhist, self._step,
             ids, chosen) = fn(
                self._key, self._step, self.counts, self.queue, self.qhist,
                *operands,
            )
        self.steps_done += k
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
