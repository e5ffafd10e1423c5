"""Multi-chip scale-out of the placement/diff path (DESIGN.md section 11).

Everything the repo does at cluster scale -- uniformity histograms,
section-6.D movement accounting, migration planning -- is bulk throughput
over millions-to-billions of ids, and the placement/diff kernels are
embarrassingly parallel over ids.  ``ShardedSweep`` is the ``shard_map``
driver that turns one device's sweep into a mesh sweep:

  * the ID STREAM is partitioned over the mesh's single ``data`` axis
    (host-padded to a shard multiple; pad lanes carry weight 0),
  * the TABLE ARTIFACTS (length/cumsum/node tables, baseline lookup
    tables) are replicated -- they are kilobytes, the same "broadcast
    whole into VMEM" budget the Pallas kernels already assume,
  * each shard runs the UNCHANGED zero-host-sync engine kernels (the jnp
    reference bodies behind ``place_nodes_device`` /
    ``place_replica_nodes_device`` / ``diff_nodes_device`` /
    ``diff_replicas_device``), so per-lane results are bit-identical to
    the single-device sweep by construction,
  * the only cross-chip outputs -- per-node histograms, (src, dst)
    movement matrices, moved counts -- are reduced with a SINGLE ``psum``
    per sweep; integer scatter-adds, so the reduction is exact and the
    mesh result equals the single-device result bit for bit.

Per-id owner/diff arrays come back shard-partitioned (``out_specs
P('data')``); the host-facing methods re-assemble and trim the pad.

``check_vma=False`` everywhere: the placement kernels are ``while_loop``
ladders and shard_map has no replication rule for ``while`` -- every
output is either explicitly partitioned or an explicit ``psum``, so
nothing relies on the inferred-replication machinery.

jax is imported lazily (inside functions) so ``main`` can force the host
device count (``--xla_force_host_platform_device_count``, the
``launch/dryrun.py`` trick) BEFORE first jax init:

    PYTHONPATH=src python -m repro.launch.placement_mesh --selftest --devices 8

runs the bit-identity selftest -- sharded placement / histogram / diff /
replica-diff / planner vs the single-device engine path, all four
algorithms, R in {1, 3}, odd-sized id streams -- on 8 forced host
devices.  ``tests/test_sharded_placement.py`` runs the same selftest as a
subprocess; CI runs it at 4 devices in the fast job.
"""

from __future__ import annotations

import numpy as np

DATA_AXIS = "data"


def force_host_devices(n_devices: int) -> None:
    """Split the host CPU into ``n_devices`` JAX devices (tests, CI and the
    CPU scaling workers).  Must run before JAX's first backend init.
    Raises on any platform but the CPU: forced host devices exist only
    there, and a process holding a chip must not pose as a CPU mesh."""
    import os

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices} "
        + os.environ.get("XLA_FLAGS", "")
    ).strip()
    require_cpu("forcing host devices")


def require_cpu(what: str) -> None:
    """Raise a directed error unless this process's JAX platform is the
    CPU (on-chip runs use the devices JAX finds, never forced ones)."""
    import jax

    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            f"{what} needs the CPU platform, but this process runs on "
            f"{platform!r}; run it with JAX_PLATFORMS=cpu, or drop the "
            "forced device count to use the devices JAX finds"
        )


def make_data_mesh(n_devices: int | None = None):
    """1-D placement mesh over the first ``n_devices`` devices (default:
    all).  The placement sweep has no model axis -- ids are the only
    partitioned dimension."""
    import jax

    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"asked for {n_devices} devices, only {len(devs)} present "
                "(force more with --xla_force_host_platform_device_count)"
            )
        devs = devs[:n_devices]
    return jax.sharding.Mesh(np.asarray(devs), (DATA_AXIS,))


class ShardedSweep:
    """Mesh-wide bulk placement/diff sweeps bound to one ``PlacementEngine``.

    Construction is cheap (no compile, no upload); the shard_map callables
    are built and jitted lazily per (kind, static-config) and cached, so a
    steady-state sweep re-traces nothing.  All methods accept id streams of
    ANY length -- ids are zero-padded to a shard multiple on the host and
    the pad lanes carry weight 0, so they cannot contribute to any
    histogram, matrix or count (tested), and per-id outputs are trimmed
    back by the host-facing wrappers.
    """

    def __init__(self, engine, mesh=None):
        self.engine = engine
        self.mesh = make_data_mesh() if mesh is None else mesh
        if tuple(self.mesh.axis_names) != (DATA_AXIS,):
            raise ValueError(
                f"placement mesh must be 1-D over ('{DATA_AXIS}',); "
                f"got axes {tuple(self.mesh.axis_names)}"
            )
        self.n_devices = int(self.mesh.devices.size)
        self._fns: dict[tuple, object] = {}

    # -- padding --------------------------------------------------------------

    def _pad(self, datum_ids):
        """(ids_padded, weights, n_valid): host-side zero-pad to a multiple
        of ``n_devices`` so every shard gets an equal slice.  Pad lanes get
        weight 0 -- the single mechanism that keeps them out of every
        reduction (and out of ``moved`` in the diff paths)."""
        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        n = ids.shape[0]
        pad = (-n) % self.n_devices
        w = np.ones(n + pad, dtype=np.int32)
        if pad:
            ids = np.concatenate([ids, np.zeros(pad, dtype=np.uint32)])
            w[n:] = 0
        return ids, w, n

    # -- shard_map plumbing ---------------------------------------------------

    def _cached(self, key: tuple, build):
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = build()
        return fn

    def _shard_jit(self, body, n_tables: int, *, n_out: int = 1, reduced: bool):
        """jit(shard_map(body)): ids+weights partitioned, tables replicated,
        outputs either partitioned per-lane arrays or one psum-reduced
        (replicated) array."""
        import jax
        from jax.sharding import PartitionSpec as P

        in_specs = (P(DATA_AXIS), P(DATA_AXIS)) + (P(),) * n_tables
        one = P() if reduced else P(DATA_AXIS)
        out_specs = one if n_out == 1 else (one,) * n_out
        return jax.jit(
            jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=out_specs,
                check_vma=False,  # while_loop ladders have no replication rule
            )
        )

    # -- table plumbing (replicated operands) ---------------------------------

    def _asura_tables(self, version: int | None):
        eng = self.engine
        if version is None:
            art = eng._device_artifact("asura")
        else:
            art = eng._device_artifact_for(version, "asura")
        return art, (art.len32_dev, art.cum_hi_dev, art.cum_lo_dev, art.node_of_dev)

    def _alg_tables(self, alg: str):
        """(tables, statics) for the single-version owner sweep."""
        eng = self.engine
        if alg == "asura":
            art, tables = self._asura_tables(None)
            statics = (art.top_level, eng.params.s_log2, eng.params.max_draws)
        else:
            art = eng._device_artifact(alg)
            tables = (art.keys_dev, art.vals_dev)
            statics = ()
        return tables, statics

    @staticmethod
    def _owners_body(alg: str, statics: tuple):
        """Per-shard owners: (ids, *tables) -> int32 node ids -- the same
        jnp kernels the single-device ``place_nodes_device`` runs."""
        if alg == "asura":
            from repro.kernels.ops import _place_fused_ref

            top_level, s_log2, max_draws = statics

            def owners(ids, len32, cum_hi, cum_lo, node_of):
                return _place_fused_ref(
                    ids, len32, cum_hi, cum_lo, node_of,
                    top_level=top_level, s_log2=s_log2, max_draws=max_draws,
                    emit_nodes=True,
                )

            return owners
        from repro.kernels.baselines import ch_lookup, rs_lookup, wrh_lookup

        lookup = {"ch": ch_lookup, "rs": rs_lookup, "wrh": wrh_lookup}[alg]

        def owners(ids, keys, vals):
            return lookup(ids, keys, vals)

        return owners

    # -- per-id sweeps (partitioned outputs) ----------------------------------

    def place_nodes_device(self, datum_ids, algorithm: str | None = None):
        """Mesh-partitioned batch placement -> (padded_batch,) int32 owners,
        shard-sharded device array (pad lanes place id 0 -- callers that
        need the exact stream use ``place_nodes``)."""
        alg = self.engine._resolve_algorithm(algorithm)
        tables, statics = self._alg_tables(alg)
        ids, w, _ = self._pad(datum_ids)
        owners = self._owners_body(alg, statics)

        def build():
            def body(ids_l, w_l, *tabs):
                return owners(ids_l, *tabs)

            return self._shard_jit(body, len(tables), reduced=False)

        fn = self._cached(("owners", alg, statics), build)
        return fn(ids, w, *tables)

    def place_nodes(self, datum_ids, algorithm: str | None = None) -> np.ndarray:
        """Host-facing mesh placement -> int64 owners, bit-identical to
        ``engine.place_nodes`` (one cross-shard gather + pad trim)."""
        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        out = self.place_nodes_device(ids, algorithm)
        return np.asarray(out)[: ids.shape[0]].astype(np.int64)

    def diff_nodes_device(self, datum_ids, v_from: int, v_to: int):
        """Mesh-partitioned two-version diff -> (moved, src, dst) shard-
        sharded device arrays, padded length; pad lanes have moved=False
        (weight-masked), so downstream counts/selections see no phantoms."""
        self.engine._require_asura("diff_nodes_device")
        art_a, tabs_a = self._asura_tables(v_from)
        art_b, tabs_b = self._asura_tables(v_to)
        p = self.engine.params
        statics = (art_a.top_level, art_b.top_level, p.s_log2, p.max_draws)
        ids, w, _ = self._pad(datum_ids)

        def build():
            from repro.kernels.ops import _diff_fused_ref

            top_a, top_b, s_log2, max_draws = statics

            def body(ids_l, w_l, la, ha, ca, na, lb, hb, cb, nb):
                moved, src, dst = _diff_fused_ref(
                    ids_l, la, ha, ca, na, lb, hb, cb, nb,
                    top_a=top_a, top_b=top_b,
                    s_log2=s_log2, max_draws=max_draws,
                )
                return moved & (w_l > 0), src, dst

            return self._shard_jit(body, 8, n_out=3, reduced=False)

        fn = self._cached(("diff", statics), build)
        return fn(ids, w, *tabs_a, *tabs_b)

    def diff_replicas_device(self, datum_ids, v_from: int, v_to: int, n_replicas: int):
        """Mesh-partitioned replica-set diff -> (moved, src, dst, src_slot)
        shard-sharded (padded_batch, R) device arrays; pad rows have
        moved all-False (weight-masked)."""
        self.engine._require_asura("diff_replicas_device")
        art_a, _ = self._asura_tables(v_from)
        art_b, _ = self._asura_tables(v_to)
        tabs = (
            art_a.len32_dev, art_a.node_of_dev,
            art_b.len32_dev, art_b.node_of_dev,
        )
        p = self.engine.params
        statics = (
            art_a.top_level, art_b.top_level, p.s_log2, p.max_draws, n_replicas
        )
        ids, w, _ = self._pad(datum_ids)

        def build():
            from repro.kernels.ops import _diff_replicas_fused_ref

            top_a, top_b, s_log2, max_draws, R = statics

            def body(ids_l, w_l, la, na, lb, nb):
                moved, src, dst, src_slot = _diff_replicas_fused_ref(
                    ids_l, la, na, lb, nb,
                    top_a=top_a, top_b=top_b,
                    s_log2=s_log2, max_draws=max_draws, n_replicas=R,
                )
                return moved & (w_l > 0)[:, None], src, dst, src_slot

            return self._shard_jit(body, 4, n_out=4, reduced=False)

        fn = self._cached(("rdiff", statics), build)
        return fn(ids, w, *tabs)

    # -- one-reduction sweeps (psum outputs) ----------------------------------

    def histogram(
        self,
        datum_ids,
        n_bins: int,
        algorithm: str | None = None,
        n_replicas: int | None = None,
    ) -> np.ndarray:
        """Per-node occupancy histogram in ONE mesh sweep -> (n_bins,) int64.

        Each shard places its ids and scatter-adds its weight-masked counts
        locally; the single cross-chip ``psum`` sums the per-shard
        histograms -- exact integer addition, so the result equals
        ``np.bincount(engine.place_nodes(ids), minlength=n_bins)`` bit for
        bit while never materializing the owner array on the host.  With
        ``n_replicas`` the ASURA replica sets are counted instead (each id
        contributes R counts; non-converged -1 slots are excluded).
        """
        import jax
        import jax.numpy as jnp

        alg = self.engine._resolve_algorithm(algorithm)
        ids, w, _ = self._pad(datum_ids)
        if n_replicas is None:
            tables, statics = self._alg_tables(alg)
            owners = self._owners_body(alg, statics)
            key = ("hist", alg, statics, n_bins)

            def build():
                def body(ids_l, w_l, *tabs):
                    nodes = owners(ids_l, *tabs)
                    hist = jnp.zeros((n_bins,), jnp.int32)
                    hist = hist.at[jnp.maximum(nodes, 0)].add(
                        jnp.where(nodes >= 0, w_l, 0)
                    )
                    return jax.lax.psum(hist, DATA_AXIS)

                return self._shard_jit(body, len(tables), reduced=True)

        else:
            if alg != "asura":
                raise ValueError("replica histograms are ASURA-only")
            art, _ = self._asura_tables(None)
            tables = (art.len32_dev, art.node_of_dev)
            p = self.engine.params
            statics = (
                art.top_level, p.s_log2, p.max_draws, n_replicas, n_bins
            )
            key = ("rhist", statics)

            def build():
                from repro.kernels.ops import _place_replicas_fused_ref

                top_level, s_log2, max_draws, R, bins = statics

                def body(ids_l, w_l, len32, node_of):
                    nodes = _place_replicas_fused_ref(
                        ids_l, len32, node_of,
                        top_level=top_level, s_log2=s_log2,
                        max_draws=max_draws, n_replicas=R, emit_nodes=True,
                    )
                    hist = jnp.zeros((bins,), jnp.int32)
                    hist = hist.at[jnp.maximum(nodes, 0)].add(
                        jnp.where(nodes >= 0, w_l[:, None], 0)
                    )
                    return jax.lax.psum(hist, DATA_AXIS)

                return self._shard_jit(body, len(tables), reduced=True)

        fn = self._cached(key, build)
        return np.asarray(fn(ids, w, *tables)).astype(np.int64)

    def movement_matrix(
        self,
        datum_ids,
        v_from: int,
        v_to: int,
        n_bins: int,
        n_replicas: int | None = None,
    ) -> tuple[int, np.ndarray]:
        """(n_moved, (n_bins, n_bins) src->dst matrix) in ONE mesh sweep.

        The section-6.D movement accounting at mesh scale: each shard diffs
        its ids (single-owner, or the per-slot replica alignment with
        ``n_replicas``) and scatter-adds its weight-masked moved rows into
        a local (src, dst) matrix; the single cross-chip ``psum`` sums the
        matrices and ``n_moved`` is the matrix total -- both exact, equal
        to the single-device planner's moved rows bit for bit.
        """
        import jax
        import jax.numpy as jnp

        self.engine._require_asura("movement_matrix")
        art_a, tabs_a = self._asura_tables(v_from)
        art_b, tabs_b = self._asura_tables(v_to)
        p = self.engine.params
        ids, w, _ = self._pad(datum_ids)
        if n_replicas is None:
            tabs = tabs_a + tabs_b
            statics = (
                art_a.top_level, art_b.top_level, p.s_log2, p.max_draws, n_bins
            )
            key = ("mmat", statics)

            def build():
                from repro.kernels.ops import _diff_fused_ref

                top_a, top_b, s_log2, max_draws, bins = statics

                def body(ids_l, w_l, la, ha, ca, na, lb, hb, cb, nb):
                    moved, src, dst = _diff_fused_ref(
                        ids_l, la, ha, ca, na, lb, hb, cb, nb,
                        top_a=top_a, top_b=top_b,
                        s_log2=s_log2, max_draws=max_draws,
                    )
                    add = (moved & (w_l > 0)).astype(jnp.int32)
                    mat = jnp.zeros((bins, bins), jnp.int32)
                    mat = mat.at[jnp.maximum(src, 0), jnp.maximum(dst, 0)].add(add)
                    return jax.lax.psum(mat, DATA_AXIS)

                return self._shard_jit(body, len(tabs), reduced=True)

        else:
            tabs = (
                art_a.len32_dev, art_a.node_of_dev,
                art_b.len32_dev, art_b.node_of_dev,
            )
            statics = (
                art_a.top_level, art_b.top_level,
                p.s_log2, p.max_draws, n_replicas, n_bins,
            )
            key = ("rmmat", statics)

            def build():
                from repro.kernels.ops import _diff_replicas_fused_ref

                top_a, top_b, s_log2, max_draws, R, bins = statics

                def body(ids_l, w_l, la, na, lb, nb):
                    moved, src, dst, _slot = _diff_replicas_fused_ref(
                        ids_l, la, na, lb, nb,
                        top_a=top_a, top_b=top_b,
                        s_log2=s_log2, max_draws=max_draws, n_replicas=R,
                    )
                    add = (moved & (w_l > 0)[:, None]).astype(jnp.int32)
                    mat = jnp.zeros((bins, bins), jnp.int32)
                    mat = mat.at[jnp.maximum(src, 0), jnp.maximum(dst, 0)].add(add)
                    return jax.lax.psum(mat, DATA_AXIS)

                return self._shard_jit(body, len(tabs), reduced=True)

        fn = self._cached(key, build)
        mat = np.asarray(fn(ids, w, *tabs)).astype(np.int64)
        return int(mat.sum()), mat

    # -- serving (DESIGN.md section 12) ---------------------------------------

    def serve_stream(self, **kwargs):
        """A ``RequestStreamDriver`` sharding its request stream over this
        mesh: each shard generates its slice of the global lane range
        (bit-identical words by the counter-based construction), routes and
        selects against the replicated tables + start-of-batch counters,
        and the per-node load histogram merges with ONE exact integer psum
        per batch -- so the sharded stream equals the single-device stream
        bit for bit (selftest-enforced)."""
        from repro.serve import RequestStreamDriver

        return RequestStreamDriver(self.engine, mesh=self, **kwargs)


# ---------------------------------------------------------------------------
# Bit-identity selftest (the forced-host-device smoke; tests + CI call this)
# ---------------------------------------------------------------------------


def check_layout(sweep: ShardedSweep, datum_ids) -> None:
    """Assert that a mesh sweep really spreads over the mesh: the compiled
    owner program takes the ids partitioned over the data axis and every
    table replicated, and its per-id output comes back as one equal shard
    per device -- nothing lands whole on one device."""
    out = sweep.place_nodes_device(datum_ids, "asura")
    tables, statics = sweep._alg_tables("asura")
    ids, w, _ = sweep._pad(datum_ids)
    fn = sweep._fns[("owners", "asura", statics)]
    # (ids, weights, *tables); the owner body never reads the pad weights,
    # so the compiler prunes that argument and reports no sharding for it
    in_shardings = fn.lower(ids, w, *tables).compile().input_shardings[0]
    n = sweep.n_devices
    assert len(in_shardings) == 2 + len(tables)
    assert in_shardings[0].shard_shape(ids.shape) == (ids.shape[0] // n,), (
        f"ids not partitioned over the mesh: {in_shardings[0]}"
    )
    for s in in_shardings[2:]:
        assert s.is_fully_replicated, f"table not replicated: {s}"
    shards = out.addressable_shards
    assert len({s.device for s in shards}) == n == len(shards), (
        "owner shards do not cover every device"
    )
    assert all(s.data.shape == (ids.shape[0] // n,) for s in shards)


def selftest(
    n_devices: int | None = None,
    n_ids: int = 100_003,
    *,
    n_nodes: int = 32,
    serve_nodes: int = 16,
    serve_batch: int | None = None,
    n_keys: int = 4096,
) -> int:
    """Assert sharded == single-device, all four algorithms, R in {1, 3}.

    ``n_ids`` is deliberately odd (it must not divide the mesh) so the
    pad-lane masking is exercised on every entry point.  ``n_nodes`` sizes
    the placement/planner cluster, ``serve_nodes`` / ``serve_batch``
    (default 256 lanes per device) / ``n_keys`` the serving streams.
    Returns the device count it ran on.
    """
    from repro.core import PlacementEngine, make_uniform_cluster
    from repro.migrate import MigrationPlanner

    ids = np.arange(n_ids, dtype=np.uint32)
    mesh = make_data_mesh(n_devices)

    # placement + histogram, all four algorithms
    cluster = make_uniform_cluster(n_nodes)
    for alg in ("asura", "ch", "wrh", "rs"):
        eng = PlacementEngine(cluster, backend="ref", algorithm=alg)
        sw = ShardedSweep(eng, mesh)
        ref = eng.place_nodes(ids)
        got = sw.place_nodes(ids)
        assert np.array_equal(ref, got), f"{alg}: sharded owners differ"
        hist = sw.histogram(ids, n_nodes)
        assert np.array_equal(
            hist, np.bincount(ref, minlength=n_nodes)
        ), f"{alg}: sharded histogram differs"

    engine = PlacementEngine(cluster, backend="ref")
    sweep = ShardedSweep(engine, mesh)
    check_layout(sweep, ids)

    # replica histograms, R in {1, 3}
    for R in (1, 3):
        nodes = engine.place_replica_nodes(ids, R)
        hist = sweep.histogram(ids, n_nodes, n_replicas=R)
        assert np.array_equal(
            hist, np.bincount(nodes.ravel(), minlength=n_nodes)
        ), f"R={R}: sharded replica histogram differs"

    # version diff + movement matrix + sharded planner, R in {1, 3}
    engine.artifact()
    v0 = cluster.version
    cluster.add_node(n_nodes, 1.0)
    v1 = cluster.version
    planner = MigrationPlanner(engine)
    plan = planner.plan(ids, v0, v1)
    n_moved, mat = sweep.movement_matrix(ids, v0, v1, n_nodes + 1)
    assert n_moved == plan.n_moves, "sharded moved count differs"
    ref_mat = np.zeros((n_nodes + 1, n_nodes + 1), dtype=np.int64)
    np.add.at(ref_mat, (plan.src, plan.dst), 1)
    assert np.array_equal(mat, ref_mat), "sharded movement matrix differs"
    splan = planner.plan(ids, v0, v1, mesh=mesh)
    fields = ("ids", "src", "dst", "index", "slot", "src_slot")
    for field in fields:
        assert np.array_equal(
            getattr(plan, field), getattr(splan, field)
        ), f"sharded plan field {field} differs"
    for R in (1, 3):
        rplan = planner.plan_replicas(ids, v0, v1, R)
        srplan = planner.plan_replicas(ids, v0, v1, R, mesh=mesh)
        for field in fields:
            assert np.array_equal(
                getattr(rplan, field), getattr(srplan, field)
            ), f"R={R}: sharded replica plan field {field} differs"
        rn, _ = sweep.movement_matrix(ids, v0, v1, n_nodes + 1, n_replicas=R)
        assert rn == rplan.n_moves, f"R={R}: sharded replica moved count differs"

    # mesh-sharded serving stream == single-device stream, bit for bit:
    # chosen nodes, load counters and queue state, every batch, all four
    # algorithms, R in {1, 3} (DESIGN.md section 12)
    from repro.serve import RequestStreamDriver

    serve_cluster = make_uniform_cluster(serve_nodes)
    batch = serve_batch or 256 * int(mesh.devices.size)
    for alg in ("asura", "ch", "wrh", "rs"):
        eng_s = PlacementEngine(serve_cluster, backend="ref", algorithm=alg)
        for R in (1, 3):
            kw = dict(
                batch=batch, n_keys=n_keys, law="zipf",
                n_replicas=R, policy="pow2", seed=7,
            )
            solo = RequestStreamDriver(eng_s, **kw)
            shard = RequestStreamDriver(eng_s, mesh=mesh, **kw)
            for _step in range(3):
                a = np.asarray(solo.step())
                b = np.asarray(shard.step())
                assert np.array_equal(a, b), (
                    f"{alg} R={R} step {_step}: sharded chosen nodes differ"
                )
                assert np.array_equal(
                    solo.load_counts(), shard.load_counts()
                ), f"{alg} R={R} step {_step}: sharded load counters differ"
                assert np.array_equal(
                    np.asarray(solo.queue), np.asarray(shard.queue)
                ), f"{alg} R={R} step {_step}: sharded queue state differs"

    # metrics slab: the mesh-sharded instrumented stream's psum-merged
    # snapshot equals the single-device snapshot bit for bit (same exact
    # integer reduction contract as the load histogram)
    from repro.obs import MetricsRegistry

    eng_m = PlacementEngine(serve_cluster, backend="ref", algorithm="asura")
    for R in (1, 3):
        kw = dict(
            batch=batch, n_keys=n_keys, law="zipf",
            n_replicas=R, policy="pow2", seed=7,
        )
        reg_solo, reg_shard = MetricsRegistry(), MetricsRegistry()
        solo = RequestStreamDriver(eng_m, metrics=reg_solo, **kw)
        shard = RequestStreamDriver(eng_m, mesh=mesh, metrics=reg_shard, **kw)
        for _step in range(3):
            solo.step()
            shard.step()
        snap_a, snap_b = reg_solo.snapshot(), reg_shard.snapshot()
        assert set(snap_a) == set(snap_b), "metric name sets differ"
        assert int(snap_a["asura.ladder_draws"].sum()) == 3 * batch
        for name in snap_a:
            assert np.array_equal(snap_a[name], snap_b[name]), (
                f"R={R}: sharded metric {name!r} differs"
            )

    # two-level (domain, node) placement smoke: the fused hierarchy kernel
    # (through the engine, on the forced host devices) must equal the
    # HierarchicalCluster NumPy oracle bit for bit, and the mesh-sharded
    # serving stream on a hierarchical engine must match the single-device
    # stream (DESIGN.md section 14)
    from repro.core import HierarchicalCluster

    hcluster = HierarchicalCluster()
    for d in range(4):
        for i in range(4):
            hcluster.add_node(d, 100 + d * 4 + i, 1.0 + 0.25 * i)
    heng = PlacementEngine(hcluster, backend="ref")
    hids = ids[: min(n_ids, 20_011)]
    for R in (1, 3):
        got = heng.place_replica_pairs(hids, R)
        want = hcluster.place_replicas(hids, R)
        assert np.array_equal(got, want), (
            f"R={R}: two-level kernel differs from the oracle"
        )
    assert np.array_equal(heng.place_nodes(hids), want[:, 0, 1]), (
        "two-level place_nodes differs from the oracle primary"
    )
    for R in (1, 3):
        kw = dict(
            batch=batch, n_keys=n_keys, law="zipf",
            n_replicas=R, policy="pow2", seed=7,
        )
        solo = RequestStreamDriver(heng, **kw)
        shard = RequestStreamDriver(heng, mesh=mesh, **kw)
        for _step in range(3):
            assert np.array_equal(
                np.asarray(solo.step()), np.asarray(shard.step())
            ), f"hier R={R} step {_step}: sharded chosen nodes differ"
            assert np.array_equal(
                solo.load_counts(), shard.load_counts()
            ), f"hier R={R} step {_step}: sharded load counters differ"

    # scan-fused superstep: a mesh-sharded superstep(k) must equal k
    # single-device step() calls bit for bit -- chosen, counters, queue
    # (DESIGN.md section 15; the per-sub-batch psum stays inside the scan)
    eng_k = PlacementEngine(serve_cluster, backend="ref", algorithm="asura")
    kw = dict(
        batch=batch, n_keys=n_keys, law="zipf",
        n_replicas=3, policy="pow2", seed=7,
    )
    solo = RequestStreamDriver(eng_k, **kw)
    shard = RequestStreamDriver(eng_k, mesh=mesh, **kw)
    k = 3
    for _block in range(2):
        a = np.stack([np.asarray(solo.step()) for _ in range(k)])
        chosen = shard.superstep(k)
        # the chosen lanes come back one (k, batch / n) shard per device
        shards = chosen.addressable_shards
        assert len({s.device for s in shards}) == sweep.n_devices == len(shards)
        assert all(
            s.data.shape == (k, batch // sweep.n_devices) for s in shards
        ), "sharded superstep output is not lane-partitioned"
        b = np.asarray(chosen)
        assert np.array_equal(a, b), (
            f"block {_block}: sharded superstep chosen nodes differ"
        )
        assert np.array_equal(
            solo.load_counts(), shard.load_counts()
        ), f"block {_block}: superstep load counters differ"
        assert np.array_equal(
            np.asarray(solo.queue), np.asarray(shard.queue)
        ), f"block {_block}: superstep queue state differs"
    return sweep.n_devices


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument(
        "--devices",
        type=int,
        default=None,
        help="force this many host devices (set before first jax init)",
    )
    ap.add_argument("--ids", type=int, default=100_003)
    args = ap.parse_args(argv)
    if args.devices is not None:
        force_host_devices(args.devices)
    if not args.selftest:
        print("nothing to do (pass --selftest)")
        return 0
    n_dev = selftest(args.devices, n_ids=args.ids)
    print(f"sharded placement selftest OK on {n_dev} devices")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
