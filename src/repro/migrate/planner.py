"""Migration layer 1: the streaming version-diff planner.

A membership change turns cluster version v into v+1.  The planner answers
"which data must move, from where, to where" by placing every tracked id
under BOTH table versions (both artifacts coexist in the engine's LRU --
DESIGN.md section 6) and diffing the owners:

  * ``diff_device``   -- one chunk: (moved, src, dst) DEVICE arrays, zero
                         host syncs (the fused dual-table kernel,
                         ``kernels.ops.diff_nodes_on_tables_device``).
  * ``plan_stream``   -- the streaming sweep: iterate id chunks through
                         ``diff_device`` so tens of millions of ids are
                         diffed in fixed device memory.  Yields device
                          4-tuples and never touches the host (tested under
                         a transfer guard).
  * ``plan``          -- host-facing assembly into a ``MigrationPlan``
                         (the moved rows only).  For the common add-node
                         case, pass ``max_new_seg`` to enable the OWNER
                         prefilter: the grown nodes are read off the two
                         cached artifacts, one placement under v+1 keeps
                         the ids whose set holds one, and only those pay
                         the full dual diff.  Exact for a pure addition
                         (minimal movement, section 6.D); any change that
                         shrinks or reassigns a segment keeps every id.

The unit of work generalizes from a node to an R-way REPLICA SET
(DESIGN.md section 10): ``diff_replicas_device`` / ``plan_replicas_stream``
/ ``plan_replicas`` are the per-slot twins -- each id's full replica set is
placed under both versions in one pass and aligned slot by slot, so only
replicas whose owner actually changed produce a row (the paper's
section-5 minimal replica movement, even under replication).

ASURA's optimality theorems make the diff minimal by construction; the
oracle tests re-verify against brute force (tests/test_migrate.py).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.obs.trace import span

DEFAULT_CHUNK = 1 << 20  # ids per streaming chunk (fixed device memory)

_MASK_CACHE: dict = {}


def pad_pow2(chunk, multiple: int = 1):
    """(padded, n_valid): zero-pad a chunk into its pow2 bucket (and up to
    a device multiple for mesh sweeps), so ragged tails share one compile
    per bucket.  Full pow2 chunks pass through untouched (``padded is
    chunk`` -- the zero-sync fast path); device-array tails pad ON DEVICE
    (``kernels.ops._pad_ids``).  Shared by the streaming planner and the
    serving driver's external-batch path (DESIGN.md sections 11-12)."""
    n = int(chunk.shape[0])
    target = 1 << max(0, n - 1).bit_length()
    target += (-target) % max(1, multiple)
    if target == n:
        return chunk, n
    if isinstance(chunk, np.ndarray):
        return np.pad(chunk, (0, target - n)), n
    from repro.kernels.ops import _pad_ids

    return _pad_ids(chunk, target), n


def _mask_tail(moved, n_valid: int):
    """``moved`` with rows >= ``n_valid`` forced False, on device.

    ``n_valid`` is a TRACED argument, so every ragged tail that lands in
    the same pow2 bucket shares one compile -- the whole point of the
    bucketing (a static tail length would compile once per distinct
    raggedness, the bug this fixes)."""
    import jax
    import jax.numpy as jnp

    fn = _MASK_CACHE.get(moved.ndim)
    if fn is None:

        @jax.jit
        def fn(m, n):
            idx = jnp.arange(m.shape[0]).reshape((-1,) + (1,) * (m.ndim - 1))
            return m & (idx < n)

        _MASK_CACHE[moved.ndim] = fn
    return fn(moved, n_valid)


def _plan_fields(plan) -> dict:
    """The fields of a plan's ``planner.*`` span event."""
    return {
        "n_scanned": plan.n_scanned, "n_moves": plan.n_moves,
        "v_from": plan.v_from, "v_to": plan.v_to,
    }


@dataclasses.dataclass(frozen=True)
class MigrationPlan:
    """The moved rows of a two-version placement diff.

    The unit of work is a REPLICA SLOT, not a node: row i says replica
    slot ``slot[i]`` of datum ``ids[i]`` must move from node ``src[i]``
    (where its bytes live under v) to node ``dst[i]`` (its v+1 owner);
    ``index[i]`` is the row's position in the scanned id array (so callers
    can update per-id side tables without a search).  Single-owner plans
    are the R=1 degenerate case (``slot``/``src_slot`` all zero; one row
    per moved id).  For replica plans, ``slot`` indexes the id's v+1
    replica set and ``src_slot`` the position of ``src`` in its v set --
    rollback swaps the two so the reverse plan's slots index the reverse
    destination set (DESIGN.md section 10).  Rows keep scan order (id
    major, slot minor).
    """

    v_from: int
    v_to: int
    ids: np.ndarray  # uint32, moved ids (one row per moved (id, slot))
    src: np.ndarray  # int64, vacated owner under v_from
    dst: np.ndarray  # int64, owner under v_to
    index: np.ndarray  # int64, positions in the scanned id array
    n_scanned: int
    n_replicas: int = 1
    slot: np.ndarray | None = None  # int32, position in the v_to replica set
    src_slot: np.ndarray | None = None  # int32, position of src in the v set

    def __post_init__(self):
        # Single-owner construction sites predate replica plans; normalize
        # so every consumer can rely on the per-slot arrays existing.
        if self.slot is None:
            object.__setattr__(
                self, "slot", np.zeros(len(self.ids), dtype=np.int32)
            )
        if self.src_slot is None:
            object.__setattr__(
                self, "src_slot", np.zeros(len(self.ids), dtype=np.int32)
            )

    @property
    def n_moves(self) -> int:
        return int(self.ids.shape[0])

    @property
    def moved_fraction(self) -> float:
        """Moved fraction of the scanned REPLICA mass (R * n_scanned)."""
        return self.n_moves / max(1, self.n_scanned * self.n_replicas)

    def moves_dict(self) -> dict[int, tuple[int, int]]:
        """datum id -> (src, dst), built from the vectorized arrays (no
        per-candidate Python compare loop).  For replica plans an id with
        several moved slots keeps its LAST row -- add/remove events move at
        most one slot per id, so the dict is total there; slot-accurate
        consumers read the arrays directly."""
        return dict(
            zip(
                self.ids.tolist(),
                zip(self.src.tolist(), self.dst.tolist()),
            )
        )


class MigrationPlanner:
    """Version-diff planner bound to one ``PlacementEngine``.

    Both versions' artifacts must be cached (place at v before mutating --
    every engine consumer already does) or ``engine.artifact_for`` raises.
    """

    def __init__(self, engine, *, ledger=None, metrics=None):
        self.engine = engine
        # observability (optional): spans around plan assembly plus the
        # owner prefilter's scanned/kept counters (its hit rate is the
        # add-node fast path's effectiveness, DESIGN.md 13).
        self.ledger = ledger
        self.metrics = metrics
        # scan-fused multi-chunk diff jits, keyed (kind, statics[, R])
        self._fuse_fns: dict = {}

    def _note_prefilter(self, n_scanned: int, n_kept: int) -> None:
        if self.ledger is not None:
            self.ledger.incr("planner.prefilter_scanned", n_scanned)
            self.ledger.incr("planner.prefilter_kept", n_kept)
        if self.metrics is not None:
            self.metrics.inc_host("planner.prefilter_scanned", n_scanned)
            self.metrics.inc_host("planner.prefilter_kept", n_kept)

    def _sweep(self, mesh):
        """Resolve ``mesh=`` (a Mesh, a ``ShardedSweep``, or None) into a
        sweep bound to this planner's engine -- the multi-chip diff path
        (DESIGN.md section 11)."""
        if mesh is None:
            return None
        from repro.launch.placement_mesh import ShardedSweep

        if isinstance(mesh, ShardedSweep):
            return mesh
        return ShardedSweep(self.engine, mesh)

    # -- device streaming sweep ---------------------------------------------

    def diff_device(self, datum_ids, v_from: int, v_to: int):
        """One chunk -> (moved, src, dst) device arrays, zero host syncs."""
        return self.engine.diff_nodes_device(datum_ids, v_from, v_to)

    def diff_replicas_device(
        self, datum_ids, v_from: int, v_to: int, n_replicas: int
    ):
        """One chunk -> per-slot (moved, src, dst, src_slot) device arrays,
        each (chunk, R), zero host syncs (the fused dual-table replica
        kernel + on-device set alignment)."""
        return self.engine.diff_replicas_device(
            datum_ids, v_from, v_to, n_replicas
        )

    # -- scan-fused multi-chunk diff (DESIGN.md section 15) -------------------

    def _fuse_tables(self, v_from: int, v_to: int, replicas: bool):
        """(tables, statics) for the scan-fused diff body -- the same
        dual-version device artifacts ``diff_device`` resolves."""
        e = self.engine
        art_a = e._device_artifact_for(v_from, "asura")
        art_b = e._device_artifact_for(v_to, "asura")
        p = e.params
        statics = (art_a.top_level, art_b.top_level, p.s_log2, p.max_draws)
        if replicas:
            tables = (
                art_a.len32_dev, art_a.node_of_dev,
                art_b.len32_dev, art_b.node_of_dev,
            )
        else:
            tables = (
                art_a.len32_dev, art_a.cum_hi_dev, art_a.cum_lo_dev,
                art_a.node_of_dev,
                art_b.len32_dev, art_b.cum_hi_dev, art_b.cum_lo_dev,
                art_b.node_of_dev,
            )
        return tables, statics

    def _fuse_fn(self, statics: tuple, n_replicas: int | None):
        """Jitted ``lax.scan`` of the fused dual-table diff over a stacked
        (B, chunk) id block -- ONE dispatch per B chunks.  Cached per
        static routing configuration; block shape changes retrace inside
        jax's own cache (pow2 chunking bounds them at O(log chunk))."""
        key = ("rdiff", statics, n_replicas) if n_replicas else ("diff", statics)
        fn = self._fuse_fns.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        from repro.kernels.ops import _diff_fused_ref, _diff_replicas_fused_ref

        top_a, top_b, s_log2, max_draws = statics

        def body(tabs, ids):
            u = ids.astype(jnp.uint32)
            if n_replicas:
                out = _diff_replicas_fused_ref(
                    u, *tabs, top_a=top_a, top_b=top_b,
                    s_log2=s_log2, max_draws=max_draws, n_replicas=n_replicas,
                )
            else:
                out = _diff_fused_ref(
                    u, *tabs, top_a=top_a, top_b=top_b,
                    s_log2=s_log2, max_draws=max_draws,
                )
            return tabs, out

        @jax.jit
        def run(ids_blk, *tabs):
            _, outs = jax.lax.scan(body, tabs, ids_blk)
            return outs

        self._fuse_fns[key] = run
        return run

    def _fused_stream(
        self, id_chunks, v_from: int, v_to: int, fuse: int,
        n_replicas: int | None,
    ):
        """Shared fused-stream driver: group consecutive equal-pow2-length
        chunks into blocks of up to ``fuse``, diff each block in one
        scanned dispatch, and yield the SAME per-chunk tuples the
        unfused stream yields (pad lanes' ``moved`` masked False)."""
        import jax.numpy as jnp

        tables, statics = self._fuse_tables(v_from, v_to, bool(n_replicas))
        run = self._fuse_fn(statics, n_replicas)

        def flush(buf):
            if not buf:
                return
            stack = (
                np.stack([p for p, _, _ in buf])
                if all(isinstance(p, np.ndarray) for p, _, _ in buf)
                else jnp.stack([jnp.asarray(p) for p, _, _ in buf])
            )
            outs = run(stack, *tables)
            for i, (padded, n_valid, was_padded) in enumerate(buf):
                moved = outs[0][i]
                if was_padded:
                    moved = _mask_tail(moved, n_valid)
                yield (padded, moved, *(o[i] for o in outs[1:]))

        buf: list = []
        for chunk in id_chunks:
            padded, n_valid = self._pad_pow2(chunk, 1)
            if buf and (
                buf[0][0].shape[0] != padded.shape[0] or len(buf) >= fuse
            ):
                yield from flush(buf)
                buf = []
            buf.append((padded, n_valid, padded is not chunk))
        yield from flush(buf)

    def plan_stream(
        self, id_chunks, v_from: int, v_to: int, *, mesh=None, fuse: int = 1
    ):
        """Streaming sweep: yield ``(ids, moved, src, dst)`` per chunk.

        ``id_chunks`` is any iterable of id arrays (device arrays keep the
        whole sweep sync-free; NumPy chunks pay one upload each -- the
        host-feeding pattern).  Device memory is bounded by the largest
        chunk, not the id population.

        A ragged final chunk is padded into its pow2 bucket (the same
        buckets the prefilter path uses) so the jitted diff sees O(log
        chunk) distinct shapes instead of one extra compile per sweep; the
        yielded arrays are bucket-length with the pad lanes' ``moved``
        forced False on device, so counts and selections over the stream
        see no phantom moves.  Full chunks take the unpadded zero-sync path
        untouched.

        ``mesh=`` (a Mesh or a ``ShardedSweep``) runs each chunk's diff
        across the mesh's data axis instead of one device -- same yielded
        contract, bit-identical outputs, host-fed chunks (DESIGN.md
        section 11).

        ``fuse=`` > 1 groups consecutive equal-pow2-length chunks into
        blocks of up to ``fuse`` and diffs each block with ONE scanned
        dispatch (DESIGN.md section 15) -- same yielded per-chunk
        contract, bit-identical outputs, ~fuse-fold fewer dispatches.
        Single-device flat-ASURA only (mesh and hierarchical sweeps stay
        per-chunk).
        """
        sweep = self._sweep(mesh)
        if (
            int(fuse) > 1
            and sweep is None
            and not getattr(self.engine, "hierarchical", False)
        ):
            yield from self._fused_stream(
                id_chunks, v_from, v_to, int(fuse), None
            )
            return
        mult = 1 if sweep is None else sweep.n_devices
        for chunk in id_chunks:
            padded, n_valid = self._pad_pow2(chunk, mult)
            if sweep is None:
                moved, src, dst = self.diff_device(padded, v_from, v_to)
            else:
                moved, src, dst = sweep.diff_nodes_device(padded, v_from, v_to)
            if padded is not chunk:
                moved = _mask_tail(moved, n_valid)
            yield padded, moved, src, dst

    def plan_replicas_stream(
        self, id_chunks, v_from: int, v_to: int, n_replicas: int, *,
        mesh=None, fuse: int = 1,
    ):
        """Replica streaming sweep: yield ``(ids, moved, src, dst,
        src_slot)`` device tuples per chunk -- the R-way twin of
        ``plan_stream``, same fixed device memory, zero host syncs, pow2
        tail bucketing (pad rows' ``moved`` all False), optional ``mesh=``
        scale-out and optional ``fuse=`` scan-fused multi-chunk blocks."""
        sweep = self._sweep(mesh)
        if (
            int(fuse) > 1
            and sweep is None
            and not getattr(self.engine, "hierarchical", False)
        ):
            yield from self._fused_stream(
                id_chunks, v_from, v_to, int(fuse), int(n_replicas)
            )
            return
        mult = 1 if sweep is None else sweep.n_devices
        for chunk in id_chunks:
            padded, n_valid = self._pad_pow2(chunk, mult)
            if sweep is None:
                moved, src, dst, src_slot = self.diff_replicas_device(
                    padded, v_from, v_to, n_replicas
                )
            else:
                moved, src, dst, src_slot = sweep.diff_replicas_device(
                    padded, v_from, v_to, n_replicas
                )
            if padded is not chunk:
                moved = _mask_tail(moved, n_valid)
            yield padded, moved, src, dst, src_slot

    @staticmethod
    def chunked(ids: np.ndarray, chunk: int = DEFAULT_CHUNK):
        """Host-side chunking helper for ``plan_stream``."""
        for start in range(0, len(ids), chunk):
            yield ids[start : start + chunk]

    # kept as a staticmethod alias so planner call sites and tests read the
    # same way they always did; the shared implementation is module-level.
    _pad_pow2 = staticmethod(pad_pow2)

    # -- host-facing plan assembly ------------------------------------------

    def plan(
        self,
        datum_ids,
        v_from: int,
        v_to: int,
        *,
        chunk: int = DEFAULT_CHUNK,
        max_new_seg: int | None = None,
        known_src=None,
        mesh=None,
    ) -> MigrationPlan:
        """Assemble the full ``MigrationPlan`` for a tracked id set.

        ``max_new_seg`` (the largest segment number the v -> v+1 change
        assigned; add-node events know it) enables the owner prefilter:
        one placement under v+1 keeps the ids owned by a node that gained
        segments (or not converged, the sound fallback), and only those pay
        the full dual-version diff -- the fast path for the common
        scale-out event.  ``max_new_seg`` is cross-checked against the
        artifacts; a change that is not a pure addition keeps every id.

        ``known_src`` (aligned with ``datum_ids``) supplies the v owners a
        caller already maintains (``ElasticCoordinator``'s owner table), so
        the host path places each id once, not twice.

        On the numpy backend the diff runs on the vectorized host path
        (same bit-identical placements, no jit warm-up) -- the engine's
        usual backend contract.

        ``mesh=`` (a Mesh or ``ShardedSweep``) runs every chunk's dual
        diff across the mesh's data axis -- the assembled plan is
        bit-identical (DESIGN.md section 11); it forces the device path
        regardless of backend.
        """
        with span("planner.plan", self.ledger) as fields:
            ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
            sweep = self._sweep(mesh)
            host = self.engine.backend == "numpy" and sweep is None
            if known_src is not None:
                known_src = np.asarray(known_src, dtype=np.int64)
            out_ids: list[np.ndarray] = []
            out_src: list[np.ndarray] = []
            out_dst: list[np.ndarray] = []
            out_idx: list[np.ndarray] = []
            grown = self._grown_nodes(v_from, v_to, max_new_seg, fields)
            for start in range(0, len(ids), chunk):
                c = ids[start : start + chunk]
                base = np.arange(start, start + len(c), dtype=np.int64)
                if max_new_seg is not None:
                    with span("planner.prefilter"):
                        c, base = self._prefilter(c, base, v_to, grown, host, 1)
                if c.size == 0:
                    continue
                if host:
                    src = (
                        known_src[base]
                        if known_src is not None
                        else self.engine.place_nodes_at(c, v_from)
                    )
                    dst = self.engine.place_nodes_at(c, v_to)
                    moved = src != dst
                else:
                    # Pad ragged (prefiltered) chunks to the next power of two
                    # so the jitted diff sees O(log chunk) distinct shapes, not
                    # one compile per candidate count.
                    n_c = len(c)
                    cp, _ = self._pad_pow2(
                        c, 1 if sweep is None else sweep.n_devices
                    )
                    if sweep is None:
                        moved_d, src_d, dst_d = self.diff_device(cp, v_from, v_to)
                    else:
                        moved_d, src_d, dst_d = sweep.diff_nodes_device(
                            cp, v_from, v_to
                        )
                    moved = np.asarray(moved_d)[:n_c]
                    src = np.asarray(src_d)[:n_c].astype(np.int64)
                    dst = np.asarray(dst_d)[:n_c].astype(np.int64)
                out_ids.append(c[moved])
                out_src.append(src[moved])
                out_dst.append(dst[moved])
                out_idx.append(base[moved])
            cat = lambda parts, dtype: (  # noqa: E731
                np.concatenate(parts) if parts else np.zeros(0, dtype=dtype)
            )
            plan = MigrationPlan(
                v_from=v_from,
                v_to=v_to,
                ids=cat(out_ids, np.uint32),
                src=cat(out_src, np.int64),
                dst=cat(out_dst, np.int64),
                index=cat(out_idx, np.int64),
                n_scanned=len(ids),
            )
            fields.update(_plan_fields(plan))
        return plan

    def plan_replicas(
        self,
        datum_ids,
        v_from: int,
        v_to: int,
        n_replicas: int,
        *,
        chunk: int = DEFAULT_CHUNK,
        max_new_seg: int | None = None,
        known_before=None,
        mesh=None,
    ) -> MigrationPlan:
        """Assemble the per-slot REPLICA ``MigrationPlan`` for an id set.

        The R-way generalization of ``plan``: every id's full R-replica set
        is placed under both cached versions (the fused dual-table replica
        kernel on device backends; the vectorized host path on numpy) and
        the two sets are aligned per slot, so a row exists exactly for the
        replicas whose owner actually changed -- ``|after \\ before|`` rows
        per id, the paper's section-5 minimal replica mass; common nodes
        that merely changed position inside the set move nothing.

        ``max_new_seg`` enables the owner prefilter on the v+1 replica
        sets (exact, plan-preserving; see ``plan``).  ``known_before``
        (aligned (len(ids), R) v replica sets a caller already maintains,
        e.g. the coordinator's owner table) saves the host path one of the
        two placement sweeps.  ``mesh=`` scales the dual replica diff over
        the mesh's data axis, bit-identically, as in ``plan``.
        """
        hier = bool(getattr(self.engine, "hierarchical", False))
        with span("planner.plan_replicas", self.ledger) as fields:
            ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
            sweep = self._sweep(mesh)
            # Hierarchical engines always diff through the fused two-level
            # kernel path (node-plane alignment, domains validated globally
            # unique) -- the host replica sweep returns (batch, R, 2) pairs.
            host = self.engine.backend == "numpy" and sweep is None and not hier
            if known_before is not None:
                known_before = np.asarray(known_before, dtype=np.int64)
            out: dict[str, list[np.ndarray]] = {
                k: [] for k in ("ids", "src", "dst", "idx", "slot", "src_slot")
            }
            grown = self._grown_nodes(v_from, v_to, max_new_seg, fields)
            for start in range(0, len(ids), chunk):
                c = ids[start : start + chunk]
                base = np.arange(start, start + len(c), dtype=np.int64)
                if max_new_seg is not None:
                    with span("planner.prefilter"):
                        c, base = self._prefilter(
                            c, base, v_to, grown, host, n_replicas
                        )
                if c.size == 0:
                    continue
                if host:
                    from repro.core.asura import align_replica_sets

                    before = (
                        known_before[base]
                        if known_before is not None
                        else self.engine.place_replica_nodes_at(
                            c, v_from, n_replicas
                        )
                    )
                    dst = self.engine.place_replica_nodes_at(c, v_to, n_replicas)
                    moved, src, src_slot = align_replica_sets(before, dst)
                else:
                    with span("planner.diff"):
                        # pow2-bucketed ragged chunks, as in ``plan``
                        n_c = len(c)
                        cp, _ = self._pad_pow2(
                            c, 1 if sweep is None else sweep.n_devices
                        )
                        if sweep is None:
                            moved_d, src_d, dst_d, slot_d = (
                                self.diff_replicas_device(
                                    cp, v_from, v_to, n_replicas
                                )
                            )
                        else:
                            moved_d, src_d, dst_d, slot_d = (
                                sweep.diff_replicas_device(
                                    cp, v_from, v_to, n_replicas
                                )
                            )
                        moved = np.asarray(moved_d)[:n_c]
                        src = np.asarray(src_d)[:n_c].astype(np.int64)
                        dst = np.asarray(dst_d)[:n_c].astype(np.int64)
                        src_slot = np.asarray(slot_d)[:n_c]
                with span("planner.assemble"):
                    b_idx, r_idx = np.nonzero(moved)  # id-major, slot-minor
                    out["ids"].append(c[b_idx])
                    out["src"].append(src[b_idx, r_idx])
                    out["dst"].append(dst[b_idx, r_idx])
                    out["idx"].append(base[b_idx])
                    out["slot"].append(r_idx.astype(np.int32))
                    out["src_slot"].append(
                        src_slot[b_idx, r_idx].astype(np.int32)
                    )
            cat = lambda parts, dtype: (  # noqa: E731
                np.concatenate(parts) if parts else np.zeros(0, dtype=dtype)
            )
            with span("planner.assemble"):
                plan = MigrationPlan(
                    v_from=v_from,
                    v_to=v_to,
                    ids=cat(out["ids"], np.uint32),
                    src=cat(out["src"], np.int64),
                    dst=cat(out["dst"], np.int64),
                    index=cat(out["idx"], np.int64),
                    n_scanned=len(ids),
                    n_replicas=n_replicas,
                    slot=cat(out["slot"], np.int32),
                    src_slot=cat(out["src_slot"], np.int32),
                )
            fields.update(_plan_fields(plan))
        return plan

    def _prefilter(self, c, base, v_to, grown, host, n_replicas):
        """(ids, positions) of a chunk that reach the diff; counts them."""
        n = len(c)
        if grown is not None:
            keep = self._candidates(c, v_to, grown, host, n_replicas)
            c, base = c[keep], base[keep]
        self._note_prefilter(n, len(c))
        return c, base

    def _grown_nodes(self, v_from, v_to, max_new_seg, fields):
        """The owner filter's key: the nodes that own, under ``v_to``, a
        segment the change created or lengthened -- or None when the change
        also shrank or reassigned a held segment (not a pure addition, so
        the filter keeps every id), and None with no ``max_new_seg``.
        Records the filter taken in the plan's span event ``fields``.
        ``max_new_seg`` is cross-checked: a grown segment past it means the
        caller's addition claim is wrong."""
        if max_new_seg is None:
            return None
        if getattr(self.engine, "hierarchical", False):
            raise ValueError(
                "the owner prefilter is flat-table semantics; "
                "hierarchical plans scan the full id set (max_new_seg=None)"
            )
        a = self.engine.artifact_for(v_from)
        b = self.engine.artifact_for(v_to)
        n = max(a.n_segs, b.n_segs)
        len_a = np.zeros(n, dtype=np.int64)
        len_b = np.zeros(n, dtype=np.int64)
        len_a[: a.n_segs] = a.len32
        len_b[: b.n_segs] = b.len32
        held = np.nonzero(len_a > 0)[0]
        # a held segment past v_to's table reads length 0 there: shrunk
        if (len_b[held] < len_a[held]).any() or (
            b.node_of[held] != a.node_of[held]
        ).any():
            fields.update(filter="fallback", grown_nodes=0)
            return None
        grown = np.nonzero(len_b > len_a)[0]
        if grown.size and int(grown.max()) > max_new_seg:
            raise ValueError(
                f"max_new_seg={max_new_seg}, but the change grew segment "
                f"{int(grown.max())}"
            )
        nodes = np.unique(b.node_of[grown])
        fields.update(filter="owner", grown_nodes=len(nodes))
        return nodes

    def _candidates(
        self,
        chunk: np.ndarray,
        v_to: int,
        grown: np.ndarray,
        host: bool,
        n_replicas: int = 1,
    ) -> np.ndarray:
        """Owner-filter mask: an id is kept iff its ``v_to`` replica set
        holds a grown node, or a -1 (non-converged, so unknown -- kept).

        Exact for a pure addition (DESIGN.md section 8.1): both versions
        draw alike up to the first draw that lands in grown mass, and that
        draw puts a grown node in the ``v_to`` set.  Device backends copy
        back only the (n,) mask."""
        e = self.engine
        if host:
            if n_replicas == 1:
                # the bounded loop keeps -1 on non-converged lanes, which
                # ``place_nodes_at`` would tail-resolve
                from repro.core.asura import place_batch_u32

                art = e.artifact_for(v_to)
                segs = place_batch_u32(chunk, art.len32, art.top_level, e.params)
                nodes = np.where(segs < 0, -1, art.node_of[segs])[:, None]
            else:
                nodes = e.place_replica_nodes_at(chunk, v_to, n_replicas)
            return (nodes < 0).any(axis=1) | np.isin(nodes, grown).any(axis=1)
        padded, n = self._pad_pow2(chunk, 1)
        nodes = e.place_replica_nodes_device_at(padded, v_to, n_replicas)
        # -1 pads the grown list to a pow2 length (one compile per bucket);
        # a -1 lane is kept anyway
        g = np.full(1 << max(0, len(grown) - 1).bit_length(), -1, np.int32)
        g[: len(grown)] = grown
        return np.asarray(_holds_any_jit()(nodes, g))[:n]


def _holds_any_jit():
    """The owner filter's device mask: ``(nodes (n, R), grown (G,)) -> (n,)
    bool``, true where a row holds a -1 or one of ``grown``.  Its program is
    ``jit_holds_any`` in a profiler trace."""
    fn = _MASK_CACHE.get("holds")
    if fn is None:
        import jax

        @jax.jit
        def holds_any(nodes, grown):
            hit = nodes[:, :, None] == grown[None, None, :]
            return (nodes < 0).any(axis=1) | hit.any(axis=(1, 2))

        fn = _MASK_CACHE["holds"] = holds_any
    return fn
