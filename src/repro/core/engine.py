"""PlacementEngine: versioned, device-resident table artifacts per cluster.

Every placement consumer (router, elastic coordinator, data pipeline,
checkpoint store, serving driver) used to re-derive, re-pad and re-upload the
STEP-1 segment table on every call.  The engine owns a small LRU cache of
``TableArtifact`` snapshots keyed by ``Cluster.version``:

  * ``len32``    -- canonical u32 lengths (round(length * 2**32)),
  * ``node_of``  -- int32 seg->node map (-1 on holes),
  * ``top_level``-- the static generator-ladder entry level,
  * device copies, lane-padded for the kernels, including the u64
    length-cumsum as two u32 halves (the device-resident tail tables,
    DESIGN.md section 3.2),

so a STEP-1 mutation produces exactly ONE table materialization (one
host->device upload on accelerator backends) no matter how many placement
calls follow -- the ``uploads`` counter asserts this in tests.  The cache
holds the ``CACHE_VERSIONS`` most-recent versions, so a router flapping
between two live versions (rollback, A/B drain) re-materializes nothing.

STEP 2 dispatches to one of three bit-identical backends:

  * ``numpy``  -- vectorized NumPy (the CPU-host default; no device round
                  trip for table or ids),
  * ``ref``    -- jitted pure-jnp bodies compiled by XLA (the TPU default),
  * ``pallas`` -- the Pallas kernel family, including the section 5.A
                  replica-placement kernel (interpret mode off the TPU;
                  Mosaic does not lower it yet).

Host-facing methods (``place`` / ``place_nodes`` / ``place_replicas``)
return NumPy arrays with exactly one device->host transfer on accelerator
backends.  The ``*_device`` variants return device arrays with ZERO host
syncs -- placement, the non-converged tail and the seg->node gather all run
on device -- for consumers that chain into further device work.

The non-converged tail (p < 2**-53 per lane) follows the single
exact-integer spec (``resolve_tail_np`` on the host, ``resolve_tail_dev``
on device -- bit-identical; DESIGN.md section 3.2), so results are
bit-for-bit independent of the backend choice.

The engine also serves the paper's COMPARISON BASELINES as first-class
device backends (DESIGN.md section 9): ``algorithm`` selects ``"asura"``
(default), ``"ch"`` (consistent hashing, virtual-node ring), ``"wrh"``
(capacity-weighted rendezvous hashing) or ``"rs"`` (random slicing).  Each
baseline gets a ``BaselineArtifact`` -- its canonical lookup table,
materialized and uploaded once per cluster version, cached in a PER-
ALGORITHM LRU keyed on ``(algorithm, version)`` so an ASURA upload can
never evict or alias a same-version baseline artifact -- and the generic
``place_nodes`` / ``place_nodes_device`` / ``*_at`` entry points dispatch
on the algorithm (per-call override via ``algorithm=``).  Baseline device
paths are bit-identical to their NumPy oracles, like ASURA's.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any

import numpy as np

from .asura import (
    DEFAULT_PARAMS,
    AsuraParams,
    _upper_bound,
    lengths_to_u32,
    place_batch_u32,
    place_replicas_u32,
    resolve_tail_np,
)
from .consistent_hashing import build_ring, ch_place_np
from .random_slicing import RandomSlicingTable, rs_place_np
from .wrh import wrh_place_np

BACKENDS = ("auto", "numpy", "ref", "pallas")

ALGORITHMS = ("asura", "ch", "wrh", "rs")

CACHE_VERSIONS = 4  # most-recent table versions kept materialized per algorithm

DEFAULT_VIRTUAL_NODES = 100  # the paper's CH evaluation default

_BASELINE_ORACLE = {"ch": ch_place_np, "rs": rs_place_np, "wrh": wrh_place_np}


@dataclasses.dataclass(frozen=True)
class TableArtifact:
    """Immutable snapshot of one cluster version's placement table.

    ``len32`` / ``node_of`` are the host (unpadded) canonical arrays --
    ``node_of`` is int64 so per-call seg->node gathers never widen-copy the
    table; ``len32_dev`` / ``node_of_dev`` / ``cum_hi_dev`` / ``cum_lo_dev``
    are the lane-padded device copies (None until a device path needs them;
    the numpy backend never builds them unless a ``*_device`` variant is
    called).
    """

    version: int
    n_segs: int
    top_level: int
    len32: np.ndarray
    node_of: np.ndarray
    len32_dev: Any = None
    node_of_dev: Any = None
    cum_hi_dev: Any = None
    cum_lo_dev: Any = None

    @property
    def has_device_tables(self) -> bool:
        return self.len32_dev is not None


@dataclasses.dataclass(frozen=True)
class BaselineArtifact:
    """Immutable snapshot of one baseline algorithm's lookup table at one
    cluster version (DESIGN.md section 9).

    ``keys`` / ``vals`` are the host canonical arrays, with algorithm-
    specific meaning:

      * ``ch``  -- keys = sorted u32 ring hashes, vals = int32 owners,
      * ``rs``  -- keys = u32 interval starts (first 0), vals = int32 owners,
      * ``wrh`` -- keys = u32 node ids, vals = float32 capacity weights.

    ``keys_dev`` / ``vals_dev`` are the lane-padded device copies (None
    until a device path needs them, exactly like ``TableArtifact``).
    """

    algorithm: str
    version: int
    n_entries: int
    keys: np.ndarray
    vals: np.ndarray
    keys_dev: Any = None
    vals_dev: Any = None

    @property
    def has_device_tables(self) -> bool:
        return self.keys_dev is not None

    def memory_bytes(self) -> int:
        """Table-II accounting: 8 bytes per lookup entry (key + value)."""
        return 8 * self.n_entries


@dataclasses.dataclass(frozen=True)
class HierArtifact:
    """Immutable snapshot of one HIERARCHICAL cluster version (section 14).

    The device view of both levels: the domain-level segment table (node
    ids re-mapped to dense domain SLOTS so the section-5.A tile's
    distinct-node test is a distinct-domain test), the D per-domain tables
    stacked into flat ``(D * s_pad,)`` arrays (lengths zero-padded, node
    map -1-padded, u64-cumsum halves carried at each domain's total), and
    the per-domain top levels + domain ids as lane-padded vectors.
    ``tables_dev`` is the 8-tuple in the kernel's operand order.  Node ids
    are validated globally unique at build time (``node_domain`` is the
    host-side node -> domain accounting view).
    """

    version: int
    n_domains: int
    top_level: int
    max_top: int
    s_pad: int
    domain_ids: np.ndarray
    node_domain: dict
    tables_dev: tuple

    @property
    def statics(self) -> tuple:
        return (self.top_level, self.max_top, self.s_pad)

    @property
    def has_device_tables(self) -> bool:
        return True


class PlacementEngine:
    """Cached STEP-2 dispatcher bound to one mutable ``Cluster``.

    The engine is deliberately duck-typed on the cluster: anything exposing
    ``version``, ``params``, ``seg_lengths()`` and ``seg_to_node()`` works.
    A ``HierarchicalCluster`` (``is_hierarchical``) switches the engine into
    the domain-aware mode: two-level artifacts behind the same versioned
    LRU, ``place_replica_nodes[_device]`` emitting (domain, node) sets with
    pairwise-distinct domains, and ``diff_replicas_*`` diffing both levels
    (DESIGN.md section 14).  Flat segment-semantics methods raise a
    directed error in this mode.
    """

    def __init__(
        self,
        cluster,
        *,
        backend: str = "auto",
        algorithm: str = "asura",
        virtual_nodes: int = DEFAULT_VIRTUAL_NODES,
        interpret: bool | None = None,
        rows_per_block: int | None = None,
        cache_versions: int = CACHE_VERSIONS,
        ledger=None,
        metrics=None,
    ):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        if algorithm not in ALGORITHMS:
            raise ValueError(
                f"algorithm must be one of {ALGORITHMS}, got {algorithm!r}"
            )
        if cache_versions < 1:
            raise ValueError("cache_versions must be >= 1")
        self.cluster = cluster
        self.params: AsuraParams = getattr(cluster, "params", DEFAULT_PARAMS)
        self.hierarchical = bool(getattr(cluster, "is_hierarchical", False))
        if self.hierarchical and algorithm != "asura":
            raise ValueError(
                "hierarchical placement is ASURA-only (two-level segment "
                f"tables); got algorithm={algorithm!r}"
            )
        self.algorithm = algorithm
        self._virtual_nodes = int(virtual_nodes)
        self._backend = backend
        self._interpret = interpret
        self._rows_per_block = rows_per_block
        self._cache_versions = cache_versions
        # algorithm -> (version -> artifact, most-recently-used last).  One
        # LRU per algorithm: placements under one algorithm can never evict
        # (or alias) another algorithm's artifact of the same version.
        self._artifacts: dict[str, OrderedDict[int, Any]] = {}
        # shadow interval table mirroring cluster membership for "rs" --
        # random slicing is HISTORY-dependent (incremental re-slicing), so
        # the engine carries the table forward version to version instead of
        # re-deriving it from a membership snapshot.
        self._rs_shadow: RandomSlicingTable | None = None
        self._default_sweep = None  # lazily-built all-device ShardedSweep
        from repro.obs import TraceLedger

        # host-plane telemetry: artifact uploads / LRU hits / evictions land
        # here as counters + structured events (instance-scoped unless a
        # shared ledger is injected -- the exact upload tripwire counts in
        # the tests must never alias across engines).  ``metrics`` is the
        # optional device-plane registry consumers (planner, movers) share.
        self.ledger = ledger if ledger is not None else TraceLedger()
        self.metrics = metrics

    @property
    def uploads(self) -> int:
        """Table materializations (one per (algorithm, version)) -- a
        ledger counter behind the original attribute name."""
        return self.ledger.counter("engine.uploads")

    # -- artifact lifecycle --------------------------------------------------

    @property
    def backend(self) -> str:
        """The STEP-2 backend in use.

        ``"auto"`` resolves once, lazily (only placement imports jax): on a
        TPU to ``"ref"``, the XLA-compiled jnp path -- Mosaic does not yet
        lower the Pallas kernels (ROADMAP Speed 1.3), and an explicit
        ``backend="pallas"`` there fails to compile rather than degrade --
        and to the host ``"numpy"`` path on every other platform.  The
        resolution is recorded as an ``engine.backend`` ledger event."""
        if self._backend == "auto":
            import jax

            platform = jax.default_backend()
            self._backend = "ref" if platform == "tpu" else "numpy"
            self.ledger.event(
                "engine.backend", self._backend, requested="auto",
                platform=platform,
            )
        return self._backend

    def _build_device_tables(self, art: TableArtifact) -> TableArtifact:
        """Fill the lane-padded device copies (one host->device upload)."""
        import jax.numpy as jnp

        from repro.kernels.ops import _lane_pad_np, node_table_prep, tail_prep

        len32_pad = _lane_pad_np(art.len32, np.uint32(0))
        cum_hi, cum_lo = tail_prep(len32_pad)
        return dataclasses.replace(
            art,
            len32_dev=jnp.asarray(len32_pad),
            node_of_dev=node_table_prep(art.node_of),
            cum_hi_dev=cum_hi,
            cum_lo_dev=cum_lo,
        )

    def _resolve_algorithm(self, algorithm: str | None) -> str:
        alg = self.algorithm if algorithm is None else algorithm
        if alg not in ALGORITHMS:
            raise ValueError(f"algorithm must be one of {ALGORITHMS}, got {alg!r}")
        return alg

    def _cache(self, algorithm: str) -> OrderedDict[int, Any]:
        return self._artifacts.setdefault(algorithm, OrderedDict())

    def _store(self, algorithm: str, art) -> None:
        cache = self._cache(algorithm)
        cache[art.version] = art
        while len(cache) > self._cache_versions:
            evicted_version, _ = cache.popitem(last=False)
            self.ledger.incr("engine.lru_evictions")
            self.ledger.event(
                "engine.lru_evict", algorithm, version=evicted_version
            )

    def artifact(self, algorithm: str | None = None):
        """The current version's lookup table under ``algorithm`` (default:
        the engine's own), rebuilding (and re-uploading) only when
        ``(algorithm, cluster.version)`` is not among the cached artifacts."""
        alg = self._resolve_algorithm(algorithm)
        version = self.cluster.version
        cache = self._cache(alg)
        art = cache.get(version)
        if art is not None:
            cache.move_to_end(version)
            self.ledger.incr("engine.lru_hits")
            return art
        with self.ledger.span("engine.build_artifact", algorithm=alg,
                              version=version):
            if alg == "asura":
                art = self._build_asura_artifact(version)
            else:
                art = self._build_baseline_artifact(alg, version)
        self._store(alg, art)
        self.ledger.incr("engine.uploads")
        self.ledger.event(
            "engine.upload", alg, version=version,
            n_segs=getattr(art, "n_segs", None)
        )
        return art

    def _build_asura_artifact(self, version: int) -> TableArtifact:
        lengths = np.asarray(self.cluster.seg_lengths(), dtype=np.float64)
        len32 = lengths_to_u32(lengths)
        node_of = np.asarray(self.cluster.seg_to_node(), dtype=np.int64)
        top_level = self.params.level_for(_upper_bound(lengths))
        art = TableArtifact(
            version=version,
            n_segs=len(len32),
            top_level=top_level,
            len32=len32,
            node_of=node_of,
        )
        if self.backend != "numpy":
            art = self._build_device_tables(art)
        return art

    def _node_weights(self) -> dict[int, float]:
        nodes = getattr(self.cluster, "nodes", None)
        if nodes is None:
            raise TypeError(
                "baseline algorithms need a cluster exposing `.nodes` "
                "(node_id -> NodeInfo); this cluster is table-only"
            )
        return {int(nid): float(info.capacity) for nid, info in nodes.items()}

    def _build_baseline_artifact(self, alg: str, version: int) -> BaselineArtifact:
        weights = self._node_weights()
        node_ids = sorted(weights)
        if alg == "ch":
            # the paper's CH setup: V virtual nodes per node, unweighted.
            keys, vals = build_ring(node_ids, self._virtual_nodes)
            vals = vals.astype(np.int32)
        elif alg == "wrh":
            keys = np.asarray(node_ids, dtype=np.uint32)
            vals = np.asarray([weights[n] for n in node_ids], dtype=np.float32)
        else:  # rs
            if self._rs_shadow is None:
                self._rs_shadow = RandomSlicingTable()
            self._rs_shadow.rebalance(weights)
            keys, vals = self._rs_shadow.starts_owners()
        art = BaselineArtifact(
            algorithm=alg,
            version=version,
            n_entries=int(keys.shape[0]),
            keys=keys,
            vals=vals,
        )
        if self.backend != "numpy":
            art = self._build_baseline_device_tables(art)
        return art

    def _build_baseline_device_tables(self, art: BaselineArtifact) -> BaselineArtifact:
        """Fill the lane-padded device copies (one host->device upload)."""
        from repro.kernels.baselines import (
            ch_table_prep,
            rs_table_prep,
            wrh_table_prep,
        )

        prep = {"ch": ch_table_prep, "rs": rs_table_prep, "wrh": wrh_table_prep}
        keys_dev, vals_dev = prep[art.algorithm](art.keys, art.vals)
        return dataclasses.replace(art, keys_dev=keys_dev, vals_dev=vals_dev)

    def _with_device_tables(self, alg: str, art):
        """Ensure ``art`` carries device tables (same materialization --
        the ``uploads`` counter does not tick again)."""
        if not art.has_device_tables:
            if alg == "asura":
                art = self._build_device_tables(art)
            else:
                art = self._build_baseline_device_tables(art)
            self._cache(alg)[art.version] = art
        return art

    def _device_artifact(self, algorithm: str | None = None):
        """Like ``artifact()`` but guaranteed to carry device tables.

        On the numpy backend the device tables are built lazily on the
        first ``*_device`` call (part of the same version's one
        materialization -- the ``uploads`` counter does not tick again).
        """
        alg = self._resolve_algorithm(algorithm)
        return self._with_device_tables(alg, self.artifact(alg))

    def artifact_for(self, version: int, algorithm: str | None = None):
        """The table artifact of a SPECIFIC version (migration dual-serving,
        baseline movement accounting).

        The current version is built on demand; any other version must
        still be in the LRU (a consumer that placed at that version keeps
        it cached -- the flap/rollback pattern).  An evicted version cannot
        be rebuilt (the cluster has moved on), so this raises ``KeyError``
        rather than silently re-deriving the wrong table.
        """
        alg = self._resolve_algorithm(algorithm)
        if version == self.cluster.version:
            return self.artifact(alg)
        cache = self._cache(alg)
        art = cache.get(version)
        if art is None:
            raise KeyError(
                f"{alg} table version {version} not cached (LRU holds "
                f"{list(cache)}); place at that version before "
                "mutating, or raise cache_versions"
            )
        cache.move_to_end(version)
        return art

    def _device_artifact_for(self, version: int, algorithm: str | None = None):
        """``artifact_for`` with device tables (same materialization)."""
        alg = self._resolve_algorithm(algorithm)
        return self._with_device_tables(alg, self.artifact_for(version, alg))

    def invalidate(self) -> None:
        """Drop every cached artifact, all algorithms (next placement
        rebuilds)."""
        self._artifacts.clear()

    # -- hierarchical artifacts (DESIGN.md section 14) ------------------------

    def _require_hier(self, method: str) -> None:
        if not self.hierarchical:
            raise ValueError(
                f"{method} needs a HierarchicalCluster-bound engine; this "
                "engine's cluster is flat"
            )

    def _build_hier_artifact(self, version: int) -> HierArtifact:
        import jax.numpy as jnp

        from repro.kernels.asura_place import LANE
        from repro.kernels.ops import _lane_pad_np

        from .asura import tail_cumsum_halves

        h = self.cluster
        top = h._top
        lengths = np.asarray(top.seg_lengths(), dtype=np.float64)
        top_len32 = lengths_to_u32(lengths)
        top_level = self.params.level_for(_upper_bound(lengths))
        node_domain = h.node_domains()  # validates global node-id uniqueness
        domain_ids = np.asarray(sorted(int(d) for d in top.nodes), dtype=np.int64)
        slot_of = {int(d): i for i, d in enumerate(domain_ids)}
        top_slot = np.asarray(
            [slot_of[int(d)] if d >= 0 else -1 for d in top.seg_to_node()],
            dtype=np.int32,
        )
        dom_lens, dom_nodes, dom_tops = [], [], []
        for d in domain_ids:
            dom = h.domains[int(d)]
            dl = np.asarray(dom.seg_lengths(), dtype=np.float64)
            dom_tops.append(self.params.level_for(_upper_bound(dl)))
            dom_lens.append(lengths_to_u32(dl))
            dom_nodes.append(np.asarray(dom.seg_to_node(), dtype=np.int32))
        s_pad = -(-max(len(row) for row in dom_lens) // LANE) * LANE
        D = len(domain_ids)
        len_flat = np.zeros(D * s_pad, dtype=np.uint32)
        node_flat = np.full(D * s_pad, -1, dtype=np.int32)
        cum_hi = np.zeros(D * s_pad, dtype=np.uint32)
        cum_lo = np.zeros(D * s_pad, dtype=np.uint32)
        for i, (row, nodes) in enumerate(zip(dom_lens, dom_nodes)):
            base = i * s_pad
            len_flat[base : base + len(row)] = row
            node_flat[base : base + len(nodes)] = nodes
            hi, lo = tail_cumsum_halves(
                np.concatenate([row, np.zeros(s_pad - len(row), dtype=np.uint32)])
            )
            cum_hi[base : base + s_pad] = hi
            cum_lo[base : base + s_pad] = lo
        tables_dev = (
            jnp.asarray(_lane_pad_np(top_len32, np.uint32(0))),
            jnp.asarray(_lane_pad_np(top_slot, np.int32(-1))),
            jnp.asarray(len_flat),
            jnp.asarray(node_flat),
            jnp.asarray(cum_hi),
            jnp.asarray(cum_lo),
            jnp.asarray(_lane_pad_np(np.asarray(dom_tops, dtype=np.int32), np.int32(0))),
            jnp.asarray(_lane_pad_np(domain_ids.astype(np.int32), np.int32(0))),
        )
        return HierArtifact(
            version=version,
            n_domains=D,
            top_level=top_level,
            max_top=int(max(dom_tops)),
            s_pad=s_pad,
            domain_ids=domain_ids,
            node_domain=node_domain,
            tables_dev=tables_dev,
        )

    def hier_artifact(self) -> HierArtifact:
        """The current version's two-level artifact (same versioned LRU,
        upload ledger and eviction events as the flat artifacts)."""
        self._require_hier("hier_artifact")
        version = self.cluster.version
        cache = self._cache("hier")
        art = cache.get(version)
        if art is not None:
            cache.move_to_end(version)
            self.ledger.incr("engine.lru_hits")
            return art
        with self.ledger.span(
            "engine.build_artifact", algorithm="hier", version=version
        ):
            art = self._build_hier_artifact(version)
        self._store("hier", art)
        self.ledger.incr("engine.uploads")
        self.ledger.event(
            "engine.upload", "hier", version=version, n_segs=art.n_domains
        )
        return art

    def hier_artifact_for(self, version: int) -> HierArtifact:
        """A SPECIFIC version's two-level artifact (must be in the LRU --
        the same pin-before-mutating contract as ``artifact_for``)."""
        self._require_hier("hier_artifact_for")
        if version == self.cluster.version:
            return self.hier_artifact()
        cache = self._cache("hier")
        art = cache.get(version)
        if art is None:
            raise KeyError(
                f"hier table version {version} not cached (LRU holds "
                f"{list(cache)}); place at that version before mutating, "
                "or raise cache_versions"
            )
        cache.move_to_end(version)
        return art

    def _hier_place_kwargs(self, art: HierArtifact, n_replicas: int) -> dict:
        return dict(
            top_level=art.top_level,
            max_top=art.max_top,
            s_pad=art.s_pad,
            n_replicas=n_replicas,
            **self._device_kwargs(),
        )

    def place_replica_pairs_device(
        self, datum_ids, n_replicas: int, version: int | None = None
    ):
        """Fused two-level replication -> (2, R, batch) int32 DEVICE array
        (plane 0 domains, plane 1 nodes), zero host syncs; -1 marks
        level-1 non-convergence (too few distinct domains).  ``version``
        pins a cached table version (default: current)."""
        from repro.kernels.ops import hier_place_replicas_on_tables_device

        self._require_hier("place_replica_pairs_device")
        art = (
            self.hier_artifact()
            if version is None
            else self.hier_artifact_for(version)
        )
        return hier_place_replicas_on_tables_device(
            datum_ids, art.tables_dev, **self._hier_place_kwargs(art, n_replicas)
        )

    def place_replica_pairs(
        self, datum_ids, n_replicas: int, version: int | None = None
    ) -> np.ndarray:
        """Host-facing fused two-level replication -> (batch, R, 2) int64
        ``(domain_id, node_id)`` pairs with pairwise-DISTINCT domains,
        primary first -- bit-identical to the ``HierarchicalCluster``
        oracle.  Raises if the distinct-domain draw did not converge."""
        from repro.kernels.ops import hier_place_replicas_on_tables

        self._require_hier("place_replica_pairs")
        art = (
            self.hier_artifact()
            if version is None
            else self.hier_artifact_for(version)
        )
        return hier_place_replicas_on_tables(
            datum_ids, art.tables_dev, **self._hier_place_kwargs(art, n_replicas)
        )

    def diff_replica_domains_device(
        self, datum_ids, v_from: int, v_to: int, n_replicas: int
    ):
        """Two-level replica diff with the domain planes attached ->
        ``(moved, src, dst, src_slot, src_dom, dst_dom)`` device arrays.

        Both LEVELS of both VERSIONS are placed by the fused kernel; the
        alignment runs on the node plane (node ids are globally unique)
        and the domains ride along -- the intra-domain movement proofs and
        the durability simulator's bytes accounting read them directly.
        """
        from repro.kernels.ops import hier_diff_replicas_on_tables_device

        self._require_hier("diff_replica_domains_device")
        art_a = self.hier_artifact_for(v_from)
        art_b = self.hier_artifact_for(v_to)
        return hier_diff_replicas_on_tables_device(
            datum_ids,
            art_a.tables_dev,
            art_b.tables_dev,
            statics_a=art_a.statics,
            statics_b=art_b.statics,
            n_replicas=n_replicas,
            **self._device_kwargs(),
        )

    # -- STEP 2 dispatch -----------------------------------------------------

    def _kernel_kwargs(self) -> dict:
        kw: dict = {
            "params": self.params,
            "use_pallas": self.backend == "pallas",
            "interpret": self._interpret,
        }
        if self._rows_per_block is not None:
            kw["rows_per_block"] = self._rows_per_block
        return kw

    def _baseline_kwargs(self) -> dict:
        kw = self._kernel_kwargs()
        del kw["params"]  # baseline lookups have no generator ladder
        return kw

    def _require_asura(self, method: str) -> None:
        if self.algorithm != "asura":
            raise ValueError(
                f"{method} is segment-table semantics, ASURA-only; this "
                f"engine's algorithm is {self.algorithm!r} -- use "
                "place_nodes/place_nodes_device (they dispatch per "
                "algorithm)"
            )
        if self.hierarchical:
            raise ValueError(
                f"{method} is flat-table semantics; this engine is bound to "
                "a HierarchicalCluster -- use place_nodes / "
                "place_replica_nodes / place_replica_pairs[_device] / "
                "diff_replica{s,_domains}_device (the two-level paths)"
            )

    def place(self, datum_ids) -> np.ndarray:
        """Batch placement -> int64 segment numbers (tail-resolved, total)."""
        self._require_asura("place")
        art = self.artifact("asura")
        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        if self.backend == "numpy":
            segs = place_batch_u32(ids, art.len32, art.top_level, self.params)
            return resolve_tail_np(ids, segs, art.len32, art.top_level)
        return np.asarray(self.place_device(ids)).astype(np.int64)

    def place_nodes(self, datum_ids, algorithm: str | None = None) -> np.ndarray:
        """Batch placement -> int64 node ids (dispatches on ``algorithm``)."""
        alg = self._resolve_algorithm(algorithm)
        if self.hierarchical:
            return self.place_replica_nodes(datum_ids, 1)[:, 0, 1]
        art = self.artifact(alg)
        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        if alg != "asura":
            if self.backend == "numpy":
                return _BASELINE_ORACLE[alg](ids, art.keys, art.vals)
            return np.asarray(
                self.place_nodes_device(ids, algorithm=alg)
            ).astype(np.int64)
        if self.backend == "numpy":
            segs = place_batch_u32(ids, art.len32, art.top_level, self.params)
            segs = resolve_tail_np(ids, segs, art.len32, art.top_level)
            return art.node_of[segs]
        return np.asarray(
            self.place_nodes_device(ids, algorithm="asura")
        ).astype(np.int64)

    def place_replicas(self, datum_ids, n_replicas: int) -> np.ndarray:
        """(batch, R) segment numbers on R distinct nodes, primary first."""
        self._require_asura("place_replicas")
        art = self.artifact()
        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        if self.backend == "numpy":
            return place_replicas_u32(
                ids, art.len32, art.node_of, n_replicas, art.top_level, self.params
            )
        from repro.kernels.ops import place_replicas_on_table

        art = self._device_artifact()
        return place_replicas_on_table(
            ids,
            art.len32_dev,
            art.node_of_dev,
            n_replicas,
            top_level=art.top_level,
            **self._kernel_kwargs(),
        )

    def place_replica_nodes(
        self, datum_ids, n_replicas: int, algorithm: str | None = None
    ) -> np.ndarray:
        """(batch, R) node ids, primary first (dispatches on ``algorithm``:
        ASURA's section-5.A distinct-node draw, or the baselines' salted
        rejection fan-out -- DESIGN.md section 12).

        HIERARCHICAL engines return (batch, R, 2) ``(domain, node)`` pairs
        instead (section-5.A applied to the DOMAIN cluster, then the salted
        per-domain node draw): the replica domains are pairwise distinct,
        so a whole-domain failure holds at most one replica of any datum.
        """
        alg = self._resolve_algorithm(algorithm)
        if self.hierarchical:
            return self.place_replica_pairs(datum_ids, n_replicas)
        if alg != "asura":
            from repro.kernels.baselines import baseline_place_replicas_np

            art = self.artifact(alg)
            ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
            if self.backend == "numpy":
                out = baseline_place_replicas_np(
                    alg, ids, art.keys, art.vals, n_replicas
                )
            else:
                out = np.asarray(
                    self.place_replica_nodes_device(ids, n_replicas, algorithm=alg)
                ).astype(np.int64)
            if n_replicas > 1 and (out < 0).any():
                raise ValueError(
                    f"{alg} replica fan-out found no {n_replicas} distinct "
                    "nodes within the try budget (R exceeds live nodes?)"
                )
            return out
        art = self.artifact("asura")
        return art.node_of[self.place_replicas(datum_ids, n_replicas)]

    def remove_numbers_batch(
        self, datum_ids, n_replicas: int, version: int | None = None
    ) -> np.ndarray:
        """Vectorized section 2.D REMOVE NUMBERS -> (batch, R) sorted segs.

        A datum's remove numbers are the floors of its replica-selecting
        ASURA numbers = the segment numbers of its R replicas, so the batch
        is one replica placement against the cached artifact plus a row
        sort -- no per-id scalar trace, and on accelerator backends the
        sweep runs on device.  Row-identical to the scalar
        ``core.asura.remove_numbers`` (tested)."""
        segs = self.place_replicas_at(
            datum_ids, self.cluster.version if version is None else version,
            n_replicas,
        )
        return np.sort(np.asarray(segs, dtype=np.int64), axis=1)

    # -- version-pinned placement (migration dual-version serving) -----------

    def place_at(self, datum_ids, version: int) -> np.ndarray:
        """Batch placement under a SPECIFIC cached table version -> int64
        segments (tail-resolved, total).  Same results ``place`` gave while
        that version was current -- the dual-version read rule's building
        block (DESIGN.md section 8)."""
        self._require_asura("place_at")
        art = self.artifact_for(version)
        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        if self.backend == "numpy":
            segs = place_batch_u32(ids, art.len32, art.top_level, self.params)
            return resolve_tail_np(ids, segs, art.len32, art.top_level)
        return np.asarray(self.place_device_at(ids, version)).astype(np.int64)

    def place_nodes_at(
        self, datum_ids, version: int, algorithm: str | None = None
    ) -> np.ndarray:
        """Batch placement under a specific cached version -> int64 node ids
        (dispatches on ``algorithm`` -- the baselines' movement-accounting
        building block: diff owners across two cached versions)."""
        alg = self._resolve_algorithm(algorithm)
        if self.hierarchical:
            return self.place_replica_pairs(datum_ids, 1, version)[:, 0, 1]
        art = self.artifact_for(version, alg)
        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        if alg != "asura":
            if self.backend == "numpy":
                return _BASELINE_ORACLE[alg](ids, art.keys, art.vals)
            return np.asarray(
                self.place_nodes_device_at(ids, version, algorithm=alg)
            ).astype(np.int64)
        if self.backend == "numpy":
            segs = place_batch_u32(ids, art.len32, art.top_level, self.params)
            segs = resolve_tail_np(ids, segs, art.len32, art.top_level)
            return art.node_of[segs]
        return np.asarray(
            self.place_nodes_device_at(ids, version, algorithm="asura")
        ).astype(np.int64)

    def place_replicas_at(self, datum_ids, version: int, n_replicas: int) -> np.ndarray:
        """(batch, R) segment numbers under a SPECIFIC cached version --
        the replica twin of ``place_at`` (dual-version replica serving and
        the vectorized REMOVE-NUMBER sweep build on it)."""
        self._require_asura("place_replicas_at")
        art = self.artifact_for(version)
        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        if self.backend == "numpy":
            return place_replicas_u32(
                ids, art.len32, art.node_of, n_replicas, art.top_level, self.params
            )
        from repro.kernels.ops import place_replicas_on_table

        art = self._device_artifact_for(version)
        return place_replicas_on_table(
            ids,
            art.len32_dev,
            art.node_of_dev,
            n_replicas,
            top_level=art.top_level,
            **self._kernel_kwargs(),
        )

    def place_replica_nodes_at(
        self, datum_ids, version: int, n_replicas: int
    ) -> np.ndarray:
        """(batch, R) node ids under a specific cached version, primary
        first -- the migration window's replica read rule places the v+1
        sets through this (DESIGN.md section 10).  Hierarchical engines
        return (batch, R, 2) pairs, as in ``place_replica_nodes``."""
        if self.hierarchical:
            return self.place_replica_pairs(datum_ids, n_replicas, version)
        self._require_asura("place_replica_nodes_at")
        art = self.artifact_for(version)
        return art.node_of[self.place_replicas_at(datum_ids, version, n_replicas)]

    # -- device-resident variants (zero host syncs) --------------------------

    def place_device(self, datum_ids):
        """Batch placement -> (batch,) int32 DEVICE array, total, sync-free.

        Pass device-resident ids to keep the whole chain on device; NumPy
        ids are uploaded once.  On the numpy backend this routes through
        the jnp reference kernels (the device tables are built lazily).
        """
        from repro.kernels.ops import place_on_table_device

        self._require_asura("place_device")
        art = self._device_artifact("asura")
        return place_on_table_device(
            datum_ids,
            art.len32_dev,
            art.cum_hi_dev,
            art.cum_lo_dev,
            art.node_of_dev,  # cached: avoids a per-call dummy node table
            top_level=art.top_level,
            **self._device_kwargs(),
        )

    def place_nodes_device(self, datum_ids, algorithm: str | None = None):
        """Batch placement -> (batch,) int32 node ids on device, zero host
        syncs (dispatches on ``algorithm``: ASURA's fused seg->node gather
        with the on-device tail, or a baseline's lookup kernel)."""
        from repro.kernels.ops import place_nodes_on_table_device

        alg = self._resolve_algorithm(algorithm)
        if self.hierarchical:
            return self.place_replica_pairs_device(datum_ids, 1)[1, 0, :]
        art = self._device_artifact(alg)
        if alg != "asura":
            from repro.kernels.baselines import baseline_place_on_table_device

            return baseline_place_on_table_device(
                alg,
                datum_ids,
                art.keys_dev,
                art.vals_dev,
                **self._baseline_device_kwargs(),
            )
        return place_nodes_on_table_device(
            datum_ids,
            art.len32_dev,
            art.cum_hi_dev,
            art.cum_lo_dev,
            art.node_of_dev,
            top_level=art.top_level,
            **self._device_kwargs(),
        )

    def place_replica_nodes_device(
        self, datum_ids, n_replicas: int, algorithm: str | None = None
    ):
        """(batch, R) int32 node ids on device, primary first, zero host
        syncs (dispatches on ``algorithm``).  Non-converged entries stay -1
        (checking would force a sync); the host variant raises instead.
        Hierarchical engines return the (2, R, batch) pair planes of
        ``place_replica_pairs_device``."""
        from repro.kernels.ops import place_replicas_on_table_device

        alg = self._resolve_algorithm(algorithm)
        if self.hierarchical:
            return self.place_replica_pairs_device(datum_ids, n_replicas)
        if alg != "asura":
            from repro.kernels.baselines import (
                baseline_place_replicas_on_table_device,
            )

            art = self._device_artifact(alg)
            return baseline_place_replicas_on_table_device(
                alg,
                datum_ids,
                art.keys_dev,
                art.vals_dev,
                n_replicas=n_replicas,
                **self._baseline_device_kwargs(),
            )
        art = self._device_artifact("asura")
        return place_replicas_on_table_device(
            datum_ids,
            art.len32_dev,
            art.node_of_dev,
            n_replicas,
            top_level=art.top_level,
            emit_nodes=True,
            **self._device_kwargs(),
        )

    def place_device_at(self, datum_ids, version: int):
        """``place_device`` under a specific cached version (zero syncs)."""
        from repro.kernels.ops import place_on_table_device

        self._require_asura("place_device_at")
        art = self._device_artifact_for(version, "asura")
        return place_on_table_device(
            datum_ids,
            art.len32_dev,
            art.cum_hi_dev,
            art.cum_lo_dev,
            art.node_of_dev,
            top_level=art.top_level,
            **self._device_kwargs(),
        )

    def place_nodes_device_at(
        self, datum_ids, version: int, algorithm: str | None = None
    ):
        """``place_nodes_device`` under a specific cached version."""
        from repro.kernels.ops import place_nodes_on_table_device

        alg = self._resolve_algorithm(algorithm)
        if self.hierarchical:
            return self.place_replica_pairs_device(datum_ids, 1, version)[1, 0, :]
        art = self._device_artifact_for(version, alg)
        if alg != "asura":
            from repro.kernels.baselines import baseline_place_on_table_device

            return baseline_place_on_table_device(
                alg,
                datum_ids,
                art.keys_dev,
                art.vals_dev,
                **self._baseline_device_kwargs(),
            )
        return place_nodes_on_table_device(
            datum_ids,
            art.len32_dev,
            art.cum_hi_dev,
            art.cum_lo_dev,
            art.node_of_dev,
            top_level=art.top_level,
            **self._device_kwargs(),
        )

    def place_replica_nodes_device_at(
        self, datum_ids, version: int, n_replicas: int
    ):
        """``place_replica_nodes_device`` under a specific cached version
        (zero host syncs; -1 marks non-converged entries)."""
        from repro.kernels.ops import place_replicas_on_table_device

        if self.hierarchical:
            return self.place_replica_pairs_device(datum_ids, n_replicas, version)
        self._require_asura("place_replica_nodes_device_at")
        art = self._device_artifact_for(version, "asura")
        return place_replicas_on_table_device(
            datum_ids,
            art.len32_dev,
            art.node_of_dev,
            n_replicas,
            top_level=art.top_level,
            emit_nodes=True,
            **self._device_kwargs(),
        )

    # -- migration planner primitives ----------------------------------------

    def diff_nodes_device(self, datum_ids, v_from: int, v_to: int):
        """Two-version placement diff -> (moved, src, dst) DEVICE arrays.

        Places every id under the ``v_from`` and ``v_to`` table artifacts
        (both must be in the LRU -- they are, during a migration window) in
        one device pass: ``src``/``dst`` are int32 node ids under the two
        versions and ``moved = src != dst``.  Zero host syncs -- the
        streaming planner chains chunks of this in fixed device memory
        (DESIGN.md section 8).
        """
        from repro.kernels.ops import diff_nodes_on_tables_device

        self._require_asura("diff_nodes_device")
        art_a = self._device_artifact_for(v_from, "asura")
        art_b = self._device_artifact_for(v_to, "asura")
        return diff_nodes_on_tables_device(
            datum_ids,
            art_a.len32_dev,
            art_a.cum_hi_dev,
            art_a.cum_lo_dev,
            art_a.node_of_dev,
            art_b.len32_dev,
            art_b.cum_hi_dev,
            art_b.cum_lo_dev,
            art_b.node_of_dev,
            top_a=art_a.top_level,
            top_b=art_b.top_level,
            **self._device_kwargs(),
        )

    def diff_replicas_device(
        self, datum_ids, v_from: int, v_to: int, n_replicas: int
    ):
        """Two-version REPLICA-SET diff -> ``(moved, src, dst, src_slot)``
        DEVICE arrays, each (batch, R), zero host syncs.

        Places every id's full R-replica set under the ``v_from`` and
        ``v_to`` table artifacts (both must be in the LRU) in one device
        pass -- the fused dual-table replica kernel -- and aligns the two
        sets per slot: ``moved[b, r]`` iff slot r's owner actually changed
        (``dst[b, r]`` not in the v set: the section-5 minimal replica
        mass), ``src`` the vacated v-side node for moved slots (the common
        owner otherwise), ``src_slot`` its v-set position (rollback
        re-indexing).  DESIGN.md section 10.

        Hierarchical engines diff the NODE planes of the fused two-level
        placement under both versions (same 4-tuple contract, node ids are
        globally unique); ``diff_replica_domains_device`` adds the domain
        planes.
        """
        from repro.kernels.ops import diff_replicas_on_tables_device

        if self.hierarchical:
            return self.diff_replica_domains_device(
                datum_ids, v_from, v_to, n_replicas
            )[:4]
        self._require_asura("diff_replicas_device")
        art_a = self._device_artifact_for(v_from, "asura")
        art_b = self._device_artifact_for(v_to, "asura")
        return diff_replicas_on_tables_device(
            datum_ids,
            art_a.len32_dev,
            art_a.node_of_dev,
            art_b.len32_dev,
            art_b.node_of_dev,
            top_a=art_a.top_level,
            top_b=art_b.top_level,
            n_replicas=n_replicas,
            **self._device_kwargs(),
        )

    def diff_replicas_at(
        self, datum_ids, v_from: int, v_to: int, n_replicas: int
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Host-facing ``diff_replicas_device``: the same per-slot
        ``(moved, src, dst, src_slot)`` as NumPy arrays (int64 nodes).

        On the numpy backend both replica sweeps run on the vectorized host
        path and the alignment uses the single host spec
        (``core.asura.align_replica_sets``) -- bit-identical to the device
        twin; on accelerator backends this is the device path plus one
        final transfer.
        """
        from .asura import align_replica_sets

        ids = np.atleast_1d(np.asarray(datum_ids, dtype=np.uint32))
        if self.hierarchical:
            # Two-level diffs always run the fused kernels (jnp reference
            # twins on the numpy backend) -- one code path, both backends.
            moved, src, dst, src_slot = self.diff_replicas_device(
                ids, v_from, v_to, n_replicas
            )
            return (
                np.asarray(moved),
                np.asarray(src).astype(np.int64),
                np.asarray(dst).astype(np.int64),
                np.asarray(src_slot),
            )
        if self.backend == "numpy":
            before = self.place_replica_nodes_at(ids, v_from, n_replicas)
            after = self.place_replica_nodes_at(ids, v_to, n_replicas)
            moved, src, src_slot = align_replica_sets(before, after)
            return moved, src, after, src_slot
        moved, src, dst, src_slot = self.diff_replicas_device(
            ids, v_from, v_to, n_replicas
        )
        return (
            np.asarray(moved),
            np.asarray(src).astype(np.int64),
            np.asarray(dst).astype(np.int64),
            np.asarray(src_slot),
        )

    def addition_numbers_device(
        self, datum_ids, version: int | None = None, n_replicas: int = 1
    ):
        """Device-resident section 2.D ADDITION NUMBERs -> int32 device array.

        Computed against the (cached) ``version`` table (default:
        current).  -1 means "unknown, treat as candidate" -- the
        exact-fallback lanes the NumPy batch resolves via the scalar
        oracle would force a host sync here (see ``addition_numbers_ref``)."""
        from repro.kernels.ops import addition_numbers_on_table_device

        self._require_asura("addition_numbers_device")
        if version is None:
            version = self.cluster.version
        art = self._device_artifact_for(version, "asura")
        return addition_numbers_on_table_device(
            datum_ids,
            art.len32_dev,
            art.node_of_dev,
            top_level=art.top_level,
            n_replicas=n_replicas,
            params=self.params,
        )

    def sharded(self, mesh=None):
        """A ``ShardedSweep`` running this engine's bulk sweeps across a
        device mesh (DESIGN.md section 11): id streams partitioned over the
        data axis, table artifacts replicated, histograms / movement
        matrices / moved counts reduced with one ``psum`` -- bit-identical
        to the single-device ``*_device`` methods.

        ``mesh=None`` spans all visible devices; sweeps on the default mesh
        are cached so repeat calls share the compiled shard_map callables.
        """
        from repro.launch.placement_mesh import ShardedSweep

        if mesh is not None:
            return ShardedSweep(self, mesh)
        if self._default_sweep is None:
            self._default_sweep = ShardedSweep(self)
        return self._default_sweep

    def _device_kwargs(self) -> dict:
        kw = self._kernel_kwargs()
        # numpy backend device calls run on the jnp reference kernels.
        kw["use_pallas"] = self.backend == "pallas"
        return kw

    def _baseline_device_kwargs(self) -> dict:
        kw = self._baseline_kwargs()
        kw["use_pallas"] = self.backend == "pallas"
        return kw
