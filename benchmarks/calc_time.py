"""Paper Fig. 5: distribution-stage calculation time vs node count.

ASURA O(1), Consistent Hashing O(log NV) (VN in {1, 100, 10000}), Straw
Buckets O(N).  The paper times 1e6 scalar calls on a Core2Quad; we report
the scalar per-call latency (paper-comparable) and the vectorized per-id
throughput (the TPU-relevant metric), at reduced loop counts sized for this
container.  Also reproduces the huge-N scalability check (section IV.B:
"0.73 us at 1e8 nodes" -- we run 1e6 nodes and show the time is flat in N).

The HEADLINE ASURA number (``fig5_asura_vec_n*``) is the engine path --
placement against the cached versioned table artifact, the way every
consumer actually calls it.  ``fig5_asura_uncached_n*`` keeps the old
``place_batch`` number (re-derives the table per call) for comparison; it
understates ASURA vs Consistent Hashing.

Ladder variants (the ISSUE-2 perf_opt acceptance numbers): at a 4096-node
cluster (top_level ~ 11) ``fig5_ladder_lazy_n4096`` vs
``fig5_ladder_unrolled_n4096`` isolates the lazy-depth descend ladder
against the exact pre-PR unrolled arithmetic on the same prebuilt table;
``fig5_ladder_speedup_n4096`` is the ratio (acceptance: >= 2x).

Device variants: ``fig5_asura_device_n*`` times the engine's zero-host-sync
``place_nodes_device`` path (the jnp reference kernels, compiled by XLA),
ids resident on device, result blocked on device.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import ConsistentHashRing, PlacementEngine, StrawBucket, make_uniform_cluster
from repro.core.asura import (
    _place_batch_u32_unrolled,
    place_batch,
    place_batch_u32,
    place_scalar,
)

NODE_COUNTS = (1, 10, 100, 400, 800, 1200)
BATCH = 200_000
SCALAR_CALLS = 2_000
LADDER_NODES = 4096
LADDER_BATCH = 100_000  # large enough to amortize per-call setup
HUGE_NODES = (10_000, 1_000_000)

QUICK_NODE_COUNTS = (1, 10, 100)
QUICK_BATCH = 20_000
QUICK_SCALAR_CALLS = 200
QUICK_HUGE_NODES = (10_000,)


def _time(fn, *args) -> float:
    t0 = time.perf_counter()
    fn(*args)
    return time.perf_counter() - t0


def bench_asura_uncached(n_nodes: int, batch: int, scalar_calls: int):
    """Table re-derived per call (the pre-engine number, kept for reference)."""
    cluster = make_uniform_cluster(n_nodes)
    lengths = cluster.seg_lengths()
    ids = np.arange(batch, dtype=np.uint32)
    place_batch(ids[:1000], lengths)  # warm
    dt = _time(place_batch, ids, lengths)
    t0 = time.perf_counter()
    for i in range(scalar_calls):
        place_scalar(i, lengths)
    scalar_us = (time.perf_counter() - t0) / scalar_calls * 1e6
    return dt / batch * 1e6, scalar_us


def bench_asura_engine(n_nodes: int, batch: int):
    """HEADLINE: engine path, placement against the cached versioned table
    artifact (no per-call table canonicalization / upload)."""
    cluster = make_uniform_cluster(n_nodes)
    engine = cluster.engine
    ids = np.arange(batch, dtype=np.uint32)
    engine.place(ids[:1000])  # warm: builds the artifact (upload #1)
    dt = _time(engine.place, ids)
    assert engine.uploads == 1, "engine must not re-upload at a fixed version"
    return dt / batch * 1e6


def bench_asura_device(n_nodes: int, batch: int):
    """Engine device path: ids resident on device, zero host syncs between
    calls (placement + tail + node gather fused on device).  backend="auto"
    so the number tracks what the engine ships: the jnp reference kernels
    on every platform (on a TPU ``auto`` resolves to ``ref``)."""
    import jax.numpy as jnp

    cluster = make_uniform_cluster(n_nodes)
    engine = PlacementEngine(cluster, backend="auto")
    ids = jnp.arange(batch, dtype=jnp.uint32)
    engine.place_nodes_device(ids).block_until_ready()  # warm + compile
    t0 = time.perf_counter()
    engine.place_nodes_device(ids).block_until_ready()
    dt = time.perf_counter() - t0
    assert engine.uploads == 1
    return dt / batch * 1e6


def bench_ladder(n_nodes: int, batch: int, repeats: int = 3):
    """Lazy-depth vs unrolled descend ladder on the same prebuilt table
    (best of ``repeats`` so OS noise cannot fake or hide the speedup)."""
    cluster = make_uniform_cluster(n_nodes)
    art = cluster.engine.artifact()
    ids = np.arange(batch, dtype=np.uint32)
    place_batch_u32(ids[:1000], art.len32, art.top_level)  # warm
    _place_batch_u32_unrolled(ids[:1000], art.len32, art.top_level)
    lazy = min(
        _time(place_batch_u32, ids, art.len32, art.top_level)
        for _ in range(repeats)
    )
    unrolled = min(
        _time(_place_batch_u32_unrolled, ids, art.len32, art.top_level)
        for _ in range(repeats)
    )
    return lazy / batch * 1e6, unrolled / batch * 1e6, art.top_level


def bench_ch(n_nodes: int, virtual_nodes: int, batch: int):
    ring = ConsistentHashRing(range(n_nodes), virtual_nodes=virtual_nodes)
    ids = np.arange(batch, dtype=np.uint32)
    ring.place(ids[:1000])
    dt = _time(ring.place, ids)
    return dt / batch * 1e6


def bench_straw(n_nodes: int, batch: int = 20_000):
    straw = StrawBucket(range(n_nodes))
    ids = np.arange(batch, dtype=np.uint32)
    straw.place(ids[:100])
    dt = _time(straw.place, ids)
    return dt / batch * 1e6


def run(csv_print, quick: bool = False) -> None:
    node_counts = QUICK_NODE_COUNTS if quick else NODE_COUNTS
    batch = QUICK_BATCH if quick else BATCH
    scalar_calls = QUICK_SCALAR_CALLS if quick else SCALAR_CALLS
    for n in node_counts:
        csv_print(f"fig5_asura_vec_n{n}", bench_asura_engine(n, batch), "us_per_id")
        vec_us, scalar_us = bench_asura_uncached(n, batch, scalar_calls)
        csv_print(f"fig5_asura_uncached_n{n}", vec_us, "us_per_id")
        csv_print(f"fig5_asura_scalar_n{n}", scalar_us, "us_per_call")
        csv_print(f"fig5_asura_device_n{n}", bench_asura_device(n, batch), "us_per_id")
        for vn in (1, 100, 10_000):
            if n * vn > 20_000_000 or (quick and vn > 100):
                continue
            csv_print(f"fig5_ch_vn{vn}_n{n}", bench_ch(n, vn, batch), "us_per_id")
        csv_print(f"fig5_straw_n{n}", bench_straw(n), "us_per_id")
    # Lazy-depth ladder vs the pre-PR unrolled ladder (ISSUE-2 acceptance).
    lazy_us, unrolled_us, top = bench_ladder(LADDER_NODES, LADDER_BATCH)
    csv_print(f"fig5_ladder_lazy_n{LADDER_NODES}", lazy_us, "us_per_id")
    csv_print(f"fig5_ladder_unrolled_n{LADDER_NODES}", unrolled_us, "us_per_id")
    csv_print(f"fig5_ladder_top_level_n{LADDER_NODES}", top, "levels")
    csv_print(
        f"fig5_ladder_speedup_n{LADDER_NODES}", unrolled_us / lazy_us, "x_faster"
    )
    # huge-N scalability (paper section IV.B)
    for n in QUICK_HUGE_NODES if quick else HUGE_NODES:
        vec_us = bench_asura_engine(n, batch=min(batch, 50_000))
        csv_print(f"fig5_asura_huge_n{n}", vec_us, "us_per_id")
