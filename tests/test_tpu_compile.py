"""Compile the main path for a described TPU v5e chip (no chip attached).

The TPU compiler is installed alongside jax, and it compiles for a topology
that is described rather than attached.  These tests compile, at real
widths (2^20 ids, a 1,024-entry table, R=3, a superstep of 8 x 65,536
requests, a 65,536-row mover plan), every XLA body the engine runs on a TPU
(``backend="auto"`` resolves to ``"ref"`` there) and assert that each fits
the chip's 16 GB.  They say nothing about results or speed: nothing runs.

The Pallas kernels do not lower for Mosaic yet: the lazy ladder's
dynamic row index (``dynamic_slice``) and the per-lane gathers from a 1-D
whole-table block are refused.  Each kernel's test is a strict xfail, so a
change that makes one compile must flip it.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.
"""

from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.asura import DEFAULT_PARAMS

HBM_BYTES = 16 * 10**9  # TPU v5e: 16 GB of HBM per chip
N_IDS = 1 << 20
TABLE = 1024
R = 3
SERVE_BATCH = 1 << 16
SERVE_K = 8
MOVER_ROWS = 1 << 16
TOP = DEFAULT_PARAMS.level_for(float(TABLE))
LADDER = dict(s_log2=DEFAULT_PARAMS.s_log2, max_draws=DEFAULT_PARAMS.max_draws)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler or library lock held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described v5e chip, with the persistent compilation cache
    off: a described-chip executable is written there but can never be
    read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_fits(compiled) -> None:
    m = compiled.memory_analysis()
    used = (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )
    assert 0 < used < HBM_BYTES, f"{used} bytes do not fit the chip"


@pytest.fixture(scope="module")
def tables(one_chip):
    s = functools.partial(_shape, one_chip)
    return {
        "ids": s((N_IDS,), jnp.uint32),
        "len32": s((TABLE,), jnp.uint32),
        "cum": s((TABLE,), jnp.uint32),
        "node_of": s((TABLE,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# The XLA (jnp) bodies of the main path: must compile and fit
# ---------------------------------------------------------------------------


def test_place_fused_ref_compiles(tables):
    from repro.kernels.ops import _place_fused_ref

    t = tables
    compiled = _place_fused_ref.lower(
        t["ids"], t["len32"], t["cum"], t["cum"], t["node_of"],
        top_level=TOP, emit_nodes=True, **LADDER,
    ).compile()
    _assert_fits(compiled)


def test_place_replicas_fused_ref_compiles(tables):
    from repro.kernels.ops import _place_replicas_fused_ref

    t = tables
    compiled = _place_replicas_fused_ref.lower(
        t["ids"], t["len32"], t["node_of"],
        top_level=TOP, n_replicas=R, emit_nodes=True, **LADDER,
    ).compile()
    _assert_fits(compiled)


def _gather_sizes(hlo: str) -> list[int]:
    """Element counts of every ``gather`` result in an HLO module's text."""
    sizes = []
    for m in re.finditer(r"= \w+\[([\d,]*)\][^=]*? gather\(", hlo):
        dims = [int(d) for d in m.group(1).split(",") if d]
        sizes.append(math.prod(dims))
    return sizes


def test_replica_ladder_compacts_its_stragglers(one_chip):
    """One 65,536-lane serving batch on a 1,792-entry table (top level
    10): the replica ladder runs a second draw loop over an 8,192-lane
    straggler block, and the node ids come from the loop, not from a
    gather over the 196,608 (lane, replica) pairs."""
    from repro.kernels.ops import _place_replicas_fused_ref

    s = functools.partial(_shape, one_chip)
    table, top = 1_792, 10
    compiled = _place_replicas_fused_ref.lower(
        s((SERVE_BATCH,), jnp.uint32), s((table,), jnp.uint32),
        s((table,), jnp.int32),
        top_level=top, n_replicas=R, emit_nodes=True, **LADDER,
    ).compile()
    _assert_fits(compiled)
    hlo = compiled.as_text()
    assert R * SERVE_BATCH not in _gather_sizes(hlo)
    loops = [line for line in hlo.splitlines() if " while(" in line]
    block = SERVE_BATCH // 8
    assert any(f"u32[{top + 1},{SERVE_BATCH}]" in line for line in loops)
    assert any(f"u32[{top + 1},{block}]" in line for line in loops)


def test_diff_replicas_fused_ref_compiles(tables):
    from repro.kernels.ops import _diff_replicas_fused_ref

    t = tables
    compiled = _diff_replicas_fused_ref.lower(
        t["ids"], t["len32"], t["node_of"], t["len32"], t["node_of"],
        top_a=TOP, top_b=TOP, n_replicas=R, **LADDER,
    ).compile()
    _assert_fits(compiled)


def test_owner_prefilter_mask_compiles(one_chip):
    """The planner's owner filter on an add: the (2^20, R) v+1 sets tested
    against one grown node, copying back only the (2^20,) mask."""
    from repro.migrate.planner import _holds_any_jit

    compiled = _holds_any_jit().lower(
        _shape(one_chip, (N_IDS, R), jnp.int32), _shape(one_chip, (1,), jnp.int32)
    ).compile()
    _assert_fits(compiled)


def test_serving_superstep_compiles(one_chip):
    """The scan-fused serving superstep (generate, route, pow2 select,
    count) at k=8 x 65,536 on a 1,024-node cluster."""
    from repro.core import PlacementEngine, make_uniform_cluster
    from repro.serve import RequestStreamDriver
    from repro.serve.stream import route_statics

    engine = PlacementEngine(make_uniform_cluster(TABLE), backend="ref")
    driver = RequestStreamDriver(
        engine, batch=SERVE_BATCH, n_keys=1 << 22, law="zipf", alpha=0.99,
        n_replicas=R, policy="pow2",
    )
    tabs, statics = route_statics(engine)
    args = (
        driver._key, driver._step, driver.counts, driver.queue, driver.qhist,
        *driver._fixed_operands(), *tabs,
    )
    shapes = [_shape(one_chip, a.shape, a.dtype) for a in args]
    compiled = driver._superstep_fn(statics, SERVE_K).lower(*shapes).compile()
    _assert_fits(compiled)


def test_route_migrating_compiles(one_chip):
    """``route_batch`` through a live migration (``route_migrating``): the
    v+1 ladder, the per-slot pending probe over a (3, 2^19) view (a rack
    leaving puts nearly every row in one slot), pow2 select and count, on
    65,536 host-fed keys."""
    from repro.core import PlacementEngine, make_uniform_cluster
    from repro.migrate.live import migrating_owners
    from repro.serve import RequestStreamDriver
    from repro.serve.stream import route_statics

    engine = PlacementEngine(make_uniform_cluster(TABLE), backend="ref")
    driver = RequestStreamDriver(engine, batch=SERVE_BATCH, n_keys=1, law="uniform",
                                 n_replicas=R, policy="pow2")
    (len32, node_of), (_, top, s_log2, max_draws) = route_statics(engine)
    pad = 1 << 19
    args = (
        jnp.zeros(SERVE_BATCH, jnp.uint32), jnp.uint32(0), driver._key, driver._step,
        driver.counts, driver.queue, driver.qhist, driver._service, len32, node_of,
        jnp.zeros((R, pad), jnp.uint32), jnp.zeros((R, pad), jnp.int32),
        jnp.zeros(R, jnp.int32),
    )
    shapes = [_shape(one_chip, a.shape, a.dtype) for a in args]
    fn = driver._route_batch_fn(migrating_owners((top, s_log2, max_draws, R)), "route_migrating")
    lowered = fn.lower(*shapes)
    assert "jit_route_migrating" in lowered.as_text().splitlines()[0]
    _assert_fits(lowered.compile())


def test_mover_round_block_compiles(one_chip):
    """The throttled mover's k-round admission scan over a 65,536-row plan
    on a 1,025-node cluster."""
    from repro.migrate.mover import _get_scan_rounds_jit

    s = functools.partial(_shape, one_chip, (MOVER_ROWS,))
    axis = (s(jnp.int32), s(jnp.bool_), s(jnp.int32))
    compiled = _get_scan_rounds_jit().lower(
        s(jnp.bool_), *axis, *axis, s(jnp.bool_), s(jnp.int32), s(jnp.int32),
        n_bins=TABLE + 1, k=SERVE_K,
    ).compile()
    _assert_fits(compiled)


# ---------------------------------------------------------------------------
# The Pallas kernels: Mosaic refuses them today (strict xfail)
# ---------------------------------------------------------------------------

MOSAIC_REFUSES = pytest.mark.xfail(
    strict=True,
    raises=NotImplementedError,
    reason="Mosaic does not lower the dynamic counter-row index or the "
    "per-lane gather from a 1-D whole-table block (ROADMAP Speed 1.3)",
)
PALLAS = dict(rows_per_block=8, interpret=False)


@MOSAIC_REFUSES
def test_place_pallas_compiles(tables):
    from repro.kernels.asura_place import place_pallas

    place_pallas.lower(
        tables["ids"], tables["len32"], top_level=TOP, **LADDER, **PALLAS
    ).compile()


@MOSAIC_REFUSES
def test_place_fused_pallas_compiles(tables):
    from repro.kernels.asura_place import place_fused_pallas

    t = tables
    place_fused_pallas.lower(
        t["ids"], t["len32"], t["cum"], t["cum"], t["node_of"],
        top_level=TOP, emit_nodes=True, **LADDER, **PALLAS,
    ).compile()


@MOSAIC_REFUSES
def test_place_replicas_pallas_compiles(tables):
    from repro.kernels.asura_place import place_replicas_pallas

    t = tables
    place_replicas_pallas.lower(
        t["ids"], t["len32"], t["node_of"],
        top_level=TOP, n_replicas=R, emit_nodes=True, **LADDER, **PALLAS,
    ).compile()


@MOSAIC_REFUSES
def test_diff_nodes_pallas_compiles(tables):
    from repro.kernels.asura_place import diff_nodes_pallas

    t = tables
    side = (t["len32"], t["cum"], t["cum"], t["node_of"])
    diff_nodes_pallas.lower(
        t["ids"], *side, *side, top_a=TOP, top_b=TOP, **LADDER, **PALLAS,
    ).compile()


@MOSAIC_REFUSES
def test_diff_replicas_pallas_compiles(tables):
    from repro.kernels.asura_place import diff_replicas_pallas

    t = tables
    diff_replicas_pallas.lower(
        t["ids"], t["len32"], t["node_of"], t["len32"], t["node_of"],
        top_a=TOP, top_b=TOP, n_replicas=R, **LADDER, **PALLAS,
    ).compile()


@MOSAIC_REFUSES
@pytest.mark.parametrize(
    "name, key_dtype, val_dtype",
    [
        ("ch_place_pallas", jnp.uint32, jnp.int32),
        ("rs_place_pallas", jnp.uint32, jnp.int32),
        ("wrh_place_pallas", jnp.uint32, jnp.float32),
    ],
)
def test_baseline_pallas_compiles(name, key_dtype, val_dtype, one_chip, tables):
    from repro.kernels import baselines

    fn = getattr(baselines, name)
    fn.lower(
        tables["ids"],
        _shape(one_chip, (TABLE,), key_dtype),
        _shape(one_chip, (TABLE,), val_dtype),
        **PALLAS,
    ).compile()


@MOSAIC_REFUSES
def test_hier_place_replicas_pallas_compiles(one_chip, tables):
    """32 failure domains of 32 nodes: one lane-padded top table and 32
    stacked 128-entry domain tables."""
    from repro.kernels.hierarchy import hier_place_replicas_pallas

    s = functools.partial(_shape, one_chip)
    n_dom, s_pad = 32, 128
    top = (s((128,), jnp.uint32), s((128,), jnp.int32))
    flat = n_dom * s_pad
    stacked = (
        s((flat,), jnp.uint32), s((flat,), jnp.int32),
        s((flat,), jnp.uint32), s((flat,), jnp.uint32),
    )
    per_dom = (s((128,), jnp.int32), s((128,), jnp.int32))
    level = DEFAULT_PARAMS.level_for(float(n_dom))
    hier_place_replicas_pallas.lower(
        tables["ids"], *top, *stacked, *per_dom,
        top_level=level, max_top=level, s_pad=s_pad, n_replicas=R,
        **LADDER, **PALLAS,
    ).compile()

