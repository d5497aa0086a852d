"""Compile the graph path for a TPU v5e at the full CA road-graph shapes.

No chip is needed: JAX describes a v5e:2x2 topology and the TPU compiler
compiles for it, refusing what the chip would refuse (block shapes off
the (8, 128) tiling, VMEM overflow, programs over HBM).  Nothing runs,
so these tests say nothing about values or times.

The shapes are the smoke's plan at scale 1.0 (``chip_smoke.py``): R row-
blocks of B=16 after 64-way clustering and K tile slots (k_max 30 padded
to whole 128-lane columns).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core import placement
from repro.kernels import bsr_spmv as K
from repro.kernels import ref

R, KT, B = 122688, 32, 16
HBM = 16 << 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def plan(topo):
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return dict(vals=sds((R, B, KT * B)), cols=sds((R, KT), jnp.int32),
                nnz=sds((R,), jnp.int32), x=sds((R, B)),
                valid=sds((R, B), jnp.bool_), act=sds((R,), jnp.bool_),
                scalar=sds(()))


def _fits(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.temp_size_in_bytes < HBM


@pytest.mark.parametrize("semiring", ["min_plus", "plus_times"])
def test_pallas_spmv_compiles(plan, semiring):
    c = jax.jit(lambda v, cl, n, x: K.bsr_spmv(
        v, cl, n, x, semiring=semiring, interpret=False)).lower(
            plan["vals"], plan["cols"], plan["nnz"], plan["x"]).compile()
    assert "tpu_custom_call" in c.as_text()
    assert _fits(c)


@pytest.mark.parametrize("semiring", ["min_plus", "plus_times"])
def test_pallas_fused_compiles(plan, semiring):
    apply_kind = "relax" if semiring == "min_plus" else "pagerank"
    s = plan["scalar"]
    c = jax.jit(lambda v, cl, n, x, ok, a, d, t, i: K.bsr_spmv_fused(
        v, cl, n, x, x, ok, a, d, t, i, semiring=semiring,
        apply_kind=apply_kind, interpret=False)).lower(
            plan["vals"], plan["cols"], plan["nnz"], plan["x"],
            plan["valid"], plan["act"], s, s, s).compile()
    assert "tpu_custom_call" in c.as_text()
    assert _fits(c)


def test_ref_spmv_compiles(plan):
    c = jax.jit(lambda v, cl, x: ref.bsr_spmv_ref(
        v, cl, x, "min_plus")).lower(
            plan["vals"], plan["cols"], plan["x"]).compile()
    assert _fits(c)


def test_ref_wave_spmv_compiles(topo, plan):
    """One group step of the batched async engine's wave: 8 queries
    carried row-major, so the source gather takes one row of 8*B = 128
    lanes per tile (the vmapped single-query kernel takes 8 of B)."""
    one = SingleDeviceSharding(topo.devices[0])
    gb = R // 64
    vals = jax.ShapeDtypeStruct((gb, B, KT * B), jnp.float32, sharding=one)
    cols = jax.ShapeDtypeStruct((gb, KT), jnp.int32, sharding=one)
    x = jax.ShapeDtypeStruct((R, 8, B), jnp.float32, sharding=one)
    c = jax.jit(lambda v, cl, xw: ref.bsr_spmv_wave_ref(
        v, cl, xw, "min_plus")).lower(vals, cols, x).compile()
    gathers = [ln for ln in c.as_text().splitlines() if " gather(" in ln]
    assert gathers and all("slice_sizes={1,128}" in ln for ln in gathers)
    assert _fits(c)


@pytest.mark.parametrize("mesh_shape", [(4, 1), (2, 2)])
def test_distributed_sweep_compiles(topo, mesh_shape):
    mesh = Mesh(np.asarray(topo.devices).reshape(mesh_shape),
                ("graph", "query"))

    class Plan:   # the fields lower_distributed reads
        r_pad, k_max, b, semiring, n, gb = R, KT, B, "min_plus", R * B, \
            R // 64

    c = placement.lower_distributed(Plan, mesh, batch=8).compile()
    text = c.as_text()
    assert "all-gather" in text
    assert _fits(c)


def _s17_program(topo, d_g, q=8):
    """The g500_s17.bfs_c8 cell's program, the bulk-synchronous batched
    engine for a wave of ``q``, compiled for a (``d_g``, 4 // ``d_g``)
    mesh with the scale-17 Kronecker plan (8,192 row-blocks in groups of
    128, K 3,112, 26.1 GB) row-sharded over "graph"."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    r, k = 8192, 3112
    mesh = Mesh(np.asarray(topo.devices).reshape(d_g, 4 // d_g),
                ("graph", "query"))
    rows = NamedSharding(mesh, P("graph"))
    step = placement.rows_per_step(r // d_g, r // 64)
    run = placement.batched_sync_program(
        mesh, "min_plus", "relax", 0.85, 1.0 / (r * B), 1e-6, 10_000,
        step)
    return step, run.lower(
        jax.ShapeDtypeStruct((r, B, k * B), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((r, k), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((r,), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((r, B), jnp.bool_, sharding=rows),
        jax.ShapeDtypeStruct((q, r, B), jnp.float32, sharding=NamedSharding(
            mesh, P("query", "graph"))),
        jax.ShapeDtypeStruct((q,), jnp.bool_, sharding=NamedSharding(
            mesh, P("query")))).compile()


def test_scale_17_graph500_wave_fits_four_chips(topo):
    """On a (4, 1) mesh a chip holds its 6.5 GB of tiles and the gathered
    source blocks of one row group at a time
    (:func:`placement.local_spmv`); over all its 2,048 rows at once they
    would take 26 GB.  The wave's gather is one lane-dense row of 8*B =
    128 floats a tile, so its temporaries stay under 1 GB (the vmapped
    per-query gather padded each query's 16 floats to 128 lanes: 1.9 GB)."""
    r, k = 8192, 3112
    step, c = _s17_program(topo, 4)
    m = c.memory_analysis()
    assert step == 128
    assert m.argument_size_in_bytes > (r // 4) * B * k * B * 4
    assert m.temp_size_in_bytes < 1e9
    text = c.as_text()
    assert "all-gather" in text
    gathers = [ln for ln in text.splitlines() if " gather(" in ln]
    assert gathers and all("slice_sizes={1,128}" in ln for ln in gathers)
    # K 3,112 is gathered as 3,200 tile slots, whole lane columns, so the
    # relayout moves K to the lanes by bitcasts and copies: no reshape of
    # the gathered rows into (..., 8, 16)
    assert not re.search(r"f32\[\d+,\d+,8,16\]\{[^}]*\} reshape\(", text)
    assert _fits(c)


@pytest.mark.parametrize("d_g", [4, 2])
def test_scale_17_device_need_reads_what_the_compiler_gives(topo, d_g):
    """``placement.device_need``, which chooses the plan's mesh, is within
    5% of the compiler's bytes for the program's arguments and
    temporaries on a chip: the plan's rows and the stepped sweep's
    working set, lane-dense, with the relayout of the gathered blocks
    (the arguments ``engine.prepare`` passes)."""
    r, k = 8192, 3112
    _, c = _s17_program(topo, d_g)
    m = c.memory_analysis()
    compiled = m.argument_size_in_bytes + m.temp_size_in_bytes
    row = B * k * B * 4 + k * 4 + 4 + 2 * B + 8
    need = placement.device_need(r, row, r // 64, 2 * k * B * 4,
                                 2 * r * B * 4, num_devices=4,
                                 max_wave=8, d_g=d_g)
    assert abs(need - compiled) < 0.05 * compiled
