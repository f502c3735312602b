"""Compile the main-path programs for a TPU v5e chip, without the chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology from shapes alone, and refuses what
the chip's compiler would refuse — tiling-illegal Pallas blocks, ops
Mosaic cannot lower, programs that overflow one chip's HBM.  Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never while a module
is imported: only one process may hold the TPU library, and every test
worker imports every test file.  Pallas lane-kernel and integer-GEMV
refusals are pinned as strict xfails, so the PR that fixes either flips
them.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.core import engine
from repro.core.timing import DEFAULT_SYSTEM
from repro.kernels import lane_scan, pim_gemv, pim_gemm
from repro.models import model as M

# What the v5e compiler admits on one chip ("Used X of 15.75G hbm").
V5E_HBM_BYTES = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means skip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache entirely.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _hbm_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _lane_args(one_chip, lanes=128, length=4096):
    cyc = DEFAULT_SYSTEM.derive_cycles()
    cycs = _on(one_chip, jax.eval_shape(
        lambda: engine.stack_cycles([cyc] * lanes)))
    streams = jax.ShapeDtypeStruct((lanes, 4, length), jnp.int32,
                                   sharding=one_chip)
    return cyc.num_banks, cycs, streams


@pytest.mark.parametrize("lanes,length", [
    (128, 4096),          # a full slab
    (4, 6291456),         # gemma3-4b's lm_head GEMV: the planner's longest
])
def test_scan_lane_resolver_compiles(one_chip, lanes, length):
    """The resolver's scratch stays small at any stream length: a
    command-major layout would copy the stream 32x padded (16 GB for the
    lm_head lanes)."""
    nb, cycs, streams = _lane_args(one_chip, lanes, length)
    compiled = engine._fleet_resolver(nb).lower(cycs, streams).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic refuses the lane kernel: its (1, 28) / (1, n) blocks break "
    "the 8x128 tiling rule, and with legal blocks its loop body "
    "(dynamic_slice, lax.scan) has no lowering (ROADMAP Speed item 2)"))
def test_pallas_lane_resolver_compiles(one_chip):
    nb, cycs, streams = _lane_args(one_chip)
    lane_scan.make_lane_resolver(nb, interpret=False).lower(
        cycs, streams).compile()


@pytest.fixture(scope="module")
def gemma_params(one_chip):
    cfg = ARCHS["gemma3-4b"]
    return cfg, _on(one_chip, M.param_specs(cfg, jnp.bfloat16))


def test_gemma3_4b_decode_step_fits_one_chip(one_chip, gemma_params):
    cfg, params = gemma_params
    slots, max_seq = 4, 2048
    cache = _on(one_chip, jax.eval_shape(
        lambda: M.init_cache(cfg, slots, max_seq, jnp.bfloat16)))
    token = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, c, t, q: M.decode_step(cfg, p, c, t, q)).lower(
            params, cache, token, pos).compile()
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES


def test_gemma3_4b_prefill_fits_one_chip(one_chip, gemma_params):
    cfg, params = gemma_params
    cache = _on(one_chip, jax.eval_shape(
        lambda: M.init_cache(cfg, 1, 2048, jnp.bfloat16)))
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, b, c: M.prefill(cfg, p, b, c)).lower(
        params, {"tokens": tokens}, cache).compile()
    assert _hbm_bytes(compiled) < V5E_HBM_BYTES


def test_pim_gemv_fp_compiles(one_chip):
    w = jax.ShapeDtypeStruct((4096, 4096), jnp.float8_e4m3fn,
                             sharding=one_chip)
    x = jax.ShapeDtypeStruct((4096,), jnp.float8_e4m3fn, sharding=one_chip)
    compiled = pim_gemv.pim_gemv_fp.lower(w, x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pim_gemm_fp_compiles(one_chip):
    w = jax.ShapeDtypeStruct((4096, 4096), jnp.float8_e4m3fn,
                             sharding=one_chip)
    xb = jax.ShapeDtypeStruct((8, 4096), jnp.float8_e4m3fn,
                              sharding=one_chip)
    compiled = pim_gemm.pim_gemm_fp.lower(w, xb, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(strict=True, reason=(
    "the int32 x int32 dot_general lowers to arith.mulf on i32; with "
    "int8 operands Mosaic rejects preferred_element_type int32"))
@pytest.mark.parametrize("w_bits", [8, 4])
def test_pim_gemv_int_compiles(one_chip, w_bits):
    cols = 4096 // (2 if w_bits == 4 else 1)
    wq = jax.ShapeDtypeStruct((4096, cols), jnp.int8, sharding=one_chip)
    xq = jax.ShapeDtypeStruct((4096,), jnp.int8, sharding=one_chip)
    ws = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip)
    xs = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    pim_gemv.pim_gemv_int.lower(wq, xq, ws, xs, w_bits=w_bits,
                                interpret=False).compile()
