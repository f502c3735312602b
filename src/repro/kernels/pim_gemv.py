"""Pallas TPU kernel: PIM-tile quantized GEMV.

TPU-native re-tiling of the LP5X-PIM GEMV execution model (DESIGN.md
§2.3):

* a PIM tile ``T_h x T_w`` becomes a VMEM block ``(BH, BW)`` aligned to the
  MXU (multiples of 8 x 128 / 32 x 128 for int8);
* the SRF broadcast becomes the ``x`` block, resident in VMEM and shared
  by every row block of the H grid dimension (the grid iterates H in the
  *inner* loop for each W chunk — same reuse the SRF gives the 16 banks);
* the ACC register file becomes the int32/float32 VMEM scratch accumulator
  revisited across the W (reduction) grid dimension;
* the ACC->host flush-out becomes the masked dequantizing write of the
  final grid step.

Weight dtypes: int8, packed-int4 (two nibbles per byte — the Data Mapper's
DRAM byte layout), fp8-e4m3.  Activations: int8 / int16 / bf16 / fp8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemv_int_kernel(w_ref, x_ref, s_ref, o_ref, acc_ref, *, w_bits: int,
                     n_w: int):
    """One (BH, BW) tile step: acc += W_tile @ x_tile (int32 MACs)."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    if w_bits == 4:
        lo = jnp.right_shift(jnp.left_shift(w, 4), 4)
        hi = jnp.right_shift(w, 4)
        w = jnp.stack([lo, hi], axis=-1).reshape(w.shape[0], -1)
    x = x_ref[...]                                   # (1, BW)
    acc_ref[...] += jax.lax.dot_general(
        w.astype(jnp.int32), x.astype(jnp.int32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)            # (BH, 1)

    @pl.when(k == n_w - 1)
    def _flush():
        scale = s_ref[...]                           # (BH, 1) f32
        o_ref[...] = acc_ref[...].astype(jnp.float32) * scale


def _gemv_fp_kernel(w_ref, x_ref, o_ref, acc_ref, *, n_w: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        w, x, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_w - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret off TPU, compile on it (``ops.default_interpret``;
    imported here because ``ops`` imports this module)."""
    if interpret is None:
        from .ops import default_interpret
        return default_interpret()
    return interpret


def _pad_to(arr, axis, mult):
    n = arr.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return arr
    width = [(0, 0)] * arr.ndim
    width[axis] = (0, pad)
    return jnp.pad(arr, width)


@functools.partial(jax.jit, static_argnames=("w_bits", "block", "interpret"))
def pim_gemv_int(wq, x_q, w_scale, x_scale, *, w_bits: int = 8,
                 block: tuple[int, int] = (256, 512),
                 interpret: bool | None = None) -> jnp.ndarray:
    """Quantized GEMV: (H, W[/2]) x (W,) -> f32 (H,).

    ``block`` is (BH, BW) in *element* space; the PIM-tile-derived default
    is 4 x T_h x 1 x T_w of the W8A8 tile config, MXU aligned.
    """
    bh, bw = block
    h = wq.shape[0]
    w_elems = wq.shape[1] * (2 if w_bits == 4 else 1)
    wq = _pad_to(_pad_to(wq, 0, bh), 1, bw // (2 if w_bits == 4 else 1))
    x_q = _pad_to(x_q.reshape(1, -1), 1, bw)
    ws = _pad_to(w_scale.reshape(-1, 1).astype(jnp.float32) *
                 jnp.asarray(x_scale, jnp.float32), 0, bh)
    hp, wp = wq.shape[0], x_q.shape[1]
    n_h, n_w = hp // bh, wp // bw
    bw_bytes = bw // 2 if w_bits == 4 else bw

    out = pl.pallas_call(
        functools.partial(_gemv_int_kernel, w_bits=w_bits, n_w=n_w),
        grid=(n_h, n_w),
        in_specs=[
            pl.BlockSpec((bh, bw_bytes), lambda i, k: (i, k)),
            pl.BlockSpec((1, bw), lambda i, k: (0, k)),
            pl.BlockSpec((bh, 1), lambda i, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bh, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((hp, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bh, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(interpret),
    )(wq, x_q, ws)
    return out[:h, 0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def pim_gemv_fp(w_fp8, x, *, block: tuple[int, int] = (256, 512),
                interpret: bool | None = None) -> jnp.ndarray:
    """fp8-e4m3 weight GEMV: (H, W) x (W,) -> f32 (H,)."""
    bh, bw = block
    h, w_elems = w_fp8.shape
    w_fp8 = _pad_to(_pad_to(w_fp8, 0, bh), 1, bw)
    x = _pad_to(x.reshape(1, -1), 1, bw)
    hp, wp = w_fp8.shape
    n_h, n_w = hp // bh, wp // bw

    out = pl.pallas_call(
        functools.partial(_gemv_fp_kernel, n_w=n_w),
        grid=(n_h, n_w),
        in_specs=[
            pl.BlockSpec((bh, bw), lambda i, k: (i, k)),
            pl.BlockSpec((1, bw), lambda i, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((bh, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((hp, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bh, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(interpret),
    )(w_fp8, x)
    return out[:h, 0]
