"""Pallas TPU kernel: batched PIM-tile quantized GEMM (decode projections).

The serving hot path is a *batch* of GEMVs — one token per active request
against the same weight matrix (``(B, W) x (H, W) -> (B, H)``).  The PIM
blocking carries over from `pim_gemv`: the W (reduction) grid dimension
revisits a float32/int32 VMEM accumulator, the weight tile is the PIM tile
padded to MXU alignment, and the batch block plays the SRF-broadcast role
(one activation block reused by every H tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pim_gemv import _interpret, _pad_to


def _gemm_int_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, w_bits: int,
                     n_w: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    if w_bits == 4:
        lo = jnp.right_shift(jnp.left_shift(w, 4), 4)
        hi = jnp.right_shift(w, 4)
        w = jnp.stack([lo, hi], axis=-1).reshape(w.shape[0], -1)
    x = x_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        x.astype(jnp.int32), w.astype(jnp.int32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)            # (BB, BH)

    @pl.when(k == n_w - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(jnp.float32) * s_ref[...]


def _gemm_fp_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_w: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_w - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("w_bits", "block", "interpret"))
def pim_gemm_int(wq, xb_q, w_scale, x_scale, *, w_bits: int = 8,
                 block: tuple[int, int, int] = (8, 256, 512),
                 interpret: bool | None = None) -> jnp.ndarray:
    """Quantized GEMM: (B, W) x (H, W[/2]) -> f32 (B, H)."""
    bb, bh, bw = block
    b, _ = xb_q.shape
    h = wq.shape[0]
    wq = _pad_to(_pad_to(wq, 0, bh), 1, bw // (2 if w_bits == 4 else 1))
    xb_q = _pad_to(_pad_to(xb_q, 0, bb), 1, bw)
    ws = _pad_to(w_scale.reshape(1, -1).astype(jnp.float32)
                 * jnp.asarray(x_scale, jnp.float32), 1, bh)
    bp, wp = xb_q.shape
    hp = wq.shape[0]
    n_b, n_h, n_w = bp // bb, hp // bh, wp // bw
    bw_bytes = bw // 2 if w_bits == 4 else bw

    out = pl.pallas_call(
        functools.partial(_gemm_int_kernel, w_bits=w_bits, n_w=n_w),
        grid=(n_b, n_h, n_w),
        in_specs=[
            pl.BlockSpec((bb, bw), lambda i, j, k: (i, k)),
            pl.BlockSpec((bh, bw_bytes), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, bh), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, bh), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, hp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bh), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(interpret),
    )(xb_q, wq, ws)
    return out[:b, :h]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def pim_gemm_fp(w_fp8, xb, *, block: tuple[int, int, int] = (8, 256, 512),
                interpret: bool | None = None) -> jnp.ndarray:
    """fp8 weight GEMM: (B, W) x (H, W) -> f32 (B, H)."""
    bb, bh, bw = block
    b = xb.shape[0]
    h = w_fp8.shape[0]
    w_fp8 = _pad_to(_pad_to(w_fp8, 0, bh), 1, bw)
    xb = _pad_to(_pad_to(xb, 0, bb), 1, bw)
    bp, wp = xb.shape
    hp = w_fp8.shape[0]
    n_b, n_h, n_w = bp // bb, hp // bh, wp // bw

    out = pl.pallas_call(
        functools.partial(_gemm_fp_kernel, n_w=n_w),
        grid=(n_b, n_h, n_w),
        in_specs=[
            pl.BlockSpec((bb, bw), lambda i, j, k: (i, k)),
            pl.BlockSpec((bh, bw), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bb, bh), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, hp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(interpret),
    )(xb, w_fp8)
    return out[:b, :h]
