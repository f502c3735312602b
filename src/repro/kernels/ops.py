"""Public jit'd wrappers for the PIM-tile kernels.

`pim_linear` is the layer-facing entry point: it takes a float input and a
pre-quantized weight bundle (see :func:`prepare_weights`), quantizes the
activations on the fly, and dispatches to the Pallas kernel (interpret
mode off TPU, native on TPU: see :func:`default_interpret`).

The default block shapes come from the PIM tile configuration: the Data
Mapper's ``T_h x T_w`` scaled to MXU alignment (DESIGN.md §2.3), so the
HW/SW co-design parameters flow from the simulator into the kernels.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.timing import PimSpec
from repro.pimkernel.tileconfig import PimDType, TileConfig
from . import pim_gemm, pim_gemv, ref


def default_interpret() -> bool:
    """What ``interpret=None`` means for every kernel of this package:
    compile natively on TPU, run the Pallas interpreter elsewhere."""
    return jax.default_backend() != "tpu"


def pim_block_shape(dtype: PimDType,
                    pim: PimSpec = PimSpec()) -> tuple[int, int]:
    """PIM tile -> MXU-aligned VMEM block (BH, BW)."""
    tc = TileConfig.make(dtype, pim)
    bh = max(128, -(-tc.t_h // 128) * 128)
    bw = max(128, -(-tc.t_w // 128) * 128)
    return (min(bh, 512), min(bw, 1024))


@dataclasses.dataclass
class QuantWeights:
    """A weight matrix prepared for PIM-tile kernels."""

    dtype: PimDType
    q: jnp.ndarray           # int8 (H, W[/2]) or fp8 (H, W)
    scale: jnp.ndarray | None  # (H,) f32, int paths only
    shape: tuple[int, int]   # logical (H, W)


def prepare_weights(w, dtype: PimDType | str) -> QuantWeights:
    dtype = PimDType.parse(dtype) if isinstance(dtype, str) else dtype
    w = jnp.asarray(w, jnp.float32)
    if dtype.is_fp:
        return QuantWeights(dtype, w.astype(jnp.float8_e4m3fn), None,
                            tuple(w.shape))
    q, scale = ref.quantize_weights(w, dtype.w_bits)
    if dtype.w_bits == 4:
        q = ref.pack_w4(q)
    return QuantWeights(dtype, q, scale, tuple(w.shape))


def pim_linear(x, qw: QuantWeights, *, block=None,
               interpret: bool | None = None) -> jnp.ndarray:
    """y = x @ W^T with PIM-tile kernels.  x: (W,) or (B, W) float."""
    if block is None:
        block = pim_block_shape(qw.dtype)
    x = jnp.asarray(x)
    squeeze = x.ndim == 1
    xb = x[None] if squeeze else x

    if qw.dtype.is_fp:
        xk = xb.astype(jnp.float8_e4m3fn if qw.dtype.a_bits == 8
                       else jnp.bfloat16)
        if squeeze:
            out = pim_gemv.pim_gemv_fp(qw.q, xk[0], block=block,
                                       interpret=interpret)
        else:
            out = pim_gemm.pim_gemm_fp(qw.q, xk, block=(8,) + block,
                                       interpret=interpret)
    else:
        xq, xs = ref.quantize_acts(xb, qw.dtype.a_bits)
        if squeeze:
            out = pim_gemv.pim_gemv_int(qw.q, xq[0], qw.scale, xs,
                                        w_bits=qw.dtype.w_bits,
                                        block=block, interpret=interpret)
        else:
            out = pim_gemm.pim_gemm_int(qw.q, xq, qw.scale, xs,
                                        w_bits=qw.dtype.w_bits,
                                        block=(8,) + block,
                                        interpret=interpret)
    return out


def pim_linear_ref(x, qw: QuantWeights) -> jnp.ndarray:
    """Oracle path with identical numerics contract."""
    x = jnp.asarray(x)
    squeeze = x.ndim == 1
    xb = x[None] if squeeze else x
    if qw.dtype.is_fp:
        xk = xb.astype(jnp.float8_e4m3fn if qw.dtype.a_bits == 8
                       else jnp.bfloat16)
        out = ref.ref_gemm_fp(qw.q, xk)
    else:
        xq, xs = ref.quantize_acts(xb, qw.dtype.a_bits)
        out = ref.ref_gemm_int(qw.q, xq, qw.scale, xs,
                               w_bits=qw.dtype.w_bits)
    return out[0] if squeeze else out
