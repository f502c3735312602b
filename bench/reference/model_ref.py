"""Pieces of the plain float32 references, and the comparison of served
tokens with them.

Each architecture's reference is ``logits`` in ``bench/arch/<arch>.py``:
straight ``jax.numpy`` at ``Precision.HIGHEST``, no cache and no
batching, importing nothing of the program.  The weight multiply
(``_mm``), its fp8 rounding (``_q``) and the norm (``_norm``, weight
``1 + gamma``, the program's parameterisation of a norm weight) are here
for every reference to share.

``quant="fp8"`` is the control: every weight and every activation that
enters a weight matrix is rounded to float8 e4m3 with one scale per
tensor, the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q(x, quant):
    if quant is None:
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    return jnp.matmul(_q(x, quant), _q(w.astype(jnp.float32), quant),
                      precision=HI)


def _norm(x, gamma, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gamma.astype(jnp.float32))


def served_gaps(ref: np.ndarray, prompt_len: int,
                served: list[int]) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at the position that produced it."""
    pos = prompt_len - 1 + np.arange(len(served))
    rows = ref[pos]
    return rows.max(-1) - rows[np.arange(len(served)), served]


def control_gaps(ref: np.ndarray, ctl: np.ndarray, prompt_len: int,
                 n: int) -> np.ndarray:
    """Per position, the gap of the token the control puts first."""
    pos = prompt_len - 1 + np.arange(n)
    top = ctl[pos].argmax(-1)
    rows = ref[pos]
    return rows.max(-1) - rows[np.arange(n), top]
