"""Core transformer layers, pure JAX (no framework dependencies).

Everything here is shape-polymorphic over a leading batch and works in
three modes: training (full sequence), prefill (full sequence + returns KV
cache) and decode (single token against a cache).  Long sequences use a
blockwise streaming-softmax attention (two nested ``lax.scan``s over query
/ key blocks) so the 32k prefill and 500k decode shapes lower without
materializing S x S score tensors.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# Sequences longer than this use the blockwise streaming-softmax path.
FLASH_THRESHOLD = 2048

# §Perf knob: skip causal upper-triangle (q-block, k-block) pairs in the
# blockwise attention.  Statically halves executed attention FLOPs (the
# white-box account in distribution.roofline tracks executed blocks).
# Window-block skipping would additionally need static per-layer kinds
# (the layer scan traces them) — documented future work.
FLASH_SKIP_BLOCKS = False


def rms_norm(x, gamma, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotary embedding.  x: (..., S, H, hd), positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) *
                    jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
        axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------

def _mask(qi, ki, window):
    m = ki[None, :] <= qi[:, None]
    if window is not None:
        m &= (qi[:, None] - ki[None, :]) < window
    return m


def dense_attention(q, k, v, *, window=None, q_offset=0, kv_len=None,
                    scale=None):
    """Quadratic-path GQA attention (short sequences / decode).

    q: (B, Sq, Hq, hd); k, v: (B, Sk, Hkv, hd).  ``q_offset`` is the
    absolute position of q[0] — scalar, or (B,) for ragged decode slots;
    ``kv_len`` (scalar or (B,)) masks the valid cache prefix when Sk is a
    padded cache.  ``scale`` multiplies the scores (None: ``hd ** -0.5``).
    """
    b, sq, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    scores *= 1.0 / math.sqrt(hd) if scale is None else scale
    qo = jnp.asarray(q_offset)
    qi = (qo[:, None] if qo.ndim == 1 else qo) + jnp.arange(sq)
    qi = jnp.broadcast_to(qi.reshape(-1, sq) if qi.ndim > 1
                          else qi[None], (qi.shape[0] if qi.ndim > 1
                                          else 1, sq))
    ki = jnp.arange(sk)
    mask = ki[None, None, :] <= qi[..., None]          # (B|1, sq, sk)
    if window is not None:
        mask &= (qi[..., None] - ki[None, None, :]) < window
    if kv_len is not None:
        kl = jnp.asarray(kv_len)
        kl = kl[:, None, None] if kl.ndim == 1 else kl
        mask &= ki[None, None, :] < kl
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, hq, hd)


def flash_attention(q, k, v, *, window=None, q_offset=0,
                    block_q: int = 512, block_k: int = 512, scale=None):
    """Blockwise streaming-softmax attention (prefill / train on long S).

    Never materializes more than (B, Hkv, G, block_q, block_k) scores.
    """
    b, sq, hq, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    nq = -(-sq // bq)
    nk = -(-sk // bk)
    qp = jnp.pad(q, ((0, 0), (0, nq * bq - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * bk - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * bk - sk), (0, 0), (0, 0)))
    qb = qp.reshape(b, nq, bq, hkv, g, hd).transpose(1, 0, 3, 4, 2, 5)
    kb = kp.reshape(b, nk, bk, hkv, hd).transpose(1, 0, 3, 2, 4)
    vb = vp.reshape(b, nk, bk, hkv, hd).transpose(1, 0, 3, 2, 4)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    def kv_scan(qblk, qi, kb_sel, vb_sel, k_idx):
        def kv_step(carry, kv_blk):
            m, l, acc = carry
            ki_idx, kblk, vblk = kv_blk
            ki = ki_idx * bk + jnp.arange(bk)
            s = jnp.einsum("bkgqd,bksd->bkgqs", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            msk = _mask(qi, ki, window) & (ki < sk)[None, :]
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bksd->bkgqd", p.astype(vblk.dtype), vblk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, bq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (k_idx, kb_sel, vb_sel))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    if FLASH_SKIP_BLOCKS and q_offset == 0:
        # static q-block loop; k blocks limited to the causal triangle
        outs = []
        for qi_idx in range(nq):
            qi = qi_idx * bq + jnp.arange(bq)
            # k blocks overlapping the causal range of this q block
            hi = min(nk, -(-((qi_idx + 1) * bq) // bk))
            o = kv_scan(qb[qi_idx], qi, kb[:hi], vb[:hi],
                        jnp.arange(hi))
            outs.append(o.astype(q.dtype))
        ob = jnp.stack(outs)
    else:
        def q_step(_, qi_blk):
            qi_idx, qblk = qi_blk                  # (b, hkv, g, bq, hd)
            qi = q_offset + qi_idx * bq + jnp.arange(bq)
            out = kv_scan(qblk, qi, kb, vb, jnp.arange(nk))
            return None, out.astype(q.dtype)

        _, ob = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    out = ob.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * bq, hq, hd)
    return out[:, :sq]


def attention(q, k, v, *, window=None, q_offset=0, kv_len=None,
              flash_threshold: int | None = None, scale=None):
    if flash_threshold is None:
        flash_threshold = FLASH_THRESHOLD
    if q.shape[1] == 1 or k.shape[1] <= flash_threshold:
        return dense_attention(q, k, v, window=window, q_offset=q_offset,
                               kv_len=kv_len, scale=scale)
    assert kv_len is None, "flash path expects unpadded kv"
    return flash_attention(q, k, v, window=window, q_offset=q_offset,
                           scale=scale)


# ---------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------

def mlp_apply(params, x, kind: str):
    if kind == "swiglu":
        h = jax.nn.silu(x @ params["wg"]) * (x @ params["wi"])
    else:  # gelu
        h = jax.nn.gelu(x @ params["wi"])
    return h @ params["wo"]


def mlp_init(key, d, ff, kind: str, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(ff)
    p = {"wi": jax.random.normal(ks[0], (d, ff), dtype) * scale_in,
         "wo": jax.random.normal(ks[1], (ff, d), dtype) * scale_out}
    if kind == "swiglu":
        p["wg"] = jax.random.normal(ks[2], (d, ff), dtype) * scale_in
    return p


def mlp_logical(kind: str):
    p = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
    if kind == "swiglu":
        p["wg"] = ("embed", "mlp")
    return p
