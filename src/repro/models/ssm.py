"""Mamba2 / SSD (state-space duality) block, chunked scan + decode step.

Follows the minimal SSD formulation of arXiv:2405.21060: within a chunk
the output is computed in dual (attention-like) form with the decay mask
L, across chunks a small recurrence over the (heads, head_dim, state)
tensor carries the SSM state.  Single B/C group shared across heads.
The output passes Mamba-2's gated RMSNorm, gate first: ``rmsnorm(y *
silu(z)) * (1 + norm)`` over all ``d_inner`` channels (one group).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import SsmConfig


def ssm_init(key, d_model, ssm: SsmConfig, dtype=jnp.float32):
    d_in = ssm.expand * d_model
    nh = d_in // ssm.head_dim
    n = ssm.state_dim
    k = ssm.conv_kernel
    ks = jax.random.split(key, 5)
    si = 1.0 / math.sqrt(d_model)
    conv_dim = d_in + 2 * n
    return {
        # projects to [z | x | B | C | dt]
        "in_proj": jax.random.normal(
            ks[0], (d_model, 2 * d_in + 2 * n + nh), dtype) * si,
        "conv_w": jax.random.normal(ks[1], (k, conv_dim), dtype) * 0.5,
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh).astype(dtype)),
        "d_skip": jnp.ones((nh,), dtype),
        "dt_bias": jnp.zeros((nh,), dtype),
        "norm": jnp.zeros((d_in,), dtype),
        "out_proj": jax.random.normal(ks[2], (d_in, d_model), dtype)
        * (1.0 / math.sqrt(d_in)),
    }


def ssm_logical():
    return {"in_proj": ("embed", "ssm_inner"), "conv_w": (None, "ssm_inner"),
            "conv_b": ("ssm_inner",), "a_log": ("ssm_heads",),
            "d_skip": ("ssm_heads",), "dt_bias": ("ssm_heads",),
            "norm": ("ssm_inner",), "out_proj": ("ssm_inner", "embed")}


def _split(params, d_in, n, nh, proj):
    z, xin, bmat, cmat, dt = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + n, 2 * d_in + 2 * n], axis=-1)
    return z, xin, bmat, cmat, dt


def _causal_conv(x, w, b):
    """Depthwise causal conv, kernel k: x (B, S, C), w (k, C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    return out + b


def _segsum_chunk(dA):
    """dA (..., Q) -> cumulative log-decay L (..., Q, Q), lower-triangular."""
    q = dA.shape[-1]
    cs = jnp.cumsum(dA, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]          # seg[i] - seg[j]
    i = jnp.arange(q)
    mask = i[:, None] >= i[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_scan(xh, dt, a_log, bmat, cmat, chunk: int):
    """Chunked SSD.  xh (B,S,nh,p), dt (B,S,nh), bmat/cmat (B,S,N).

    Returns y (B,S,nh,p) and final state (B,nh,p,N).
    """
    b, s, nh, p = xh.shape
    n = bmat.shape[-1]
    q = min(chunk, s)
    nc = -(-s // q)
    pad = nc * q - s
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))
    xc = xh.reshape(b, nc, q, nh, p)
    dtc = dt.reshape(b, nc, q, nh)
    bc = bmat.reshape(b, nc, q, n)
    cc = cmat.reshape(b, nc, q, n)

    a = -jnp.exp(a_log.astype(jnp.float32))             # (nh,) negative
    dA = dtc.astype(jnp.float32) * a                    # (b,nc,q,nh)
    dAh = jnp.moveaxis(dA, -1, 2)                       # (b,nc,nh,q)
    lmat = jnp.exp(_segsum_chunk(dAh))                  # (b,nc,nh,q,q)

    # intra-chunk (dual / attention-like form)
    cb = jnp.einsum("bcqn,bckn->bcqk", cc, bc)          # (b,nc,q,q)
    dtx = xc * dtc[..., None]                           # (b,nc,q,nh,p)
    y_intra = jnp.einsum("bcqk,bchqk,bckhp->bcqhp", cb, lmat,
                         dtx.astype(jnp.float32))

    # chunk summaries -> inter-chunk recurrence
    seg = jnp.cumsum(dAh, axis=-1)                      # (b,nc,nh,q)
    decay_to_end = jnp.exp(seg[..., -1:] - seg)         # (b,nc,nh,q)
    s_chunk = jnp.einsum("bchk,bckn,bckhp->bchpn", decay_to_end, bc,
                         dtx.astype(jnp.float32))       # (b,nc,nh,p,n)
    chunk_decay = jnp.exp(seg[..., -1])                 # (b,nc,nh)

    def step(h, inp):
        s_c, dec = inp                                  # (b,nh,p,n),(b,nh)
        y_state = h                                     # state BEFORE chunk
        h_new = h * dec[..., None, None] + s_c
        return h_new, y_state

    h0 = jnp.zeros((b, nh, p, n), jnp.float32)
    hT, h_prev = jax.lax.scan(
        step, h0,
        (jnp.moveaxis(s_chunk, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                 # (b,nc,nh,p,n)

    decay_from_start = jnp.exp(seg)                     # (b,nc,nh,q)
    y_inter = jnp.einsum("bcqn,bchq,bchpn->bcqhp", cc, decay_from_start,
                         h_prev)
    y = (y_intra + y_inter).reshape(b, nc * q, nh, p)[:, :s]
    return y.astype(xh.dtype), hT


def gated_norm(y, z, gamma, eps):
    """Mamba-2's gated RMSNorm: the gate ``silu(z)`` first, then the norm
    (weight ``1 + gamma``), in float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), -1, keepdims=True)
    return g * jax.lax.rsqrt(var + eps) * (1 + gamma.astype(jnp.float32))


def ssm_block(params, x, ssm: SsmConfig, state=None, conv_state=None,
              eps: float = 1e-5):
    """Full Mamba2 mixer.  Train/prefill: state=None -> chunked scan.
    Decode (S==1): pass (state, conv_state), returns updated states.
    ``eps`` is the gated norm's (the configuration's ``norm_eps``).

    Returns (y, new_state, new_conv_state).
    """
    b, s, _ = x.shape
    d_in = params["out_proj"].shape[0]
    nh = params["a_log"].shape[0]
    p = d_in // nh
    n = ssm.state_dim
    proj = x @ params["in_proj"]
    z, xin, bmat, cmat, dt = _split(params, d_in, n, nh, proj)
    xbc = jnp.concatenate([xin, bmat, cmat], axis=-1)

    if s == 1 and conv_state is not None:
        # decode: roll the conv window
        with jax.named_scope("mamba.state"):
            window = jnp.concatenate([conv_state, xbc], axis=1)
            conv_out = (window * params["conv_w"]).sum(
                axis=1, keepdims=True) + params["conv_b"]
            new_conv_state = window[:, 1:]
    else:
        conv_out = _causal_conv(xbc, params["conv_w"], params["conv_b"])
        k = params["conv_w"].shape[0]
        tail = jnp.concatenate([jnp.zeros((b, k - 1, xbc.shape[-1]),
                                          xbc.dtype), xbc], axis=1)
        new_conv_state = tail[:, -(k - 1):]
    conv_out = jax.nn.silu(conv_out)
    xin, bmat, cmat = jnp.split(conv_out, [d_in, d_in + n], axis=-1)

    dt = jax.nn.softplus(dt + params["dt_bias"])        # (B,S,nh)
    xh = xin.reshape(b, s, nh, p)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))

    with jax.named_scope("mamba.state"):
        if s == 1 and state is not None:
            # recurrent decode step
            dA = jnp.exp(dt[:, 0].astype(jnp.float32) * a)  # (B,nh)
            dbx = jnp.einsum("bn,bhp,bh->bhpn", bmat[:, 0], xh[:, 0],
                             dt[:, 0].astype(jnp.float32))
            new_state = state * dA[..., None, None] + dbx
            y = jnp.einsum("bn,bhpn->bhp", cmat[:, 0], new_state)
            y = y[:, None]                              # (B,1,nh,p)
        else:
            y, new_state = ssd_scan(xh, dt, params["a_log"], bmat, cmat,
                                    ssm.chunk)
    y = y + xh * params["d_skip"][None, None, :, None]
    y = y.reshape(b, s, d_in)
    y = gated_norm(y, z, params["norm"], eps).astype(x.dtype)
    return y @ params["out_proj"], new_state, new_conv_state
