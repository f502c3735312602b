"""Model assembly: every assigned architecture as one scanned decoder.

A single parameter schema covers all five families (dense / local-global /
MoE / SSM / hybrid): per-layer parameters are stacked along a leading L
axis and the backbone is one ``jax.lax.scan`` over layers (bounded HLO for
the 80-cell dry-run matrix), with per-layer kind flags (local vs global
attention) as scanned leaves.

Public surface:
  init_params(cfg, key)            -> params pytree (stacked layers)
  param_logical(cfg)               -> same-structure tree of logical axes
  forward(cfg, params, batch)      -> logits (train/prefill path)
  init_cache(cfg, batch, seq)      -> KV/SSM cache pytree
  prefill(cfg, params, tokens)     -> (logits_last, cache)
  decode_step(cfg, params, cache, token, pos) -> (logits, cache)
  loss_fn / make_train_step        -> training
  input_specs(cfg, shape, ...)     -> ShapeDtypeStruct stand-ins (dry-run)
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ArchConfig, ShapeConfig
from . import layers as L
from . import moe as MOE
from . import quant as Q
from . import ssm as SSM

PyTree = Any

# When True, layer scans fully unroll (no while loop).  Used by the
# dry-run cost extrapolation: XLA's cost_analysis counts a while body
# once regardless of trip count, so exact per-layer FLOPs/bytes are
# derived from small fully-unrolled variants (see launch/dryrun.py).
UNROLL_SCAN = False

# §Perf hillclimb knobs (launch/dryrun.py --variant flips these):
#   REMAT_POLICY: "full" = nothing_saveable (max recompute, min memory),
#   "dots" = matmul outputs saved (less recompute), "none" = no remat.
#   CE_CHUNKS: > 0 computes the cross-entropy in that many sequence
#   chunks without materializing the full (B, S, vocab) logits.
REMAT_POLICY = "full"
CE_CHUNKS = 0

# Quantized serving (§Perf iterations / the paper's W8-W4 formats):
# 0 = bf16 params; 8/4 = int8 / packed-int4 matmul weights + scales
# (models/quant.py).  Embedding tables stay int8 under w4 (row gather).
QUANT_BITS = 0

# int8 KV cache (§Perf Cell A next step): halves the decode memory floor.
# Per-(layer, batch, head) scales fixed at prefill; decode clips to them.
KV_QUANT = False


def _deq(leaf):
    """Dequantize a possibly-quantized parameter leaf on use."""
    if Q.is_bundle(leaf):
        return Q.dequant_leaf(leaf, QUANT_BITS or 8)
    return leaf


def _head_matrix(cfg, params):
    if cfg.tie_embeddings:
        emb = params["embed"]
        if Q.is_bundle(emb):
            return Q.dequant_leaf(emb, 8).T   # embed is always 8-bit
        return emb.T
    lm = params["lm_head"]
    return Q.dequant_leaf(lm, QUANT_BITS or 8) if Q.is_bundle(lm) else lm


def _remat_wrap(body):
    if REMAT_POLICY == "none":
        return body
    if REMAT_POLICY == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    if REMAT_POLICY == "moe-save":
        # keep expert outputs across the remat boundary: the backward
        # pass must not re-run the dispatch collectives
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "moe_out"))
    if REMAT_POLICY == "tp-save":
        # keep TP-boundary outputs (post all-reduce): the recompute
        # must not re-run the Megatron activation all-reduces
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "tp_out"))
    return jax.checkpoint(
        body, policy=jax.checkpoint_policies.nothing_saveable)


def _scan(body, init, xs):
    if UNROLL_SCAN:
        length = jax.tree.leaves(xs)[0].shape[0]
        return jax.lax.scan(body, init, xs, unroll=length)
    return jax.lax.scan(body, init, xs)


# ---------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------

def _attn_init(key, cfg: ArchConfig, dtype):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(ks[0], (d, hq * hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (d, hkv * hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (d, hkv * hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (hq * hd, d), dtype)
        * (1.0 / math.sqrt(hq * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    return p


def _attn_logical(cfg: ArchConfig):
    p = {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
         "wv": ("embed", "kv_heads"), "wo": ("heads", "embed")}
    if cfg.qkv_bias:
        p.update({"bq": ("heads",), "bk": ("kv_heads",),
                  "bv": ("kv_heads",)})
    return p


def _layer_init(key, cfg: ArchConfig, dtype):
    ks = jax.random.split(key, 4)
    p: dict = {"ln1": jnp.zeros((cfg.d_model,), dtype),
               "ln2": jnp.zeros((cfg.d_model,), dtype)}
    if not cfg.attention_free:
        p["attn"] = _attn_init(ks[0], cfg, dtype)
    if cfg.family == "moe":
        p["moe"] = MOE.moe_init(ks[1], cfg.d_model, cfg.d_ff,
                                cfg.moe.n_experts, cfg.mlp, dtype)
    elif cfg.d_ff > 0:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
    if cfg.ssm is not None:
        p["ssm"] = SSM.ssm_init(ks[2], cfg.d_model, cfg.ssm, dtype)
    return p


def layer_kinds(cfg: ArchConfig) -> jnp.ndarray:
    """(L,) int32: 1 = global attention, 0 = local (sliding window)."""
    idx = jnp.arange(cfg.n_layers)
    if cfg.sliding_window is None or cfg.global_every == 0:
        return jnp.ones((cfg.n_layers,), jnp.int32)
    return (idx % cfg.global_every == cfg.global_every - 1).astype(
        jnp.int32)


def init_params(cfg: ArchConfig, key, dtype=jnp.float32) -> PyTree:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    v = cfg.vocab_padded
    params = {
        "embed": jax.random.normal(k_emb, (v, cfg.d_model), dtype) * 0.02,
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
        "blocks": jax.vmap(
            lambda k: _layer_init(k, cfg, dtype))(
                jax.random.split(k_layers, cfg.n_layers)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            k_head, (cfg.d_model, v), dtype) * 0.02
    if cfg.prefix_patches:
        params["patch_proj"] = jax.random.normal(
            k_head, (cfg.d_model, cfg.d_model), dtype) * 0.02
    return params


def param_logical(cfg: ArchConfig) -> PyTree:
    blk: dict = {"ln1": ("layers", "embed"), "ln2": ("layers", "embed")}
    if not cfg.attention_free:
        blk["attn"] = {k: ("layers",) + v
                       for k, v in _attn_logical(cfg).items()}
    if cfg.family == "moe":
        blk["moe"] = {k: ("layers",) + v
                      for k, v in MOE.moe_logical(cfg.mlp).items()}
    elif cfg.d_ff > 0:
        blk["mlp"] = {k: ("layers",) + v
                      for k, v in L.mlp_logical(cfg.mlp).items()}
    if cfg.ssm is not None:
        blk["ssm"] = {k: ("layers",) + v
                      for k, v in SSM.ssm_logical().items()}
    out = {"embed": ("vocab", "embed"), "ln_f": ("embed",),
           "blocks": blk}
    if not cfg.tie_embeddings:
        out["lm_head"] = ("embed", "vocab")
    if cfg.prefix_patches:
        out["patch_proj"] = ("embed", "embed2")
    return out



# ---------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------

def _attn_apply(p, cfg: ArchConfig, x, kind, positions, cache_kv=None,
                pos: Optional[jnp.ndarray] = None, kv_len=None,
                kv_scale=None):
    """kind: per-layer scalar (0 local / 1 global).  Returns (out, (k,v))."""
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)

    new_scale = kv_scale
    if cache_kv is not None:
        ck, cv = cache_kv
        kv_q = ck.dtype == jnp.int8
        if s == 1:
            # decode: per-slot write positions (ragged continuous batching)
            posv = jnp.broadcast_to(pos, (b,)).astype(jnp.int32)
            upd = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice(
                c, u, (p, 0, 0)))
            if kv_q:
                # quantize the new entries to the prefill-time scales
                sk, sv = kv_scale
                kq = jnp.clip(jnp.round(k / sk), -127, 127).astype(
                    jnp.int8)
                vq = jnp.clip(jnp.round(v / sv), -127, 127).astype(
                    jnp.int8)
            else:
                kq, vq = k.astype(ck.dtype), v.astype(cv.dtype)
            ck = upd(ck, kq, posv)
            cv = upd(cv, vq, posv)
            new_cache = (ck, cv)
            # attend over the cache (padded; mask via kv_len)
            if kv_q:
                k_all = (ck.astype(jnp.float32) * sk).astype(q.dtype)
                v_all = (cv.astype(jnp.float32) * sv).astype(q.dtype)
            else:
                k_all, v_all = ck, cv
            q_offset = posv
            kv_len_eff = posv + 1
        else:
            if kv_q:
                # per-(batch, head) scales fixed at prefill time
                sk = jnp.max(jnp.abs(k), axis=(1, 3), keepdims=True
                             ).astype(jnp.float32) / 127 + 1e-8
                sv = jnp.max(jnp.abs(v), axis=(1, 3), keepdims=True
                             ).astype(jnp.float32) / 127 + 1e-8
                kq = jnp.clip(jnp.round(k / sk), -127, 127).astype(
                    jnp.int8)
                vq = jnp.clip(jnp.round(v / sv), -127, 127).astype(
                    jnp.int8)
                new_scale = (sk, sv)
            else:
                kq, vq = k.astype(ck.dtype), v.astype(cv.dtype)
            ck = jax.lax.dynamic_update_slice(ck, kq, (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, vq, (0, pos, 0, 0))
            new_cache = (ck, cv)
            # prefill: the fresh k/v ARE the valid cache prefix
            k_all, v_all = k, v
            q_offset = 0
            kv_len_eff = None
    else:
        k_all, v_all = k, v
        q_offset = 0
        new_cache = (k, v)
        kv_len_eff = None

    window = None
    if cfg.sliding_window is not None:
        # kind==1 -> global: disable the window via a huge value.
        big = 1 << 30
        window = jnp.where(kind == 1, big, cfg.sliding_window)
    out = L.attention(q, k_all.astype(q.dtype), v_all.astype(q.dtype),
                      window=window, q_offset=q_offset,
                      kv_len=kv_len_eff)
    return out.reshape(b, s, hq * hd) @ p["wo"], new_cache, new_scale


def _block_apply(cfg: ArchConfig, params, kind, x, positions,
                 cache=None, pos=None):
    """One decoder layer.  cache: dict of per-layer state or None."""
    if QUANT_BITS:
        params = Q.dequant_tree(params, QUANT_BITS,
                                dtype=params["ln1"].dtype)
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    h = L.rms_norm(x, params["ln1"], cfg.norm_eps)
    mix = 0.0
    if not cfg.attention_free:
        attn_out, kv, kv_scale = _attn_apply(
            params["attn"], cfg, h, kind, positions,
            cache_kv=None if cache is None else cache.get("kv"),
            pos=pos,
            kv_scale=None if cache is None else cache.get("kv_scale"))
        new_cache["kv"] = kv
        if kv_scale is not None:
            new_cache["kv_scale"] = kv_scale
        mix = checkpoint_name(attn_out, "tp_out")
    if cfg.ssm is not None:
        y, st, cst = SSM.ssm_block(
            params["ssm"], h, cfg.ssm,
            state=None if cache is None else cache.get("ssm"),
            conv_state=None if cache is None else cache.get("conv"))
        new_cache["ssm"] = st
        new_cache["conv"] = cst
        if cfg.family == "hybrid":
            # Hymba: parallel attn + SSM heads, normalized mean fusion.
            mix = 0.5 * (_rmsn(mix) + _rmsn(y))
        else:
            mix = y
    x = x + mix
    h = L.rms_norm(x, params["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = MOE.moe_apply(params["moe"], h, top_k=cfg.moe.top_k,
                               capacity_factor=cfg.moe.capacity_factor,
                               mlp_kind=cfg.mlp)
    elif cfg.d_ff > 0:
        y = checkpoint_name(L.mlp_apply(params["mlp"], h, cfg.mlp),
                            "tp_out")
    else:
        y = jnp.zeros_like(h)
    return x + y, aux, new_cache


def _rmsn(x):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6)).astype(
        x.dtype)


def _embed_inputs(cfg: ArchConfig, params, batch):
    """tokens and/or stub-modality embeddings -> (B, S, d), positions."""
    if cfg.input_mode == "embeddings":
        x = batch["embeds"]
    else:
        emb = params["embed"]
        if Q.is_bundle(emb):
            rows = jnp.take(emb["q"], batch["tokens"], axis=0)
            x = (rows.astype(jnp.float32) * emb["s"]).astype(
                params["ln_f"].dtype)
        else:
            x = jnp.take(emb, batch["tokens"], axis=0)
        if cfg.prefix_patches:
            patches = batch["patches"] @ _deq(params["patch_proj"])
            x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    return x, positions


def _backbone(cfg: ArchConfig, params, x, positions, remat: bool = True):
    kinds = layer_kinds(cfg)

    def body(carry, scanned):
        xc, aux = carry
        blk, kind = scanned
        xc, a, _ = _block_apply(cfg, blk, kind, xc, positions)
        return (xc, aux + a), None

    if remat:
        body = _remat_wrap(body)
    (x, aux), _ = _scan(body, (x, jnp.zeros((), jnp.float32)),
                        (params["blocks"], kinds))
    return x, aux


def forward(cfg: ArchConfig, params, batch, remat: bool = True):
    """Full-sequence forward -> (logits (B, S, vocab), aux_loss)."""
    x, positions = _embed_inputs(cfg, params, batch)
    x, aux = _backbone(cfg, params, x, positions, remat)
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = x @ _head_matrix(cfg, params)
    if cfg.prefix_patches:
        logits = logits[:, cfg.prefix_patches:]
    return logits, aux


# ---------------------------------------------------------------------
# Loss / train step
# ---------------------------------------------------------------------

def loss_fn(cfg: ArchConfig, params, batch, remat: bool = True):
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    if CE_CHUNKS > 1:
        # chunked CE: never materialize the full (B, S, vocab) logits.
        x, positions = _embed_inputs(cfg, params, batch)
        x, aux = _backbone(cfg, params, x, positions, remat)
        x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
        if cfg.prefix_patches:
            x = x[:, cfg.prefix_patches:]
        head = _head_matrix(cfg, params)
        s = x.shape[1]
        nc = CE_CHUNKS
        csz = -(-s // nc)
        nll_sum = jnp.zeros((), jnp.float32)
        for i in range(nc):  # static unroll: probe-visible FLOPs
            xc = x[:, i * csz:(i + 1) * csz]
            lc = labels[:, i * csz:(i + 1) * csz]
            mc = mask[:, i * csz:(i + 1) * csz]
            if xc.shape[1] == 0:
                continue
            logits_c = (xc @ head).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits_c, axis=-1)
            nll = -jnp.take_along_axis(logp, lc[..., None],
                                       axis=-1)[..., 0]
            nll_sum = nll_sum + (nll * mc).sum()
        loss = nll_sum / jnp.maximum(mask.sum(), 1.0)
        return loss + 0.01 * aux, dict(loss=loss, aux=aux)
    logits, aux = forward(cfg, params, batch, remat)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss + 0.01 * aux, dict(loss=loss, aux=aux)


# ---------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, seq: int,
               dtype=jnp.bfloat16) -> PyTree:
    cache = {}
    nl = cfg.n_layers
    if not cfg.attention_free:
        kv_shape = (nl, batch, seq, cfg.n_kv_heads, cfg.d_head)
        kv_dtype = jnp.int8 if KV_QUANT else dtype
        cache["kv"] = (jnp.zeros(kv_shape, kv_dtype),
                       jnp.zeros(kv_shape, kv_dtype))
        if KV_QUANT:
            s_shape = (nl, batch, 1, cfg.n_kv_heads, 1)
            cache["kv_scale"] = (jnp.ones(s_shape, jnp.float32),
                                 jnp.ones(s_shape, jnp.float32))
    if cfg.ssm is not None:
        nh = cfg.n_ssm_heads
        p = cfg.ssm.head_dim
        cache["ssm"] = jnp.zeros((nl, batch, nh, p, cfg.ssm.state_dim),
                                 jnp.float32)
        conv_dim = cfg.d_inner + 2 * cfg.ssm.state_dim
        cache["conv"] = jnp.zeros(
            (nl, batch, cfg.ssm.conv_kernel - 1, conv_dim), dtype)
    return cache


def _cache_layer(cache, i=None):
    """Slice / restructure helpers handled by scan's xs mechanism."""
    return cache


def _serve_scan(cfg: ArchConfig, params, x, positions, cache, pos):
    kinds = layer_kinds(cfg)

    def body(carry, scanned):
        xc = carry
        blk, kind, layer_cache = scanned
        lc = {}
        if "kv" in layer_cache:
            lc["kv"] = layer_cache["kv"]
            if "kv_scale" in layer_cache:
                lc["kv_scale"] = layer_cache["kv_scale"]
        if "ssm" in layer_cache:
            lc["ssm"] = layer_cache["ssm"]
            lc["conv"] = layer_cache["conv"]
        xc, _, new_lc = _block_apply(cfg, blk, kind, xc, positions,
                                     cache=lc, pos=pos)
        out = {}
        if "kv" in new_lc:
            out["kv"] = tuple(a.astype(layer_cache["kv"][0].dtype)
                              for a in new_lc["kv"])
            if "kv_scale" in new_lc:
                out["kv_scale"] = new_lc["kv_scale"]
        if "ssm" in new_lc:
            out["ssm"] = new_lc["ssm"]
            out["conv"] = new_lc["conv"].astype(layer_cache["conv"].dtype)
        return xc, out

    x, new_cache = _scan(body, x, (params["blocks"], kinds, cache))
    return x, new_cache


def prefill(cfg: ArchConfig, params, batch, cache):
    """Process the prompt, fill the cache.  Returns (last_logits, cache)."""
    x, positions = _embed_inputs(cfg, params, batch)
    x, new_cache = _serve_scan(cfg, params, x, positions, cache,
                               pos=jnp.zeros((), jnp.int32))
    x = L.rms_norm(x[:, -1:], params["ln_f"], cfg.norm_eps)
    return (x @ _head_matrix(cfg, params))[:, 0], new_cache


def decode_step(cfg: ArchConfig, params, cache, token, pos):
    """One decode step.  token (B, 1) int32 or embeds (B,1,d); pos scalar.

    This is the PIM-offload target: with batch B it is a batch of GEMVs
    against every projection matrix (see serving/offload.py).
    """
    if cfg.input_mode == "embeddings":
        x = token  # (B, 1, d) frame embedding (modality stub)
    else:
        emb = params["embed"]
        if Q.is_bundle(emb):
            rows = jnp.take(emb["q"], token, axis=0)
            x = (rows.astype(jnp.float32) * emb["s"]).astype(
                params["ln_f"].dtype)
        else:
            x = jnp.take(emb, token, axis=0)
    b = x.shape[0]
    positions = jnp.broadcast_to(pos[None], (b, 1)) \
        if jnp.ndim(pos) == 0 else pos[:, None]
    x, new_cache = _serve_scan(cfg, params, x, positions, cache, pos=pos)
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return (x @ _head_matrix(cfg, params))[:, 0], new_cache


def _jit_named(fn, name: str):
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def jit_prefill(cfg: ArchConfig):
    """``prefill`` of ``cfg``, jitted as ``(params, batch, cache)``; its XLA
    module is named ``jit_prefill`` in a trace."""
    return _jit_named(lambda p, b, c: prefill(cfg, p, b, c), "prefill")


def jit_decode_step(cfg: ArchConfig):
    """``decode_step`` of ``cfg``, jitted as ``(params, cache, token, pos)``;
    its XLA module is named ``jit_decode_step`` in a trace."""
    return _jit_named(lambda p, c, t, pos: decode_step(cfg, p, c, t, pos),
                      "decode_step")


# ---------------------------------------------------------------------
# Dry-run input specs (no allocation)
# ---------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeConfig,
                param_dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    f = jax.ShapeDtypeStruct
    out: dict = {}
    if shape.kind == "train":
        if cfg.input_mode == "embeddings":
            batch = {"embeds": f((b, s, cfg.d_model), param_dtype),
                     "labels": f((b, s), jnp.int32)}
        else:
            toks = s - cfg.prefix_patches
            batch = {"tokens": f((b, toks), jnp.int32),
                     "labels": f((b, toks), jnp.int32)}
            if cfg.prefix_patches:
                batch["patches"] = f((b, cfg.prefix_patches, cfg.d_model),
                                     param_dtype)
        out["batch"] = batch
    elif shape.kind == "prefill":
        if cfg.input_mode == "embeddings":
            out["batch"] = {"embeds": f((b, s, cfg.d_model), param_dtype)}
        else:
            toks = s - cfg.prefix_patches
            out["batch"] = {"tokens": f((b, toks), jnp.int32)}
            if cfg.prefix_patches:
                out["batch"]["patches"] = f(
                    (b, cfg.prefix_patches, cfg.d_model), param_dtype)
        out["cache"] = jax.eval_shape(
            lambda: init_cache(cfg, b, s, jnp.bfloat16))
    else:  # decode
        if cfg.input_mode == "embeddings":
            out["token"] = f((b, 1, cfg.d_model), param_dtype)
        else:
            out["token"] = f((b, 1), jnp.int32)
        out["pos"] = f((), jnp.int32)
        out["cache"] = jax.eval_shape(
            lambda: init_cache(cfg, b, s, jnp.bfloat16))
    return out


def param_specs(cfg: ArchConfig, dtype=jnp.bfloat16) -> PyTree:
    """ShapeDtypeStruct tree of the parameters (dry-run, no allocation)."""
    def build(key):
        p = init_params(cfg, key, dtype=dtype)
        if QUANT_BITS:
            p = quantize_for_serving(p, QUANT_BITS)
        return p
    return jax.eval_shape(build, jax.random.PRNGKey(0))


def quantize_for_serving(params, w_bits: int):
    """Quantize matmul weights (embedding stays 8-bit for row gather)."""
    emb = params.get("embed")
    out = Q.quantize_params(params, w_bits)
    if w_bits == 4 and emb is not None:
        out["embed"] = Q.quantize_params({"embed": emb}, 8)["embed"]
    return out
