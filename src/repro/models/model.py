"""Model assembly: every assigned architecture as one scanned decoder.

A single parameter schema covers the uniform families (dense /
local-global / MoE / SSM / hybrid): per-layer parameters are stacked
along a leading L axis and the backbone is one ``jax.lax.scan`` over
layers (bounded HLO for the 80-cell dry-run matrix), with per-layer kind
flags (local vs global attention) as scanned leaves.

The ``interleaved`` family (GraniteMoeHybrid) has layers of two kinds in
``cfg.layer_types`` order: Mamba-2 mixers (stack ``mamba_blocks``) and
attention (stack ``attn_blocks``), each followed by the MoE.  Each run of
consecutive layers of one kind is a scan over that stack; its cache holds
K/V for the attention layers only and SSM and conv state for the Mamba
layers only.

Granite's multipliers (embedding, attention, residual, logits) are
applied where the config sets them; a value of 1 adds no operation.

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


def _moe_init(key, cfg: ArchConfig, dtype):
    """The MoE of one layer: with neither an expert share nor a shared
    expert, ``moe_init`` from the layer's key as it always was."""
    if not (cfg.moe.n_held or cfg.moe.shared_d_ff):
        return MOE.moe_init(key, cfg.d_model, cfg.d_ff, cfg.moe.n_experts,
                            cfg.mlp, dtype)
    k1, k2 = jax.random.split(key)
    p = MOE.moe_init(k1, cfg.d_model, cfg.d_ff, cfg.moe.n_experts,
                     cfg.mlp, dtype, held=cfg.moe.n_held)
    if cfg.moe.shared_d_ff:
        p.update(MOE.shared_init(k2, cfg.d_model, cfg.moe.shared_d_ff,
                                 dtype))
    return p


def _moe_logical(cfg: ArchConfig):
    p = MOE.moe_logical(cfg.mlp)
    if cfg.moe.shared_d_ff:
        p.update(MOE.shared_logical())
    return p


def _mixers(cfg: ArchConfig, kind: str | None) -> tuple[bool, bool]:
    """(attention, SSM) of a layer: an interleaved layer's ``kind``
    ("mamba" / "attention") is its one mixer; otherwise the config's."""
    if kind is not None:
        return kind == "attention", kind == "mamba"
    return not cfg.attention_free, cfg.ssm is not None


def _has_moe(cfg: ArchConfig) -> bool:
    return cfg.family in ("moe", "interleaved")


def _layer_init(key, cfg: ArchConfig, dtype, kind: str | None = None):
    ks = jax.random.split(key, 4)
    attn, ssm = _mixers(cfg, kind)
    p: dict = {"ln1": jnp.zeros((cfg.d_model,), dtype),
               "ln2": jnp.zeros((cfg.d_model,), dtype)}
    if attn:
        p["attn"] = _attn_init(ks[0], cfg, dtype)
    if _has_moe(cfg):
        p["moe"] = _moe_init(ks[1], cfg, dtype)
    elif cfg.d_ff > 0:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
    if ssm:
        p["ssm"] = SSM.ssm_init(ks[2], cfg.d_model, cfg.ssm, dtype)
    return p


STACKS = {"mamba": "mamba_blocks", "attention": "attn_blocks"}


def segments(cfg: ArchConfig) -> list[tuple[str, int, int]]:
    """Runs of consecutive layers of one kind in ``cfg.layer_types``:
    (kind, first, stop) with indices into that kind's stack."""
    out: list = []
    seen = {k: 0 for k in STACKS}
    for kind in cfg.layer_types:
        i = seen[kind]
        seen[kind] += 1
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], i + 1)
        else:
            out.append((kind, i, i + 1))
    return out


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
    }
    if cfg.layer_types:
        for i, (kind, name) in enumerate(STACKS.items()):
            n = cfg.n_layers_of(kind)
            if n:
                params[name] = jax.vmap(
                    lambda k, kind=kind: _layer_init(k, cfg, dtype, kind))(
                        jax.random.split(jax.random.fold_in(k_layers, i),
                                         n))
    else:
        params["blocks"] = jax.vmap(
            lambda k: _layer_init(k, cfg, dtype))(
                jax.random.split(k_layers, cfg.n_layers))
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            k_head, (cfg.d_model, v), dtype) * 0.02
    if cfg.prefix_patches:
        params["patch_proj"] = jax.random.normal(
            k_head, (cfg.d_model, cfg.d_model), dtype) * 0.02
    return params


def _block_logical(cfg: ArchConfig, kind: str | None = None) -> dict:
    attn, ssm = _mixers(cfg, kind)
    blk: dict = {"ln1": ("layers", "embed"), "ln2": ("layers", "embed")}
    if attn:
        blk["attn"] = {k: ("layers",) + v
                       for k, v in _attn_logical(cfg).items()}
    if _has_moe(cfg):
        blk["moe"] = {k: ("layers",) + v
                      for k, v in _moe_logical(cfg).items()}
    elif cfg.d_ff > 0:
        blk["mlp"] = {k: ("layers",) + v
                      for k, v in L.mlp_logical(cfg.mlp).items()}
    if ssm:
        blk["ssm"] = {k: ("layers",) + v
                      for k, v in SSM.ssm_logical().items()}
    return blk


def param_logical(cfg: ArchConfig) -> PyTree:
    out = {"embed": ("vocab", "embed"), "ln_f": ("embed",)}
    if cfg.layer_types:
        for kind, name in STACKS.items():
            if cfg.n_layers_of(kind):
                out[name] = _block_logical(cfg, kind)
    else:
        out["blocks"] = _block_logical(cfg)
    if not cfg.tie_embeddings:
        out["lm_head"] = ("embed", "vocab")
    if cfg.prefix_patches:
        out["patch_proj"] = ("embed", "embed2")
    return out


# ---------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------

def _write_rows(c, rows, layer, pos):
    """``c`` (L, B, Hkv, S, hd) with ``rows`` (B, Hkv, 1, hd) written into
    layer ``layer`` at position ``pos``: a scalar, or (B,) with slot b's
    row at ``pos[b]`` (one update per slot, each in place in a carried
    ``c``)."""
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice(c, rows[None],
                                            (layer, 0, 0, pos, 0))
    for b in range(rows.shape[0]):
        c = jax.lax.dynamic_update_slice(c, rows[b][None, None],
                                         (layer, b, 0, pos[b], 0))
    return c


def _write_layer(c, new, layer):
    """``c`` (L, ...) with layer ``layer`` replaced by ``new``."""
    return jax.lax.dynamic_update_slice(
        c, new[None].astype(c.dtype), (layer,) + (0,) * (c.ndim - 1))


def _attn_apply(p, cfg: ArchConfig, x, kind, positions, cache_kv=None,
                pos: Optional[jnp.ndarray] = None, kv_scale=None, layer=None):
    """kind: per-layer scalar (0 local / 1 global).  ``cache_kv`` and
    ``kv_scale`` are the whole stacked (L, ...) cache, of which this is
    layer ``layer``; they come back with this layer's new entries written
    in.  Returns (out, kv, kv_scale)."""
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
    if cfg.position_embedding == "rope":
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)

    new_scale = kv_scale
    if cache_kv is not None:
        ck, cv = cache_kv
        kv_q = ck.dtype == jnp.int8
        if s == 1:
            # decode: per-slot write positions (ragged continuous batching)
            posv = jnp.broadcast_to(pos, (b,)).astype(jnp.int32)
            if kv_q:
                # quantize the new entries to the prefill-time scales
                sk, sv = (a[layer] for a in kv_scale)
                kq = jnp.clip(jnp.round(k / sk), -127, 127).astype(
                    jnp.int8)
                vq = jnp.clip(jnp.round(v / sv), -127, 127).astype(
                    jnp.int8)
            else:
                kq, vq = k.astype(ck.dtype), v.astype(cv.dtype)
            ck = _write_rows(ck, kq.swapaxes(1, 2), layer, pos)
            cv = _write_rows(cv, vq.swapaxes(1, 2), layer, pos)
            new_cache = (ck, cv)
            # attend over the cache (padded; mask via kv_len)
            k_all, v_all = (a[layer].swapaxes(1, 2) for a in (ck, cv))
            if kv_q:
                k_all = (k_all.astype(jnp.float32) * sk).astype(q.dtype)
                v_all = (v_all.astype(jnp.float32) * sv).astype(q.dtype)
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
                new_scale = tuple(_write_layer(a, n, layer)
                                  for a, n in zip(kv_scale, (sk, sv)))
            else:
                kq, vq = k.astype(ck.dtype), v.astype(cv.dtype)
            # the prompt's rows, from position ``pos`` of this layer
            ck = _write_rows(ck, kq.swapaxes(1, 2), layer, pos)
            cv = _write_rows(cv, vq.swapaxes(1, 2), layer, pos)
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
                      kv_len=kv_len_eff, scale=cfg.attention_multiplier)
    return out.reshape(b, s, hq * hd) @ p["wo"], new_cache, new_scale


def _block_apply(cfg: ArchConfig, params, kind, x, positions,
                 cache=None, pos=None, layer=None):
    """One decoder layer, with the mixers whose weights it holds
    (``"attn"``, ``"ssm"``; an interleaved layer holds one).  cache: the
    whole stacked cache dict, of which this is layer ``layer`` of the
    mixer's stack, or None; it comes back (None for None) with this
    layer's new state written in and every other entry as it was."""
    if QUANT_BITS:
        params = Q.dequant_tree(params, QUANT_BITS,
                                dtype=params["ln1"].dtype)
    aux = jnp.zeros((), jnp.float32)
    new_cache = {} if cache is None else dict(cache)
    h = L.rms_norm(x, params["ln1"], cfg.norm_eps)
    mix = 0.0
    if "attn" in params:
        with jax.named_scope("attn"):
            attn_out, kv, kv_scale = _attn_apply(
                params["attn"], cfg, h, kind, positions,
                cache_kv=None if cache is None else cache.get("kv"),
                pos=pos,
                kv_scale=None if cache is None else cache.get("kv_scale"),
                layer=layer)
        new_cache["kv"] = kv
        if kv_scale is not None:
            new_cache["kv_scale"] = kv_scale
        mix = checkpoint_name(attn_out, "tp_out")
    if "ssm" in params:
        with jax.named_scope("mamba.mix"):
            y, st, cst = SSM.ssm_block(
                params["ssm"], h, cfg.ssm,
                state=None if cache is None else cache["ssm"][layer],
                conv_state=None if cache is None else cache["conv"][layer],
                eps=cfg.norm_eps)
        if cache is not None:
            new_cache["ssm"] = _write_layer(cache["ssm"], st, layer)
            new_cache["conv"] = _write_layer(cache["conv"], cst, layer)
        if cfg.family == "hybrid":
            # Hymba: parallel attn + SSM heads, normalized mean fusion.
            mix = 0.5 * (_rmsn(mix) + _rmsn(y))
        else:
            mix = y
    x = x + _mul(mix, cfg.residual_multiplier)
    h = L.rms_norm(x, params["ln2"], cfg.norm_eps)
    if "moe" in params:
        y, aux = _moe_apply(cfg, params["moe"], h)
    elif "mlp" in params:
        y = checkpoint_name(L.mlp_apply(params["mlp"], h, cfg.mlp),
                            "tp_out")
    else:
        y = jnp.zeros_like(h)
    return (x + _mul(y, cfg.residual_multiplier), aux,
            None if cache is None else new_cache)


def _mul(x, m):
    """``x * m``, decided at trace time: 1 adds no operation."""
    return x if m == 1 else x * m


def _moe_apply(cfg: ArchConfig, p, h):
    return MOE.moe_apply(p, h, top_k=cfg.moe.top_k,
                         capacity_factor=cfg.moe.capacity_factor,
                         mlp_kind=cfg.mlp, first=cfg.moe.first_held)


def _typed_layers(cfg: ArchConfig, params, x, positions, cache=None,
                  pos=None, remat: bool = False):
    """The interleaved family's layers in ``layer_types`` order: a scan
    over each run of one kind, the layer's weights indexed out of its
    stack by the scanned layer id, the cache (if any) in the carry."""
    aux = jnp.zeros((), jnp.float32)
    for kind, first, stop in segments(cfg):
        stack = params[STACKS[kind]]

        def body(carry, layer, stack=stack):
            xc, c, ax = carry
            blk = jax.tree.map(lambda w: w[layer], stack)
            xc, a, c = _block_apply(cfg, blk, 1, xc, positions,
                                    cache=c, pos=pos, layer=layer)
            return (xc, c, ax + a), None

        if remat:
            body = _remat_wrap(body)
        (x, cache, aux), _ = _scan(body, (x, cache, aux),
                                   jnp.arange(first, stop, dtype=jnp.int32))
    return x, aux, cache


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
        x = _mul(x, cfg.embedding_multiplier)
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


def _layers(cfg: ArchConfig, params, x, positions, remat: bool):
    if cfg.layer_types:
        x, aux, _ = _typed_layers(cfg, params, x, positions, remat=remat)
        return x, aux
    return _backbone(cfg, params, x, positions, remat)


def _logits(cfg: ArchConfig, x, head):
    """``x @ head``, over ``logits_scaling`` where the config sets it."""
    out = x @ head
    return out if cfg.logits_scaling == 1 else out / cfg.logits_scaling


def forward(cfg: ArchConfig, params, batch, remat: bool = True):
    """Full-sequence forward -> (logits (B, S, vocab), aux_loss)."""
    x, positions = _embed_inputs(cfg, params, batch)
    x, aux = _layers(cfg, params, x, positions, remat)
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = _logits(cfg, x, _head_matrix(cfg, params))
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
        x, aux = _layers(cfg, params, x, positions, remat)
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
            logits_c = _logits(cfg, xc, head).astype(jnp.float32)
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
    """The serving cache.  K and V are (L, B, Hkv, S, hd): heads before
    positions, the order decode attention reads a layer in, so that the
    layer is read where it lies and not copied out in another order.
    The interleaved family holds K/V for its attention layers only, and
    SSM and conv state for its Mamba layers only."""
    if cfg.layer_types:
        n_kv = cfg.n_layers_of("attention")
        n_ssm = cfg.n_layers_of("mamba")
    else:
        n_kv = 0 if cfg.attention_free else cfg.n_layers
        n_ssm = cfg.n_layers if cfg.ssm is not None else 0
    cache = {}
    if n_kv:
        kv_shape = (n_kv, batch, cfg.n_kv_heads, seq, cfg.d_head)
        kv_dtype = jnp.int8 if KV_QUANT else dtype
        cache["kv"] = (jnp.zeros(kv_shape, kv_dtype),
                       jnp.zeros(kv_shape, kv_dtype))
        if KV_QUANT:
            s_shape = (n_kv, batch, 1, cfg.n_kv_heads, 1)
            cache["kv_scale"] = (jnp.ones(s_shape, jnp.float32),
                                 jnp.ones(s_shape, jnp.float32))
    if n_ssm:
        nh = cfg.n_ssm_heads
        p = cfg.ssm.head_dim
        cache["ssm"] = jnp.zeros((n_ssm, batch, nh, p, cfg.ssm.state_dim),
                                 jnp.float32)
        conv_dim = cfg.d_inner + 2 * cfg.ssm.state_dim
        cache["conv"] = jnp.zeros(
            (n_ssm, batch, cfg.ssm.conv_kernel - 1, conv_dim), dtype)
    return cache


def _serve_scan(cfg: ArchConfig, params, x, positions, cache, pos):
    """The layers over ``x`` with the cache: the whole cache rides in the
    scan's carry, and each layer writes only its new entries into it, so a
    donated cache is updated in place and never copied whole."""
    if cfg.layer_types:
        x, _, cache = _typed_layers(cfg, params, x, positions, cache, pos)
        return x, cache
    kinds = layer_kinds(cfg)

    def body(carry, scanned):
        xc, c = carry
        blk, kind, layer = scanned
        xc, _, c = _block_apply(cfg, blk, kind, xc, positions,
                                cache=c, pos=pos, layer=layer)
        return (xc, c), None

    (x, cache), _ = _scan(body, (x, cache),
                          (params["blocks"], kinds,
                           jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    return x, cache


def prefill(cfg: ArchConfig, params, batch, cache):
    """Process the prompt, fill the cache.  Returns (last_logits, cache)."""
    x, positions = _embed_inputs(cfg, params, batch)
    x, new_cache = _serve_scan(cfg, params, x, positions, cache,
                               pos=jnp.zeros((), jnp.int32))
    x = L.rms_norm(x[:, -1:], params["ln_f"], cfg.norm_eps)
    return _logits(cfg, x, _head_matrix(cfg, params))[:, 0], new_cache


def decode_step(cfg: ArchConfig, params, cache, token, pos):
    """One decode step.  token (B, 1) int32 or embeds (B, 1, d); pos (B,)
    int32, each slot's write position (a scalar puts every slot there).

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
        x = _mul(x, cfg.embedding_multiplier)
    b = x.shape[0]
    positions = jnp.broadcast_to(pos[None], (b, 1)) \
        if jnp.ndim(pos) == 0 else pos[:, None]
    x, new_cache = _serve_scan(cfg, params, x, positions, cache, pos=pos)
    x = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return _logits(cfg, x, _head_matrix(cfg, params))[:, 0], new_cache


def _jit_named(fn, name: str, donate: int):
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, donate_argnums=donate)


def jit_prefill(cfg: ArchConfig):
    """``prefill`` of ``cfg``, jitted as ``(params, batch, cache)``; its XLA
    module is named ``jit_prefill`` in a trace.  The cache is donated: the
    returned cache reuses its buffers, and the one passed in is gone."""
    return _jit_named(lambda p, b, c: prefill(cfg, p, b, c), "prefill", 2)


def jit_decode_step(cfg: ArchConfig):
    """``decode_step`` of ``cfg``, jitted as ``(params, cache, token, pos)``;
    its XLA module is named ``jit_decode_step`` in a trace.  The cache is
    donated: the returned cache reuses its buffers, and the one passed in
    is gone."""
    return _jit_named(lambda p, c, t, pos: decode_step(cfg, p, c, t, pos),
                      "decode_step", 1)


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
