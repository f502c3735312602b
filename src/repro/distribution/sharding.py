"""Logical-axis sharding rules (per arch x shape-kind x mesh).

Parameters carry *logical* axis names (``models.model.param_logical``);
this module maps them to mesh ``PartitionSpec``s with divisibility-checked
greedy assignment (a mesh axis is used at most once per leaf; dims whose
size does not divide the axis fall back to replication).

Policy (DESIGN.md §4):
  * tensor-parallel axes (vocab / heads / kv_heads / mlp / experts) -> "model"
  * FSDP: "embed" -> "data" for archs >= `fsdp_threshold` params, so the
    72B/132B train states fit; small archs replicate over data.
  * batch -> ("pod", "data"); pods are pure DP (only grad all-reduce
    crosses pod links).
  * decode caches: batch -> data when divisible, else sequence -> (data,
    model) (sequence parallelism for long_500k).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.launch.mesh import data_axes
from repro.models import model as M

FSDP_THRESHOLD = 5_000_000_000

# §Perf hillclimb knob: when True, decode/prefill cells shard params
# TP-only (no FSDP over "data") — weight-stationary serving kills the
# per-step parameter all-gathers at the cost of 16x param memory/chip.
SERVE_TP_ONLY = False


def tp_rules(cfg: ArchConfig, mesh, kind: str = "train") -> dict:
    """logical axis -> mesh axis (or None)."""
    msize = mesh.shape["model"]
    fsdp = cfg.param_count() >= FSDP_THRESHOLD
    if SERVE_TP_ONLY and kind in ("decode", "prefill"):
        fsdp = False
    rules = {
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "experts": None,
        "embed": "data" if fsdp else None,
        "embed2": None,
        "ssm_inner": None,
        "ssm_heads": None,
        "layers": None,
    }
    if cfg.moe and cfg.moe.n_experts % msize == 0:
        rules["experts"] = "model"
        rules["mlp"] = None          # expert dim claims the model axis
    return rules


def _leaf_pspec(logical: tuple, shape: tuple, rules: dict, mesh) -> P:
    spec = []
    used = set()
    for name, dim in zip(logical, shape):
        axis = rules.get(name)
        if axis is not None and axis not in used and \
                dim % mesh.shape[axis] == 0:
            spec.append(axis)
            used.add(axis)
        else:
            spec.append(None)
    return P(*spec)


def param_shardings(cfg: ArchConfig, mesh, kind: str = "train"):
    """NamedSharding tree matching ``model.param_specs(cfg)``."""
    from repro.models.quant import quantize_logical
    rules = tp_rules(cfg, mesh, kind)
    logical = M.param_logical(cfg)
    if M.QUANT_BITS:
        logical = quantize_logical(logical)
    specs = M.param_specs(cfg)

    def mk(log, spec):
        return NamedSharding(mesh,
                             _leaf_pspec(tuple(log), spec.shape, rules,
                                         mesh))

    return jax.tree.map(mk, logical, specs,
                        is_leaf=lambda x: isinstance(x, tuple) and
                        all(isinstance(s, (str, type(None))) for s in x))


def _batch_dim_axes(mesh, n: int):
    """Sharding for a global-batch dim of size n (prefers pod+data)."""
    dax = data_axes(mesh)
    total = 1
    for a in dax:
        total *= mesh.shape[a]
    if n % total == 0:
        return dax if len(dax) > 1 else dax[0]
    if n % mesh.shape["data"] == 0:
        return "data"
    return None


def cache_shardings(cfg: ArchConfig, mesh, shape: ShapeConfig):
    """Sharding tree for the KV/SSM cache of a decode/prefill cell."""
    b = shape.global_batch
    batch_ax = _batch_dim_axes(mesh, b)

    def kv_spec(leaf_shape):
        # (L, B, kv, S, hd)
        _, _, kv, s, hd = leaf_shape
        used = {a for a in (batch_ax if isinstance(batch_ax, tuple)
                            else (batch_ax,)) if a}
        seq_ax = None
        if batch_ax is None:
            cand = tuple(a for a in ("data", "model"))
            tot = mesh.shape["data"] * mesh.shape["model"]
            if s % tot == 0:
                seq_ax = cand
        elif "model" not in used and s % mesh.shape["model"] == 0:
            seq_ax = "model"
        return P(None, batch_ax, None, seq_ax, None)

    def ssm_spec(leaf_shape):
        # (L, B, nh, p, n)
        _, _, nh, p, n = leaf_shape
        head_ax = "model" if nh % mesh.shape["model"] == 0 else (
            "model" if p % mesh.shape["model"] == 0 else None)
        if nh % mesh.shape["model"] == 0:
            return P(None, batch_ax, "model", None, None)
        if p % mesh.shape["model"] == 0:
            return P(None, batch_ax, None, "model", None)
        return P(None, batch_ax, None, None, None)

    def conv_spec(leaf_shape):
        return P(None, batch_ax, None, None)

    cache_spec = jax.eval_shape(
        lambda: M.init_cache(cfg, b, shape.seq_len, jnp.bfloat16))
    out = {}
    if "kv" in cache_spec:
        out["kv"] = tuple(NamedSharding(mesh, kv_spec(l.shape))
                          for l in cache_spec["kv"])
        if "kv_scale" in cache_spec:
            out["kv_scale"] = tuple(
                NamedSharding(mesh, P(None, batch_ax, None, None, None))
                for _ in cache_spec["kv_scale"])
    if "ssm" in cache_spec:
        out["ssm"] = NamedSharding(mesh, ssm_spec(cache_spec["ssm"].shape))
        out["conv"] = NamedSharding(mesh,
                                    conv_spec(cache_spec["conv"].shape))
    return out


class MeshShape:
    """Axis-size view of a mesh (rule math without device state)."""

    def __init__(self, shape: dict):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)

    @property
    def size(self):
        n = 1
        for v in self.shape.values():
            n *= v
        return n


def _shard_bytes(shape, pspec, mesh) -> int:
    n = 1
    for d in shape:
        n *= d
    denom = 1
    for ax in tuple(pspec):
        if ax is None:
            continue
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            denom *= mesh.shape[a]
    return n // denom


def state_bytes_per_device(cfg: ArchConfig, shape: ShapeConfig,
                           mesh=None, with_opt: bool | None = None
                           ) -> dict:
    """Exact per-device byte footprint of params / opt / cache under the
    sharding rules (drives the memory roofline term and fit checks)."""
    import jax.numpy as jnp
    from repro.models import model as M

    from repro.models.quant import quantize_logical
    mesh = mesh or MeshShape({"data": 16, "model": 16})
    rules = tp_rules(cfg, mesh, shape.kind)
    logical = M.param_logical(cfg)
    if M.QUANT_BITS:
        logical = quantize_logical(logical)
    specs = M.param_specs(cfg, jnp.bfloat16)
    is_leaf = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        isinstance(s, (str, type(None))) for s in x)
    flat_l = jax.tree.leaves(logical, is_leaf=is_leaf)
    flat_s = jax.tree.leaves(specs)
    params = 0
    for log, spec in zip(flat_l, flat_s):
        ps = _leaf_pspec(tuple(log), spec.shape, rules, mesh)
        params += _shard_bytes(spec.shape, ps, mesh) * spec.dtype.itemsize
    out = dict(params=params)
    if with_opt if with_opt is not None else shape.kind == "train":
        out["opt"] = params * 4          # m, v in f32
        out["grads"] = params
    if shape.kind != "train":
        b = shape.global_batch
        cache_specs = jax.eval_shape(
            lambda: M.init_cache(cfg, b, shape.seq_len, jnp.bfloat16))
        batch_ax = _batch_dim_axes(mesh, b)
        cache = 0
        if "kv" in cache_specs:
            for leaf in cache_specs["kv"]:
                denom = 1
                used = {a for a in ((batch_ax,) if not isinstance(
                    batch_ax, tuple) else batch_ax) if a}
                if batch_ax is not None:
                    for a in used:
                        denom *= mesh.shape[a]
                s = leaf.shape[3]
                if batch_ax is None and s % (mesh.shape["data"]
                                             * mesh.shape["model"]) == 0:
                    denom *= mesh.shape["data"] * mesh.shape["model"]
                elif "model" not in used and s % mesh.shape["model"] == 0:
                    denom *= mesh.shape["model"]
                n = 1
                for d in leaf.shape:
                    n *= d
                cache += n * leaf.dtype.itemsize // denom
        for key in ("ssm", "conv"):
            if key in cache_specs:
                leaf = cache_specs[key]
                n = 1
                for d in leaf.shape:
                    n *= d
                denom = mesh.shape["data"] if b % mesh.shape["data"] == 0 \
                    else 1
                itemsize = 4 if key == "ssm" else 2
                cache += n * itemsize // denom
        out["cache"] = cache
    out["total"] = sum(out.values())
    return out


def input_shardings(cfg: ArchConfig, mesh, shape: ShapeConfig) -> dict:
    """Shardings matching ``model.input_specs(cfg, shape)``."""
    b = shape.global_batch
    batch_ax = _batch_dim_axes(mesh, b)
    bspec2 = NamedSharding(mesh, P(batch_ax, None))
    bspec3 = NamedSharding(mesh, P(batch_ax, None, None))
    out: dict = {}
    if shape.kind == "train":
        batch = {}
        if cfg.input_mode == "embeddings":
            batch = {"embeds": bspec3, "labels": bspec2}
        else:
            batch = {"tokens": bspec2, "labels": bspec2}
            if cfg.prefix_patches:
                batch["patches"] = bspec3
        out["batch"] = batch
    elif shape.kind == "prefill":
        if cfg.input_mode == "embeddings":
            out["batch"] = {"embeds": bspec3}
        else:
            out["batch"] = {"tokens": bspec2}
            if cfg.prefix_patches:
                out["batch"]["patches"] = bspec3
        out["cache"] = cache_shardings(cfg, mesh, shape)
    else:
        out["token"] = bspec3 if cfg.input_mode == "embeddings" else bspec2
        out["pos"] = NamedSharding(mesh, P())
        out["cache"] = cache_shardings(cfg, mesh, shape)
    return out
