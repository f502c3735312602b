"""Mixture-of-Experts block: top-k routing, dropless by default.

Two dispatches, chosen at trace time.  A few tokens (a decode step) go
through a GShard-style buffer: position-in-expert by one (N, E) cumsum
per top-k slot, every held expert over ``cap`` rows, no (N, E, C)
dispatch tensors and no global sort.  On one device, many tokens (a
prefill) are sorted by expert and run as grouped matmuls
(``jax.lax.ragged_dot``) over the rows the router sent (``_grouped``),
so the work follows the routed count rather than every held expert over
every token.  Under a mesh of several devices (``_spmd``: the dry-run's
production meshes) the buffer runs at every token count: experts are
sharded on the model axis, the buffer's scatters/gathers lower to
all-to-all-class collectives, and ``DISPATCH_SPEC`` places its rows.
Capacity is derived from the flattened token count so it never binds
(routing is batching-invariant — prefill, teacher-forced decode and B>1
decode steps agree exactly); ``drop_tokens=True`` gives the legacy
capacity-factor-bounded buffer, which drops what overflows it (no caller
in the program passes it).

Expert parallelism without the exchange: a layer whose ``wi`` holds
fewer experts than the router's width is one chip's share.  It routes
over every expert, dispatches and computes only the tokens' assignments
to the experts it holds (ids ``first`` ..), and returns their weighted
part of the result; the absent experts' part is left out.  A shared
expert (``shared_wi`` / ``shared_wo``, GraniteMoE's shared MLP: one
input projection split into gate and up) is added to every token.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# §Perf knob: PartitionSpec dims for the (e*cap, d) dispatch buffer.
# None = let SPMD choose (baseline — the partitioner replicates it, which
# the roofline exposes as massive all-gather traffic); ("data", None)
# shards the capacity rows so dispatch lowers to all-to-all-class
# traffic.  Only consulted when tracing under a mesh (dry-run/launcher).
DISPATCH_SPEC = None


def _constrain(x, spec):
    if spec is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.PartitionSpec(*spec))
    except (ValueError, RuntimeError):
        return x  # no mesh in context (unit tests)


def moe_init(key, d, ff, n_experts, mlp_kind, dtype=jnp.float32,
             held=None):
    """A router over ``n_experts`` and ``held`` of them (default all)."""
    ks = jax.random.split(key, 4)
    si, so = 1.0 / math.sqrt(d), 1.0 / math.sqrt(ff)
    e = n_experts if held is None else held
    p = {
        "router": jax.random.normal(ks[0], (d, n_experts), dtype) * si,
        "wi": jax.random.normal(ks[1], (e, d, ff), dtype) * si,
        "wo": jax.random.normal(ks[2], (e, ff, d), dtype) * so,
    }
    if mlp_kind == "swiglu":
        p["wg"] = jax.random.normal(ks[3], (e, d, ff), dtype) * si
    return p


def shared_init(key, d, ff, dtype=jnp.float32):
    """The shared expert: ``shared_wi`` (d, 2·ff) = [gate | up]."""
    k1, k2 = jax.random.split(key)
    return {"shared_wi": jax.random.normal(k1, (d, 2 * ff), dtype)
            / math.sqrt(d),
            "shared_wo": jax.random.normal(k2, (ff, d), dtype)
            / math.sqrt(ff)}


def shared_apply(params, x):
    """SwiGLU of the shared expert: silu(gate) * up, then ``shared_wo``."""
    gate, up = jnp.split(x @ params["shared_wi"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ params["shared_wo"]


def moe_logical(mlp_kind: str):
    p = {"router": ("embed", "experts"),
         "wi": ("experts", "embed", "mlp"),
         "wo": ("experts", "mlp", "embed")}
    if mlp_kind == "swiglu":
        p["wg"] = ("experts", "embed", "mlp")
    return p


def shared_logical():
    return {"shared_wi": ("embed", "mlp"), "shared_wo": ("mlp", "embed")}


def moe_apply(params, x, *, top_k: int, capacity_factor: float,
              mlp_kind: str, drop_tokens: bool = False, first: int = 0):
    """x: (B, S, d) -> (B, S, d), plus aux load-balancing loss.

    ``params["wi"]`` holds the experts ``first`` .. ``first + held - 1``
    of the router's ``e``; with ``held < e`` the layer is that share (see
    the module's doc), and with a ``shared_wi`` leaf the shared expert is
    added.  Every expert held and no shared expert is the whole layer.

    Routing is *dropless* by default: expert capacity is derived from the
    flattened token count ``n`` such that it can never bind — a token
    lands in a given expert through at most one of its (distinct) top-k
    slots, so ``cap = n`` always holds every assignment.  That makes the
    routing decision independent of how the same tokens are batched,
    which is what prefill/decode consistency requires: the legacy
    per-call GShard capacity ``ceil(n*k*cf/e)`` shrank with ``n``, so a
    decode-shaped call (B, S=1) silently dropped batch rows > 0 whose
    position-in-expert (a cumsum across the flattened *batch* rows)
    overflowed the tiny per-step capacity (see
    ``test_moe_decode_drops_batch_rows``).  ``drop_tokens=True`` restores
    the capacity-factor-bounded dispatch buffer, accepting the drops.
    """
    b, s, d = x.shape
    e_all = params["router"].shape[-1]
    n = b * s
    xt = x.reshape(n, d)
    with jax.named_scope("moe.routed"):
        y, probs, gate_idx = _routed(params, xt, top_k, capacity_factor,
                                     mlp_kind, drop_tokens, first)
    if "shared_wi" in params:
        with jax.named_scope("moe.shared"):
            y = y + shared_apply(params, xt)
    # Switch-style load-balance aux loss.
    frac_tokens = jax.nn.one_hot(gate_idx[:, 0], e_all).mean(axis=0)
    frac_probs = probs.mean(axis=0)
    aux = e_all * jnp.sum(frac_tokens * frac_probs)
    return y.reshape(b, s, d), aux


# What the grouped form costs per held expert beyond the routed rows, in
# rows of the buffer, from the routed experts timed on a TPU v5e
# (``benchmarks/moe_grouped_speed.py``, ``layer/*``): grouped and buffer
# cross between 384 and 512 tokens in granite-moe-3b-a800m's whole layer
# (1.54 ms each at 384; 1.56 against 2.20 at 512) and between 512 and
# 640 in granite-4.0-h-small's share, which gathers and gates its bound
# of 9 rows a token whatever the router sends (1.67 against 1.60 at 512,
# 1.65 against 1.83 at 640).  384 puts every prompt and decode step of
# the MoE cells on the faster side; the share at 446-639 tokens, in no
# cell, may run grouped about 4 % slower.
OVERHEAD_ROWS = 384


def _grouped(n: int, top_k: int, e_all: int) -> bool:
    """Whether the grouped dispatch runs ``n`` tokens, decided by shape.

    The buffer holds ``e·n`` rows; the router sends the ``e`` held experts
    ``n·k·e/e_all`` of them on average, and the grouped form costs
    ``OVERHEAD_ROWS`` more per expert: it wins where ``e·n`` exceeds that
    sum, which does not depend on ``e``.  A prefill of 481 tokens or more
    takes it in granite-moe-3b-a800m (40 experts, top-8), of 446 or more
    in granite-4.0-h-small (72, top-10); a decode step's few rows keep the
    buffer.
    """
    return n * (e_all - top_k) > OVERHEAD_ROWS * e_all


def _spmd() -> bool:
    """Whether the trace runs under a mesh of several devices (the
    dry-run's ``with mesh:``).  There the buffer runs at every token
    count: the partitioner lowers its dispatch to all-to-all, placed by
    ``DISPATCH_SPEC``, and keeps experts sharded on the model axis where
    they are, while it partitions a ragged dot over experts by gathering
    every expert's weights onto each device (v5e 2x2 compile)."""
    from jax._src.mesh import thread_resources   # ``with mesh:`` context
    return max(thread_resources.env.physical_mesh.size,
               jax.sharding.get_abstract_mesh().size) > 1


def _routed(params, xt, top_k, capacity_factor, mlp_kind, drop_tokens,
            first):
    """The held experts' weighted outputs (N, d), the router's softmax
    and its top-k ids."""
    n = xt.shape[0]
    e_all = params["router"].shape[-1]
    e = params["wi"].shape[0]
    logits = (xt @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)        # (N, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    # ids among the held experts; an assignment elsewhere is not held
    local = gate_idx if e == e_all else gate_idx - first

    if not drop_tokens and not _spmd() and _grouped(n, top_k, e_all):
        with jax.named_scope("moe.grouped"):
            got = _grouped_rows(params, xt, local, mlp_kind)
    else:
        cap = int(max(1, math.ceil(n * top_k * capacity_factor / e))) \
            if drop_tokens else n
        got = _buffered_rows(params, xt, local, mlp_kind, cap)
    y = jnp.zeros_like(xt)
    for slot, (rows, ok) in enumerate(got):
        w = (gate_vals[:, slot] * ok).astype(y.dtype)
        y = y + rows * w[:, None]

    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(y, "moe_out"), probs, gate_idx


def _experts(h, params, mlp_kind, mm):
    """The expert MLP over rows ``h``; ``mm(h, w)`` applies each row's
    expert of the stacked weight ``w``."""
    if mlp_kind == "swiglu":
        hid = jax.nn.silu(mm(h, params["wg"])) * mm(h, params["wi"])
    else:
        hid = jax.nn.gelu(mm(h, params["wi"]))
    return mm(hid, params["wo"])


def _buffered_rows(params, xt, local, mlp_kind, cap):
    """Per top-k slot, each token's row of its expert's output and whether
    it was computed: every held expert over ``cap`` rows of a buffer."""
    n, d = xt.shape
    e_all = params["router"].shape[-1]
    e = params["wi"].shape[0]
    # GShard dispatch: per top-k slot, position-in-expert via cumsum.
    # An expert share fills its buffer with one gather of token rows, the
    # row of each (expert, position) found by a scatter of token ids: a
    # scatter of whole rows per slot took ~60 % of a long prefill's
    # expert time (v5e).  Both fill every row with its token, or zeros.
    if e == e_all:
        buf = _constrain(jnp.zeros((e * cap, d), xt.dtype), DISPATCH_SPEC)
    else:
        src = jnp.full((e * cap,), n, jnp.int32)       # n: a zero row
    locs = []
    counts = jnp.zeros((e,), jnp.int32)
    for slot in range(local.shape[1]):
        # one_hot of an id outside [0, e) is all zeros: not dispatched
        onehot = jax.nn.one_hot(local[:, slot], e, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot + counts[None, :]
        counts = counts + onehot.sum(axis=0)
        pos_tok = (pos * onehot).sum(-1)                     # (N,)
        ok = pos_tok < cap
        if e != e_all:
            ok &= (local[:, slot] >= 0) & (local[:, slot] < e)
        idx = jnp.where(ok, local[:, slot] * cap + pos_tok, e * cap)
        if e == e_all:
            buf = _constrain(buf.at[idx].add(xt, mode="drop"),
                             DISPATCH_SPEC)
        else:
            src = src.at[idx].set(jnp.arange(n, dtype=jnp.int32),
                                  mode="drop")
        locs.append((idx, ok))
    if e != e_all:
        buf = _constrain(jnp.take(
            jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)]), src,
            axis=0), DISPATCH_SPEC)

    out_e = _experts(buf.reshape(e, cap, d), params, mlp_kind,
                     lambda h, w: jnp.einsum("ecd,edf->ecf", h, w))
    out_flat = out_e.reshape(e * cap, d)
    # lazily, so each slot's gather is traced beside its weighting
    return ((jnp.take(out_flat, jnp.minimum(idx, e * cap - 1), axis=0), ok)
            for idx, ok in locs)


def _grouped_rows(params, xt, local, mlp_kind):
    """Per top-k slot, each token's row of its expert's output and whether
    it is held: the assignments sorted by expert, one gather of their
    token rows, grouped matmuls over the routed rows alone, and a gather
    back per slot through the inverse of the sort."""
    n = xt.shape[0]
    k = local.shape[1]
    e = params["wi"].shape[0]
    flat = local.reshape(-1)                               # token-major
    held = (flat >= 0) & (flat < e)
    ids = jnp.where(held, flat, e)                  # e: not held, last
    order = jnp.argsort(ids, stable=True)
    # a token's k experts are distinct, so at most min(k, e) are held
    m = n * min(k, e)
    sizes = jax.nn.one_hot(ids, e, dtype=jnp.int32).sum(axis=0)
    # in range by construction: "clip" spares the out-of-range fill's pass
    h = jnp.take(xt, order[:m] // k, axis=0, mode="clip")
    out = _experts(h, params, mlp_kind,
                   lambda h, w: jax.lax.ragged_dot(h, w, sizes))
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=order.dtype), unique_indices=True)
    back = back.reshape(n, k)
    held = held.reshape(n, k)
    # a gather per slot, as the buffer's: one of (n·k, d) rows would
    # raise the peak; rows not held are zero, whatever ``out`` holds there
    return [(jnp.where(held[:, slot, None],
                       jnp.take(out, back[:, slot], axis=0, mode="clip"),
                       0),
             held[:, slot]) for slot in range(k)]
