"""Grouped expert matmuls against the dropless buffer, on one chip.

Three tables, as CSV rows ``name,us,derived`` (and the same rows in
``experiments/moe_grouped_speed.json``):

- ``kernel/...``: one expert matmul at the prefill shapes of the two MoE
  cells, as ``jax.lax.ragged_dot``, as megablox ``gmm`` (a few tilings)
  and as the buffer's batched einsum.  The share of granite-4.0-h-small
  (9 of 72 experts, top-10) holds ``9·n`` rows, of which the router sends
  it ``1.25·n`` on average and ``9·n`` at most; granite-moe-3b-a800m's
  whole layer (40 experts, top-8) holds ``40·n`` rows for ``8·n`` routed.
  ``derived`` is the routed rows.
- ``layer/...``: ``models/moe.py``'s routed experts (router, dispatch,
  expert MLP, combine) at both shapes over a range of token counts, with
  the dispatch forced to the buffer (``dense``) or to the grouped form
  (``grouped``); ``derived`` is the rule's choice.  This sets the rule's
  threshold, ``moe.OVERHEAD_ROWS``.
- ``scope/...``: one prefill of each MoE cell's configuration, as the
  benchmark builds it (``bench/configs``, weights from a seed), through
  either dispatch: device self time by named scope, from a profile
  (``derived`` is the prefill's device seconds in all).

Needs a TPU: ``python benchmarks/moe_grouped_speed.py [kernel] [layer]
[scope]`` (with ``src`` on ``PYTHONPATH``; no table named runs all
three).  Kernel and layer times are host wall time around
``block_until_ready``, the median of 10 calls after 2 warm-ups.
"""
from __future__ import annotations

import json
import os
import re
import statistics
import sys
import tempfile
import time
from collections import defaultdict
from functools import partial
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import moe as MOE

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
BF16 = jnp.bfloat16
# (name, d, ff, e_all, held, top_k, shared_ff): the cells' MoE layers
SHAPES = [("share", 4096, 768, 72, 9, 10, 1536),
          ("whole", 1536, 512, 40, 40, 8, 0)]
TILINGS = [(128, 128, 128), (512, 512, 512), (512, 1024, 1024)]


def _time(fn, *args) -> float:
    """Median seconds of a call, its result on the device."""
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _sizes(rng, e, rows, bound):
    """``e`` group sizes summing to ``rows``, none above ``bound``."""
    if rows >= e * bound:
        return np.full(e, bound, np.int32)
    sizes = rng.multinomial(rows, np.full(e, 1.0 / e)).astype(np.int32)
    return np.minimum(sizes, bound)


def kernel_rows(rng):
    """One expert matmul (gate/up, then down) at each cell's prefill."""
    out = []
    for name, d, ff, e_all, e, k, _ in SHAPES:
        n = 8192 if name == "share" else 1024
        m = n * min(k, e)
        routed = {"share": [n * k * e // e_all, m], "whole": [m]}[name]
        for kdim, ndim, part in ((d, ff, "up"), (ff, d, "down")):
            lhs = jnp.asarray(rng.standard_normal((m, kdim), np.float32), BF16)
            rhs = jnp.asarray(rng.standard_normal((e, kdim, ndim), np.float32) /
                              np.sqrt(kdim), BF16)
            buf = jnp.asarray(rng.standard_normal((e, n, kdim), np.float32), BF16)
            t = _time(jax.jit(lambda a, w: jnp.einsum(
                "ecd,edf->ecf", a, w)), buf, rhs)
            out.append((f"kernel/{name}/{part}/einsum", t, e * n))
            for rows in routed:
                gs = jnp.asarray(_sizes(rng, e, rows, n))
                t = _time(jax.jit(jax.lax.ragged_dot), lhs, rhs, gs)
                out.append((f"kernel/{name}/{part}/ragged_dot", t, rows))
                from jax.experimental.pallas.ops.tpu import megablox
                for tm, tk, tn in TILINGS:
                    tiling = (tm, min(tk, kdim), min(tn, ndim))
                    fn = jax.jit(partial(megablox.gmm, tiling=tiling,
                                         preferred_element_type=BF16))
                    try:
                        t = _time(fn, lhs, rhs, gs)
                    except Exception as err:  # Mosaic refuses a tiling
                        print(f"# gmm {tiling}: {type(err).__name__}: "
                              f"{str(err)[:200]}", flush=True)
                        continue
                    out.append((f"kernel/{name}/{part}/gmm"
                                f"{tm}x{tiling[1]}x{tiling[2]}", t, rows))
    return out


def layer_rows(rng):
    """The routed experts, dense against grouped, over token counts."""
    out = []
    counts = {"share": [16, 128, 512, 640, 2048, 8192, 12288],
              "whole": [8, 16, 128, 384, 512, 640, 768, 1024, 1536]}
    for name, d, ff, e_all, e, k, sff in SHAPES:
        p = MOE.moe_init(jax.random.PRNGKey(0), d, ff, e_all, "swiglu",
                         BF16, held=e)
        if sff:
            p.update(MOE.shared_init(jax.random.PRNGKey(1), d, sff, BF16))
        for n in counts[name]:
            x = jnp.asarray(rng.standard_normal((1, n, d), np.float32), BF16)
            for path in ("dense", "grouped"):
                fn = jax.jit(partial(_forced, path == "grouped", top_k=k))
                out.append((f"layer/{name}/n{n}/{path}", _time(fn, p, x),
                            "grouped" if MOE._grouped(n, k, e_all)
                            else "dense"))
    return out


def _forced(grouped, p, x, *, top_k):
    with _dispatch(grouped):
        return MOE.moe_apply(p, x, top_k=top_k, capacity_factor=1.0,
                             mlp_kind="swiglu")[0]


def _dispatch(grouped):
    """Within: every MoE call traced takes the one dispatch given."""
    return mock.patch.object(MOE, "_grouped", lambda *_: grouped)


# innermost first: a nested scope's ops also carry the outer name
SCOPES = ("moe.grouped", "moe.routed", "moe.shared", "mamba.state",
          "mamba.mix", "attn")


def _op_names(hlo: str) -> dict:
    """Each instruction of a compiled module's text: its ``op_name``."""
    return dict(re.findall(r'^\s*(?:ROOT )?%?([\w.\-]+) = [^\n]*?'
                           r'metadata=\{[^}\n]*?op_name="([^"]*)"', hlo,
                           re.M))


def _scope(ev, op_names) -> str:
    """The innermost named scope of a device op, from its instruction's
    ``op_name`` in the module run (the event's stats name the op)."""
    try:
        stats = dict(ev.stats)
    except Exception:  # noqa: BLE001 - stats are optional in a trace
        stats = {}
    op = str(stats.get("hlo_op", ev.name)).lstrip("%")
    text = " ".join([op_names.get(op, ""), ev.name]
                    + [str(v) for v in stats.values()])
    for s in SCOPES:
        if s + "/" in text:
            return s
    return "unscoped"


def _self_seconds_by_scope(path, op_names) -> dict:
    """Device self time (an op less the ops nested in it) by scope."""
    from jax.profiler import ProfileData
    acc = defaultdict(float)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            ops = sorted((e.start_ns, -e.duration_ns,
                          _scope(e, op_names)) for e in line.events)
            stack = []                        # [end, scope, self ns]
            for st, neg_d, sc in ops:
                while stack and stack[-1][0] <= st:
                    _, name, own = stack.pop()
                    acc[name] += own
                if stack:
                    stack[-1][2] -= min(-neg_d, stack[-1][0] - st)
                stack.append([st - neg_d, sc, -neg_d])
            for _, name, own in stack:
                acc[name] += own
        break                                # the first chip
    if not acc:
        print("# no TPU ops in the profile: planes "
              f"{[p.name for p in ProfileData.from_file(path).planes]}",
              file=sys.stderr)
    return {k: v / 1e9 for k, v in acc.items()}


def scope_rows(_rng):
    """One prefill per cell's configuration and dispatch, by scope."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    import registry
    from weights import make_params
    from repro.models import model as M
    out = []
    for name, n in (("granite-moe-3b-a800m", 1024),
                    ("granite-4.0-h-small", 8192)):
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            c = json.load(f)
        cfg = registry.arch(c).arch_config(c)
        params = make_params(c, 1)
        toks = {"tokens": jnp.ones((1, n), jnp.int32)}

        def cache():
            return M.init_cache(cfg, 1, c["serve"]["max_seq"], jnp.float32)

        for path in ("dense", "grouped"):
            with _dispatch(path == "grouped"):
                fn = M.jit_prefill(cfg).lower(params, toks,
                                              cache()).compile()
            jax.block_until_ready(fn(params, toks, cache()))
            caches = [cache() for _ in range(2)]
            with tempfile.TemporaryDirectory() as d:
                with jax.profiler.trace(d):
                    for one in caches:
                        jax.block_until_ready(fn(params, toks, one))
                xp = [os.path.join(r, f) for r, _, fs in os.walk(d)
                      for f in fs if f.endswith(".xplane.pb")]
                secs = _self_seconds_by_scope(xp[0],
                                              _op_names(fn.as_text()))
            total = sum(secs.values()) / 2
            for sc, v in sorted(secs.items()):
                out.append((f"scope/{name}/n{n}/{path}/{sc}", v / 2,
                            round(total, 6)))
        del params
    return out


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    rng = np.random.default_rng(0)
    rows = []
    os.makedirs("experiments", exist_ok=True)
    tables = [kernel_rows, layer_rows, scope_rows]
    if sys.argv[1:]:             # e.g. ``scope``: only the tables named
        tables = [t for t in tables if t.__name__[:-5] in sys.argv[1:]]
    for part in tables:
        for name, t, derived in part(rng):
            print(f"{name},{t * 1e6:.1f},{derived}", flush=True)
            rows.append(dict(name=name, us=t * 1e6, derived=derived))
        # after each table, so a later one that fails leaves the earlier
        with open("experiments/moe_grouped_speed.json", "w") as f:
            json.dump(dict(device=dev.device_kind, rows=rows), f, indent=1)


if __name__ == "__main__":
    main()
