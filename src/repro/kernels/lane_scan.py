"""Pallas lane resolver: the timing engine's hot loop as a kernel.

The engine's scan resolver is a ``vmap``-ed ``lax.scan`` over a ~100-op
branchless int32 state machine (``core/engine._build_step``).  This
module re-expresses the *same* body as a Pallas kernel so the fleet axis
becomes the Pallas grid and the per-lane channel state — ~20 small
per-bank int32 vectors — stays in VMEM/registers for the whole command
stream instead of round-tripping through the vmapped batch between
steps, with the opcode-masked timing updates fused inside one kernel.

Bit-identity with the scan resolver (and therefore with ``RefEngine``)
is by *construction*, not by reimplementation: the kernel body calls the
shared ``engine._lane_runner`` scan, exactly the way the ``shard_map``
mesh resolver shares it.  The differential suites
(``tests/test_pallas_resolver.py``, the conformance battery run under
``REPRO_LANE_BACKEND=pallas``) enforce the contract.

Interpret-mode plumbing mirrors ``kernels/ops.py``: off TPU the kernel
runs under the Pallas interpreter (how CI exercises it).  On TPU, Mosaic
refuses it today: the ``(1, 28)`` timing block and the ``(1, n)`` output
blocks break the 8x128 tiling rule, and with legal blocks the body — a
loop of ``dynamic_slice`` blocks and a ``lax.scan`` over the command
stream — has no Mosaic lowering (``NotImplementedError``: unimplemented
primitive ``dynamic_slice``; the scan fails in ``_scan_lowering_rule``).
:func:`pallas_lane_supported` states that as a platform rule, which is
what ``auto`` follows; an explicit ``pallas`` request builds the kernel
through :func:`require_lane_kernel` and raises the compiler's reason
where it cannot be built.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import commands as C
from repro.core import engine as _engine
from repro.core.timing import DEFAULT_SYSTEM, TimingCycles
from .ops import default_interpret

# The timing configuration rides the grid as an int32 matrix: one row per
# lane, one column per cycle field (``tck_ns``/``num_banks`` excluded —
# the former is unused by the step, the latter is static kernel metadata).
CYC_FIELDS = tuple(f.name for f in dataclasses.fields(TimingCycles)
                   if f.name not in ("tck_ns", "num_banks"))


def _lane_kernel(cyc_ref, stream_ref, issue_ref, total_ref, *,
                 num_banks: int, unroll: int):
    """One grid step = one lane: loop over the command stream with the
    shared step body; carry (the ChannelState pytree) lives in
    VMEM/registers."""
    cyc = TimingCycles(
        tck_ns=0.0, num_banks=num_banks,
        **{name: cyc_ref[0, j] for j, name in enumerate(CYC_FIELDS)})
    issue, total = _engine._lane_runner(num_banks, unroll)(
        cyc, stream_ref[0])
    issue_ref[0, :] = issue
    total_ref[0, 0] = total


def pack_cycles(cycs: TimingCycles) -> jnp.ndarray:
    """Stacked fleet-axis ``TimingCycles`` -> int32 ``(F, len(CYC_FIELDS))``."""
    return jnp.stack(
        [jnp.asarray(getattr(cycs, name)).astype(jnp.int32)
         for name in CYC_FIELDS], axis=-1)


def make_lane_resolver(num_banks: int, unroll: int | None = None,
                       interpret: bool | None = None):
    """Build the jitted Pallas fleet resolver for one bank count.

    The returned ``fn(cycs, streams)`` honours the exact
    ``engine._fleet_resolver`` contract — ``cycs`` a ``TimingCycles``
    pytree stacked along the fleet axis, ``streams`` int32 ``(F, 4, N)``,
    result ``(issue (F, N), total (F,))`` int32 — so the engine's slab
    dispatch, dedupe and lane LRU are backend-oblivious.  The jit cache
    keys only on shapes (the timing data is traced), preserving the
    compile-count story of the scan path.
    """
    if unroll is None:
        unroll = _engine.scan_unroll()
    kern = functools.partial(_lane_kernel, num_banks=num_banks,
                             unroll=unroll)
    ncyc = len(CYC_FIELDS)

    def fn(cycs, streams):
        f, _, n = streams.shape
        interp = default_interpret() if interpret is None else interpret
        issue, total = pl.pallas_call(
            kern,
            grid=(f,),
            in_specs=[
                pl.BlockSpec((1, ncyc), lambda i: (i, 0)),
                pl.BlockSpec((1, 4, n), lambda i: (i, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, n), lambda i: (i, 0)),
                pl.BlockSpec((1, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((f, n), jnp.int32),
                jax.ShapeDtypeStruct((f, 1), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interp,
        )(pack_cycles(cycs), streams)
        return issue, total[:, 0]

    return jax.jit(fn)


def _probe_stream(num_banks: int) -> np.ndarray:
    """A tiny but non-trivial lane touching ACT/RD/MAC/fence paths."""
    ops = [(C.ACT, 0, 3, 0), (C.RD, 0, 0, 0), (C.PRE, 0, 0, 0),
           (C.MODE_MB, 0, 0, 0), (C.ACT_MB, 1 % num_banks, 2, 0),
           (C.WR_SRF, 0, 0, 0), (C.MAC, 0, 0, 0), (C.RD_ACC, 0, 0, 0),
           (C.FENCE, 0, 0, 0), (C.MODE_SB, 0, 0, 0)]
    s = np.zeros((16, 4), dtype=np.int32)
    s[: len(ops)] = np.asarray(ops, dtype=np.int32)
    return s


def pallas_lane_supported() -> bool:
    """Whether the lane kernel runs here — the platform rule ``auto``
    follows.

    It runs only where Pallas interprets it: on TPU, where kernels
    compile natively, Mosaic refuses it (see the module docstring), so
    ``auto`` means the scan resolver there until the kernel is
    rewritten.
    """
    return default_interpret()


@functools.lru_cache(maxsize=None)
def require_lane_kernel() -> None:
    """Build and run the kernel on one probe lane, demanding bit-identity
    with the scan resolver.

    Raises whatever the compiler raises where the kernel cannot be built
    (an explicit ``pallas`` request then fails with that reason instead
    of running another backend), and ``RuntimeError`` on a mismatch.
    Cached per process once it has passed.
    """
    cyc = DEFAULT_SYSTEM.derive_cycles()
    stream = _probe_stream(cyc.num_banks).T[None]
    cycs = _engine.stack_cycles([cyc])
    ref_iss, ref_tot = _engine._fleet_resolver(cyc.num_banks)(cycs, stream)
    got_iss, got_tot = make_lane_resolver(cyc.num_banks)(cycs, stream)
    if not (np.array_equal(np.asarray(got_iss), np.asarray(ref_iss))
            and np.array_equal(np.asarray(got_tot), np.asarray(ref_tot))):
        raise RuntimeError("pallas lane kernel disagrees with the scan "
                           "resolver on the probe lane")
