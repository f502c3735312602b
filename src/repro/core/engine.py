"""JAX cycle-accurate timing engine — the fleet execution core.

The same semantics as ``engine_ref.RefEngine`` expressed as a
``jax.lax.scan`` over the command stream (block by block, see
``_lane_runner``) with a branchless, opcode-masked step (see
``_build_step``).  The scan carry is a :class:`ChannelState` pytree; each
step emits the command's issue cycle.

Unlike the original per-spec design (one compilation per ``TimingCycles``
instance), the timing configuration is a *traced* pytree argument of the
scan step: :class:`TimingCycles` is registered as a JAX dataclass whose
cycle fields are data leaves and whose ``num_banks`` (which fixes array
shapes) is static metadata.  One jitted resolver per bank count is
``vmap``-ed over the flat *(design point x channel)* fleet axis, with both
the stream length and the fleet width padded to power-of-two buckets, so
the total number of XLA compilations is O(log points * log length) and —
critically — independent of how many distinct ``SystemSpec`` variants are
in flight.  That is what makes design-space sweeps (Fig. 4 grids, HW-knob
surfaces) dispatch-bound work into one engine call: ~10^6-10^7 resolved
commands/s per CPU core, and on TPU the fleet axis is the data-parallel
axis of the sweep (DESIGN.md §2.1/§2.3).
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import threading
from collections import OrderedDict
from typing import Callable, Hashable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro import obs
from . import commands as C
from . import faults
from .timing import TimingCycles

NEG = -(1 << 30)
I32 = jnp.int32


@dataclasses.dataclass
class ChannelState:
    """Per-channel timing state carried through the scan (a pytree).

    Vector fields are per-bank (length ``num_banks``) except ``faw``
    (sliding window of the last four ACT issue cycles).
    """

    open_row: jax.Array
    ready_act: jax.Array
    act_cycle: jax.Array
    rd_cycle: jax.Array
    wr_end: jax.Array
    faw: jax.Array
    faw_i: jax.Array
    last_act: jax.Array
    last_actmb: jax.Array
    last_cas: jax.Array
    bus_free: jax.Array
    bus_dir: jax.Array
    cmd_free: jax.Array
    last_mac: jax.Array
    srf_ready: jax.Array
    mac_pipe_end: jax.Array
    mode: jax.Array
    mode_ready: jax.Array
    drain: jax.Array
    fence_until: jax.Array


jax.tree_util.register_dataclass(
    ChannelState,
    data_fields=[f.name for f in dataclasses.fields(ChannelState)],
    meta_fields=[],
)

_replace = dataclasses.replace


def _fresh_state(nb: int) -> ChannelState:
    z = jnp.zeros((), I32)
    neg = jnp.full((), NEG, I32)
    return ChannelState(
        open_row=jnp.full((nb,), -1, I32),
        ready_act=jnp.zeros((nb,), I32),
        act_cycle=jnp.full((nb,), NEG, I32),
        rd_cycle=jnp.full((nb,), NEG, I32),
        wr_end=jnp.full((nb,), NEG, I32),
        faw=jnp.full((4,), NEG, I32),
        faw_i=z, last_act=neg, last_actmb=neg, last_cas=neg,
        bus_free=z, bus_dir=z, cmd_free=z,
        last_mac=neg, srf_ready=z, mac_pipe_end=z,
        mode=z, mode_ready=z, drain=z, fence_until=z,
    )


def _build_step(nb: int):
    """Build the scan step for ``nb`` banks.

    The step is *branchless*: instead of a ``lax.switch`` over 17 opcode
    branches (each of which vmap would execute in full, building 17
    alternate channel states per cycle), the issue-time candidates of all
    opcodes are computed from shared subexpressions and gathered by
    opcode, and every state field is written exactly once under opcode
    masks.  ``c`` is a *traced* :class:`TimingCycles` — the timing
    configuration is data, not a compile-time constant, so one
    compilation serves every spec variant.  Semantics are bit-identical
    to ``engine_ref.RefEngine`` (the oracle tests enforce this).
    """
    bank_ids = jnp.arange(nb, dtype=I32)

    def step(c, st, cmd):
        op, a, b, _col = cmd[0], cmd[1], cmd[2], cmd[3]

        # ---- opcode predicates (scalars) -------------------------------
        is_nop = op == C.NOP
        is_act = op == C.ACT
        is_pre = op == C.PRE
        is_prea = (op == C.PREA) | (op == C.PRE_MB)
        is_rd = op == C.RD
        is_wr = op == C.WR
        is_refab = op == C.REFAB
        is_mode_mb = op == C.MODE_MB
        is_mode_sb = op == C.MODE_SB
        is_mode = is_mode_mb | is_mode_sb
        is_actmb = op == C.ACT_MB
        is_wrsrf = op == C.WR_SRF
        is_wrreg = is_wrsrf | (op == C.WR_IRF)
        is_mac = op == C.MAC
        is_rdacc = op == C.RD_ACC
        is_mov = op == C.MOV_ACC
        is_fence = op == C.FENCE
        is_actfam = is_act | is_actmb
        rd_bus = is_rd | is_rdacc
        wr_bus = is_wr | is_wrreg
        sets_cas = rd_bus | wr_bus | is_mov

        # ---- shared subexpressions -------------------------------------
        t0 = jnp.maximum(jnp.maximum(st.cmd_free, st.fence_until),
                         st.mode_ready)
        act_a = st.act_cycle[a]
        onehot_a = bank_ids == a
        quad = (bank_ids % 4) == a
        max_act = jnp.max(st.act_cycle)
        turn_r = jnp.where(st.bus_dir == 1, c.cWTR, 0)
        turn_w = jnp.where(st.bus_dir == 0, c.cRTW, 0)
        prea_t = jnp.maximum(
            jnp.maximum(t0, max_act + c.cRAS),
            jnp.maximum(jnp.maximum(jnp.max(st.rd_cycle) + c.cRTP,
                                    jnp.max(st.wr_end) + c.cWR),
                        st.last_mac + c.cRTP))
        mode_t = jnp.maximum(t0, st.drain)
        wrreg_t = jnp.maximum(
            jnp.maximum(t0, st.last_cas + c.cSRFI),
            jnp.maximum(st.bus_free + turn_w - c.cWL,
                        st.last_mac + c.cMACWR))

        # ---- issue-time candidates, gathered by opcode -----------------
        cand = jnp.stack([
            t0,                                                  # NOP
            jnp.maximum(jnp.maximum(t0, st.ready_act[a]),        # ACT
                        jnp.maximum(jnp.maximum(act_a + c.cRC,
                                                st.last_act + c.cRRD),
                                    st.faw[st.faw_i] + c.cFAW)),
            jnp.maximum(jnp.maximum(t0, act_a + c.cRAS),         # PRE
                        jnp.maximum(st.rd_cycle[a] + c.cRTP,
                                    st.wr_end[a] + c.cWR)),
            prea_t,                                              # PREA
            jnp.maximum(jnp.maximum(t0, act_a + c.cRCD),         # RD
                        jnp.maximum(jnp.maximum(st.last_cas + c.cCCD,
                                                st.bus_free + turn_r
                                                - c.cRL),
                                    st.wr_end[a] + c.cWTR)),
            jnp.maximum(jnp.maximum(t0, act_a + c.cRCD),         # WR
                        jnp.maximum(st.last_cas + c.cCCD,
                                    st.bus_free + turn_w - c.cWL)),
            jnp.maximum(t0, jnp.max(st.ready_act)),              # REFAB
            mode_t,                                              # MODE_MB
            mode_t,                                              # MODE_SB
            jnp.maximum(                                         # ACT_MB
                jnp.maximum(t0, st.last_actmb + c.cRRDMB),
                jnp.maximum(
                    st.last_act + c.cRRD,
                    jnp.maximum(
                        jnp.max(jnp.where(quad, st.ready_act, NEG)),
                        jnp.max(jnp.where(quad, st.act_cycle, NEG))
                        + c.cRC))),
            prea_t,                                              # PRE_MB
            wrreg_t,                                             # WR_SRF
            wrreg_t,                                             # WR_IRF
            jnp.maximum(jnp.maximum(t0, st.last_mac + c.cMACI),  # MAC
                        jnp.maximum(st.srf_ready,
                                    max_act + c.cRCD)),
            jnp.maximum(jnp.maximum(t0, st.mac_pipe_end),        # RD_ACC
                        jnp.maximum(st.last_cas + c.cCCD,
                                    st.bus_free + turn_r - c.cRL)),
            jnp.maximum(jnp.maximum(t0, st.mac_pipe_end),        # MOV_ACC
                        st.last_cas + c.cCCD),
            st.drain + c.cFENCE,                                 # FENCE
        ])
        t = cand[op]

        # Per-opcode command-bus occupancy and drain horizon (FENCE: 0 so
        # max(drain, t) == t, matching the drain=t of the branch form).
        zero = jnp.zeros((), I32)
        cmd_add = jnp.stack([
            zero, c.cACT, c.cPRE, c.cPRE, c.cCAS, c.cCAS, c.cACT,
            c.cACT, c.cACT, c.cACT, c.cPRE, c.cCAS, c.cCAS, c.cMACCMD,
            c.cCAS, c.cCAS, zero])
        rdburst = c.cRL + c.cBURST
        wrburst = c.cWL + c.cBURST
        drain_add = jnp.stack([
            zero, c.cRCD, c.cRP, c.cRP, rdburst, wrburst, c.cRFC,
            c.cMODE, c.cMODE, c.cRCD, c.cRP, wrburst, wrburst,
            c.cMACPIPE, rdburst, c.cMOV, zero])
        end_w = t + wrburst

        # ---- masked single-write updates per state field ---------------
        open_row = jnp.where(is_act & onehot_a, b, st.open_row)
        open_row = jnp.where(is_pre & onehot_a, -1, open_row)
        open_row = jnp.where(is_prea, -1, open_row)
        open_row = jnp.where(is_actmb & quad, b, open_row)

        ready_act = jnp.where(is_pre & onehot_a, t + c.cRP, st.ready_act)
        ready_act = jnp.where(is_prea, t + c.cRP, ready_act)
        ready_act = jnp.where(is_refab, t + c.cRFC, ready_act)

        act_cycle = jnp.where((is_act & onehot_a) | (is_actmb & quad), t,
                              st.act_cycle)

        rd_cycle = jnp.where(is_rd & onehot_a, t, st.rd_cycle)
        rd_cycle = jnp.where(is_mac, t, rd_cycle)

        wr_end = jnp.where(is_wr & onehot_a, end_w, st.wr_end)
        wr_end = jnp.where(is_mov, jnp.maximum(wr_end, t + c.cMOV), wr_end)

        faw = jnp.where(is_actfam, st.faw.at[st.faw_i].set(t), st.faw)
        faw_i = jnp.where(is_actfam, (st.faw_i + 1) % 4, st.faw_i)

        st = ChannelState(
            open_row=open_row,
            ready_act=ready_act,
            act_cycle=act_cycle,
            rd_cycle=rd_cycle,
            wr_end=wr_end,
            faw=faw,
            faw_i=faw_i,
            last_act=jnp.where(is_actfam, t, st.last_act),
            last_actmb=jnp.where(is_actmb, t, st.last_actmb),
            last_cas=jnp.where(sets_cas, t, st.last_cas),
            bus_free=jnp.where(rd_bus, t + rdburst,
                               jnp.where(wr_bus, end_w, st.bus_free)),
            bus_dir=jnp.where(rd_bus, 0,
                              jnp.where(wr_bus, 1, st.bus_dir)),
            cmd_free=jnp.where(is_nop, st.cmd_free, t + cmd_add[op]),
            last_mac=jnp.where(is_mac, t, st.last_mac),
            srf_ready=jnp.where(is_wrsrf,
                                jnp.maximum(st.srf_ready, end_w),
                                st.srf_ready),
            mac_pipe_end=jnp.where(is_mac, t + c.cMACPIPE,
                                   st.mac_pipe_end),
            mode=jnp.where(is_mode_mb, 1,
                           jnp.where(is_mode_sb, 0, st.mode)),
            mode_ready=jnp.where(is_mode, t + c.cMODE, st.mode_ready),
            drain=jnp.where(is_nop, st.drain,
                            jnp.maximum(st.drain, t + drain_add[op])),
            fence_until=jnp.where(is_fence, t, st.fence_until),
        )
        return st, t

    return step


# ---------------------------------------------------------------------------
# The fleet resolver: one compilation per (num_banks, fleet/length bucket).
# ---------------------------------------------------------------------------

_RESOLVERS: dict[tuple[int, int], Callable] = {}
_MESH_RESOLVERS: dict[tuple[int, Mesh, int], Callable] = {}
_PALLAS_RESOLVERS: dict[tuple[int, int], Callable] = {}

# Scan unroll factor: amortizes the compiled loop's per-step overhead
# (the step body is ~a hundred tiny int32 ops, so trip-count overhead is
# a real fraction of the cycle-resolution cost on CPU).  Bit-identical
# to unroll=1 — the parity/conformance suites run against the oracle.
# Default 4; override with configure_scan_unroll() or REPRO_SCAN_UNROLL.
_SCAN_UNROLL = 4
_SCAN_UNROLL_OVERRIDE: int | None = None


def configure_scan_unroll(n: int | None) -> int:
    """Set the scan unroll factor (None restores env/default).

    Unroll is a pure lowering knob — every value is bit-identical to
    unroll=1 (asserted by the parity suite); resolvers are cached per
    (num_banks, unroll), so flipping it never invalidates compiled
    programs for the other settings.
    """
    global _SCAN_UNROLL_OVERRIDE
    if n is not None and int(n) < 1:
        raise ValueError(f"scan unroll must be >= 1, got {n}")
    _SCAN_UNROLL_OVERRIDE = None if n is None else int(n)
    return scan_unroll()


def scan_unroll() -> int:
    """The active scan unroll factor (override > REPRO_SCAN_UNROLL > 4)."""
    if _SCAN_UNROLL_OVERRIDE is not None:
        return _SCAN_UNROLL_OVERRIDE
    env = int(os.environ.get("REPRO_SCAN_UNROLL", "0") or 0)
    return env if env >= 1 else _SCAN_UNROLL


# Commands per inner-loop block (see _lane_runner).
_BLOCK = 128


def _lane_runner(num_banks: int, unroll: int | None = None):
    """The single-lane loop ``(cyc, stream) -> (issue, total)`` for one
    bank count — the body the vmapped, shard_map and Pallas resolvers
    all wrap, so every backend shares semantics by construction.

    ``stream`` is int32 ``(4, N)``: one row per command field, commands
    along the minor axis.  An outer loop takes aligned blocks of up to
    ``_BLOCK`` commands and an inner scan steps through each block.  A
    scan over the whole command-major ``(N, 4)`` stream instead makes
    the TPU compiler copy it with 4 on its 128-wide minor axis, 32x
    padded (16 GB for four 6.3 M-command lanes, more than a v5e holds);
    per block, that copy is a few KB.
    """
    step = _build_step(num_banks)
    if unroll is None:
        unroll = scan_unroll()

    def run_one(cyc, stream):
        n = stream.shape[1]
        blk = min(_BLOCK, n & -n)          # length buckets are k * 2^j

        def run_block(j, carry):
            st, issue = carry
            block = jax.lax.dynamic_slice_in_dim(stream, j * blk, blk, 1)
            st, out = jax.lax.scan(lambda st, cmd: step(cyc, st, cmd), st,
                                   block.T, unroll=unroll)
            return st, jax.lax.dynamic_update_slice_in_dim(
                issue, out, j * blk, 0)

        st, issue = jax.lax.fori_loop(
            0, n // blk, run_block,
            (_fresh_state(num_banks), jnp.zeros((n,), I32)))
        return issue, st.drain

    return run_one


def _fleet_resolver(num_banks: int):
    """The jitted resolver for one bank count.

    ``fn(cycs, streams)`` where ``cycs`` is a :class:`TimingCycles` pytree
    stacked along the fleet axis (every data leaf shape ``(F,)``) and
    ``streams`` is int32 ``(F, 4, N)`` (field-major, see
    :func:`_lane_runner`); returns ``(issue (F, N), total (F,))``.  The
    timing configuration is traced, so the jit cache keys only on shapes
    — new spec variants reuse the existing executable.
    """
    key = (num_banks, scan_unroll())
    fn = _RESOLVERS.get(key)
    if fn is None:
        fn = jax.jit(jax.vmap(_lane_runner(num_banks, key[1])))
        _RESOLVERS[key] = fn
    return fn


def _mesh_resolver(num_banks: int, mesh: Mesh):
    """The jitted ``shard_map`` resolver for one (bank count, mesh).

    Same signature as :func:`_fleet_resolver`, but the fleet axis is a
    *mesh* axis: the ``(F, ...)`` inputs are sharded over the mesh's
    ``lanes`` dimension and every device runs the identical vmapped scan
    on its ``F / mesh.size`` rows — ONE compiled SPMD program per
    (num_banks, per-shard width bucket, length bucket), so
    :func:`compile_cache_size` stays as flat under a mesh as under the
    threaded dispatch.  Lanes are independent, so the program contains no
    collectives and results are bit-identical to the single-device path.
    """
    key = (num_banks, mesh, scan_unroll())
    fn = _MESH_RESOLVERS.get(key)
    if fn is None:
        spec = PartitionSpec(mesh.axis_names[0])
        # check_vma=False: the scan's fresh carry is mesh-invariant and
        # its output varies over ``lanes``; lanes never communicate, so
        # there is no replication for the checker to track.
        fn = jax.jit(jax.shard_map(jax.vmap(_lane_runner(num_banks, key[2])),
                                   mesh=mesh, in_specs=(spec, spec),
                                   out_specs=(spec, spec), check_vma=False))
        _MESH_RESOLVERS[key] = fn
    return fn


def _pallas_resolver(num_banks: int):
    """The jitted Pallas resolver for one bank count.

    Same signature as :func:`_fleet_resolver`; the fleet axis becomes the
    Pallas grid and the per-lane channel state lives in VMEM/registers
    for the whole command stream (see ``kernels/lane_scan.py``).  Lazily
    imported so the engine has no hard dependency on the kernels package.
    """
    from repro.kernels import lane_scan

    key = (num_banks, scan_unroll())
    fn = _PALLAS_RESOLVERS.get(key)
    if fn is None:
        fn = lane_scan.make_lane_resolver(num_banks, unroll=key[1])
        _PALLAS_RESOLVERS[key] = fn
    return fn


def compile_cache_size() -> int:
    """Number of engine executables compiled so far (all resolvers).

    One per (num_banks, fleet-width bucket, stream-length bucket) — for
    the mesh resolvers the width bucket is *per shard*, so the count is
    independent of the mesh size; the traced timing configuration
    contributes nothing, which is what the fleet tests assert across
    ``SystemSpec`` variants.
    """
    return (sum(fn._cache_size() for fn in _RESOLVERS.values())
            + sum(fn._cache_size() for fn in _MESH_RESOLVERS.values())
            + sum(fn._cache_size() for fn in _PALLAS_RESOLVERS.values()))


# ---------------------------------------------------------------------------
# Lane-resolver backend selection: "scan" is the vmapped lax.scan family
# (single-device / threaded / shard_map dispatch above it); "pallas" swaps
# the per-slab executable for the Pallas lane kernel, keeping the dedupe /
# LRU / slab machinery identical.  "auto" is decided from the platform in
# ONE place, resolved_lane_backend: pallas where the kernel runs
# (lane_scan.pallas_lane_supported), scan elsewhere.  An explicit
# "pallas" request never becomes another backend: where the kernel cannot
# be built it raises the compiler's reason.
#
# Execution follows the DEGRADATION LADDER pallas → mesh → threaded →
# single-device scan (see _ladder_rungs): a run starts on the highest
# configured rung and, on failure — a kernel raise, a mesh shard loss, an
# injected chaos fault — steps down after bounded retries, with a circuit
# breaker skipping rungs that have failed K consecutive resolves.
# Because every rung is bit-identical by contract, a degraded resolve
# returns byte-exact results; every step-down is recorded as a
# structured event (core/faults.py).
#
# Backend configuration lives in a BackendScope: the process keeps ONE
# default scope behind the classic configure_* API (so single-cell
# callers never see scopes), and serving cells each carry their own —
# one cell's mesh, backend, ladder and circuit breaker can no longer
# bleed into the other's (the old process-global _LANE_BACKEND /
# _LANE_MESH state meant a breaker tripped by prefill-side faults
# skipped that rung for decode too).
# ---------------------------------------------------------------------------

_LANE_BACKENDS = ("scan", "pallas", "auto")


@dataclasses.dataclass
class BackendScope:
    """One lane-execution scope: requested backend, lane mesh, device
    cap and its OWN circuit breaker.

    ``None`` fields fall through to the same environment defaults the
    old module globals used (``REPRO_LANE_BACKEND`` /
    ``REPRO_LANE_DEVICES``), so a fresh scope behaves exactly like an
    unconfigured process.  Serving cells construct one scope each and
    activate it around their tick work (:class:`backend_scope`), which
    is what keeps a prefill-side degradation or breaker trip from ever
    changing the decode cell's ladder.  ``mesh`` accepts an ``int`` n
    (builds a 1-D ``lanes`` mesh over the first n devices) or a
    prebuilt 1-D mesh.
    """

    backend: str | None = None
    mesh: "Mesh | int | None" = None
    max_devices: int | None = None
    breaker: "faults.CircuitBreaker | None" = dataclasses.field(
        default_factory=faults.CircuitBreaker)
    name: str = ""

    def __post_init__(self):
        if self.backend is not None:
            b = str(self.backend).lower()
            if b not in _LANE_BACKENDS:
                raise ValueError(f"lane backend must be one of "
                                 f"{_LANE_BACKENDS}, got {self.backend!r}")
            self.backend = b
        if self.mesh is not None:
            if isinstance(self.mesh, int):
                self.mesh = build_lane_mesh(self.mesh)
            elif len(self.mesh.axis_names) != 1:
                raise ValueError(f"lane mesh must be 1-D, got axes "
                                 f"{self.mesh.axis_names}")

    def scope_breaker(self) -> "faults.CircuitBreaker":
        """This scope's breaker; the default scope (``breaker=None``)
        delegates to the process breaker so ``faults.configure_breaker``
        and the chaos harness keep their classic behavior."""
        return (self.breaker if self.breaker is not None
                else faults.backend_breaker())

    def describe(self) -> dict:
        """Trace-exportable view: what this scope resolves to here."""
        return dict(
            name=self.name,
            backend=lane_backend(self),
            resolved=resolved_lane_backend(self),
            mesh=(None if self.mesh is None else int(self.mesh.size)),
            devices=len(lane_devices(self)),
            rungs=ladder_rungs(self),
            breaker=self.scope_breaker().info())


# The process-default scope: what the classic configure_* API mutates
# and what resolve_lanes runs under when no scope is active.  Its
# breaker field stays None so faults.configure_breaker() keeps
# governing the default ladder.
_DEFAULT_SCOPE = BackendScope(breaker=None, name="default")
_ACTIVE_SCOPE: BackendScope | None = None


def default_backend_scope() -> BackendScope:
    """The process-default scope (the classic configure_* target)."""
    return _DEFAULT_SCOPE


def active_backend_scope() -> BackendScope:
    """The scope lane resolution runs under right now — the default
    scope unless a :class:`backend_scope` block is active."""
    return _ACTIVE_SCOPE if _ACTIVE_SCOPE is not None else _DEFAULT_SCOPE


class backend_scope:
    """Context manager: activate ``scope`` for every lane resolve in
    the block (``None`` = the process-default scope), then restore.

    Serving cells wrap their per-tick work in this so planner →
    executor → resolve_fleet chains land in the cell's scope without
    plumbing a parameter through every layer."""

    def __init__(self, scope: BackendScope | None):
        self._scope = scope

    def __enter__(self) -> BackendScope:
        global _ACTIVE_SCOPE
        self._prev = _ACTIVE_SCOPE
        _ACTIVE_SCOPE = self._scope
        return active_backend_scope()

    def __exit__(self, *exc):
        global _ACTIVE_SCOPE
        _ACTIVE_SCOPE = self._prev
        return False


def reset_backend_scopes() -> None:
    """Deactivate any active scope and restore the default scope's
    fields to boot state (tests/conftest.py hygiene)."""
    global _ACTIVE_SCOPE
    _ACTIVE_SCOPE = None
    _DEFAULT_SCOPE.backend = None
    _DEFAULT_SCOPE.mesh = None
    _DEFAULT_SCOPE.max_devices = None


def configure_lane_backend(name: str | None) -> str:
    """Select the default scope's lane-resolver backend ("scan" |
    "pallas" | "auto").

    ``None`` restores the default (REPRO_LANE_BACKEND env var, else
    "scan").  Returns the *requested* backend; the capability-checked
    choice is :func:`resolved_lane_backend`.
    """
    if name is not None:
        name = str(name).lower()
        if name not in _LANE_BACKENDS:
            raise ValueError(f"lane backend must be one of "
                             f"{_LANE_BACKENDS}, got {name!r}")
    _DEFAULT_SCOPE.backend = name
    return lane_backend()


def lane_backend(scope: BackendScope | None = None) -> str:
    """The requested lane backend (scope > env > "scan")."""
    scope = active_backend_scope() if scope is None else scope
    if scope.backend is not None:
        return scope.backend
    env = os.environ.get("REPRO_LANE_BACKEND", "").lower()
    return env if env in _LANE_BACKENDS else "scan"


def resolved_lane_backend(scope: BackendScope | None = None) -> str:
    """The backend slabs will actually run on: "scan" or "pallas".

    The one place "auto" is decided: pallas on a platform where the lane
    kernel runs, scan elsewhere (today: scan on TPU, where Mosaic refuses
    the kernel).  An explicit "pallas" request builds the kernel first
    and raises the compiler's reason where it cannot be built.
    """
    req = lane_backend(scope)
    if req == "scan":
        return "scan"
    from repro.kernels import lane_scan
    if req == "auto":
        return "pallas" if lane_scan.pallas_lane_supported() else "scan"
    lane_scan.require_lane_kernel()
    return "pallas"


class lane_backend_scope:
    """Context manager: run lane resolution on ``name``, then restore the
    previous backend (benchmarks, parity tests)."""

    def __init__(self, name: str | None):
        self._name = name

    def __enter__(self):
        self._prev = _DEFAULT_SCOPE.backend
        return configure_lane_backend(self._name)

    def __exit__(self, *exc):
        _DEFAULT_SCOPE.backend = self._prev
        return False


def _length_bucket(n: int) -> int:
    """Pad stream lengths to {2^k, 3*2^(k-2)} buckets (>= 16).

    The intermediate 3/4 point keeps the NOP-tail waste under 1.5x (vs 2x
    for pure powers of two); the extra executables are cheap because they
    are shared across every spec variant.
    """
    n = max(n, 1)
    b = 1 << max(4, (n - 1).bit_length())
    three_q = (3 * b) // 4
    return three_q if (n <= three_q and three_q >= 16) else b


# Widest fleet slab per engine call: beyond this the per-step state no
# longer fits cache and per-lane cost rises again, so larger groups are
# split into <=_MAX_WIDTH chunks instead of padded to the next power.
_MAX_WIDTH = 128


def _fleet_bucket(n: int) -> int:
    """Pad the fleet width to powers of two (>= 4) to bound recompiles."""
    return 1 << max(2, (max(n, 1) - 1).bit_length())


def stack_cycles(cycs: Sequence[TimingCycles]) -> TimingCycles:
    """Stack timing configs leaf-wise into one fleet-axis pytree.

    All configs must share ``num_banks`` (static metadata — it fixes the
    channel-state shapes).
    """
    return jax.tree.map(lambda *xs: jnp.asarray(xs), *cycs)


@dataclasses.dataclass
class FleetResult:
    """Resolved timing for one fleet point (one spec + channel streams).

    ``issue`` entries are ``None`` when the fleet was resolved with
    ``need_issue=False`` (totals-only — the sweep/serving fast path).
    """

    issue: list[np.ndarray | None]  # per-channel issue cycles, true lengths
    totals: np.ndarray              # (n_channels,) int32 total cycles


# ---------------------------------------------------------------------------
# Resolved-lane LRU: (TimingCycles, stream key) -> (total, issue | None).
#
# Serving loops (per-step PIM telemetry, offload plan grids) re-resolve the
# *same* lanes every decode step / replan; with planner-provided structural
# keys the repeat costs a dict lookup instead of an engine dispatch.  Totals
# are always cached; issue arrays only up to ``_LANE_ISSUE_BYTES`` so the
# cache stays memory-light (totals are what the sweep/serving layers use).
# ---------------------------------------------------------------------------

# Entries are (total, issue | None, integrity tag): the tag is a cheap
# constant-time checksum verified on every hit, so a poisoned entry —
# bit-flipped totals, truncated issue arrays — is detected and the lane
# falls back to a cold resolve instead of serving stale timing.
_LANE_CACHE: "OrderedDict[tuple, tuple[int, np.ndarray | None, int]]" = \
    OrderedDict()
_LANE_CACHE_LOCK = threading.Lock()
_LANE_CACHE_MAX = 4096
_LANE_ISSUE_BYTES = 1 << 16
_LANE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def _lane_tag(total: int, issue: np.ndarray | None) -> int:
    """Constant-time integrity tag over a cache entry.

    Deliberately not cryptographic — it runs on the hit fast path, so it
    mixes the total with the issue array's endpoints and size instead of
    hashing the full buffer.  That catches the realistic poison modes
    (flipped totals, truncation, swapped arrays); byte-level interior
    corruption of a cached issue array is out of scope.
    """
    h = (int(total) * 0x9E3779B1) & 0xFFFFFFFF
    if issue is not None and issue.size:
        h ^= (int(issue[0]) * 31 + int(issue[-1]) * 17
              + int(issue.size)) & 0xFFFFFFFF
    return h


def configure_lane_cache(maxsize: int) -> None:
    """Set the lane-cache capacity (entries); 0 disables caching.

    Calling with the capacity already in effect is a no-op: entries AND
    the hit/miss/eviction counters survive, so policies that account
    against the counters (the sticky epoch watches ``misses``) are not
    skewed by a redundant reconfiguration.  A capacity *change* keeps the
    old semantics — entries dropped, counters zeroed — which is also the
    explicit fresh-state escape hatch (or use :func:`lane_cache_reset`).
    """
    global _LANE_CACHE_MAX
    maxsize = max(0, int(maxsize))
    with _LANE_CACHE_LOCK:
        if maxsize == _LANE_CACHE_MAX:
            return
        _LANE_CACHE_MAX = maxsize
        _LANE_CACHE.clear()
        for k in _LANE_STATS:
            _LANE_STATS[k] = 0


def lane_cache_reset() -> None:
    """Drop every cached lane AND zero the counters (capacity survives).

    The test/benchmark fresh-state primitive now that re-configuring an
    unchanged capacity no longer clears."""
    with _LANE_CACHE_LOCK:
        _LANE_CACHE.clear()
        for k in _LANE_STATS:
            _LANE_STATS[k] = 0


def lane_cache_clear() -> None:
    """Drop every cached lane (capacity and stats counters survive)."""
    with _LANE_CACHE_LOCK:
        _LANE_CACHE.clear()


def lane_cache_info() -> dict:
    """Lane-LRU counters.  ``misses`` is the fleet-resolve count signal
    serving policies watch: a replan against a warm cache leaves it
    untouched, so a growing miss count means real engine work happened
    (cache cleared, capacity pressure, or genuinely new lanes)."""
    with _LANE_CACHE_LOCK:
        return dict(size=len(_LANE_CACHE), maxsize=_LANE_CACHE_MAX,
                    hits=_LANE_STATS["hits"], misses=_LANE_STATS["misses"],
                    evictions=_LANE_STATS["evictions"])


def lane_cache_export() -> list[tuple]:
    """Snapshot the lane LRU as ``[(key, total, issue | None), ...]`` in
    LRU order (oldest first, so re-importing preserves eviction order).

    Keys are ``(TimingCycles, 0, structural key)`` / ``(TimingCycles, 1,
    length, byte digest)`` tuples — plain frozen dataclasses, enums and
    bytes, so the snapshot pickles (see ``core/warmstart.py`` for the
    versioned, fingerprinted on-disk format).
    """
    with _LANE_CACHE_LOCK:
        return [(k, total, issue)
                for k, (total, issue, _tag) in _LANE_CACHE.items()]


def lane_cache_import(entries: Iterable[tuple]) -> int:
    """Insert exported entries into the lane LRU; returns the count kept.

    Deliberately silent on the stats counters: warm-starting a process
    from a snapshot is not engine work, so policies watching ``misses``
    see the same world as after an in-process warm-up.  Entries beyond
    capacity evict oldest-first without bumping the eviction counter.
    """
    n = 0
    with _LANE_CACHE_LOCK:
        if _LANE_CACHE_MAX <= 0:
            return 0
        for key, total, issue in entries:
            if issue is not None:
                issue = np.asarray(issue)
                issue.setflags(write=False)
            total = int(total)
            _LANE_CACHE[key] = (total, issue, _lane_tag(total, issue))
            _LANE_CACHE.move_to_end(key)
            n += 1
        while len(_LANE_CACHE) > _LANE_CACHE_MAX:
            _LANE_CACHE.popitem(last=False)
            n -= 1
    return n


def lane_cache_touch(pairs: Iterable[tuple]) -> int:
    """Mark structurally-keyed lanes most-recently-used; returns hits.

    ``pairs`` are ``(TimingCycles, structural key)`` — the identity a
    planner hands :func:`resolve_lanes` via ``keys`` (byte-hash-keyed
    entries cannot be addressed without their bytes and are not the use
    case).  Present entries move to the MRU end of the lane LRU; absent
    ones are ignored.  This is the eviction shield for *hot small-shape
    lanes*: a speculative-decode serve touches its tiny draft-GEMV
    lanes every tick, so capacity pressure from big heterogeneous spec
    grids evicts cold sweep lanes instead of the lanes the next tick
    needs.  Deliberately silent on the hit/miss counters — touching is
    not engine work, and policies watching ``misses`` (sticky epochs)
    must not see phantom activity.
    """
    n = 0
    with _LANE_CACHE_LOCK:
        for cyc, key in pairs:
            ukey = (cyc, 0, key)
            if ukey in _LANE_CACHE:
                _LANE_CACHE.move_to_end(ukey)
                n += 1
    return n


def _lane_cache_get(key, need_issue: bool):
    if _LANE_CACHE_MAX <= 0:
        return None
    with _LANE_CACHE_LOCK:
        ent = _LANE_CACHE.get(key)
        if ent is None or (need_issue and ent[1] is None):
            _LANE_STATS["misses"] += 1
            return None
        total, issue, tag = ent
        if tag != _lane_tag(total, issue):
            # Poisoned entry: evict and fall back cold — never serve a
            # stale lane.  Counted as a miss (the caller re-resolves).
            del _LANE_CACHE[key]
            _LANE_STATS["misses"] += 1
            faults.record_event("lane_cache", "detect",
                                "poisoned entry evicted (tag mismatch)")
            return None
        _LANE_CACHE.move_to_end(key)
        _LANE_STATS["hits"] += 1
        return (total, issue)


def _lane_cache_put(key, total: int, issue: np.ndarray | None) -> None:
    if _LANE_CACHE_MAX <= 0:
        return
    if issue is not None and issue.nbytes > _LANE_ISSUE_BYTES:
        issue = None
    with _LANE_CACHE_LOCK:
        prev = _LANE_CACHE.get(key)
        if issue is None and prev is not None:
            issue = prev[1]          # never downgrade a cached issue array
        _LANE_CACHE[key] = (total, issue, _lane_tag(total, issue))
        _LANE_CACHE.move_to_end(key)
        while len(_LANE_CACHE) > _LANE_CACHE_MAX:
            _LANE_CACHE.popitem(last=False)
            _LANE_STATS["evictions"] += 1


def lane_cache_poison(n: int = 1, seed: int = 0) -> int:
    """Chaos hook: corrupt the totals of up to ``n`` cached entries in
    place (stale tags, so the integrity check catches them on the next
    hit or :func:`lane_cache_verify` sweep).  Returns entries poisoned.
    """
    rng = np.random.default_rng(seed)
    with _LANE_CACHE_LOCK:
        keys = list(_LANE_CACHE)
        if not keys:
            return 0
        picks = rng.choice(len(keys), size=min(int(n), len(keys)),
                           replace=False)
        for i in picks:
            total, issue, tag = _LANE_CACHE[keys[i]]
            _LANE_CACHE[keys[i]] = (total + 1 + int(rng.integers(1000)),
                                    issue, tag)
        return len(picks)


def lane_cache_verify() -> int:
    """Integrity sweep: evict every poisoned entry (tag mismatch),
    recording one ``detect`` event each; returns the eviction count.

    The scrub analogue of the per-hit check in ``_lane_cache_get`` —
    chaos timelines schedule it so detection is deterministic even for
    entries no request touches again.
    """
    with _LANE_CACHE_LOCK:
        bad = [k for k, (total, issue, tag) in _LANE_CACHE.items()
               if tag != _lane_tag(total, issue)]
        for k in bad:
            del _LANE_CACHE[k]
    for _ in bad:
        faults.record_event("lane_cache", "detect",
                            "poisoned entry evicted (scrub)")
    return len(bad)


# ---------------------------------------------------------------------------
# Multi-device lane sharding: slabs are load-balanced (greedy, by padded
# step count) across the visible JAX devices and dispatched from one
# worker thread per device — the lane axis is embarrassingly parallel, so
# results are bit-identical to the single-device path.  On a stock CPU
# backend there is exactly one device (single-device fallback, no threads);
# ``--xla_force_host_platform_device_count=N`` turns a multi-core host
# into an N-device fleet (how CI and the benchmarks exercise this).
# ---------------------------------------------------------------------------

def configure_lane_devices(n: int | None) -> None:
    """Cap the devices the default scope shards over (None = env/all)."""
    _DEFAULT_SCOPE.max_devices = n


def lane_devices(scope: BackendScope | None = None) -> list:
    """Devices the lane resolver shards over (default-backend order)."""
    devs = jax.devices()
    scope = active_backend_scope() if scope is None else scope
    n = scope.max_devices
    if n is None:
        n = int(os.environ.get("REPRO_LANE_DEVICES", "0") or 0) or len(devs)
    return devs[: max(1, min(n, len(devs)))]


# ---------------------------------------------------------------------------
# Mesh-sharded lane execution: when a 1-D ``lanes`` mesh is configured,
# every bucketed slab resolves as ONE jitted shard_map program whose fleet
# axis is sharded over the mesh — the compiled-program-per-(banks, bucket)
# story of the ROADMAP's fleet axis at any device count.  The thread-per-
# device dispatch above remains the fallback and the parity oracle
# (tests/test_mesh.py asserts bit-identity between the two).
# ---------------------------------------------------------------------------

def build_lane_mesh(n: int) -> Mesh:
    """Construct (without configuring) a 1-D ``lanes`` mesh over the
    first ``n`` visible devices — the one place that validates lane-mesh
    sizes (``launch.mesh.make_lane_mesh`` delegates here)."""
    devs = jax.devices()
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"lane mesh size {n} needs 1..{len(devs)} of the "
            f"visible devices (hint: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n})")
    return Mesh(np.array(devs[:n]), ("lanes",))


def configure_lane_mesh(mesh: "Mesh | int | None") -> Mesh | None:
    """Select the default scope's mesh backend for lane resolution.

    ``None`` restores the threaded fallback; an ``int`` n builds a 1-D
    ``lanes`` mesh over the first n visible devices; a prebuilt 1-D
    :class:`jax.sharding.Mesh` is used as-is (its single axis is the lane
    axis, whatever its name).  Returns the configured mesh (or None).
    """
    if mesh is None:
        _DEFAULT_SCOPE.mesh = None
        return None
    if isinstance(mesh, int):
        mesh = build_lane_mesh(mesh)
    if len(mesh.axis_names) != 1:
        raise ValueError(f"lane mesh must be 1-D, got axes "
                         f"{mesh.axis_names}")
    _DEFAULT_SCOPE.mesh = mesh
    return mesh


def lane_mesh(scope: BackendScope | None = None) -> Mesh | None:
    """The configured lane mesh (None = threaded dispatch)."""
    scope = active_backend_scope() if scope is None else scope
    return scope.mesh


class lane_mesh_scope:
    """Context manager: run lane resolution under ``mesh``, then restore
    the previous backend (used by the serve cell, benchmarks, tests)."""

    def __init__(self, mesh: "Mesh | int | None"):
        self._mesh = mesh

    def __enter__(self):
        self._prev = _DEFAULT_SCOPE.mesh
        return configure_lane_mesh(self._mesh)

    def __exit__(self, *exc):
        _DEFAULT_SCOPE.mesh = self._prev
        return False


# Padded slab rows resolved per device, counted from the result arrays'
# own shards — which device really ran each slab or mesh shard, not
# which one it was meant for.
_SLAB_ROWS: dict[str, int] = {}
_SLAB_ROWS_LOCK = threading.Lock()


def _count_slab_rows(out: jax.Array) -> None:
    with _SLAB_ROWS_LOCK:
        for shard in out.addressable_shards:
            key = str(shard.device)
            _SLAB_ROWS[key] = _SLAB_ROWS.get(key, 0) + shard.data.shape[0]


def slab_device_rows() -> dict[str, int]:
    """Padded lane rows resolved so far, per device (process lifetime)."""
    with _SLAB_ROWS_LOCK:
        return dict(_SLAB_ROWS)


def _mesh_width(n: int, m: int) -> int:
    """Global fleet width for ``n`` lanes on an ``m``-way mesh.

    The *per-shard* width is power-of-two bucketed (so the executable
    count stays O(log width), exactly like the threaded path) and every
    shard gets the same shape — the global width is ``m`` times that.
    """
    return _fleet_bucket(-(-n // m)) * m


# Padded slab buffers are reused across resolve calls (serving loops
# re-pack identical shapes every step); each shape keeps at most two
# spares.  Buffers are only recycled after the call's device arrays are
# materialized, so aliasing device_put backends stay safe.
_SLAB_POOL: dict[tuple[int, int], list[np.ndarray]] = {}
_SLAB_POOL_LOCK = threading.Lock()


def _take_slab(width: int, length: int) -> np.ndarray:
    """A zeroed ``(width, 4, length)`` slab: field-major lanes, NOP tails."""
    with _SLAB_POOL_LOCK:
        spares = _SLAB_POOL.get((width, length))
        buf = spares.pop() if spares else None
    if buf is None:
        return np.zeros((width, 4, length), dtype=np.int32)
    buf.fill(0)
    return buf


def _give_slab(buf: np.ndarray) -> None:
    key = (buf.shape[0], buf.shape[2])
    with _SLAB_POOL_LOCK:
        spares = _SLAB_POOL.setdefault(key, [])
        if len(spares) < 2:
            spares.append(buf)


def _ladder_rungs(scope: BackendScope | None = None) -> list[str]:
    """The degradation ladder for ``scope`` (default: the active
    scope), highest rung first: pallas → mesh → threaded →
    single-device scan.

    Only configured rungs appear — "pallas" when the resolved backend is
    the Pallas kernel, "mesh" when a lane mesh is configured, "threaded"
    when more than one device is visible — and "scan" is always the
    terminal rung (a single-device vmapped lax.scan needs nothing but
    the default device).  Execution starts on the first rung whose
    breaker is closed and steps down on failure; since every rung is
    bit-identical by contract, where a resolve lands never changes its
    bytes.
    """
    scope = active_backend_scope() if scope is None else scope
    rungs = []
    if resolved_lane_backend(scope) == "pallas":
        rungs.append("pallas")
    if lane_mesh(scope) is not None:
        rungs.append("mesh")
    if len(lane_devices(scope)) > 1:
        rungs.append("threaded")
    rungs.append("scan")
    return rungs


def ladder_rungs(scope: BackendScope | None = None) -> list[str]:
    """Public view of a scope's degradation ladder (highest first) —
    what the chaos harness arms fault schedules against.  With no
    argument this is the active scope's ladder (the default scope
    unless a cell's :class:`backend_scope` block is live)."""
    return _ladder_rungs(scope)


def resolve_lanes(
    lanes: Sequence[tuple[TimingCycles, np.ndarray]],
    keys: Sequence[Hashable | None] | None = None,
    need_issue: bool = True,
    scope: BackendScope | None = None,
) -> list[tuple[np.ndarray | None, int]]:
    """Resolve a flat list of (timing config, stream) lanes.

    This is the primitive under ``resolve_fleet``: lanes are deduplicated,
    grouped by ``(num_banks, length bucket)``, and each group becomes one
    vmapped engine call per <=128-lane slab with NOP tail padding
    (semantics-preserving: NOP advances nothing).  Lanes may use
    *different* ``TimingCycles`` — the config rides along the fleet axis
    as traced data.  Returns ``(issue cycles, total cycles)`` per lane,
    in input order; issue arrays are read-only (deduplicated lanes and
    the resolved-lane LRU share them).

    Backend: execution walks the degradation ladder pallas → mesh →
    threaded → single-device scan (:func:`_ladder_rungs`), starting on
    the highest configured rung — the Pallas kernel when the resolved
    backend is "pallas" (:func:`configure_lane_backend`), else ONE
    ``shard_map`` program per slab over a configured lane mesh
    (:func:`configure_lane_mesh`), else thread-dispatched slabs across
    ``lane_devices()``.  A rung that raises (kernel fault, shard loss,
    injected chaos) is retried with backoff and then stepped past, its
    breaker counting toward a trip; every rung is bit-identical by
    contract (tests/test_mesh.py, tests/test_pallas_resolver and the
    conformance battery), so a degraded resolve returns byte-exact
    results.

    ``keys`` — optional per-lane *structural* identity: a hashable value
    the planner guarantees to determine the stream bytes (equal key ==
    byte-identical stream under the same config).  Keyed lanes dedupe —
    and hit the resolved-lane LRU — without hashing megabytes of int32;
    ``None`` entries fall back to the byte hash.  Cache *misses* are
    additionally deduplicated by byte hash (one hash per unique key, not
    per lane), so structurally-distinct requests whose streams coincide
    — e.g. equal-byte baselines of different dtypes — still resolve
    once.  ``need_issue=False`` skips materializing per-command issue
    cycles (totals-only, the ``run_many``/serving path) and makes totals
    LRU hits possible for lanes whose issue arrays were too large to
    cache.

    ``scope`` — the :class:`BackendScope` to resolve under (ladder,
    mesh, devices AND circuit breaker); defaults to the active scope,
    so cells that activate their scope with :class:`backend_scope` need
    not pass it explicitly.

    Program spans (``repro.obs``): ``sim.lookup`` around the dedupe and
    LRU pass; per slab, on every rung, ``sim.pack`` (packing, configs,
    ``device_put``) and ``sim.resolve`` (resolver call, readback, store;
    ``commands`` is the slab's unpadded command count).
    """
    scope = active_backend_scope() if scope is None else scope
    lanes = list(lanes)
    with obs.span("sim.lookup", lanes=len(lanes)) as sp:
        uniq: list[list] = []              # [cyc, stream, ukey]
        lane_of: list[int] = []            # flat lane -> unique lane
        uniq_index: dict = {}
        for i, (cyc, s) in enumerate(lanes):
            k = keys[i] if keys is not None else None
            if k is not None:
                ukey = (cyc, 0, k)
            else:
                s = np.ascontiguousarray(s, dtype=np.int32)
                ukey = (cyc, 1, s.shape[0],
                        hashlib.blake2b(s.tobytes(),
                                        digest_size=16).digest())
            u = uniq_index.get(ukey)
            if u is None:
                u = len(uniq)
                uniq_index[ukey] = u
                uniq.append([cyc, s, ukey])
            lane_of.append(u)

        issues: list[np.ndarray | None] = [None] * len(uniq)
        totals = np.zeros(len(uniq), dtype=np.int32)
        misses: list[int] = []
        for u, (cyc, s, ukey) in enumerate(uniq):
            ent = _lane_cache_get(ukey, need_issue)
            if ent is not None:
                totals[u] = ent[0]
                issues[u] = ent[1] if need_issue else None
            else:
                misses.append(u)

        # Second-level dedupe of the misses by byte identity; ``todo``
        # holds one representative per distinct (config, bytes),
        # ``alias`` the cache-key lanes that share its result.
        todo: list[int] = []
        alias: dict[int, list[int]] = {}
        hash_index: dict = {}
        for u in misses:
            cyc, s, _ukey = uniq[u]
            s = np.ascontiguousarray(s, dtype=np.int32)
            uniq[u][1] = s
            hkey = (cyc, s.shape[0],
                    hashlib.blake2b(s.tobytes(), digest_size=16).digest())
            rep = hash_index.get(hkey)
            if rep is None:
                hash_index[hkey] = u
                todo.append(u)
                alias[u] = []
            else:
                alias[rep].append(u)

        groups: dict[tuple[int, int], list[int]] = {}
        for u in todo:
            cyc, s, _ukey = uniq[u]
            groups.setdefault(
                (cyc.num_banks, _length_bucket(s.shape[0])), []).append(u)
        sp.set(unique=len(uniq), hits=len(uniq) - len(misses),
               misses=len(misses), todo=len(todo))

    done: dict[int, bool] = {u: False for u in todo}

    def _store(chunk: list[int], iss, tot) -> None:
        """Write one slab's rows (true lengths) into the result arrays
        and the lane LRU — shared by every ladder rung, and the reason
        padded tail rows never contribute: only ``chunk`` rows are ever
        read back.  Marks the reps done so a rung failing mid-way hands
        only the unfinished remainder to the next rung."""
        for row, u in enumerate(chunk):
            if need_issue:
                # copy: a view would pin the whole padded slab;
                # read-only: results are shared between deduped
                # lanes and the LRU, so mutation must be an error
                arr = iss[row, : uniq[u][1].shape[0]].copy()
                arr.setflags(write=False)
                issues[u] = arr
            for v in (u, *alias[u]):
                totals[v] = tot[row]
                issues[v] = issues[u]
                _lane_cache_put(uniq[v][2], int(tot[row]), issues[u])
            done[u] = True

    def _pack(chunk: list[int], width: int, length: int, target):
        """One slab: the chunk's lanes packed into a pooled buffer and
        padded to ``width`` rows, then placed on ``target`` (a device or
        a sharding).  Returns the buffer, the placed (configs, slab) and
        the chunk's true command count."""
        with obs.span("sim.pack", width=width, length=length):
            buf = _take_slab(width, length)
            commands = 0
            for row, u in enumerate(chunk):
                s = uniq[u][1]
                buf[row, :, : s.shape[0]] = s.T
                commands += s.shape[0]
            cycs = [uniq[u][0] for u in chunk]
            cycs += [cycs[0]] * (width - len(chunk))
            placed = (jax.device_put(stack_cycles(cycs), target),
                      jax.device_put(buf, target))
        return buf, placed, commands

    def _resolve(resolver, chunk: list[int], placed, commands: int) -> None:
        with obs.span("sim.resolve", commands=commands):
            iss, tot = resolver(*placed)
            _count_slab_rows(tot)
            tot = np.asarray(tot)
            _store(chunk, np.asarray(iss) if need_issue else None, tot)

    def _pending_groups() -> dict[tuple[int, int], list[int]]:
        return {gk: left for gk, idxs in sorted(groups.items())
                if (left := [u for u in idxs if not done[u]])}

    def _run_mesh() -> None:
        # Mesh rung: every (banks, length-bucket) group runs as ONE
        # shard_map program per <=(128 x mesh) slab — the fleet axis is
        # sharded over the ``lanes`` mesh axis, the width is padded so
        # each shard gets the same power-of-two bucket, and tail rows
        # (config of lane 0, all-NOP streams) are masked by _store.
        mesh = lane_mesh(scope)
        sharding = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
        m = mesh.size
        for (nb, length), idxs in _pending_groups().items():
            for lo in range(0, len(idxs), _MAX_WIDTH * m):
                chunk = idxs[lo:lo + _MAX_WIDTH * m]
                buf, placed, commands = _pack(
                    chunk, _mesh_width(len(chunk), m), length, sharding)
                _resolve(_mesh_resolver(nb, mesh), chunk, placed, commands)
                _give_slab(buf)

    def _run_sharded(resolver, devs) -> None:
        # Chunk each group into <=128-lane slabs, then greedily balance
        # the slabs across devices by padded step count (width x
        # length).  The per-slab executable is the rung's (scan vs
        # Pallas); everything around it — dedupe, LRU, pooling,
        # dispatch — is shared.  With one device this degenerates to
        # the single-device scan (no worker threads).
        slabs: list[tuple[int, list[int], int, int]] = []
        for (nb, length), idxs in _pending_groups().items():
            for lo in range(0, len(idxs), _MAX_WIDTH):
                chunk = idxs[lo:lo + _MAX_WIDTH]
                slabs.append((nb, chunk, _fleet_bucket(len(chunk)),
                              length))
        loads = [0] * len(devs)
        assignment = [0] * len(slabs)
        for i in sorted(range(len(slabs)),
                        key=lambda j: -(slabs[j][2] * slabs[j][3])):
            d = loads.index(min(loads))
            assignment[i] = d
            loads[d] += slabs[i][2] * slabs[i][3]

        # Pack + place in the main thread (the pooled host buffer is
        # free for reuse once device_put has copied it); execute per
        # device in worker threads — jit execution releases the GIL, so
        # devices overlap.
        borrowed: list[np.ndarray] = []
        per_dev: list[list] = [[] for _ in devs]
        for i, (nb, chunk, width, length) in enumerate(slabs):
            buf, placed, commands = _pack(chunk, width, length,
                                          devs[assignment[i]])
            borrowed.append(buf)
            per_dev[assignment[i]].append((nb, chunk, placed, commands))

        def _run_dev(jobs) -> None:
            for nb, chunk, placed, commands in jobs:
                _resolve(resolver(nb), chunk, placed, commands)

        act = [jobs for jobs in per_dev if jobs]
        if len(act) <= 1:
            for jobs in act:
                _run_dev(jobs)
        else:
            errors: list[BaseException] = []

            def _worker(jobs) -> None:
                try:
                    _run_dev(jobs)
                except BaseException as e:      # re-raised below
                    errors.append(e)

            workers = [threading.Thread(target=_worker, args=(jobs,))
                       for jobs in act[1:]]
            for w in workers:
                w.start()
            try:
                _run_dev(act[0])
            finally:
                for w in workers:
                    w.join()
            if errors:
                raise errors[0]
        for buf in borrowed:
            _give_slab(buf)

    def _run_rung(rung: str) -> None:
        if rung == "mesh":
            _run_mesh()
        elif rung == "pallas":
            _run_sharded(_pallas_resolver, lane_devices(scope))
        elif rung == "threaded":
            _run_sharded(_fleet_resolver, lane_devices(scope))
        else:                                   # single-device scan
            _run_sharded(_fleet_resolver, lane_devices(scope)[:1])

    # Walk the degradation ladder: start on the highest closed rung,
    # absorb transient faults with bounded retries, step down on
    # persistent failure (counting it toward the rung's breaker).  The
    # terminal scan rung is never skipped; if IT fails after retries the
    # error propagates — there is nothing below.
    if todo:
        breaker = scope.scope_breaker()
        rungs = _ladder_rungs(scope)
        for i, rung in enumerate(rungs):
            site = "backend." + rung
            terminal = i == len(rungs) - 1
            if not terminal and breaker.tripped(site):
                faults.record_event(site, "skip", "circuit open")
                continue
            try:
                faults.retry_call(lambda: _run_rung(rung), site)
                breaker.record_success(site)
                break
            except Exception as e:  # noqa: BLE001 - ladder absorbs it
                breaker.record_failure(site)
                if terminal:
                    raise
                faults.record_event(
                    site, "degrade",
                    f"stepping down to backend.{rungs[i + 1]}: "
                    f"{type(e).__name__}: {e}")

    return [(issues[lane_of[i]], int(totals[lane_of[i]]))
            for i in range(len(lane_of))]


def resolve_fleet(
    points: Sequence[tuple[TimingCycles, Iterable[np.ndarray]]],
    keys: Sequence[Sequence[Hashable | None]] | None = None,
    need_issue: bool = True,
    scope: BackendScope | None = None,
) -> list[FleetResult]:
    """Resolve many (timing config, per-channel streams) points at once.

    Flattens the *(point x channel)* fleet into lanes, resolves them with
    one :func:`resolve_lanes` pass (dedupe + bucketed vmapped engine
    calls), and regroups per point.  This absorbs the old ``run_fleet``
    helper and is the single resolution path for every layer above.
    ``keys`` optionally carries per-point per-channel structural stream
    keys (see :func:`resolve_lanes`); ``need_issue=False`` is the
    totals-only fast path.
    """
    flat: list[tuple[TimingCycles, np.ndarray]] = []
    flat_keys: list = []
    owner: list[int] = []
    for pi, (cyc, streams) in enumerate(points):
        pkeys = keys[pi] if keys is not None else None
        for ci, s in enumerate(streams):
            flat.append((cyc, s))
            flat_keys.append(pkeys[ci] if pkeys is not None else None)
            owner.append(pi)

    resolved = resolve_lanes(flat, keys=flat_keys if keys is not None
                             else None, need_issue=need_issue, scope=scope)
    out = [FleetResult(issue=[], totals=np.zeros(0, np.int32))
           for _ in points]
    per_point: list[list[int]] = [[] for _ in points]
    for pi, (iss, tot) in zip(owner, resolved):
        out[pi].issue.append(iss)
        per_point[pi].append(tot)
    for pi, fr in enumerate(out):
        fr.totals = np.asarray(per_point[pi], dtype=np.int32)
    return out


def run_streams(cyc: TimingCycles, streams) -> tuple[np.ndarray, np.ndarray]:
    """Resolve a list/array of per-channel streams; pads to equal length."""
    if isinstance(streams, list):
        streams = C.pad_streams(streams)
    streams = np.asarray(streams, dtype=np.int32)
    if streams.ndim == 2:
        streams = streams[None]
    if streams.shape[0] == 0:
        return (np.zeros((0, streams.shape[1]), dtype=np.int32),
                np.zeros((0,), dtype=np.int32))
    fr = resolve_fleet([(cyc, list(streams))])[0]
    return np.stack(fr.issue), fr.totals
