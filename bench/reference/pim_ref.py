"""Plain reference of the LPDDR5X-PIM command timing, and its control.

A command-by-command Python resolver of one channel's in-order stream:
every timing constraint is an explicit ``max(last_event + t, ...)``
term.  It follows the semantics of the repository's oracle
(``core/engine_ref.py``) and imports nothing of the program: the opcode
numbers, the nanosecond-to-cycle conversion and the state are its own.

``cycles(params)`` converts one device family's nanosecond timings (the
traffic file's numbers, after the sweep's scaling) to command-clock
cycles.  ``total_cycles(..., drop="RCD")`` is the control: a column
command (RD, WR, MAC) no longer waits ``tRCD`` after its row's
activate, a guarantee every family states.
"""
from __future__ import annotations

import math

NOP, ACT, PRE, PREA, RD, WR, REFAB, MODE_MB, MODE_SB = range(9)
ACT_MB, PRE_MB, WR_SRF, WR_IRF, MAC, RD_ACC, MOV_ACC, FENCE = range(9, 17)
NEG = -(1 << 30)


def cycles(fam: dict) -> dict:
    """Cycle counts of one family: ``ceil(ns / tCK)`` for every time."""
    t, p = fam["timings"], fam["pim"]
    tck = 1.0 / t["ck_ghz"]

    def ck(ns: float) -> int:
        return int(math.ceil(ns / tck - 1e-9))

    return dict(
        nb=t["num_bankgroups"] * t["banks_per_group"],
        RCD=ck(t["tRCD"]), RP=ck(t["tRP"]), RAS=ck(t["tRAS"]),
        RC=ck(t["tRC"]), RRD=ck(t["tRRD"]), FAW=ck(t["tFAW"]),
        CCD=t["tCCD_ck"], RTP=ck(t["tRTP"]), WR=ck(t["tWR"]),
        WTR=ck(t["tWTR"]), RTW=ck(t["tRTW_bus"]), RL=ck(t["tRL"]),
        WL=ck(t["tWL"]), BURST=t["tCCD_ck"], RFC=ck(t["tRFCab"]),
        ACT=t["cmd_act_ck"], CAS=t["cmd_cas_ck"], PRE=t["cmd_pre_ck"],
        MODE=ck(p["tMODE_ns"]), MACI=p["mac_interval_ck"],
        MACCMD=p["mac_cmd_ck"], MACPIPE=p["mac_pipe_ck"],
        MACWR=p["mac_wr_gap_ck"], SRFI=p["srf_wr_interval_ck"],
        RRDMB=p["tRRD_mb_ck"], MOV=p["mov_acc_ck"],
        FENCE=ck(fam["fence_ns"]), tck_ns=tck)


def total_cycles(c: dict, stream, drop: str | None = None) -> int:
    """Cycles until the channel drains after ``stream`` ((N, 4) ints);
    ``drop`` names one timing that is then not enforced."""
    if drop is not None:
        c = dict(c, **{drop: 0})
    nb = c["nb"]
    open_row = [-1] * nb
    ready_act = [0] * nb
    act_cycle = [NEG] * nb
    rd_cycle = [NEG] * nb
    wr_end = [NEG] * nb
    faw = [NEG] * 4
    faw_i = 0
    last_act = last_actmb = last_cas = last_mac = NEG
    bus_free = bus_dir = cmd_free = 0
    srf_ready = mac_pipe_end = mode_ready = drain = fence_until = 0
    for op, a, b, _col in stream.tolist():
        t0 = max(cmd_free, fence_until, mode_ready)
        if op == NOP:
            continue
        if op == ACT:
            t = max(t0, ready_act[a], act_cycle[a] + c["RC"],
                    last_act + c["RRD"], faw[faw_i] + c["FAW"])
            open_row[a] = b
            act_cycle[a] = last_act = faw[faw_i] = t
            faw_i = (faw_i + 1) % 4
            cmd_free = t + c["ACT"]
            drain = max(drain, t + c["RCD"])
        elif op == PRE:
            t = max(t0, act_cycle[a] + c["RAS"], rd_cycle[a] + c["RTP"],
                    wr_end[a] + c["WR"])
            open_row[a] = -1
            ready_act[a] = t + c["RP"]
            cmd_free = t + c["PRE"]
            drain = max(drain, t + c["RP"])
        elif op in (PREA, PRE_MB):
            t = max(t0, max(act_cycle) + c["RAS"], max(rd_cycle) + c["RTP"],
                    max(wr_end) + c["WR"], last_mac + c["RTP"])
            open_row = [-1] * nb
            ready_act = [t + c["RP"]] * nb
            cmd_free = t + c["PRE"]
            drain = max(drain, t + c["RP"])
        elif op == RD:
            turn = c["WTR"] if bus_dir == 1 else 0
            t = max(t0, act_cycle[a] + c["RCD"], last_cas + c["CCD"],
                    bus_free + turn - c["RL"], wr_end[a] + c["WTR"])
            rd_cycle[a] = last_cas = t
            bus_free = t + c["RL"] + c["BURST"]
            bus_dir = 0
            cmd_free = t + c["CAS"]
            drain = max(drain, bus_free)
        elif op == WR:
            turn = c["RTW"] if bus_dir == 0 else 0
            t = max(t0, act_cycle[a] + c["RCD"], last_cas + c["CCD"],
                    bus_free + turn - c["WL"])
            wr_end[a] = t + c["WL"] + c["BURST"]
            last_cas = t
            bus_free = wr_end[a]
            bus_dir = 1
            cmd_free = t + c["CAS"]
            drain = max(drain, bus_free)
        elif op == REFAB:
            t = max(t0, max(ready_act))
            ready_act = [t + c["RFC"]] * nb
            cmd_free = t + c["ACT"]
            drain = max(drain, t + c["RFC"])
        elif op in (MODE_MB, MODE_SB):
            t = max(t0, drain)
            mode_ready = t + c["MODE"]
            cmd_free = t + c["ACT"]
            drain = max(drain, t + c["MODE"])
        elif op == ACT_MB:
            banks = [bg * 4 + a for bg in range(nb // 4)]
            t = max(t0, last_actmb + c["RRDMB"], last_act + c["RRD"],
                    max(ready_act[x] for x in banks),
                    max(act_cycle[x] for x in banks) + c["RC"])
            for x in banks:
                open_row[x] = b
                act_cycle[x] = t
            last_act = last_actmb = faw[faw_i] = t
            faw_i = (faw_i + 1) % 4
            cmd_free = t + c["ACT"]
            drain = max(drain, t + c["RCD"])
        elif op in (WR_SRF, WR_IRF):
            turn = c["RTW"] if bus_dir == 0 else 0
            t = max(t0, last_cas + c["SRFI"], bus_free + turn - c["WL"],
                    last_mac + c["MACWR"])
            end = t + c["WL"] + c["BURST"]
            if op == WR_SRF:
                srf_ready = max(srf_ready, end)
            last_cas = t
            bus_free = end
            bus_dir = 1
            cmd_free = t + c["CAS"]
            drain = max(drain, end)
        elif op == MAC:
            t = max(t0, last_mac + c["MACI"], srf_ready,
                    max(act_cycle) + c["RCD"])
            last_mac = t
            rd_cycle = [t] * nb
            mac_pipe_end = t + c["MACPIPE"]
            cmd_free = t + c["MACCMD"]
            drain = max(drain, mac_pipe_end)
        elif op == RD_ACC:
            turn = c["WTR"] if bus_dir == 1 else 0
            t = max(t0, mac_pipe_end, last_cas + c["CCD"],
                    bus_free + turn - c["RL"])
            last_cas = t
            bus_free = t + c["RL"] + c["BURST"]
            bus_dir = 0
            cmd_free = t + c["CAS"]
            drain = max(drain, bus_free)
        elif op == MOV_ACC:
            t = max(t0, mac_pipe_end, last_cas + c["CCD"])
            wr_end = [max(w, t + c["MOV"]) for w in wr_end]
            last_cas = t
            cmd_free = t + c["CAS"]
            drain = max(drain, t + c["MOV"])
        elif op == FENCE:
            t = drain + c["FENCE"]
            fence_until = cmd_free = drain = t
        else:
            raise ValueError(f"unknown opcode {op}")
    return drain
