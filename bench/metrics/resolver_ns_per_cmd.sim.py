"""Host nanoseconds per resolved command: the summed length of the
window's ``sim.resolve`` spans (per slab: resolver call, readback of the
totals, store) over the sum of their ``commands`` (each slab's unpadded
command count)."""
from spans import in_window


def read(run):
    rs = in_window(run, "sim.resolve")
    cmds = sum(r.attrs.get("commands", 0) for r in rs)
    return sum(r.t1_ns - r.t0_ns for r in rs) / cmds if cmds else None
