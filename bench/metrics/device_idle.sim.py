"""Share of the traced sample, in percent, in which no operation ran on
the device.  Sweep cells trace a sample that starts at a sweep boundary
(see ``sweep_driver.py``): the host path of that sweep, then the start of
its resolver scan.  The profiler cannot keep a whole window of the
scan's per-iteration events."""


def read(run):
    a, b = run.window_ns
    return 100.0 * (1.0 - run.trace.busy_s(a, b) / run.window_s)
