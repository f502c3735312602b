"""Share of the window, in percent, in which no operation ran on the
device (profiler trace, mean over the cell's chips)."""


def read(run):
    a, b = run.window_ns
    return 100.0 * (1.0 - run.trace.busy_s(a, b) / run.window_s)
