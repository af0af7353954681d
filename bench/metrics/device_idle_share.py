"""Share of the traced fit in which no operation ran on the device.  Not
read from a trace whose end the profiler dropped: its window would be the
fit's first moments only."""


def read(run):
    tr = run.trace
    if not tr.complete or tr.window_s <= 0 or not tr.devices:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
