"""Share of the traced window in which no op ran on the device (mean over
the cell's chips): 1 - union of the ``XLA Ops`` intervals / window."""


def read(run):
    t = run.trace
    if t is None or not t.window_s:
        return None
    return (1.0 - t.busy_s / t.window_s) * 100.0
