"""Device time of the skip tier's ``adaptive_filter_tile_stats`` kernel
per step, from the trace."""


def read(run):
    t = run.trace
    k = None if t is None else t.kernel_s("adaptive_filter_tile_stats")
    if k is None:
        return None
    return k / run.window.steps * 1e3
