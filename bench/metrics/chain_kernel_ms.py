"""Device time of the chain kernel (``adaptive_filter_chain`` and its
``_skip`` launch; the fused compaction runs inside it) per step, from the
trace."""


def read(run):
    t = run.trace
    k = None if t is None else t.kernel_s("adaptive_filter_chain")
    if k is None:
        return None
    return k / run.window.steps * 1e3
