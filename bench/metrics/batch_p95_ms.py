"""95th percentile, over every batch completed in the window, of the time
from handing the host batch to ``session.step`` to its mask being on the
host (host clock)."""

import numpy as np


def read(run):
    w = run.window
    if w.latency_kind != "batch" or not w.latencies_s:
        return None
    return float(np.percentile(w.latencies_s, 95)) * 1e3
