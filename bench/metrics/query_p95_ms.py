"""95th percentile, over every query completed in the window, of the time
from the query's first partition step to its answer being on the host
(host clock)."""

import numpy as np


def read(run):
    w = run.window
    if w.latency_kind != "query" or not w.latencies_s:
        return None
    return float(np.percentile(w.latencies_s, 95)) * 1e3
