"""Host→device transfer of the batch, per batch: the union of the spans
from the runtime's ``XlaLinearize`` (the host lays the batch out for the
device) to the transfer's completion, over the batches traced."""


def read(run):
    t = run.trace
    if t is None or not t.h2d_s or not t.h2d_count:
        return None
    return t.h2d_s / t.h2d_count * 1e3
