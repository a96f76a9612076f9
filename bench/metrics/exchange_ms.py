"""Device time of the epoch exchange's collectives (the deferred psum at
each epoch boundary) per boundary, mean over the cell's chips, from the
trace."""


def read(run):
    t = run.trace
    c = None if t is None else t.collective_s()
    n = run.counts.get("boundaries")
    if c is None or not n:
        return None
    return c / n * 1e3
