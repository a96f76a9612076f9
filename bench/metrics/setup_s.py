"""Process start to the first measured step: imports, device start-up,
data, session build, compilation or cache loads, warm-up (host clock)."""


def read(run):
    return run.setup_s
