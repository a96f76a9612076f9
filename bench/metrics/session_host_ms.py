"""Mean host time inside ``session.step`` per step (the program's own
``StepResult.gate_s``): dispatch, and the host→device copy where the batch
comes from the host. It ends before the device work."""


def read(run):
    gate = [g for g in run.counts["gate_s"] if g is not None]
    return sum(gate) / len(gate) * 1e3 if gate else None
