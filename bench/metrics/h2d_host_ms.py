"""Host time of the batch's host→device transfer per step: the program's
``session.h2d`` span around ``jnp.asarray(batch)`` inside
``session.step`` (the part of ``h2d_ms`` that holds the host thread)."""

from bench import program_spans


def read(run):
    return program_spans.per_step_ms(("session.h2d",))
