"""Host time of the step's jitted dispatches per step: the program's
``session.triage`` (the skip tier's triage) and ``session.dispatch`` (the
plain, skip, compacting or tokenizing step) spans inside
``session.step``."""

from bench import program_spans


def read(run):
    return program_spans.per_step_ms(("session.triage", "session.dispatch"))
