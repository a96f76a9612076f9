"""Host time spent waiting on sanctioned device→host syncs per step: the
program's ``session.sync`` spans (the deferred exchange's boundary check,
the skip tier's ambiguous-tile count, the auto tuners); 0.0 when the
window synced nothing."""

from bench import program_spans


def read(run):
    return program_spans.per_step_ms(("session.sync",))
