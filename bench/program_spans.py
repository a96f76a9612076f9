"""The one place the benchmark reads the program's own spans
(``repro.obs``): the ``program_span`` readers of ``bench/metrics`` divide
the time in some spans by the number of ``session.step`` spans. The
program records spans only while the profiler runs, so these readers find
something in a ``--trace 1`` run alone; a program without ``repro.obs``
gives them nothing to read."""

from __future__ import annotations


def snapshot():
    """What the program recorded, or None where it records nothing."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.snapshot()


def per_step_ms(names: tuple) -> float | None:
    """Summed duration of the spans named ``names``, per ``session.step``
    span, in ms (0.0 when none of them opened); None when no step was
    recorded."""
    snap = snapshot()
    if snap is None:
        return None
    steps = sum(s.name == "session.step" for s in snap.spans)
    if not steps:
        return None
    ns = sum(s.t1_ns - s.t0_ns for s in snap.spans if s.name in names)
    return ns / steps / 1e6
