"""Scheduler: chip-0 idle time in the traced window while the innermost host
span was a ``sched.*`` or ``req.*`` one, per step (``bench/spans.py``)."""

from bench import spans


def read(run):
    return spans.read(run, "sched_idle_ms")
