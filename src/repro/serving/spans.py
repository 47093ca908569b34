"""Host spans of the serving round, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation``: while a profiler trace
runs it is recorded on the host plane of the same trace as the device's
operations, so a reader can say what the host was doing while the chip
sat idle; otherwise it costs about a microsecond.  ``span(name, **ids)``
attaches ids (``rid``, ``slot``...) as the event's stats, and
``set_metadata(**ids)`` on the open span adds ones learned inside it.

Spans nest on the host thread: ``req.admit`` inside ``sched.admit``, and
every ``sched.*``/``runner.*`` span inside whatever the caller has open.
The scheduler imports no jax, so it takes its span factory injected (the
engine passes :func:`span`); :func:`no_span` is its default.
"""

from __future__ import annotations

import jax

SCHED_ADMIT = "sched.admit"  # Scheduler.admit
SCHED_PLAN = "sched.plan"  # Scheduler.plan_chunk
SCHED_ABSORB = "sched.absorb"  # Scheduler.absorb
SCHED_MAINTAIN = "sched.maintain"  # Scheduler.maintain
RUNNER_LAUNCH = "runner.launch"  # packing, budget upload, fused dispatch
RUNNER_COLLECT = "runner.collect"  # the step's one device_get
REQ_SUBMIT = "req.submit"  # rid
REQ_ADMIT = "req.admit"  # rid; slot and prefix once admitted

span = jax.profiler.TraceAnnotation


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **ids) -> None:
        pass


_NO_SPAN = _NoSpan()


def no_span(name: str, **ids) -> _NoSpan:
    """A span that records nothing."""
    return _NO_SPAN
