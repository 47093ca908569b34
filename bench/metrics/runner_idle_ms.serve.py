"""Runner: chip-0 idle time in the traced window while the innermost host span
was a ``runner.*`` one (dispatch, uploads, the step's one ``device_get``),
per step (``bench/spans.py``)."""

from bench import spans


def read(run):
    return spans.read(run, "runner_idle_ms")
