"""Scheduler: from the start of a request's ``req.submit`` span to the start
of the first ``req.admit`` that installed it, 75th percentile over the
requests submitted in the traced window (``bench/spans.py``)."""

from bench import spans


def read(run):
    return spans.read(run, "admit_wait_p75_s")
