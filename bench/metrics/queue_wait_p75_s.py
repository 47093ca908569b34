"""Scheduler: due time to the first step in which the request runs, 75th percentile over the requests due in the window."""

from bench.readers import queue_wait_p75_s as read  # noqa: F401
