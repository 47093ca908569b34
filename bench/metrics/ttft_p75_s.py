"""Time to first token, 75th percentile over every request due in the window, from when it was due (open-loop cells)."""

from bench.readers import ttft_p75_s as read  # noqa: F401
