"""Gap between consecutive tokens of a request as its client receives them, 95th percentile over every gap that ends in the window."""

from bench.readers import itl_p95_ms as read  # noqa: F401
