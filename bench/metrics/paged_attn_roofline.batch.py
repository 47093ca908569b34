"""Paged-attention kernel: the least time the window's attention work needs on this chip over the kernel's device time (closed-loop cells)."""

from bench.readers import paged_attn_roofline as read  # noqa: F401
