"""Device: one minus the busy union of the chip's operations over the traced window (closed-loop cells)."""

from bench.readers import device_idle_share as read  # noqa: F401
