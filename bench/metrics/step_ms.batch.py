"""Runner and fused step: host time around each engine.step() in the window, over the steps (closed-loop cells)."""

from bench.readers import step_ms as read  # noqa: F401
