"""Model step: model operations of the window's tokens over the chips' bf16 peak for the window's length (open-loop cells)."""

from bench.readers import mfu as read  # noqa: F401
