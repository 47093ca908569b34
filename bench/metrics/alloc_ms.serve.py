"""Allocator: chip-0 device self time in the traced window of operations
under a ``pool.*`` scope, inline in the fused step or in the pool's own
programs, per step (``bench/spans.py``)."""

from bench import spans


def read(run):
    return spans.read(run, "alloc_ms")
