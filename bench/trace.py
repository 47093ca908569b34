"""Reduce a profiler trace (``.xplane.pb``) of the measured window to the
numbers the per-layer metrics read.

- The window is the host span ``bench.window`` that the harness opens
  around the measured loop.
- Busy time: the union of the intervals in which an XLA operation ran on a
  chip (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to
  the window, averaged over the chips the cell uses.  The idle share is
  one minus busy time over the window.
- Kernel time: the summed device time of the operations whose name
  contains one of the kernel's name patterns.  An operation's name is its
  HLO instruction's (``%paged_attention_pallas.7``): the trace gives the
  whole instruction text, operands included, and only the part before
  `` = `` is the operation's own.
- ``breakdown``: the device operations that took the most time by self
  time (a ``while`` holds the operations of its body on the same line; its
  own share is what its body does not cover), and the longest idle gaps of
  chip 0, each labelled with the innermost harness span open on the host
  at the gap's middle.

Read with ``jax.profiler.ProfileData``, nothing else.
"""

from __future__ import annotations

import collections
import glob
import os

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.submit", "engine.step", "bench.wait_arrival",
              "bench.bookkeeping")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals: list) -> list:
    """Merge [start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(ops: list) -> collections.Counter:
    """Self time by name of (name, start, end), sorted by start and longest
    first: an operation that lies inside another is taken off its parent."""
    out: collections.Counter = collections.Counter()
    stack: list = []  # (name, end) of the operations enclosing this one
    for n, s, e in ops:
        while stack and stack[-1][1] <= s:
            stack.pop()
        out[n] += e - s
        if stack:
            out[stack[-1][0]] -= e - s
        stack.append((n, e))
    return out


def _device_index(name: str) -> int | None:
    if not name.startswith(DEVICE_PREFIX):
        return None
    tail = name[len(DEVICE_PREFIX):]
    return int(tail) if tail.isdigit() else None


def op_name(text: str) -> str:
    """``%name = type op(operands)`` -> ``%name``."""
    return text.split(" = ", 1)[0]


def load_events(pd) -> dict:
    """The events the reduction needs from a ``ProfileData``, as plain
    tuples (ns): ``host``: (name, start, end) of the harness's spans;
    ``devices``: chip index -> [(op name, start, end)]."""
    host, devices = [], {}
    for plane in pd.planes:
        idx = _device_index(plane.name)
        if idx is not None:
            ops = devices.setdefault(idx, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((op_name(ev.name), float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN or ev.name in HOST_SPANS:
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.start_ns + ev.duration_ns)))
    return {"host": host, "devices": devices}


def reduce(events: dict, chips: int, kernel_patterns: list[str]) -> dict:
    """busy_s, window_s, idle_share, kernel_s (per chip, averaged over the
    first ``chips`` chips) and the breakdown (module docstring)."""
    windows = [(s, e) for n, s, e in events["host"] if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    window_ns = w1 - w0
    busy, kernel = [], []
    op_time: collections.Counter = collections.Counter()
    gaps0: list = []
    devs = sorted(events["devices"])[:chips]
    if not devs:
        raise ValueError("trace holds no TPU device plane")
    for d in devs:
        ops = sorted((s, -min(e, w1), n) for n, s, e in events["devices"][d]
                     if e > w0 and s < w1)
        ops = [(n, max(s, w0), -ne) for s, ne, n in ops]
        merged = _union([[s, e] for _, s, e in ops])
        busy.append(sum(e - s for s, e in merged))
        kernel.append(sum(e - s for n, s, e in ops
                          if any(p in n for p in kernel_patterns)))
        for n, t in _self_times(ops).items():
            op_time[n] += t / len(devs)
        if d == devs[0]:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps0 = [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    spans = [(n, s, e) for n, s, e in events["host"] if n in HOST_SPANS]
    labelled = []
    for s, e in sorted(gaps0, key=lambda g: g[0] - g[1])[:10]:
        mid = (s + e) / 2
        open_ = [(ss, n) for n, ss, ee in spans if ss <= mid < ee]
        label = max(open_)[1] if open_ else "no harness span"
        labelled.append([label, (e - s) / 1e9])
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "idle_share": 1.0 - sum(busy) / len(busy) / window_ns,
        "kernel_s": sum(kernel) / len(kernel) / 1e9,
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in op_time.most_common(10)],
            "idle_gaps": labelled,
        },
    }
