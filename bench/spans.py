"""The program's own spans and scopes in a traced window, and the per-layer
metrics that read them.

Two sources, both written by the one ``jax.profiler.stop_trace``:

- the ``.xplane.pb``, read with ``jax.profiler.ProfileData`` as
  ``bench/trace.py`` reads it: the host spans (the program's ``sched.*``,
  ``runner.*`` and ``req.*``, with their ids; the harness's ``bench.*`` and
  ``engine.step``) and each chip's operations and program runs, all on one
  clock;
- the ``.trace.json.gz`` beside it, for each device operation's ``tf_op``:
  the named-scope path of the operation
  (``jit(fused_decode_step)/step.grant/pool.alloc/...``), which
  ``ProfileData`` does not expose.  It is read one event at a time and
  joined to the ``.xplane.pb``'s operations on (operation name, its
  ``device_offset_ps``).

What it gives, for chip 0 over the ``bench.window`` span:

- idle time split by the innermost span open on the host: every idle
  interval is cut at span edges, each piece labelled by the span that was
  innermost over it (``none`` where no span but the window was open);
- device self time by scope path (the ``step.*``, ``layer.*``, ``pool.*``
  and ``kv.*`` components of ``tf_op``; an operation without ``tf_op``,
  such as a copy the compiler inserted, is ``unscoped``; one of another
  program without those scopes is named by its program);
- for each request submitted in the window, the wait from the start of its
  ``req.submit`` to the start of its first ``req.admit`` that installed it.

"Per step" divides by the harness's ``engine.step`` spans that start in
the window.  The metrics return None when the trace lacks what they read
(a program without these spans and scopes).

Run on a trace directory it prints the step's phases: device ms per step
by scope, chip-0 idle ms per step by innermost span and by family
(``sched``, ``runner``, ``harness``, ``none``), the longest idle gaps with
the spans over them, and the four metrics:

    python bench/spans.py <dir holding a .xplane.pb and its .trace.json.gz>
"""

from __future__ import annotations

import collections
import gzip
import json
import os
import pathlib
import re
import sys

if __package__ in (None, ""):  # run as a script
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import common  # noqa: E402
from bench import trace as trace_lib  # noqa: E402
from bench.harness import SPAN_STEP, percentile  # noqa: E402

#: where ``bench/run.py`` has the profiler write a traced run
TRACE_DIR = common.OUT_DIR / "trace"
#: the program's span families (``src/repro/serving/spans.py``)
SCHED, RUNNER, REQ = "sched.", "runner.", "req."
REQ_SUBMIT, REQ_ADMIT = "req.submit", "req.admit"
#: the components of a ``tf_op`` that are the program's own scopes
SCOPE = re.compile(r"^(step|layer|pool|kv)\.")
POOL = "pool."
MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
NONE = "none"
#: the request-wait percentile, as the harness's request tails have it
REQUEST_TAIL = 75
_CHUNK = 1 << 24
_EVENTS = re.compile(r'"traceEvents"\s*:\s*\[')


def _is_program(name: str) -> bool:
    return name.startswith((SCHED, RUNNER, REQ))


def group(label: str) -> str:
    """The family an innermost span belongs to: ``sched`` (the scheduler's
    spans and the per-request ones, which it opens), ``runner``,
    ``harness`` or ``none``."""
    if label.startswith((SCHED, REQ)):
        return "sched"
    if label.startswith(RUNNER):
        return "runner"
    return NONE if label == NONE else "harness"


def trace_json(xplane: str) -> str | None:
    """The ``.trace.json.gz`` the profiler wrote beside ``xplane``, if any."""
    path = xplane[: -len(".xplane.pb")] + ".trace.json.gz"
    return path if os.path.isfile(path) else None


def json_events(path: str):
    """The ``traceEvents`` of a Chrome-trace ``.json.gz``, one at a time,
    without holding the whole text."""
    dec = json.JSONDecoder()
    with gzip.open(path, "rt") as f:
        buf = f.read(_CHUNK)
        while not (m := _EVENTS.search(buf)):
            more = f.read(_CHUNK)
            if not more:
                return
            buf += more
        i = m.end()
        while True:
            while i < len(buf) and buf[i] in " \n\r\t,":
                i += 1
            if i < len(buf) and buf[i] == "]":
                return
            try:
                ev, j = dec.raw_decode(buf, i)
            except json.JSONDecodeError:
                more = f.read(_CHUNK)
                if not more:
                    raise
                buf, i = buf[i:] + more, 0
                continue
            yield ev
            i = j
            if i > _CHUNK:
                buf, i = buf[i:], 0


def load_tf_ops(path: str, chip: int = 0) -> dict:
    """(operation name, ``device_offset_ps``) -> ``tf_op`` of the chip's
    ``XLA Ops`` in the ``.trace.json.gz``."""
    pids, tids, ops = {}, {}, []
    for ev in json_events(path):
        ph = ev.get("ph")
        if ph == "M":
            name = ev.get("args", {}).get("name")
            if ev.get("name") == "process_name":
                pids[ev["pid"]] = name
            elif ev.get("name") == "thread_name":
                tids[(ev["pid"], ev.get("tid"))] = name
        elif ph == "X":
            args = ev.get("args")
            if args and "tf_op" in args and "device_offset_ps" in args:
                ops.append((ev["pid"], ev.get("tid"), ev.get("name"),
                            int(args["device_offset_ps"]), args["tf_op"]))
    want = f"{trace_lib.DEVICE_PREFIX}{chip}"
    interned: dict = {}
    return {(name, off): interned.setdefault(tf, tf)
            for pid, tid, name, off, tf in ops
            if pids.get(pid) == want
            and tids.get((pid, tid)) == trace_lib.OPS_LINE}


def load(log_dir, chip: int = 0) -> dict:
    """The traced window in ``log_dir`` (:func:`from_profile`)."""
    from jax.profiler import ProfileData

    xplane = trace_lib.find_xplane(str(log_dir))
    jpath = trace_json(xplane)
    return from_profile(ProfileData.from_file(xplane),
                        load_tf_ops(jpath, chip) if jpath else {}, chip)


def from_profile(pd, tf_ops: dict, chip: int = 0) -> dict:
    """The window, the host spans and chip ``chip``'s operations of a
    ``ProfileData`` (module docstring), each operation given its ``tf_op``
    from ``tf_ops`` (:func:`load_tf_ops`), as plain tuples in ns:

    - ``window``: (start, end) of the longest ``bench.window`` span;
    - ``spans``: (name, start, end, ids) of the program's and the
      harness's spans;
    - ``ops``: (start, end, tf_op or None) of the chip's operations;
    - ``modules``: (program name, start, end) of the chip's program runs;
    - ``steps``: the harness's ``engine.step`` spans that start in the
      window."""
    spans, raw_ops, modules, windows = [], [], [], []
    harness = set(trace_lib.HOST_SPANS)
    want = f"{trace_lib.DEVICE_PREFIX}{chip}"
    for plane in pd.planes:
        if plane.name == want:
            for line in plane.lines:
                if line.name == trace_lib.OPS_LINE:
                    for ev in line.events:
                        off = next((v for k, v in ev.stats
                                    if k == "device_offset_ps"), None)
                        raw_ops.append((float(ev.start_ns), float(ev.end_ns),
                                        trace_lib.op_name(ev.name), off))
                elif line.name == MODULES_LINE:
                    modules += [(ev.name.split("(", 1)[0],
                                 float(ev.start_ns), float(ev.end_ns))
                                for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    n = ev.name
                    if n == trace_lib.WINDOW_SPAN:
                        windows.append((float(ev.start_ns),
                                        float(ev.end_ns)))
                    elif n in harness or _is_program(n):
                        ids = ({k: v for k, v in ev.stats}
                               if n.startswith(REQ) else {})
                        spans.append((n, float(ev.start_ns),
                                      float(ev.end_ns), ids))
    if not windows:
        raise ValueError(f"trace holds no {trace_lib.WINDOW_SPAN!r} span")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    ops = [(s, e, tf_ops.get((n.lstrip("%"), off))) for s, e, n, off
           in raw_ops]
    steps = sum(1 for n, s, _, _ in spans
                if n == SPAN_STEP and w0 <= s < w1)
    return {"window": (w0, w1), "spans": spans, "ops": ops,
            "modules": modules, "steps": steps}


# -- the reductions -------------------------------------------------------


def _clipped(win: dict) -> list:
    """The chip's operations inside the window, clipped to it, sorted by
    start and longest first (as ``trace.reduce`` has them)."""
    w0, w1 = win["window"]
    ops = sorted((s, -min(e, w1), t) for s, e, t in win["ops"]
                 if e > w0 and s < w1)
    return [(max(s, w0), -ne, t) for s, ne, t in ops]


def idle_intervals(win: dict) -> list:
    """The chip's idle intervals in the window: what the busy union of its
    operations leaves, as ``trace.reduce`` computes it."""
    w0, w1 = win["window"]
    merged = trace_lib._union([[s, e] for s, e, _ in _clipped(win)])
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def innermost(spans: list, w0: float, w1: float) -> list:
    """[(start, end, label)] covering [w0, w1): each piece labelled by the
    innermost span open over it (spans on one thread nest), ``none`` where
    none is."""
    out: list = []
    stack: list = []  # (end, name), innermost last
    t = w0

    def upto(x: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
        if x > t:
            out.append((t, x, stack[-1][1] if stack else NONE))
            t = x

    for s, e, name in sorted(((s, e, n) for n, s, e, _ in spans
                              if e > w0 and s < w1),
                             key=lambda x: (x[0], -x[1])):
        upto(max(s, w0))
        stack.append((min(e, w1), name))
    upto(w1)
    return out


def idle_split(win: dict) -> list:
    """Each idle interval of the chip in the window, longest first, with
    the ns of it under each innermost span: [(ns, Counter)]."""
    w0, w1 = win["window"]
    segs = innermost(win["spans"], w0, w1)
    out = []
    j = 0
    for s, e in idle_intervals(win):
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        by: collections.Counter = collections.Counter()
        k = j
        while k < len(segs) and segs[k][0] < e:
            a, b, label = segs[k]
            by[label] += min(b, e) - max(a, s)
            k += 1
        out.append((e - s, by))
    return sorted(out, key=lambda x: -x[0])


def idle_by_span(win: dict) -> collections.Counter:
    """ns of the chip's idle time in the window by innermost span."""
    out: collections.Counter = collections.Counter()
    for _, by in idle_split(win):
        out.update(by)
    return out


def scope_path(tf_op: str | None, depth: int = 2) -> str:
    """The program's scopes in ``tf_op``, outermost first, up to ``depth``;
    the program's name (``jit(...)``) when it has none of them; and
    ``unscoped`` for an operation the compiler made, which has no
    ``tf_op`` or one that names only its own kind."""
    parts = (tf_op or "").split("/")
    scoped = [p for p in parts if SCOPE.match(p)]
    if scoped:
        return "/".join(scoped[:depth])
    return parts[0] if parts[0].startswith("jit(") else UNSCOPED


def device_by_scope(win: dict, depth: int = 2) -> collections.Counter:
    """ns of the chip's self time in the window by scope path."""
    return trace_lib._self_times([(scope_path(t, depth), s, e)
                                  for s, e, t in _clipped(win)])


def pool_ns(win: dict) -> float | None:
    """ns of the chip's self time in the window of operations with a
    ``pool.*`` scope; None when none has one."""
    seen = False
    total = 0.0
    for tf, t in trace_lib._self_times(
            [(t or "", s, e) for s, e, t in _clipped(win)]).items():
        if any(p.startswith(POOL) for p in tf.split("/")):
            seen = True
            total += t
    return total if seen else None


def admit_waits(win: dict) -> list:
    """Seconds from each request's ``req.submit`` in the window to its first
    ``req.admit`` that installed it (one carrying a ``slot``); a request
    not admitted by the window's close waits until the close."""
    w0, w1 = win["window"]
    submitted, admitted = {}, {}
    for n, s, _, ids in win["spans"]:
        rid = ids.get("rid")
        if rid is None:
            continue
        if n == REQ_SUBMIT and w0 <= s < w1:
            submitted[rid] = s
        elif n == REQ_ADMIT and "slot" in ids:
            admitted[rid] = min(s, admitted.get(rid, s))
    return [(min(admitted.get(rid, w1), w1) - s) / 1e9
            for rid, s in submitted.items()]


# -- the metrics ----------------------------------------------------------


def _per_step_ms(ns: float, win: dict) -> float | None:
    return 1e-6 * ns / win["steps"] if win["steps"] else None


def _idle_ms(win: dict, family: str) -> float | None:
    if not any(n.startswith(family) for n, _, _, _ in win["spans"]):
        return None
    by = idle_by_span(win)
    return _per_step_ms(sum(t for n, t in by.items()
                            if group(n) == family.rstrip(".")), win)


def _alloc_ms(win: dict) -> float | None:
    ns = pool_ns(win)
    return None if ns is None else _per_step_ms(ns, win)


METRICS = {
    "admit_wait_p75_s": lambda win: percentile(admit_waits(win),
                                               REQUEST_TAIL),
    "sched_idle_ms": lambda win: _idle_ms(win, SCHED),
    "runner_idle_ms": lambda win: _idle_ms(win, RUNNER),
    "alloc_ms": _alloc_ms,
}

_LOADED: dict = {}


def window_of(run) -> dict | None:
    """The traced window of ``run`` (loaded once per trace), or None for a
    run without a trace."""
    if run.trace is None:
        return None
    try:
        xplane = trace_lib.find_xplane(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    key = (xplane, os.path.getmtime(xplane))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = load(TRACE_DIR)
    return _LOADED[key]


def read(run, metric: str):
    """``METRICS[metric]`` of the run's traced window; None without one."""
    win = window_of(run)
    return None if win is None else METRICS[metric](win)


def report(win: dict) -> str:
    """The step's phases as text (module docstring)."""
    n = win["steps"] or 1
    w0, w1 = win["window"]
    idle = idle_by_span(win)
    total_idle = sum(idle.values())
    lines = [f"window {1e-9 * (w1 - w0):.3f} s, {win['steps']} steps; "
             f"chip 0 idle {1e-6 * total_idle / n:.3f} ms a step"]
    lines.append("device ms a step by scope:")
    for name, t in sorted(device_by_scope(win, 1).items(),
                          key=lambda x: -x[1]):
        lines.append(f"  {name:<40} {1e-6 * t / n:9.3f}")
    lines.append("device ms a step by scope, two deep:")
    for name, t in sorted(device_by_scope(win, 2).items(),
                          key=lambda x: -x[1]):
        if "/" in name:
            lines.append(f"  {name:<40} {1e-6 * t / n:9.3f}")
    lines.append("idle ms a step by innermost span:")
    for name, t in idle.most_common():
        lines.append(f"  {name:<40} {1e-6 * t / n:9.3f}")
    groups: collections.Counter = collections.Counter()
    for name, t in idle.items():
        groups[group(name)] += t
    lines.append("longest idle gaps, ms, and the spans over them:")
    for ns, by in idle_split(win)[:10]:
        lines.append(f"  {1e-6 * ns:9.3f}  " + ", ".join(
            f"{n} {1e-6 * t:.3f}" for n, t in by.most_common(3)))
    lines.append("idle by family: " + ", ".join(
        f"{g} {1e-6 * t / n:.3f} ms ({100 * t / max(total_idle, 1):.1f}%)"
        for g, t in groups.most_common()))
    for name, fn in METRICS.items():
        lines.append(f"{name}: {fn(win)}")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> None:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        raise SystemExit("usage: python bench/spans.py <trace dir>")
    print(report(load(args[0])))


if __name__ == "__main__":
    main()
