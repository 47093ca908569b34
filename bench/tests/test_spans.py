"""The reduction of the program's spans and scopes (``bench/spans.py``): on a
small trace whose numbers are worked by hand, and on a window traced on
the chip with the spans and scopes in place (kept beside this file)."""

from __future__ import annotations

import gzip
import json
import pathlib
import shutil
import types

import pytest
from jax.profiler import ProfileData

from bench import common, spans
from bench import trace as trace_lib

NS = 1000  # offsets are in picoseconds: 1 ns = 1000 ps
FUSED_STEP = "jit_fused_decode_step"
OFF, RID, SLOT = 1, 2, 3  # stat metadata ids


def _stat(mid, v):
    return f"stats {{ metadata_id: {mid} int64_value: {v} }}"


def _plane(pid, name, lines, names):
    """``lines``: {line name: [(metadata id, start, end, [stats])]}."""
    meta = "\n".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in names.items())
    stat_meta = "\n".join(
        f'  stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in ((OFF, "device_offset_ps"), (RID, "rid"),
                     (SLOT, "slot")))
    body = ""
    for lid, (lname, evs) in enumerate(lines.items(), 1):
        body += f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n'
        for m, s, e, st in evs:
            body += (f"    events {{ metadata_id: {m} offset_ps: {s * NS} "
                     f"duration_ps: {(e - s) * NS} {' '.join(st)} }}\n")
        body += "  }\n"
    return (f'planes {{\n  id: {pid}\n  name: "{name}"\n{body}{meta}\n'
            f"{stat_meta}\n}}\n")


# the window [0, 10000) ns.  The host: rid 7 submitted and admitted to slot
# 0; one engine.step with its plan, launch, collect and absorb; maintain in
# the bookkeeping, during which rid 8 is submitted and one attempt to admit
# it finds no room (a req.admit without a slot); nothing open after 9500.
HOST_NAMES = {1: "bench.window", 2: "bench.submit", 3: "req.submit",
              4: "sched.admit", 5: "req.admit", 6: "engine.step",
              7: "sched.plan", 8: "runner.launch", 9: "runner.collect",
              10: "sched.absorb", 11: "bench.bookkeeping",
              12: "sched.maintain"}
HOST = _plane(2, "/host:CPU", {"python": [
    (1, 0, 10000, []), (2, 0, 1000, []),
    (3, 100, 900, [_stat(RID, 7)]), (4, 1000, 3000, []),
    (5, 1200, 2800, [_stat(RID, 7), _stat(SLOT, 0)]),
    (6, 3000, 9000, []), (7, 3100, 3300, []), (8, 3400, 4000, []),
    (9, 4000, 8000, []), (10, 8000, 8800, []), (11, 9000, 9500, []),
    (12, 9100, 9400, []), (3, 9150, 9200, [_stat(RID, 8)]),
    (5, 9300, 9350, [_stat(RID, 8)])]}, HOST_NAMES)
# chip 0: the pool's own program [1500, 2000); the fused step [3800, 7800):
# a grant [3800, 5800), then the layer loop [5800, 7800) whose attention
# [6000, 7000) lies inside it.  Busy is [1500, 2000) + [3800, 7800).
DEVICE = _plane(1, "/device:TPU:0", {
    "XLA Modules": [(1, 1500, 2000, []), (2, 3800, 7800, [])],
    "XLA Ops": [(3, 1500, 2000, [_stat(OFF, 1500 * NS)]),
                (4, 3800, 5800, [_stat(OFF, 3800 * NS)]),
                (5, 5800, 7800, [_stat(OFF, 5800 * NS)]),
                (6, 6000, 7000, [_stat(OFF, 6000 * NS)])]},
    {1: "jit_alloc_pages(5)", 2: "jit_fused_decode_step(9)",
     3: "%fusion.1 = s32[4] fusion(%p)", 4: "%fusion.1 = s32[4] fusion(%q)",
     5: "%while.2 = (s32[]) while(%t)",
     6: "%paged_attention_c16.3 = bf16[8] custom-call(%x)"})
TF_OPS = {("fusion.1", 1500 * NS): "jit(alloc_pages)/pool.alloc/add",
          ("fusion.1", 3800 * NS):
          "jit(fused_decode_step)/step.grant/pool.alloc/sort",
          ("while.2", 5800 * NS): "jit(fused_decode_step)/step.layers/while",
          ("paged_attention_c16.3", 6000 * NS):
          "jit(fused_decode_step)/step.layers/while/body/closed_call/"
          "layer.attention/paged_attention_c16/pallas_call"}


@pytest.fixture(scope="module")
def hand():
    return spans.from_profile(ProfileData.from_text_proto(DEVICE + HOST),
                              TF_OPS)


def test_idle_split_at_span_edges_by_hand(hand):
    assert hand["steps"] == 1
    assert spans.idle_intervals(hand) == [(0, 1500), (2000, 3800),
                                          (7800, 10000)]
    assert spans.idle_by_span(hand) == {
        "bench.submit": 200, "req.submit": 850, "sched.admit": 400,
        "req.admit": 1150, "engine.step": 400, "sched.plan": 200,
        "runner.launch": 400, "runner.collect": 200, "sched.absorb": 800,
        "bench.bookkeeping": 200, "sched.maintain": 200, "none": 500}
    assert spans.METRICS["sched_idle_ms"](hand) == pytest.approx(3600e-6)
    assert spans.METRICS["runner_idle_ms"](hand) == pytest.approx(600e-6)


def test_device_time_by_scope_by_hand(hand):
    assert spans.device_by_scope(hand) == {
        "pool.alloc": 500, "step.grant/pool.alloc": 2000,
        "step.layers": 1000, "step.layers/layer.attention": 1000}
    assert spans.device_by_scope(hand, 1) == {
        "pool.alloc": 500, "step.grant": 2000, "step.layers": 2000}
    assert spans.pool_ns(hand) == 2500
    assert spans.METRICS["alloc_ms"](hand) == pytest.approx(2500e-6)


def test_admit_wait_by_hand(hand):
    # rid 7: admitted 1100 ns after its submit; rid 8: its one attempt
    # installed nothing, so it waits until the close
    assert sorted(spans.admit_waits(hand)) == pytest.approx(
        [850e-9, 1100e-9])
    assert spans.METRICS["admit_wait_p75_s"](hand) == pytest.approx(
        850e-9 + 0.75 * 250e-9)


def test_a_trace_without_the_programs_spans_reads_nothing():
    """The parent program: no ``sched.*``/``runner.*``/``req.*`` span and no
    ``pool.*`` scope, so every metric is None and nothing raises."""
    host = "\n".join(line for line in HOST.splitlines()
                     if "metadata_id: 1 " in line or "events" not in line)
    win = spans.from_profile(ProfileData.from_text_proto(DEVICE + host), {})
    assert [n for n, *_ in win["spans"]] == []
    assert {k: f(win) for k, f in spans.METRICS.items()} == dict.fromkeys(
        spans.METRICS)


def test_trace_json_read_one_event_at_a_time(tmp_path, monkeypatch):
    events = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 4, "name": "process_name",
         "args": {"name": "/device:TPU:1"}},
        {"ph": "M", "pid": 4, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 1.5, "dur": 0.5,
         "name": "fusion.1", "args": {"device_offset_ps": "1500000",
                                      "tf_op": "jit(f)/pool.alloc/add"}},
        {"ph": "X", "pid": 4, "tid": 3, "ts": 1.5, "dur": 0.5,
         "name": "fusion.1", "args": {"device_offset_ps": "1500000",
                                      "tf_op": "jit(f)/other"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 2.0, "dur": 0.1,
         "name": "copy.2", "args": {"device_offset_ps": "2000000"}},
    ]
    path = tmp_path / "h.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"displayTimeUnit": "ns", "metadata": {},
                   "traceEvents": events}, f)
    monkeypatch.setattr(spans, "_CHUNK", 7)  # refill mid-event, often
    assert list(spans.json_events(str(path))) == events
    assert spans.load_tf_ops(str(path)) == {
        ("fusion.1", 1500000): "jit(f)/pool.alloc/add"}
    assert spans.load_tf_ops(str(path), chip=1) == {
        ("fusion.1", 1500000): "jit(f)/other"}


# a 1-s window of olmo-1b.chat traced on one v5e with the program's spans
# and scopes in place: the .xplane.pb and the .trace.json.gz beside it
RECORDED = pathlib.Path(__file__).parent / "data" / "window_spans.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return spans.from_profile(
        ProfileData.from_file(str(RECORDED)),
        spans.load_tf_ops(spans.trace_json(str(RECORDED))))


def test_recorded_fused_steps_lie_between_launch_and_collect(recorded):
    """One clock: each fused step starts on the chip after the host began
    the ``runner.launch`` that dispatched it, and ends before the
    ``runner.collect`` that waited for it returned.  The profiler aligns
    the host's and the chip's timestamps to a few microseconds (a step
    was seen to start 2 us before its launch span), which ``SLACK``
    allows; a step is 80 ms."""
    slack = 50e3  # ns
    launches = sorted(s[1:3] for s in recorded["spans"]
                      if s[0] == "runner.launch")
    collects = sorted(s[1:3] for s in recorded["spans"]
                      if s[0] == "runner.collect")
    steps = [m[1:] for m in recorded["modules"] if m[0] == FUSED_STEP]
    checked = 0
    for s, e in steps:
        before = [x for x in launches if x[0] <= s + slack]
        if not before:
            continue  # dispatched before the trace began
        launch = before[-1]
        collect = next(x for x in collects if x[0] >= launch[1])
        assert launch[0] - slack <= s and e <= collect[1] + slack
        checked += 1
    assert checked == len(steps) >= 10


def test_recorded_idle_split_covers_the_reduced_idle(recorded):
    red = trace_lib.reduce(trace_lib.load_events(
        ProfileData.from_file(str(RECORDED))), 1, ["paged_attention"])
    idle_ns = (red["window_s"] - red["busy_s"]) * 1e9
    by = spans.idle_by_span(recorded)
    assert sum(by.values()) == pytest.approx(idle_ns, rel=0.01)
    named = sum(t for n, t in by.items() if n != spans.NONE)
    assert named >= 0.9 * idle_ns  # a span accounts for the idle time


def test_recorded_fused_step_ops_are_scoped(recorded):
    """Every operation of a fused step is under a ``step.*`` scope, or is
    one the compiler added, with no ``tf_op``."""
    steps = [(s, e) for n, s, e in recorded["modules"]
             if n == FUSED_STEP]
    seen = 0
    for s, e, tf in recorded["ops"]:
        if any(a <= s < b for a, b in steps):
            seen += 1
            # no tf_op, or one without the program's path: an operation
            # the compiler made (a cumsum's reduce-window split is named
            # ``reduce_window_sum:`` alone)
            assert tf is None or "/step." in tf or "jit(" not in tf, tf
    assert seen > 1000
    scopes = spans.device_by_scope(recorded, 1)
    assert {"step.inputs", "step.grant", "step.layers", "step.unembed",
            "step.validate", spans.UNSCOPED} <= set(scopes)


def test_recorded_readers(tmp_path, monkeypatch):
    """The four metric files, found by name, on the recorded window."""
    for f in (RECORDED, pathlib.Path(spans.trace_json(str(RECORDED)))):
        shutil.copy(f, tmp_path / f.name)
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    run = types.SimpleNamespace(trace={})
    got = {m: common.load_module(common.ROOT, "metrics", m).read(run)
           for m in ("admit_wait_p75_s", "sched_idle_ms.serve",
                     "runner_idle_ms.serve", "alloc_ms.serve")}
    assert got == pytest.approx({
        "admit_wait_p75_s": 0.00010178,
        "sched_idle_ms.serve": 1.1699206666666666,
        "runner_idle_ms.serve": 2.9260029999999997,
        "alloc_ms.serve": 0.07223041666666667}, rel=1e-9)
    assert common.load_module(common.ROOT, "metrics", "alloc_ms.serve").read(
        types.SimpleNamespace(trace=None)) is None  # an untraced run
