"""The trace reduction on a small trace whose numbers are worked by hand,
and on a trace recorded on the chip (kept beside this file)."""

from __future__ import annotations

import pathlib

import pytest
from jax.profiler import ProfileData

from bench import trace

NS = 1000  # offsets are in picoseconds: 1 ns = 1000 ps


def _events(line_ts, evs):
    return "\n".join(
        f"    events {{ metadata_id: {m} offset_ps: {(s - line_ts) * NS} "
        f"duration_ps: {(e - s) * NS} }}" for m, s, e in evs)


def _plane(pid, name, line, ts, names, evs):
    meta = "\n".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in names.items())
    return (f'planes {{\n  id: {pid}\n  name: "{name}"\n'
            f'  lines {{ id: 1 name: "{line}" timestamp_ns: {ts}\n'
            f'{_events(ts, evs)}\n  }}\n{meta}\n}}\n')


# one chip; ops busy over [1000, 3000) and [4000, 6000) ns, the kernel
# [4000, 5000) inside a while loop that spans [4000, 6000); the window
# [500, 8000); the host in engine.step until 5500, then bookkeeping, then
# waiting for an arrival.  Event names are whole HLO instructions, and one
# names the kernel among its operands.
DEVICE = _plane(1, "/device:TPU:0", "XLA Ops", 100,
                {1: "%fusion.1 = bf16[8] fusion(%paged_attention_pallas.2)",
                 2: "%paged_attention_pallas.2 = bf16[8] custom-call(%p)",
                 3: "%fusion.2 = bf16[8] fusion(%fusion.1)",
                 4: "%while.3 = (s32[]) while(%t)"},
                [(4, 4000, 6000), (1, 1000, 3000), (2, 4000, 5000),
                 (3, 5000, 6000), (1, 9000, 9500)])
HOST = _plane(2, "/host:CPU", "python", 100,
              {1: "bench.window", 2: "engine.step",
               3: "bench.bookkeeping", 4: "bench.wait_arrival"},
              [(1, 500, 8000), (2, 500, 5500), (3, 5500, 7000),
               (4, 7000, 8000)])


def test_reduction_by_hand():
    ev = trace.load_events(ProfileData.from_text_proto(DEVICE + HOST))
    red = trace.reduce(ev, 1, ["paged_attention"])
    assert red["window_s"] == pytest.approx(7500e-9)
    assert red["busy_s"] == pytest.approx(4000e-9)
    assert red["idle_share"] == pytest.approx(1 - 4000 / 7500)
    assert red["kernel_s"] == pytest.approx(1000e-9)
    ops = dict(red["breakdown"]["device_ops"])
    # self time: the loop's body covers it whole
    assert ops == pytest.approx({"%fusion.1": 2000e-9,
                                 "%paged_attention_pallas.2": 1000e-9,
                                 "%fusion.2": 1000e-9, "%while.3": 0.0})
    # gaps [6000, 8000), [3000, 4000), [500, 1000), longest first, each
    # named by the host span open at its middle
    assert red["breakdown"]["idle_gaps"] == [
        ["bench.wait_arrival", pytest.approx(2000e-9)],
        ["engine.step", pytest.approx(1000e-9)],
        ["engine.step", pytest.approx(500e-9)]]


def test_no_window_span_is_an_error():
    ev = trace.load_events(ProfileData.from_text_proto(DEVICE))
    with pytest.raises(ValueError):
        trace.reduce(ev, 1, ["paged_attention"])


RECORDED = pathlib.Path(__file__).parent / "data" / "window.xplane.pb"


def test_recorded_chip_trace():
    """A few seconds of the olmo-1b.chat window, traced on one v5e: every
    number the reduction gives is in range, and the paged-attention kernel
    is found by its name."""
    from bench.run import PAGED_ATTN_KERNEL

    ev = trace.load_events(ProfileData.from_file(str(RECORDED)))
    red = trace.reduce(ev, 1, PAGED_ATTN_KERNEL)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 <= red["idle_share"] < 1
    assert 0 < red["kernel_s"] < red["busy_s"]
    assert len(red["breakdown"]["device_ops"]) == 10
    assert red["breakdown"]["idle_gaps"]
