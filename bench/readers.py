"""What the metric files read: the run's record, its reduced trace, and the
arithmetic they share.  Each metric is a file of its own,
``bench/metrics/<name>.py``, with one function ``read(run)`` that returns
the metric's value, or None when the run holds nothing to read it from
(the harness then leaves the metric out of the result line).

Times are on the host's clock, in seconds from the window's opening; the
window runs from 0 to ``close_t``, the end of the last step that started
before ``--seconds`` had passed.
"""

from __future__ import annotations

import dataclasses

from bench import counts
from bench.harness import Record, percentile


@dataclasses.dataclass
class RunData:
    record: Record
    model: dict  # the configuration's model sizes
    page_size: int
    chips: int
    peaks: dict  # bench/peaks.py entry of the device kind
    setup_s: float
    trace: dict | None = None  # bench/trace.py's reduction, traced runs
    engine: dict = dataclasses.field(default_factory=dict)  # its settings

    @property
    def close(self) -> float:
        return self.record.close_t

    def window_steps(self) -> tuple[int, list]:
        """(index of the first, the steps that started in the window)."""
        steps = self.record.steps
        first = next((i for i, s in enumerate(steps) if s.t0 >= 0.0),
                     len(steps))
        return first, [s for s in steps[first:] if s.t0 < self.close]

    def tokens_delivered(self) -> int:
        return sum(1 for r in self.record.recs for t in r.token_times
                   if 0.0 <= t <= self.close)


def occupancy(run: RunData) -> dict:
    """How full the engine ran in the window: running rows per step over
    ``max_batch``, and the distinct KV pages those rows held over the
    arena's pages (mean and most)."""
    _, steps = run.window_steps()
    if not steps:
        return {}
    rows = [len(s.rows) for s in steps]
    pages = [s.pages for s in steps]
    n = len(steps)
    return {"batch_share": sum(rows) / n / run.engine["max_batch"],
            "arena_share": sum(pages) / n / run.engine["num_pages"],
            "arena_share_max": max(pages) / run.engine["num_pages"]}


#: the percentile of the per-request tails (time to first token, queue
#: wait): a window of an open-loop cell holds some fifty requests due, and
#: a percentile stands on the samples beyond it, ten at the least
REQUEST_TAIL = 75


def ttft_waits(run: RunData) -> list:
    """Due time to first token of every request due in the window; one
    still waiting at the close counts what it waited."""
    return [(r.token_times[0] if r.token_times else run.close) - r.due
            for r in run.record.due_in_window()]


def queue_waits(run: RunData) -> list:
    """Due time to the first step that ran the request, likewise."""
    return [(r.running_at if r.running_at is not None else run.close)
            - r.due for r in run.record.due_in_window()]


def ttft_p75_s(run: RunData):
    return percentile(ttft_waits(run), REQUEST_TAIL)


def itl_p95_ms(run: RunData):
    gaps = []
    for r in run.record.recs:
        tt = r.token_times
        gaps += [tt[i] - tt[i - 1] for i in range(1, len(tt))
                 if 0.0 <= tt[i] <= run.close]
    p = percentile(gaps, 95)
    return None if p is None else 1e3 * p


def output_tok_s(run: RunData):
    return run.tokens_delivered() / run.close


def queue_wait_p75_s(run: RunData):
    return percentile(queue_waits(run), REQUEST_TAIL)


def step_ms(run: RunData):
    _, steps = run.window_steps()
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)


def device_idle_share(run: RunData):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]


def paged_attn_roofline(run: RunData):
    """The least time the chip could take for the window's attention work
    (operations over peak, or bytes over bandwidth, whichever is larger,
    step by step) over the kernel's device time in the trace."""
    if run.trace is None or run.trace["kernel_s"] <= 0:
        return None
    first, steps = run.window_steps()
    work = counts.attention_work(run.model, run.page_size, steps, first,
                                 run.record.chains)
    p = run.peaks
    least = sum(max(f / p["bf16_flops"], b / p["hbm_bytes_per_s"])
                for f, b in work)
    return 100.0 * least / run.trace["kernel_s"]


def mfu(run: RunData):
    """Model operations of the window's tokens over the chips' peak for the
    window's length."""
    _, steps = run.window_steps()
    flops = counts.model_flops(run.model, steps, run.tokens_delivered())
    return 100.0 * flops / (run.close * run.chips * run.peaks["bf16_flops"])
