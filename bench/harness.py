"""Drive the engine through a cell's traffic and record what a client sees.

The measured window drives the engine's public round and nothing else, as
``launch/serve.py``'s trace replay does: ``submit`` for the requests now
due, ``admit``, ``step``, ``maintain``, through the engine module the
configuration names (``bench/engines/<builder>.py``).  The harness never
re-implements a step, so a later change inside ``step()`` is measured
without an edit here.

What is recorded, all on the host's clock (``time.perf_counter``):

- for each request: when it was due, when it was first seen running, and
  the moment ``step()`` returned with each of its new tokens (a token that
  a preempted request generates again is not delivered twice);
- for each step: its start and end, and for each running row the length
  it had committed before the step and the tokens the step committed to
  it, with the pages the row shares through the prefix cache (for the
  kernel's count of distinct pages read);
- the engine's counters at the window's opening and closing.

Host work is labelled with ``jax.profiler.TraceAnnotation`` spans
(``bench.submit``, ``engine.step``, ``bench.wait_arrival``,
``bench.bookkeeping``), so that a traced run can say what the host was
doing while the device sat idle.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from bench.traffic import Ask, Traffic

SPAN_SUBMIT = "bench.submit"
SPAN_STEP = "engine.step"
SPAN_WAIT = "bench.wait_arrival"
SPAN_BOOK = "bench.bookkeeping"


@dataclasses.dataclass
class Rec:
    """One request as its client sees it (times relative to the window's
    opening)."""

    due: float
    prompt: list
    max_new: int
    req: object = None
    submitted: float | None = None
    running_at: float | None = None
    token_times: list = dataclasses.field(default_factory=list)
    seen: int = 0
    ask: Ask | None = None  # what the traffic generator sent


@dataclasses.dataclass
class StepLog:
    t0: float
    t1: float
    rows: list  # (rid, committed before, tokens committed by the step)
    pages: int = 0  # distinct KV pages the running rows held as it began


@dataclasses.dataclass
class Record:
    """Everything one window produced, for the metric readers."""

    window_s: float
    recs: list
    steps: list
    counters_open: dict
    counters_close: dict
    chains: dict  # rid -> list of (first step index, shared page ids)
    close_t: float = 0.0
    compiles_in_window: list = dataclasses.field(default_factory=list)
    late: list = dataclasses.field(default_factory=list)

    def in_window(self, t: float) -> bool:
        return 0.0 <= t < self.window_s

    def window_steps(self) -> list:
        return [s for s in self.steps if self.in_window(s.t0)]

    def due_in_window(self) -> list:
        return [r for r in self.recs if self.in_window(r.due)]


class Driver:
    """Runs a cell's traffic against one engine (module docstring)."""

    def __init__(self, engine, traffic: Traffic, window_s: float,
                 compile_log=None):
        self.eng = engine
        self.traffic = traffic
        self.window_s = float(window_s)
        self.compile_log = compile_log
        self.recs: list[Rec] = []
        self.steps: list[StepLog] = []
        self.by_rid: dict[int, Rec] = {}
        self.chains: dict[int, list] = {}
        self._chain_key: dict[int, tuple] = {}
        self.late: list[float] = []
        self._pending: list[Rec] = []  # due, not yet submitted (time order)
        self._origin = 0.0

    # -- the clock -------------------------------------------------------------

    def now(self) -> float:
        return time.perf_counter() - self._origin

    # -- what is due -------------------------------------------------------------

    def _plan(self) -> None:
        self._pending = sorted(
            (Rec(a.due, a.prompt, a.max_new, ask=a)
             for a in self.traffic.first()), key=lambda r: r.due)

    def _insert(self, ask: Ask) -> None:
        rec = Rec(ask.due, ask.prompt, ask.max_new, ask=ask)
        i = len(self._pending)
        while i > 0 and self._pending[i - 1].due > rec.due:
            i -= 1
        self._pending.insert(i, rec)

    def _on_finish(self, rec: Rec, t: float) -> None:
        """What the finished request leads to: a closed-loop client's next
        request, an agent session's next turn..."""
        for ask in self.traffic.after(rec.ask, list(rec.req.generated), t):
            self._insert(ask)

    # -- one round ---------------------------------------------------------------

    def _submit_due(self, now: float) -> None:
        with jax.profiler.TraceAnnotation(SPAN_SUBMIT):
            while self._pending and self._pending[0].due <= now:
                rec = self._pending.pop(0)
                rec.req = self.eng.submit(rec.prompt, rec.max_new)
                rec.submitted = self.now()
                self.late.append(rec.submitted - rec.due)
                self.recs.append(rec)
                self.by_rid[rec.req.rid] = rec

    def _chain(self, req, step_idx: int) -> None:
        """Keep the row's shared pages whenever they change (a new request,
        or a copy-on-write that turned a shared page into its own)."""
        chain = req.shared_chain
        key = (id(chain), len(chain))
        if self._chain_key.get(req.rid) != key:
            self._chain_key[req.rid] = key
            self.chains.setdefault(req.rid, []).append(
                (step_idx, tuple(sorted(chain.items()))))

    def round(self) -> bool:
        """submit, admit, step, maintain; False when there is nothing to do
        now (the caller waits for the next arrival)."""
        now = self.now()
        self._submit_due(now)
        self.eng.admit()
        running = self.eng.running()
        if not running:
            if self.eng.queued():
                if not self.eng.unblock():
                    raise MemoryError("queued work cannot be admitted and "
                                      "nothing is running")
                return True
            return False
        with jax.profiler.TraceAnnotation(SPAN_BOOK):
            t0 = self.now()
            before = []
            for req in running:
                rec = self.by_rid[req.rid]
                if rec.running_at is None:
                    rec.running_at = t0
                self._chain(req, len(self.steps))
                before.append((req, req.committed))
            pages = self.eng.pages_in_use()
        with jax.profiler.TraceAnnotation(SPAN_STEP):
            self.eng.step()
        t1 = self.now()
        with jax.profiler.TraceAnnotation(SPAN_BOOK):
            rows = []
            for req, c0 in before:
                rows.append((req.rid, c0, max(0, req.committed - c0)))
                rec = self.by_rid[req.rid]
                g = len(req.generated)
                if g > rec.seen:
                    rec.token_times.extend([t1] * (g - rec.seen))
                    rec.seen = g
                if req.state == "finished":
                    self._on_finish(rec, t1)
            self.steps.append(StepLog(t0, t1, rows, pages))
            self.eng.maintain()
        return True

    def _wait(self, until: float) -> None:
        with jax.profiler.TraceAnnotation(SPAN_WAIT):
            if self._pending:
                dt = min(0.005, self._pending[0].due - self.now())
                if dt > 0:
                    time.sleep(dt)
            elif until > self.now():
                time.sleep(min(0.005, until - self.now()))

    # -- phases -------------------------------------------------------------------

    def start(self) -> None:
        """Start the traffic: the lead-in begins now, and the window opens
        ``lead_in_s`` later."""
        self._origin = time.perf_counter() + self.traffic.lead_in_s
        self._plan()

    def run_until(self, t_end: float) -> None:
        while self.now() < t_end:
            if not self.round():
                self._wait(t_end)

    def lead_in(self) -> None:
        self.run_until(0.0)

    def window(self, on_open=None) -> Record:
        """Run the measured window; ``on_open`` is called as it opens (the
        traced run starts the profiler there)."""
        if on_open is not None:
            on_open()
        mark = len(self.compile_log.events) if self.compile_log else 0
        opened = self.eng.counters()
        self.run_until(self.window_s)
        close = self.now()
        return Record(
            window_s=self.window_s, recs=self.recs, steps=self.steps,
            counters_open=opened, counters_close=self.eng.counters(),
            chains=self.chains, close_t=close,
            compiles_in_window=([n for n, _ in self.compile_log.since(mark)]
                                if self.compile_log else []),
            late=[lt for lt, r in zip(self.late, self.recs)
                  if 0.0 <= r.due < self.window_s])


def warm_up(engine, traffic: Traffic) -> None:
    """Compile and load every program the cell's traffic uses, at its final
    shapes, before anything is timed.

    One long request brings the engine's prompt buffer to its final width
    at its first admission (the bring-up smoke's ``first_len``; the buffer
    doubles, so anything over half the longest prompt will do), and runs
    the prefill chunk and decode programs; a second that repeats half of
    its prompt runs the prefix cache's share and copy-on-write path.  The
    long request starts with the traffic's first prefix (an agent's system
    prompt), and every prefix is then in the prefix cache when the traffic
    starts: set-up the traffic needs.  Last, whatever the engine module
    warms of its own (``warm_up_extra``)."""
    prefixes = traffic.prefixes()
    width = traffic.max_prompt_len() // 2 + 1
    head = list(prefixes[0]) if prefixes else []
    first = head + traffic.tokens(("warm", 0), max(1, width - len(head)))
    reqs = [engine.submit(first, 2)]
    engine.drain()
    reqs.append(engine.submit(first[: max(1, len(first) // 2)] + [0], 2))
    engine.drain()
    if prefixes:
        reqs += [engine.submit(list(p), 1) for p in prefixes]
        engine.drain()
    bad = [r.rid for r in reqs if r.state != "finished"]
    if bad:
        raise RuntimeError(f"warm-up requests {bad} did not finish")
    engine.warm_up_extra()


def percentile(values, q: float) -> float | None:
    """The q-th percentile (0-100) of all values, linear between the
    nearest ranks; None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
