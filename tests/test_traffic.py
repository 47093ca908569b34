"""Overload-robust serving: bounded multi-class admission, the
graceful-degradation ladder, monotonic-clock SLO bookkeeping, streaming
percentiles and the replayable open-loop trace format.

The pure-host pieces run against the FakeAllocator stack from
``test_layering`` (no jax); the engine-facade pieces (blocking submit,
streaming drain) run a real tiny model.  Property tests (hypothesis) pin
the invariants the scheduler's overload behaviour is built on:

- strict priority: a queued higher class is never passed over at admission
- bounded queues: no class queue ever exceeds its cap; overflow is an
  explicit rejection, not silent growth
- shed-at-admission-only: a RUNNING request is never shed (preempted and
  requeued, yes — shed, never)
- ladder monotonicity: the level moves at most one rung per observation
  and stays within [0, 4]
"""

import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serving import (DEFAULT_CLASSES, ClassQueues, ClassStats,
                           DegradationLadder, EngineStats, LadderConfig,
                           LatencyReservoir, RequestClass, TraceEvent,
                           aggregate_stats, dump_trace, load_trace,
                           replay_arrivals, synthesize_trace)
from repro.serving.overload import VICTIM_POLICIES
from test_layering import FakeRunner, _fake_stack

CLS_NAMES = sorted(DEFAULT_CLASSES)  # background < batch < interactive (abc)
PRIO = {n: DEFAULT_CLASSES[n].priority for n in CLS_NAMES}


class FakeClock:
    """Injectable monotonic clock for deterministic SLO tests."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _drain(sched, kvm, runner, max_steps=200):
    for _ in range(max_steps):
        sched.admit()
        if not sched.running and not sched.queue:
            return
        sched.absorb(runner.execute(kvm), 1, 1)
    raise AssertionError("did not drain")


# ---------------------------------------------------------------------------
# satellite: monotonic deadline bookkeeping


def test_deadlines_ignore_wall_clock_jumps(monkeypatch):
    """Regression: deadlines used to be absolute ``time.time()`` values, so
    an NTP step (or any wall-clock jump) mass-shed the queue.  The
    scheduler now runs on ``time.monotonic`` — a huge forward jump of
    ``time.time`` must not shed anything."""
    alloc, kvm, sched, stats = _fake_stack()
    req = sched.submit([1, 2], 2, deadline=30.0)
    monkeypatch.setattr(time, "time", lambda: time.monotonic() + 1e6)
    _drain(sched, kvm, FakeRunner())
    assert req.state == "finished"
    assert stats.requests_shed == 0


def test_mocked_clock_sheds_hopeless_deadlines_deterministically():
    """With an injected clock: a queued request whose deadline passes is
    shed at admission (state ``"shed"``, per-class counter); one whose
    deadline holds is admitted and finishes."""
    clk = FakeClock()
    alloc, kvm, sched, stats = _fake_stack(clock=clk)
    doomed = sched.submit([1, 2], 2, deadline=5.0, cls="batch")
    fine = sched.submit([3, 4], 2, deadline=500.0)
    clk.advance(10.0)  # past doomed's deadline, inside fine's
    _drain(sched, kvm, FakeRunner())
    assert doomed.state == "shed" and fine.state == "finished"
    assert stats.requests_shed == 1
    assert stats.class_stats["batch"].shed == 1


def test_speed_model_runs_on_injected_clock():
    """The EWMA seconds-per-token estimator samples the scheduler clock,
    so a mocked clock makes the shedding estimator fully deterministic:
    at 1 s/token (est. 12 s for 12 tokens), a 2 s deadline is hopeless."""
    clk = FakeClock()
    alloc, kvm, sched, stats = _fake_stack()
    sched.clock = clk
    sched._speed_warmup = 0
    runner = FakeRunner()
    first = sched.submit([1, 2], 6)
    sched.admit()
    for _ in range(20):  # 1 token per step, clock advancing 1 s per step
        if not sched.running:
            break
        clk.advance(1.0)
        sched.absorb(runner.execute(kvm), 1, 1)
    assert first.state == "finished"
    assert sched.sec_per_token == pytest.approx(1.0, rel=0.2)
    late = sched.submit([1, 2], 6, deadline=2.0)  # est ~8 s of work
    sched.admit()
    assert late.state == "shed"


# ---------------------------------------------------------------------------
# bounded multi-class admission


def test_bounded_queue_rejects_then_requeues():
    alloc, kvm, sched, stats = _fake_stack(max_queue_depth=2)
    a = sched.submit([1, 2], 2)
    b = sched.submit([1, 2], 2)
    c = sched.submit([1, 2], 2)  # over the bound: explicit backpressure
    assert a.state == b.state == "queued" and c.state == "rejected"
    assert len(sched.queue) == 2
    assert stats.requests_rejected == 1
    assert stats.class_stats["interactive"].rejected == 1
    _drain(sched, kvm, FakeRunner())
    assert sched.requeue(c) is True and c.state == "queued"
    _drain(sched, kvm, FakeRunner())
    assert c.state == "finished"


def test_unknown_class_is_a_clear_error():
    alloc, kvm, sched, stats = _fake_stack()
    with pytest.raises(ValueError, match="unknown request class"):
        sched.submit([1, 2], 2, cls="platinum")
    with pytest.raises(ValueError, match="unknown victim_policy"):
        _fake_stack(victim_policy="oldest-first")


def test_strict_priority_drain_order():
    """ClassQueues drains interactive before batch before background
    regardless of submit order; FIFO within a class."""
    q = ClassQueues(DEFAULT_CLASSES)

    class R:
        def __init__(self, cls, tag):
            self.cls, self.tag = cls, tag

    order = [R("background", 0), R("batch", 1), R("interactive", 2),
             R("interactive", 3), R("batch", 4)]
    for r in order:
        q.append(r)
    assert q[0].tag == 2
    drained = [q.popleft().tag for _ in range(len(q))]
    assert drained == [2, 3, 1, 4, 0]
    assert not q and len(q) == 0


# Property tests: the checkers below run over hypothesis-generated inputs.


def _check_priority_never_starved(classes):
    """At every admission the admitted request belongs to the
    highest-priority class then queued — a lower class can never jump a
    queued higher one (strict priority, no aging)."""
    alloc, kvm, sched, stats = _fake_stack(max_batch=1)
    runner = FakeRunner()
    for c in classes:
        sched.submit([1, 2], 2, cls=c)
    for _ in range(200):
        queued_best = min((PRIO[r.cls] for r in sched.queue), default=None)
        before = set(id(r) for r in sched.running)
        sched.admit()
        admitted = [r for r in sched.running if id(r) not in before]
        for r in admitted:
            assert queued_best is not None
            assert PRIO[r.cls] == queued_best
        if not sched.running and not sched.queue:
            break
        sched.absorb(runner.execute(kvm), 1, 1)
    assert not sched.queue and not sched.running


def _check_bounded_queue(ops, cap):
    """Under any interleaving of submits and drain steps, no class queue
    exceeds its cap and accepted + rejected == submitted."""
    alloc, kvm, sched, stats = _fake_stack(max_batch=1, max_queue_depth=cap)
    runner = FakeRunner()
    submitted = rejected = 0
    for cls, do_step in ops:
        r = sched.submit([1, 2], 2, cls=cls)
        submitted += 1
        rejected += r.state == "rejected"
        for c in CLS_NAMES:
            assert sched.queue.depth(c) <= cap
        if do_step:
            sched.admit()
            if sched.running:
                sched.absorb(runner.execute(kvm), 1, 1)
    assert stats.requests_rejected == rejected
    total_cls = sum(cs.submitted for cs in stats.class_stats.values())
    assert total_cls == submitted - rejected


def _check_ladder_monotone(pressures):
    ladder = DegradationLadder(LadderConfig(engage_after=2, release_after=2))
    prev = ladder.level
    for p in pressures:
        lvl = ladder.observe(p)
        assert 0 <= lvl <= DegradationLadder.NUM_RUNGS
        assert abs(lvl - prev) <= 1  # monotone engagement, no rung skipped
        prev = lvl


@settings(max_examples=50, deadline=None)
@given(st.lists(st.sampled_from(CLS_NAMES), min_size=1, max_size=12))
def test_prop_high_priority_never_starved_by_lower(classes):
    _check_priority_never_starved(classes)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.sampled_from(CLS_NAMES), st.booleans()),
                min_size=1, max_size=30),
       st.integers(min_value=1, max_value=3))
def test_prop_bounded_queue_never_exceeds_cap(ops, cap):
    _check_bounded_queue(ops, cap)


@settings(max_examples=100, deadline=None)
@given(st.lists(st.floats(min_value=0.0, max_value=2.0,
                          allow_nan=False), min_size=1, max_size=60))
def test_prop_ladder_moves_one_rung_at_a_time(pressures):
    _check_ladder_monotone(pressures)


# ---------------------------------------------------------------------------
# the degradation ladder through the scheduler


def _pressured_stack(**kw):
    cfg = LadderConfig(high_water=0.9, low_water=0.1, engage_after=1,
                       release_after=1, queue_soft_limit=2)
    return _fake_stack(max_batch=1, ladder=cfg, **kw)


def test_ladder_rungs_engage_in_order_and_reverse():
    """Sustained pressure engages chunk-shrink → spec-off → cache-evict →
    shed, one rung per observation (engage_after=1); sustained calm
    releases in reverse.  Each transition is observable in EngineStats."""
    alloc, kvm, sched, stats = _pressured_stack()
    levels = []
    for _ in range(4):
        sched._tick_ladder(pool_pressure=1.0)
        levels.append(sched.ladder.level)
    assert levels == [1, 2, 3, 4]
    assert stats.ladder_engagements == 4 and stats.degradation_level == 4
    assert sched._ladder_chunk_cap == max(1, sched.prefill_chunk // 2)
    assert sched._ladder_spec_off is True
    for _ in range(4):
        sched._tick_ladder(pool_pressure=0.0)
    assert sched.ladder.level == 0
    assert stats.ladder_releases == 4 and stats.degradation_level == 0
    assert sched._ladder_chunk_cap is None and not sched._ladder_spec_off


def test_ladder_rung4_sheds_lowest_class_queued_only():
    """Rung 4 drops QUEUED work from the lowest class (newest first) down
    to the soft limit; running requests are untouched."""
    alloc, kvm, sched, stats = _pressured_stack()
    running = sched.submit([1, 2], 4)
    sched.admit()
    assert running.state == "running"
    keep = sched.submit([1, 2], 2, cls="interactive")
    low1 = sched.submit([1, 2], 2, cls="background")
    low2 = sched.submit([1, 2], 2, cls="background")
    mid = sched.submit([1, 2], 2, cls="batch")
    for _ in range(4):
        sched._tick_ladder(pool_pressure=1.0)
    assert sched.ladder.level == 4
    # 4 queued > soft limit 2: the two NEWEST lowest-class entries go
    assert low2.state == "shed" and low1.state == "shed"
    assert keep.state == "queued" and mid.state == "queued"
    assert running.state == "running"  # never shed mid-decode
    assert stats.ladder_sheds == 2
    _drain(sched, kvm, FakeRunner())
    assert running.state == "finished" and keep.state == "finished"


def test_shed_only_ever_hits_queued_requests_under_pressure():
    """Invariant sweep: drive an overloaded stack (tiny pool, ladder hot)
    and assert no request transitions to ``"shed"`` while running."""
    alloc, kvm, sched, stats = _pressured_stack()
    runner = FakeRunner()
    reqs = [sched.submit([1, 2], 2,
                         cls=CLS_NAMES[i % len(CLS_NAMES)])
            for i in range(12)]
    for _ in range(300):
        sched.admit()
        assert all(r.state == "running" for r in sched.running)
        assert all(r.slot is None for r in reqs if r.state == "shed")
        if not sched.running and not sched.queue:
            break
        sched.absorb(runner.execute(kvm), 1, 1)
    assert all(r.state in ("finished", "shed") for r in reqs)
    assert stats.ladder_engagements > 0  # the queue backlog tripped it


def test_deadline_victim_policy_spares_tight_deadlines():
    """The ``"deadline"`` victim policy preempts the request with the most
    slack (here: the one with NO deadline) instead of the youngest."""
    clk = FakeClock()
    alloc, kvm, sched, stats = _fake_stack(max_batch=2,
                                           victim_policy="deadline",
                                           clock=clk)
    tight = sched.submit([1, 2], 4, deadline=3.0)
    loose = sched.submit([1, 2], 4)
    sched.admit()
    sched.sec_per_token = 0.1
    victim = sched.pick_victim()
    assert victim is loose
    # youngest policy on the same state picks by committed work instead
    assert VICTIM_POLICIES["youngest"](sched, sched.running) is not None


# ---------------------------------------------------------------------------
# adaptive release driven by real arrival gaps


def test_adaptive_release_learns_real_arrival_gaps():
    """ROADMAP 3c: with a measured maintain-tick cadence, the adaptive
    threshold folds the REAL inter-arrival gap (seconds / sec-per-tick),
    not just the counted queue-empty ticks — a driver that ticks slowly
    no longer under-estimates the burst cadence."""
    clk = FakeClock()
    alloc, kvm, sched, stats = _fake_stack(release_quiescence="adaptive",
                                           clock=clk)
    runner = FakeRunner()
    for _ in range(3):  # learn the cadence: 1 s per maintain tick
        clk.advance(1.0)
        sched.maintain()
    assert sched._sec_per_tick == pytest.approx(1.0)
    sched.submit([1, 2], 2)
    _drain(sched, kvm, runner)
    for _ in range(2):  # only TWO counted idle ticks...
        clk.advance(1.0)
        sched.maintain()
    clk.advance(5.0)  # ...but 7 s of real silence before the next burst
    sched.submit([1, 2], 2)
    assert sched._gap_ewma is not None
    # counted ticks alone would fold 2; the real gap folds ~7
    assert sched._gap_ewma > 3.0
    _drain(sched, kvm, runner)


# ---------------------------------------------------------------------------
# streaming percentiles and aggregation


def test_latency_reservoir_percentiles_and_cap():
    r = LatencyReservoir(cap=100, seed=1)
    for v in range(1, 101):
        r.add(float(v))
    assert r.percentile(50) == pytest.approx(50, abs=1)
    assert r.percentile(99) == pytest.approx(99, abs=1)
    for v in range(10_000):
        r.add(float(v % 100) + 1)
    assert len(r.samples) == 100 and r.seen == 10_100
    assert 1 <= r.percentile(50) <= 100
    assert LatencyReservoir().percentile(99) == 0.0  # empty: no crash


def test_class_stats_aggregate_across_replicas():
    a, b = EngineStats(), EngineStats()
    a.record_ttft(3, 0.1, cls="interactive")
    a.record_rejection("interactive")
    a.record_ladder(1)
    b.record_ttft(5, 0.3, cls="interactive")
    b.record_itl("interactive", 0.01)
    b.record_shed(cls="background", by_ladder=True)
    tot = aggregate_stats([a, b])
    cs = tot.class_stats["interactive"]
    assert cs.ttft.seen == 2 and sorted(cs.ttft.samples) == [0.1, 0.3]
    assert cs.rejected == 1 and tot.requests_rejected == 1
    assert tot.class_stats["background"].shed == 1
    assert tot.ladder_sheds == 1 and tot.degradation_level == 1
    assert "ttft_p99" in cs.summary()


# ---------------------------------------------------------------------------
# the replayable trace format


def test_trace_roundtrip_is_byte_identical(tmp_path):
    kw = dict(duration_s=10.0, rate_rps=4.0, process="bursty",
              class_mix={"interactive": 0.6, "batch": 0.3,
                         "background": 0.1})
    evs = synthesize_trace(11, **kw)
    assert evs == synthesize_trace(11, **kw)  # deterministic in the seed
    assert evs != synthesize_trace(12, **kw)
    p1, p2 = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    dump_trace(evs, str(p1))
    assert load_trace(str(p1)) == evs
    dump_trace(synthesize_trace(11, **kw), str(p2))
    assert p1.read_bytes() == p2.read_bytes()


def test_trace_validation_and_replay(tmp_path):
    with pytest.raises(ValueError, match="arrival process"):
        synthesize_trace(0, duration_s=1.0, rate_rps=1.0, process="weibull")
    with pytest.raises(ValueError, match="positive"):
        synthesize_trace(0, duration_s=1.0, rate_rps=0.0)
    with pytest.raises(ValueError, match="mix"):
        synthesize_trace(0, duration_s=1.0, rate_rps=1.0,
                         class_mix={"interactive": -1.0})
    evs = synthesize_trace(3, duration_s=30.0, rate_rps=2.0)
    assert all(e2.t >= e1.t for e1, e2 in zip(evs, evs[1:]))
    cursor, seen = 0, 0
    for now in np.arange(0.0, 31.0, 0.5):
        due, cursor = replay_arrivals(evs, float(now), cursor)
        seen += len(due)
        assert all(e.t <= now for e in due)
    assert seen == len(evs)  # open loop delivers everything exactly once
    p = tmp_path / "bad.jsonl"
    p.write_text('{"trace_version": 99}\n')
    with pytest.raises(ValueError, match="version"):
        load_trace(str(p))
    prompt = evs[0].prompt(vocab_size=64)
    assert len(prompt) == evs[0].prompt_len
    assert prompt == evs[0].prompt(vocab_size=64)  # event-seeded, stable
    assert all(2 <= t < 64 for t in prompt)
