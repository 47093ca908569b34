"""Chaos layer + self-healing serving: seeded fault injection through the
allocator protocol (grant denials, spurious validation failures, delayed
frees, unmap-under-reader), SLO-aware admission shedding, bounded grant
retries with backpressure gauges, and data-parallel failover — a killed or
stalled replica's requests migrate to survivors token-exact, and a revived
replica rejoins the fleet.  The sync-free invariant (one host transfer per
steady step) is re-asserted with faults enabled."""

import dataclasses
import threading
import time

import jax
import pytest

from repro.configs import get_config, reduced
from repro.core import Allocator, ChaosAllocator, ChaosConfig
from repro.core.pagepool import DevicePagePool
from repro.serving import (DataParallelEngine, PagedServingEngine,
                           ReplicaStalled, WatchdogConfig)

CFG = dataclasses.replace(reduced(get_config("olmo-1b")), n_layers=1)


@pytest.fixture(scope="module")
def params():
    from repro.models import build_model
    return build_model(CFG).init(jax.random.PRNGKey(0))


def _engine(params, **kw):
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_pages_per_seq", 8)
    return PagedServingEngine(CFG, params, **kw)


def _fleet(params, n, **kw):
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_pages_per_seq", 8)
    return DataParallelEngine(CFG, params, replicas=n, **kw)


PROMPTS = [[5, 9, 13], [7, 11], [3, 4, 5, 6], [2, 8], [17, 23, 29], [6, 10]]


def _oracle(params, prompts, max_new):
    """Fault-free reference outputs, one fresh engine per prompt."""
    out = []
    for p in prompts:
        e = _engine(params)
        r = e.submit(p, max_new)
        e.run()
        out.append(r.generated)
    return out


# ---------------------------------------------------------------------------
# the chaos allocator itself

def test_chaos_allocator_conforms_and_is_transparent_at_p_zero():
    """A zero-probability ChaosAllocator satisfies the Allocator protocol
    and behaves exactly like the pool it wraps (incl. attribute
    forwarding, the state passthrough and deferred-free flush)."""
    chaotic = ChaosAllocator(DevicePagePool(16, 4), ChaosConfig(seed=1))
    assert isinstance(chaotic, Allocator)
    assert chaotic.num_pages == 16 and chaotic.pages_per_superblock == 4
    ids, ok = chaotic.alloc(3)
    assert ok and len(ids) == 3
    assert chaotic.view().pages_mapped == 16
    chaotic.free(ids)
    chaotic.flush()  # no deferrals at p=0: must be a no-op
    assert chaotic.faults == {"grant_denial": 0, "spurious_invalid": 0,
                              "delayed_free": 0, "unmap_under_reader": 0}
    # state passthrough: the wrapper never copies or perturbs the pytree
    assert chaotic.state is chaotic.inner.state
    chaotic.state = chaotic.inner.state
    assert isinstance(chaotic.inner, DevicePagePool)


def test_chaos_denies_grants_deterministically():
    """Same seed, same denial schedule — chaos runs are reproducible."""
    def denials(seed):
        c = ChaosAllocator(DevicePagePool(16, 4),
                           ChaosConfig(seed=seed, grant_denial_p=0.5))
        return [c.alloc(1)[1] for _ in range(20)]
    assert denials(7) == denials(7)
    assert False in denials(7) and True in denials(7)


# ---------------------------------------------------------------------------
# the engine under injected faults (token-exact recovery)

def test_grant_denials_are_retried_to_completion(params):
    """10%+ injected grant denials: the bounded retry absorbs them, every
    request finishes, outputs are token-exact, and the denial/retry
    counters prove the schedule actually fired."""
    base = _oracle(params, PROMPTS[:4], 5)
    eng = _engine(params, chaos=ChaosConfig(seed=3, grant_denial_p=0.3))
    rs = [eng.submit(p, 5) for p in PROMPTS[:4]]
    eng.run()
    assert all(r.state == "finished" for r in rs)
    assert [r.generated for r in rs] == base
    assert eng.kv_manager.allocator.faults["grant_denial"] > 0
    assert eng.stats.grant_denials > 0
    assert eng.stats.grant_retries > 0


def test_spurious_validation_failures_restart_and_recover(params):
    """Perturbed snapshots make rows fail OA validation exactly as if a
    reclaimer raced them: the engine restarts those requests and still
    produces token-exact output."""
    base = _oracle(params, PROMPTS[:4], 5)
    eng = _engine(params, chaos=ChaosConfig(seed=5, spurious_invalid_p=0.4))
    rs = [eng.submit(p, 5) for p in PROMPTS[:4]]
    eng.run()
    assert all(r.state == "finished" for r in rs)
    assert [r.generated for r in rs] == base
    assert eng.kv_manager.allocator.faults["spurious_invalid"] > 0
    assert eng.stats.reader_restarts > 0


def test_delayed_frees_and_unmap_under_reader_recover(params):
    """Deferred frees starve the free list and spontaneous releases unmap
    EMPTY superblocks under the engine; retries + remap absorb both."""
    base = _oracle(params, PROMPTS[:4], 5)
    eng = _engine(params, chaos=ChaosConfig(
        seed=11, delayed_free_p=0.6, delay_ops=2, unmap_under_reader_p=0.5))
    rs = [eng.submit(p, 5) for p in PROMPTS[:4]]
    eng.run()
    assert all(r.state == "finished" for r in rs)
    assert [r.generated for r in rs] == base
    faults = eng.kv_manager.allocator.faults
    assert faults["delayed_free"] > 0


# ---------------------------------------------------------------------------
# SLO-aware shedding + backpressure

def test_expired_deadline_is_shed_at_admission(params):
    """A request whose deadline already passed is rejected at admission
    (state "shed", counted), without disturbing its queue neighbours."""
    eng = _engine(params)
    doomed = eng.submit([5, 9, 13], 5, deadline=0.0)
    healthy = eng.submit([7, 11], 5)  # no deadline: best effort
    eng.run()
    assert doomed.state == "shed" and doomed.generated == []
    assert healthy.state == "finished"
    assert eng.stats.requests_shed == 1


def test_generous_deadline_is_not_shed(params):
    """A deadline with plenty of slack admits and finishes normally."""
    eng = _engine(params)
    r = eng.submit([5, 9, 13], 5, deadline=3600.0)
    eng.run()
    assert r.state == "finished" and eng.stats.requests_shed == 0


def test_deadline_expiry_mid_decode_never_sheds(params):
    """Shedding happens AT ADMISSION only: once a request is running its
    committed KV is sunk cost, and an expiry mid-decode must not kill it."""
    eng = _engine(params)
    r = eng.submit([5, 9, 13], 6, deadline=3600.0)
    eng._admit()
    eng.step()  # running, some work committed
    r.deadline = time.time() - 1.0  # expires mid-decode
    eng.run()
    assert r.state == "finished"
    assert eng.stats.requests_shed == 0


def test_backpressure_gauges_surface_through_stats(params):
    """Every absorbed step refreshes the throttling gauges: pool pressure
    in (0, 1], the AIMD ratio in (0, 1], and the queue depth."""
    eng = _engine(params)
    for p in PROMPTS[:4]:
        eng.submit(p, 4)
    eng._admit()
    eng.step()  # mid-run: live pages pin the pressure gauge above zero
    assert 0.0 < eng.stats.pool_pressure <= 1.0
    assert 0.0 < eng.stats.aimd_ratio <= 1.0
    assert eng.stats.queue_depth >= 0
    eng.run()
    assert eng.stats.queue_depth == 0  # drained


# ---------------------------------------------------------------------------
# submit() input validation (satellite)

@pytest.mark.parametrize("prompt,max_new", [
    ([], 5),                  # empty prompt
    ([1, 2, 3], 0),           # no generation budget
    ([1, 2, 3], -2),          # negative budget
    ([1, 2, 3], 1.5),         # non-int budget
    ([1, 2, 3], True),        # bool is not a token count
    ([1, "two", 3], 5),       # non-int token id
    ([1, 2.5, 3], 5),         # float token id
    ([1, True, 3], 5),        # bool token id
])
def test_submit_rejects_degenerate_inputs(params, prompt, max_new):
    eng = _engine(params)
    with pytest.raises(ValueError):
        eng.submit(prompt, max_new)
    assert not eng.scheduler.queue  # nothing half-enqueued


def test_submit_accepts_numpy_integer_tokens(params):
    """np.int32/np.int64 ids (the usual tokenizer output) must pass."""
    import numpy as np
    eng = _engine(params)
    r = eng.submit(list(np.asarray([5, 9, 13], np.int32)),
                   np.int64(4))
    assert r.prompt == [5, 9, 13] and r.max_new_tokens == 4


# ---------------------------------------------------------------------------
# replica failover / watchdog / revive

class _Kill(RuntimeError):
    pass


def _kill_after(n):
    """A step hook that raises on its ``n``-th invocation, once."""
    state = {"calls": 0}

    def hook(_eng):
        state["calls"] += 1
        if state["calls"] == n:
            raise _Kill(f"injected kill at driver iteration {n}")
    return hook


def test_replica_kill_fails_over_with_zero_lost_requests(params):
    """Killing replica 0 mid-run migrates its queued AND in-flight requests
    onto the survivor; every request finishes and the stitched outputs
    (``output_tokens``) are token-exact vs the fault-free oracle."""
    base = _oracle(params, PROMPTS, 8)
    fleet = _fleet(params, 2, watchdog=WatchdogConfig(stall_timeout=30.0))
    rs = [fleet.submit(p, 8) for p in PROMPTS]
    victims = [r for r in rs if r._engine is fleet.replicas[0]]
    assert victims, "router sent nothing to replica 0?"
    fleet.step_hooks[0] = _kill_after(3)
    fleet.run()
    assert all(r.state == "finished" for r in rs)
    assert [r.output_tokens for r in rs] == base
    assert not fleet.alive[0]
    stats = fleet.stats
    assert stats.replica_failures == 1
    assert stats.requests_migrated >= len(victims)
    assert any(r.migrations == 1 for r in victims)


def test_stalled_replica_is_detected_by_heartbeat(params):
    """A replica wedged inside a step (hook blocks forever) trips the
    stall timeout; the watchdog abandons it and the fleet still drains
    every request on the survivor."""
    fleet = _fleet(params, 2, watchdog=WatchdogConfig(
        stall_timeout=2.0, poll_interval=0.02))
    # warm the jit caches first: a cold compile inside the drive loop is a
    # legitimate >2s heartbeat gap and would trip the short test timeout
    warm = [fleet.submit(p, 2) for p in PROMPTS[:2]]
    fleet.run()
    assert all(r.state == "finished" for r in warm)
    rs = [fleet.submit(p, 6) for p in PROMPTS[:4]]
    wedge = threading.Event()  # never set: the hook hangs forever

    def hook(_eng):
        wedge.wait()
    fleet.step_hooks[0] = hook
    fleet.run()
    assert all(r.state == "finished" for r in rs)
    assert not fleet.alive[0]
    assert fleet.stats.replica_failures == 1


def test_revived_replica_rejoins_the_fleet(params):
    """With ``auto_revive`` the dead slot gets a fresh engine, the backlog
    rebalances over it, and the fleet reports the revival."""
    fleet = _fleet(params, 2, watchdog=WatchdogConfig(
        stall_timeout=30.0, auto_revive=True))
    old = fleet.replicas[0]
    rs = [fleet.submit(p, 8) for p in PROMPTS]
    fleet.step_hooks[0] = _kill_after(2)
    fleet.run()
    assert all(r.state == "finished" for r in rs)
    assert fleet.alive[0] and fleet.replicas[0] is not old
    stats = fleet.stats
    assert stats.replica_failures == 1 and stats.replica_revivals == 1
    # the revived replica is routable again
    r = fleet.submit([41, 42, 43], 3)
    fleet.run()
    assert r.state == "finished"


def test_worker_exception_propagates_promptly_without_watchdog(params):
    """Satellite: no watchdog means no self-healing — but a raising
    replica must park the fleet (bounded join) and propagate, not hang."""
    fleet = _fleet(params, 2)  # watchdog=None
    for p in PROMPTS[:4]:
        fleet.submit(p, 6)
    fleet.step_hooks[0] = _kill_after(2)
    with pytest.raises(_Kill):
        fleet.run()


def test_single_replica_failure_with_no_survivor_raises(params):
    """A 1-replica fleet has nobody to fail over to: the error surfaces."""
    fleet = _fleet(params, 1, watchdog=WatchdogConfig())
    fleet.submit([5, 9, 13], 4)
    fleet.step_hooks[0] = _kill_after(1)
    with pytest.raises(_Kill):
        fleet.run()


# ---------------------------------------------------------------------------
# the sync-free invariant survives injected faults

def test_steady_steps_stay_sync_free_under_chaos(monkeypatch, params):
    """Faults land only at the allowed sync points (admission, finish,
    maintenance): a window of steady fused steps under an aggressive
    chaos schedule still performs at most ONE host transfer per step."""
    import jax._src.array as jarray
    eng = _engine(params, num_pages=64, max_pages_per_seq=16,
                  chaos=ChaosConfig(seed=2, grant_denial_p=0.3,
                                    spurious_invalid_p=0.3,
                                    delayed_free_p=0.3))
    for i in range(3):
        eng.submit([1 + i, 2 + i, 3 + i], 30)
    for _ in range(4):  # admit + compile + settle (restarts may re-admit)
        eng._admit()
        eng.step()

    class Counter:
        def __init__(self):
            self.count, self._inside = 0, False

        def wrap(self, fn):
            def wrapped(*a, **k):
                if self._inside:
                    return fn(*a, **k)
                self.count += 1
                self._inside = True
                try:
                    return fn(*a, **k)
                finally:
                    self._inside = False
            return wrapped

    c = Counter()
    monkeypatch.setattr(jax, "device_get", c.wrap(jax.device_get))
    for name in ("__array__", "__bool__", "__int__", "__float__",
                 "__index__"):
        orig = getattr(jarray.ArrayImpl, name, None)
        if orig is not None:
            monkeypatch.setattr(jarray.ArrayImpl, name, c.wrap(orig))
    nsteps = 6
    for _ in range(nsteps):
        eng.step()  # no admissions inside the window: steady decode only
    assert c.count <= nsteps, (
        f"{c.count} host transfers across {nsteps} chaos steps")


def test_watchdog_config_reexported():
    """The serving package re-exports the failover surface."""
    import repro.serving as serving
    assert serving.WatchdogConfig is WatchdogConfig
    assert serving.ReplicaStalled is ReplicaStalled
