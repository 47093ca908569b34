"""Data-parallel multi-pool serving: routing, correctness, per-replica
sync-freedom, and a hypothesis interleaving test asserting global page
conservation, no cross-pool leakage and single-pool-equivalent release
floors across 2–4 replicas.  Replicas share the single CPU test device
(the device-count flag belongs to the benchmark subprocess, not tier-1 —
see tests/conftest.py); every invariant here is device-count independent.
"""

import dataclasses

import hypothesis.strategies as st
import jax
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings

from repro.configs import get_config, reduced
from repro.core.vm import superblock_floor
from repro.models import build_model
from repro.serving import DataParallelEngine, PagedServingEngine

CFG = dataclasses.replace(reduced(get_config("olmo-1b")), n_layers=1)
SYS = list(range(40, 48))


@pytest.fixture(scope="module")
def params():
    return build_model(CFG).init(jax.random.PRNGKey(0))


def _fleet(params, n, **kw):
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_pages_per_seq", 8)
    return DataParallelEngine(CFG, params, replicas=n, **kw)


def _conservation(eng):
    """Per-replica page conservation: mapped capacity splits exactly into
    the free list and the distinct live pages; nothing leaks across pools
    (every live refcount belongs to this pool's own accounting)."""
    for e in eng.replicas:
        free = int(e.pool.free_top)
        distinct = e.scheduler.distinct_pages_in_use()
        assert free == e.kv_manager.mapped_pages - distinct, \
            f"conservation broke: free={free} mapped={e.kv_manager.mapped_pages} live={distinct}"
        live = [p for r in e.running for p in r.pages]
        assert len(live) == len(set(live)), "page double-mapped inside a pool"
        rc = np.asarray(e.pool.page_refcount)
        for r in e.running:
            assert r._engine is e, "request migrated across pools"
            for p in r.pages:
                assert 0 <= p < e.num_pages and rc[p] > 0, \
                    "block table names a page the pool does not hold live"


def test_outputs_match_single_engine(params):
    """Greedy decode through the fleet equals a single engine per prompt —
    routing must not change results."""
    prompts = [[5, 9, 13], [7, 11], [3, 4, 5, 6], [2, 8]]
    base = []
    for p in prompts:
        e = PagedServingEngine(CFG, params, num_pages=32, page_size=4,
                               max_batch=2, max_pages_per_seq=8)
        r = e.submit(p, 5)
        e.run()
        base.append(r.generated)
    fleet = _fleet(params, 2)
    rs = [fleet.submit(p, 5) for p in prompts]
    stats = fleet.run()
    assert all(r.state == "finished" for r in rs)
    for r, b in zip(rs, base):
        assert r.generated == b
    assert stats.tokens_committed == sum(
        e.stats.tokens_committed for e in fleet.replicas)
    _conservation(fleet)


def test_router_prefers_prefix_affinity_then_pressure(params):
    """A prompt matching replica 0's resident prefix routes there (sharing
    only pays inside one pool); an unrelated prompt goes to the least
    loaded replica."""
    fleet = _fleet(params, 2, prefix_cache=True)
    r0 = fleet.submit(SYS + [101, 201], 4)
    assert r0._engine is fleet.replicas[0]  # empty fleet: tie -> replica 0
    fleet.run()  # seeds replica 0's prefix index
    ra = fleet.submit(SYS + [102, 202], 4)
    assert ra._engine is fleet.replicas[0], "affinity must beat round-robin"
    rb = fleet.submit([900, 901, 902], 4)
    assert rb._engine is fleet.replicas[1], "no match -> least pressure"
    fleet.run()
    assert fleet.replicas[0].stats.prefix_hits >= 1
    assert ra.prefix_reused >= len(SYS)
    _conservation(fleet)


def test_fleet_steps_stay_sync_free_per_replica(monkeypatch, params):
    """The interleaved fleet step keeps the per-replica hot-path contract:
    at most ONE host transfer per replica per step."""
    import jax._src.array as jarray
    fleet = _fleet(params, 2, num_pages=64, max_pages_per_seq=10)
    for i in range(4):
        fleet.submit([1 + i, 2 + i, 3 + i], 20)
    for _ in range(4):  # admit + compile + settle
        fleet.step()

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
    for name in ("__array__", "__bool__", "__int__", "__float__", "__index__"):
        orig = getattr(jarray.ArrayImpl, name, None)
        if orig is not None:
            monkeypatch.setattr(jarray.ArrayImpl, name, c.wrap(orig))
    nsteps = 4
    for _ in range(nsteps):
        fleet.step()
    assert c.count <= nsteps * len(fleet.replicas), (
        f"{c.count} transfers across {nsteps} fleet steps of "
        f"{len(fleet.replicas)} replicas")


def test_per_replica_release_floor_matches_single_pool(params):
    """After drain, each replica's shrink parks exactly the superblocks a
    single-pool engine would: down to the same ``superblock_floor`` of its
    own distinct live pages."""
    fleet = _fleet(params, 2, num_pages=32, pages_per_superblock=4)
    for i in range(4):
        fleet.submit([5 + i, 9, 13], 4)
    fleet.run()
    fleet.shrink()
    for e in fleet.replicas:
        floor = superblock_floor(e.scheduler.distinct_pages_in_use(),
                                 e.pages_per_superblock, 1)
        assert e.kv_manager.allocator.superblocks_mapped == floor
        assert e.stats.superblocks_mapped == floor


# ---------------------------------------------------------------------------
# hypothesis: random interleavings across the fleet


@given(n_replicas=st.integers(2, 4),
       ops=st.lists(st.one_of(
           st.tuples(st.just("submit"), st.integers(0, 3), st.integers(1, 5)),
           st.tuples(st.just("step"), st.just(0), st.just(0)),
           st.tuples(st.just("preempt"), st.integers(0, 3), st.just(0)),
       ), min_size=1, max_size=10))
@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.too_slow,
                                 HealthCheck.function_scoped_fixture])
def test_random_interleavings_conserve_pages_per_pool(params, n_replicas,
                                                      ops):
    """Random submit/step/preempt interleavings across 2–4 replicas: after
    every fleet step each pool's pages balance exactly (free + distinct
    live == mapped), no page crosses a pool, and the drained fleet releases
    down to the single-pool floor per replica."""
    fleet = _fleet(params, n_replicas, num_pages=16, pages_per_superblock=4,
                   max_batch=2)
    handles = []
    for op, a, b in ops:
        if op == "submit":
            prompt = [10 + a, 11 + a, 12 + a][: 1 + a % 3]
            handles.append(fleet.submit(prompt, b))
        elif op == "step":
            fleet.step()
            _conservation(fleet)
        elif op == "preempt":
            running = [r for e in fleet.replicas for r in e.running]
            if running:
                victim = running[a % len(running)]
                victim._engine.scheduler.preempt(victim)
                _conservation(fleet)
    for _ in range(200):
        if fleet.drained():
            break
        fleet.step()
    assert fleet.drained()
    assert all(r.state == "finished" for r in handles)
    _conservation(fleet)
    fleet.shrink()
    for e in fleet.replicas:
        floor = superblock_floor(e.scheduler.distinct_pages_in_use(),
                                 e.pages_per_superblock, 1)
        assert e.kv_manager.allocator.superblocks_mapped == floor
