"""OA reclamation on the serving stack: the one path, tested end to end.

Every fused step validates every row's version snapshot against the page
pool (``pagepool.validate_and_commit`` inside ``fused_decode_step``); a row
whose pages were reclaimed since its snapshot fails validation, is
discarded and restarts from its prompt.  Greedy decoding makes the token
stream a strong oracle: any page handed out while a stale reader could
still observe it changes that reader's KV, and the divergence shows up in
the tokens.  The suite covers each kind of row the fused step validates,
the host mirrors after mixed workloads, the chaos layer's faults, the
pool's version rule as a property, and batching against serving alone.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, reduced
from repro.core import pagepool as pp
from repro.core.chaos import ChaosConfig
from repro.core.pagepool import DevicePagePool
from repro.core.vm import ReleaseStrategy
from repro.models import build_model
from repro.serving import PagedServingEngine
from repro.serving.paged_decode import fused_decode_step

CFG = reduced(get_config("olmo-1b"))
PAGE = 4
SHARED = list(range(1, 11))  # ten-token common prefix (2.5 pages)


@pytest.fixture(scope="module")
def params():
    return build_model(CFG).init(jax.random.PRNGKey(0))


def _engine(params, **kw):
    base = dict(num_pages=48, page_size=PAGE, max_batch=3,
                max_pages_per_seq=12, prefix_cache=True, speculative_k=2,
                prefill_chunk=2, release_quiescence=3,
                release_strategy=ReleaseStrategy.MADVISE)
    base.update(kw)
    return PagedServingEngine(CFG, params, **base)


def _drive_mixed(params, preempt=True, **kw):
    """The mixed workload: chunked prefill over a shared prefix,
    speculative decode, one deterministic mid-run preemption, a late burst
    arriving while earlier requests still run, and a full drain."""
    eng = _engine(params, **kw)
    reqs = [eng.submit(SHARED + [20 + i], 10) for i in range(3)]
    eng._admit()
    for _ in range(4):
        eng.step()
        eng._maintain()
    if preempt:
        # deterministic mid-run preemption of the youngest running request
        victim = min(eng.running, key=lambda r: r.rid)
        eng._preempt(victim)
    # a late burst while the first wave still decodes
    reqs += [eng.submit(SHARED + [30 + i], 8) for i in range(2)]
    eng.run()
    return eng, reqs


def _assert_mirrors_balanced(eng, clock=True):
    """The host clock mirror equals the device clock exactly (``clock``),
    and every remaining device reference is a prefix-cache pin."""
    if clock:
        assert eng.stats.warnings_fired == int(eng.pool.clock)
    rc = np.asarray(eng.pool.page_refcount)
    assert int(rc.sum()) == len(eng._cache_pages), (
        f"{int(rc.sum())} device refs vs {len(eng._cache_pages)} cache pins")
    assert (rc[sorted(eng._cache_pages)] == 1).all()


# -- external reclaim, one case per kind of validated row ---------------------


def _until(eng, cond, limit=40):
    for _ in range(limit):
        if cond():
            return
        eng.step()
        eng._maintain()
    raise AssertionError("setup never reached its state")


def _reclaim(eng, reqs, victims):
    """Free each victim's pages behind the scheduler's back."""
    for r in victims:
        eng.inject_external_reclaim(r)
    return eng, reqs, victims


def _decode_c1(params, reclaim):
    eng = _engine(params, prefix_cache=False, speculative_k=0,
                  prefill_chunk=1)
    req = eng.submit(SHARED, 8)
    eng._admit()
    _until(eng, lambda: len(req.generated) >= 2)
    return _reclaim(eng, [req], [req] if reclaim else [])


def _two_decode_rows_c1(params, reclaim):
    eng = _engine(params, prefix_cache=False, speculative_k=0,
                  prefill_chunk=1)
    reqs = [eng.submit([3, 5, 7], 8), eng.submit([4, 6, 8, 10], 8)]
    eng._admit()
    _until(eng, lambda: all(len(r.generated) >= 2 for r in reqs))
    return _reclaim(eng, reqs, reqs if reclaim else [])


def _decode_in_mixed_c2(params, reclaim):
    eng = _engine(params, prefix_cache=False, speculative_k=0,
                  prefill_chunk=2)
    dec = eng.submit([3, 5, 7, 9], 10)
    eng._admit()
    _until(eng, lambda: len(dec.generated) >= 2)
    pre = eng.submit(list(range(40, 54)), 4)
    eng._admit()
    assert pre.state == "running" and pre.committed < len(pre.prompt)
    out = _reclaim(eng, [dec, pre], [dec] if reclaim else [])
    chunked = eng.stats.chunked_steps
    eng.step()  # the step that validates the reclaimed row is C=2, mixed
    assert eng.stats.chunked_steps == chunked + 1
    return out


def _mid_prefill(chunk):
    def setup(params, reclaim):
        eng = _engine(params, prefix_cache=False, speculative_k=0,
                      prefill_chunk=chunk)
        req = eng.submit(list(range(40, 58)), 6)
        eng._admit()
        eng.step()
        eng.step()
        assert 0 < req.committed < len(req.prompt)
        return _reclaim(eng, [req], [req] if reclaim else [])
    return setup


def _speculative_k2(params, reclaim):
    eng = _engine(params, prefix_cache=False, speculative_k=2,
                  prefill_chunk=1)
    req = eng.submit([1, 2, 3, 1, 2, 3, 1, 2], 16)
    eng._admit()
    _until(eng, lambda: eng.stats.spec_steps >= 2
           and eng.scheduler.spec_k_cap > 0)
    out = _reclaim(eng, [req], [req] if reclaim else [])
    spec = eng.stats.spec_steps
    eng.step()  # the step that validates the reclaimed row drafts
    assert eng.stats.spec_steps == spec + 1
    return out


def _shared_prefix_cow(params, reclaim):
    eng = _engine(params, prefix_cache=True, speculative_k=0,
                  prefill_chunk=2)
    first = eng.submit(SHARED, 2)
    eng.run()  # finishes and donates its pages to the prefix cache
    req = eng.submit(SHARED + [50, 51], 8)
    eng._admit()
    assert req.shared_held >= 2
    _until(eng, lambda: eng.stats.cow_copies >= 1
           and len(req.generated) >= 1)
    assert req.shared_held >= 1
    return _reclaim(eng, [first, req], [req] if reclaim else [])


RECLAIMED_ROWS = {
    "decode_c1": _decode_c1,
    "decode_in_mixed_c2": _decode_in_mixed_c2,
    "two_decode_rows_c1": _two_decode_rows_c1,
    "mid_prefill_c1": _mid_prefill(1),
    "mid_prefill_c2": _mid_prefill(2),
    "mid_prefill_c4": _mid_prefill(4),
    "speculative_k2": _speculative_k2,
    "shared_prefix_cow": _shared_prefix_cow,
}


@pytest.mark.parametrize("kind", sorted(RECLAIMED_ROWS))
def test_external_reclaim_detected(params, kind):
    """The use-after-release race: a reclaimer frees a RUNNING row's pages
    while the scheduler still believes its snapshot valid.  The next fused
    step's validation must fail the row, which restarts and still finishes
    with the tokens of an unreclaimed run; after the drain the mirrors
    balance."""
    setup = RECLAIMED_ROWS[kind]
    ref, ref_reqs, _ = setup(params, reclaim=False)
    ref.run()
    eng, reqs, victims = setup(params, reclaim=True)
    eng.run()
    assert ref.stats.reader_restarts == 0
    assert eng.stats.reader_restarts >= len(victims)
    assert all(v.restarts >= 1 for v in victims)
    for r, b in zip(reqs, ref_reqs):
        assert r.state == b.state == "finished", (r.rid, r.state)
        assert r.generated == b.generated, r.rid
    _assert_mirrors_balanced(eng)


def test_external_reclaim_detected_on_split_launch(params):
    """The same race through the fleet's split step (``launch_step`` /
    ``collect_step``): validation runs in the launched dispatch and the
    restart happens at collect."""
    ref, ref_reqs, _ = _decode_c1(params, reclaim=False)
    ref.run()
    eng, (req,), _ = _decode_c1(params, reclaim=True)
    eng.collect_step(eng.launch_step())
    assert eng.stats.reader_restarts == 1
    assert req.state == "queued" and req.generated == []
    eng.run()
    assert req.generated == ref_reqs[0].generated
    _assert_mirrors_balanced(eng)


# -- mirrors over the workload's knobs ----------------------------------------


@pytest.mark.parametrize("prefill_chunk", [1, 2])
@pytest.mark.parametrize("speculative_k", [0, 2])
@pytest.mark.parametrize("prefix_cache", [True, False])
def test_mirrors_and_refcounts_balanced(params, prefix_cache, speculative_k,
                                        prefill_chunk):
    """After the mixed workload drains, every request has finished, the
    host clock mirror equals the device clock exactly, and every remaining
    device reference is accounted for by a prefix-cache pin — nothing
    leaked, nothing double-freed."""
    eng, reqs = _drive_mixed(params, prefix_cache=prefix_cache,
                             speculative_k=speculative_k,
                             prefill_chunk=prefill_chunk)
    for r in reqs:
        assert r.state == "finished", (r.rid, r.state)
        # the final sampled token is emitted, never KV-appended
        assert r.committed == len(r.prompt) + r.max_new_tokens - 1
    assert eng.stats.preemptions >= 1
    _assert_mirrors_balanced(eng)


# -- chaos faults ---------------------------------------------------------------

# the workload makes few protocol calls (device grants bypass the chaos
# layer), so each fault fires at one in two of its calls
CHAOS = {
    "grant_denial": dict(grant_denial_p=0.5),
    "spurious_invalid": dict(spurious_invalid_p=0.5),
    "delayed_free": dict(delayed_free_p=0.5, delay_ops=2),
    "unmap_under_reader": dict(unmap_under_reader_p=0.5),
    "all_four": dict(grant_denial_p=0.5, spurious_invalid_p=0.5,
                     delayed_free_p=0.5, delay_ops=2,
                     unmap_under_reader_p=0.5),
}


@pytest.mark.parametrize("fault", sorted(CHAOS))
def test_chaos_fault_drains_token_exact(params, fault):
    """Each chaos fault alone, and all four together, under the mixed
    workload (shared prefix, chunked prefill, speculation): every request
    finishes with the fault-free run's tokens, the fault actually fired,
    and once the chaos layer's deferred frees are applied the mirrors
    balance.  A spontaneous unmap releases superblocks behind the manager,
    ticking the device clock where the host mirror cannot see it, so the
    clock is compared only where that fault is off."""
    _, base = _drive_mixed(params)
    eng, reqs = _drive_mixed(params, chaos=ChaosConfig(seed=7,
                                                       **CHAOS[fault]))
    for r, b in zip(reqs, base):
        assert r.state == "finished", (r.rid, r.state)
        assert r.generated == b.generated, r.rid
    faults = eng.kv_manager.allocator.faults
    for name in (CHAOS if fault == "all_four" else [fault]):
        if name != "all_four":
            assert faults[name] > 0, (name, faults)
    eng.kv_manager.allocator.flush()
    _assert_mirrors_balanced(
        eng, clock=not CHAOS[fault].get("unmap_under_reader_p"))


# -- the pool's version rule, as a property -----------------------------------

FREE_PATH_OPS = ("alloc", "free", "share", "unshare", "snap")
RELEASE_MAP_OPS = ("alloc", "free", "release", "map", "snap")
_WIDTH = 4  # snapshots pad to one width: one compiled validate


def _check_snapshots(ops, data):
    """Replay random ops on a DevicePagePool, snapshotting held pages as an
    optimistic reader would.  A snapshot whose pages saw a zero-transition
    free since it was taken never validates again (whatever was granted,
    released or mapped since); one whose pages saw none always does."""
    pool = DevicePagePool(8, 4, ReleaseStrategy.MADVISE)
    refs: dict[int, int] = {}
    snaps = []  # [pages, versions, stale]
    for op in ops:
        live = sorted(refs)
        if op == "alloc":
            got, ok = pool.alloc(data.draw(st.integers(1, 2)))
            for p in got:
                assert p not in refs, "granted a page that has holders"
                refs[p] = 1
        elif op in ("free", "unshare") and live:
            page = data.draw(st.sampled_from(live))
            getattr(pool, op)([page])
            refs[page] -= 1
            if refs[page] == 0:
                del refs[page]
                for s in snaps:
                    s[2] = s[2] or page in s[0]
        elif op == "share" and live:
            page = data.draw(st.sampled_from(live))
            assert pool.share([page])
            refs[page] += 1
        elif op == "release":
            pool.release(data.draw(st.integers(0, 2)))
        elif op == "map":
            pool.map(data.draw(st.integers(0, 2)))
        elif op == "snap" and live:
            pages = data.draw(st.lists(st.sampled_from(live), min_size=1,
                                       max_size=_WIDTH, unique=True))
            pages = pages + [-1] * (_WIDTH - len(pages))
            snaps.append([pages, pool.snapshot(pages), False])
        for pages, vers, stale in snaps:
            ok = bool(pp.validate_read(
                pool.state, jnp.asarray(pages, jnp.int32), vers))
            assert ok == (not stale), (pages, stale, ops)


@pytest.mark.parametrize("op_set", [FREE_PATH_OPS, RELEASE_MAP_OPS],
                         ids=["free_path", "release_map"])
@given(data=st.data())
@settings(max_examples=25, deadline=None)
def test_snapshot_validates_iff_no_free_since(op_set, data):
    """The device rule OA rests on, fuzzed over the pool's free path
    (alloc / free / share / unshare) and over its release / map path."""
    ops = data.draw(st.lists(st.sampled_from(op_set), max_size=30))
    _check_snapshots(ops, data)


# -- batching against serving alone ---------------------------------------------


@pytest.mark.parametrize("preempt", [True, False],
                         ids=["preempted", "unpreempted"])
def test_mixed_batch_matches_serving_alone(params, preempt):
    """The mixed workload's outputs equal the same requests served one at
    a time on an idle engine: batching, sharing, speculation and (when
    forced) a mid-run preemption change dispatches, never tokens."""
    _, reqs = _drive_mixed(params, preempt=preempt)
    alone = _engine(params)
    for r in reqs:
        a = alone.submit(list(r.prompt), r.max_new_tokens)
        alone.run()
        assert a.state == r.state == "finished", (r.rid, r.state)
        assert a.generated == r.generated, r.rid
    assert alone.stats.preemptions == 0


# -- the step's shape ---------------------------------------------------------


@pytest.mark.parametrize("chunk_size,speculative",
                         [(1, False), (2, False), (3, True)],
                         ids=["decode", "chunked", "speculative"])
def test_fused_step_validates_unconditionally(params, chunk_size,
                                              speculative):
    """Every executable the engine builds runs the validation pass
    straight-line: no conditional anywhere in the traced step, and the
    pass sits under the ``step.validate`` / ``pool.validate`` scopes the
    trace reader times."""
    eng = _engine(params)
    st_ = eng.kv_manager.step_state()
    r = eng.runner
    B = eng.max_batch
    drafts = ((jnp.zeros((B, chunk_size - 1), jnp.int32),
               jnp.zeros((B,), jnp.int32)) if speculative else (None, None))
    traced = fused_decode_step.trace(
        params, st_.kv, st_.pool, st_.block_tables, st_.snapshot,
        st_.lengths, st_.last_tok, st_.active, st_.prompt_buf,
        st_.prompt_len, r._base_key, r._temperature, r._budget_one,
        *drafts, cfg=CFG, impl=r.attn_impl, chunk_size=chunk_size,
        speculative=speculative)
    text = str(traced.jaxpr)
    assert " cond[" not in text and "cond(" not in text
    hlo = traced.lower().as_text(debug_info=True)
    assert "step.validate/pool.validate" in hlo


def test_reclaim_policy_surface_defers_nothing(params):
    """The benchmark harness's unblock hook reads
    ``engine.reclaim_policy.drain_pending()``.  OA applies every free the
    step it is made — the device clock has ticked as soon as a finished
    row's pages are freed, with work still queued — so there is never
    anything to drain."""
    eng = _engine(params, max_batch=1, prefix_cache=False, speculative_k=0)
    first = eng.submit(SHARED, 2)
    second = eng.submit(SHARED[:4], 2)
    eng._admit()
    assert second.state == "queued"
    _until(eng, lambda: first.state == "finished")
    assert second.state == "queued"
    assert eng.stats.warnings_fired == int(eng.pool.clock) >= 1
    assert eng.reclaim_policy.drain_pending() is False
    eng.run()
    assert second.state == "finished"


# -- adaptive release threshold (Hyaline-style) ------------------------------


def test_adaptive_release_keeps_capacity_under_regular_bursts(params):
    """Bursts arriving on a cadence SHORTER than 1.5x their own gap EWMA
    must not trigger release/remap thrash: the adaptive threshold rises
    above the observed gap, so no superblock is released between bursts —
    where a static floor of 2 would have released on every gap."""
    eng = _engine(params, prefix_cache=False,
                  speculative_k=0, release_quiescence="adaptive")
    for burst in range(3):
        for i in range(2):
            eng.submit(SHARED[:4], 6)
        eng.run()
        # idle gap of 4 maintain ticks between bursts (the cadence)
        for _ in range(4):
            eng._maintain()
    # gap EWMA ~4 -> threshold 6 > the 4-tick gaps: nothing released
    assert eng.scheduler._release_threshold() > 4
    assert eng.stats.superblocks_released == 0
    assert eng.stats.superblocks_remapped == 0


def test_adaptive_release_fires_on_genuine_drain(params):
    """Once the idle gap outlasts the learned cadence, the release fires
    and the mapped watermark drops — adaptivity must not mean never."""
    eng = _engine(params, prefix_cache=False,
                  speculative_k=0, release_quiescence="adaptive")
    for burst in range(2):
        for i in range(2):
            eng.submit(SHARED, 6)
        eng.run()
        for _ in range(3):
            eng._maintain()
    threshold = eng.scheduler._release_threshold()
    for _ in range(threshold + 2):  # a drain longer than the cadence
        eng._maintain()
    assert eng.stats.superblocks_released > 0
    assert eng.stats.mapped_pages < eng.num_pages
    assert eng.stats.warnings_fired == int(eng.pool.clock)
