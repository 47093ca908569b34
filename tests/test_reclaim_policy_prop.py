"""Property tests for the reclamation-policy seam (core/reclaim_policy.py).

The invariant, per policy, extending the PR-2/PR-3 pagepool state-machine
tests up to the policy layer: NO interleaving of alloc / free / release /
map / read operations may hand out a page that a pending optimistic reader
could access without detection —

- ``oa-validate``: the page's version bumped at the free, so the reader's
  snapshot fails validation (and the policy never skips the pass);
- ``epoch-grace``: a step may skip validation ONLY if no reclamation
  ticked the epoch since the last validated step — a reclaim can never be
  followed by a skipped step before one validated pass;
- ``interval``: a freed page cannot be re-granted before interval
  ``i + 2``, so every dispatch that could have read it has retired.

Deterministic scripted interleavings always run; when the ``hypothesis``
package is available (it is not baked into the minimal image) the same
invariants are fuzzed over random interleavings.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.pagepool import DevicePagePool
from repro.core.reclaim_policy import (INTERVAL_LAG, EpochGracePolicy,
                                       IntervalAllocator, IntervalPolicy,
                                       OAValidatePolicy, make_policy)
from repro.core.vm import ReleaseStrategy


def _pool(num_pages=16, sb=4):
    return DevicePagePool(num_pages, sb, ReleaseStrategy.MADVISE)


# -- oa-validate -------------------------------------------------------------


def test_oa_policy_always_validates():
    pol = OAValidatePolicy()
    for clock in (0, 1, 5):
        assert pol.needs_validation(clock)
        pol.on_validated(clock)
        assert pol.needs_validation(clock)  # validating never earns a skip
    assert pol.detects_stale_readers


def test_oa_stale_snapshot_detected_after_free_realloc():
    """The device invariant the policy relies on: free bumps the version,
    so a reader's pre-free snapshot can never match a re-granted page."""
    pool = _pool()
    ids, ok = pool.alloc(2)
    assert ok
    before = np.asarray(pool.snapshot(ids))
    pool.free(ids)
    again, ok = pool.alloc(2)
    assert ok and set(again) == set(ids)  # LIFO free list re-grants them
    after = np.asarray(pool.snapshot(ids))
    assert (after != before).all(), "free->realloc must be snapshot-visible"


# -- epoch-grace -------------------------------------------------------------


def _check_epoch_sequence(events):
    """Replay reclaim/step events against EpochGracePolicy and assert a
    reclamation is never followed by a skipped step before a validated
    pass (the grace-period soundness condition)."""
    pol = EpochGracePolicy()
    mirror = 0
    dirty = True  # an unvalidated epoch is outstanding (first step validates)
    validated = skipped = 0
    for ev in events:
        if ev == "reclaim":
            mirror += 1  # the clock mirror ticks (free/release/evict)
            dirty = True
        else:  # one planned step
            need = pol.needs_validation(mirror)
            if dirty:
                assert need, (
                    "epoch-grace skipped a step with an unvalidated "
                    f"reclamation outstanding (events={events})")
            if need:
                pol.on_validated(mirror)
                dirty = False
                validated += 1
            else:
                skipped += 1
    return validated, skipped


def test_epoch_validates_first_step_and_after_every_reclaim():
    v, s = _check_epoch_sequence(
        ["step", "step", "reclaim", "step", "step", "reclaim", "reclaim",
         "step", "step", "step"])
    assert v == 3  # first step + one per reclaim burst
    assert s == 4  # every clean steady-state step skipped (7 steps total)


def test_epoch_steady_state_skips_everything_after_first_pass():
    v, s = _check_epoch_sequence(["step"] * 20)
    assert v == 1 and s == 19


def test_epoch_mid_step_tick_forces_next_validation():
    """A tick landing between plan and absorb (e.g. a COW zero-transition)
    moves the mirror PAST the planned value, so the next plan validates."""
    pol = EpochGracePolicy()
    assert pol.needs_validation(0)
    pol.on_validated(0)  # planned at mirror 0 ...
    # ... but the step itself freed something: mirror is now 1
    assert pol.needs_validation(1)


# -- interval ----------------------------------------------------------------


def test_interval_page_not_grantable_before_lag():
    pool = _pool(num_pages=4, sb=4)
    ia = IntervalAllocator(pool)
    ids, ok = ia.alloc(4)  # drain the free list entirely
    assert ok
    victim = ids[0]
    ia.free([victim])
    freed_at = ia.interval
    for _ in range(INTERVAL_LAG):
        got, ok = ia.alloc(1)
        assert not ok and got == [], (
            f"page {victim} grantable at interval {ia.interval}, freed at "
            f"{freed_at}: a reader from interval {freed_at} could still "
            "be in flight")
        ia.tick()
    got, ok = ia.alloc(1)
    assert ok and got == [victim]
    assert ia.interval >= freed_at + INTERVAL_LAG


def test_interval_flush_applies_all_pending():
    pool = _pool(num_pages=4, sb=4)
    ia = IntervalAllocator(pool)
    ids, _ = ia.alloc(4)
    ia.free(ids[:2])
    ia.unshare([ids[2]])
    assert ia.pending() == 2
    ia.flush()  # caller guarantees zero readers
    assert ia.pending() == 0
    got, ok = ia.alloc(2)
    assert ok and len(got) == 2


def test_interval_wrapper_forwards_protocol():
    """The wrapper must be transparent for everything but free/unshare:
    state pass-through, views, share, release/map — the serving stack
    above cannot tell it is wrapped (same discipline as ChaosAllocator)."""
    pool = _pool()
    ia = IntervalAllocator(pool)
    assert ia.state is pool.state
    assert ia.view() == pool.view()
    assert ia.pages_per_superblock == pool.pages_per_superblock
    ids, ok = ia.alloc(1)
    assert ok
    assert ia.share(ids)
    ia.unshare(ids)  # drops the share ref (deferred)
    snap = np.asarray(ia.snapshot(ids))
    assert snap.shape == (1,)
    pol = IntervalPolicy()
    wrapped = pol.wrap(pool)
    assert isinstance(wrapped, IntervalAllocator)
    assert not pol.needs_validation(0)
    assert not pol.detects_stale_readers


def test_interval_release_cannot_take_limbo_pages():
    """A superblock with deferred frees is not EMPTY (refcounts still
    held), so physical release cannot unmap pages a pending reader could
    reach; once the frees mature the superblock releases normally."""
    pool = _pool(num_pages=4, sb=4)  # exactly one superblock
    ia = IntervalAllocator(pool)
    ids, _ = ia.alloc(4)  # fills it
    ia.free(ids)  # parked in limbo: pool still sees them as allocated
    n_sb, _ = ia.release(0)
    assert n_sb == 0, "released a superblock whose frees are still in limbo"
    for _ in range(INTERVAL_LAG):
        ia.tick()
    n_sb, n_units = ia.release(0)
    assert n_sb == 1 and n_units == 4


def test_make_policy_env_default(monkeypatch):
    monkeypatch.delenv("RECLAIM_POLICY", raising=False)
    assert make_policy().name == "oa-validate"
    monkeypatch.setenv("RECLAIM_POLICY", "interval")
    assert make_policy().name == "interval"
    monkeypatch.setenv("RECLAIM_POLICY", "epoch-grace")
    assert make_policy(None).name == "epoch-grace"
    assert make_policy("oa-validate").name == "oa-validate"  # explicit wins


# -- fuzzed interleavings ----------------------------------------------------
#
# Fuzzed with hypothesis over random interleavings; the deterministic
# scripted tests above pin the known cases.


def _interval_invariant(ops):
    """Replay ``ops`` against an IntervalAllocator and assert a page freed
    at interval i is never granted again before interval i + LAG."""
    pool = _pool(num_pages=8, sb=4)
    ia = IntervalAllocator(pool)
    held: list[int] = []
    freed_at: dict[int, int] = {}
    for op in ops:
        if op == "alloc":
            got, ok = ia.alloc(1)
            if ok:
                p = got[0]
                if p in freed_at:
                    assert ia.interval >= freed_at.pop(p) + INTERVAL_LAG, (
                        f"page {p} re-granted early (ops={ops})")
                held.append(p)
        elif op == "free" and held:
            p = held.pop(0)
            ia.free([p])
            freed_at[p] = ia.interval
        elif op == "tick":
            ia.tick()
        elif op == "release":
            ia.release(1)
        elif op == "map":
            ia.map(1)


@given(st.lists(st.sampled_from(["reclaim", "step"]), max_size=60))
@settings(max_examples=200, deadline=None)
def test_epoch_property_no_skip_across_reclaim(events):
    """Fuzzed grace-period soundness: no random reclaim/step
    interleaving makes epoch-grace skip a step with an unvalidated
    reclaim outstanding."""
    _check_epoch_sequence(events)


@given(st.lists(
    st.sampled_from(["alloc", "free", "tick", "release", "map"]),
    max_size=30))
@settings(max_examples=25, deadline=None)
def test_interval_property_no_early_regrant(ops):
    """Fuzzed IBR soundness: across random alloc/free/tick/release/map
    interleavings, a page freed at interval i is never granted again
    before interval i + 2."""
    _interval_invariant(ops)
