"""Device page pool: OA invariants, unit + hypothesis property tests.

(The hypothesis-free batch-API tests live in test_pagepool_batch.py so a
bare environment still exercises the pool.)"""

import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")

import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
from hypothesis import HealthCheck, given, settings

from repro.core import pagepool as pp
from repro.core.vm import ReleaseStrategy

SETTINGS = dict(max_examples=25, deadline=None,
                suppress_health_check=[HealthCheck.too_slow])


def test_alloc_unique_and_exhaustion():
    pool = pp.pool_init(8)
    pool, a, ok1 = pp.alloc_pages(pool, 5)
    pool, b, ok2 = pp.alloc_pages(pool, 3)
    assert bool(ok1) and bool(ok2)
    ids = np.concatenate([np.asarray(a), np.asarray(b)])
    assert len(set(ids.tolist())) == 8
    pool, c, ok3 = pp.alloc_pages(pool, 1)
    assert not bool(ok3) and int(c[0]) == -1
    assert int(pool.free_top) == 0


def test_free_bumps_version_and_clock():
    pool = pp.pool_init(8)
    pool, pages, _ = pp.alloc_pages(pool, 4)
    snap = pp.snapshot_versions(pool, pages)
    assert bool(pp.validate_read(pool, pages, snap))
    clock0 = int(pool.clock)
    pool = pp.free_pages(pool, pages)
    assert int(pool.clock) == clock0 + 1  # one warning per batch (Alg. 1)
    assert not bool(pp.validate_read(pool, pages, snap))


def test_free_ignores_unmapped_entries():
    pool = pp.pool_init(8)
    pool, pages, _ = pp.alloc_pages(pool, 2)
    padded = jnp.concatenate([pages, jnp.full((3,), -1, jnp.int32)])
    pool = pp.free_pages(pool, padded)
    assert int(pool.free_top) == 8


def test_stale_read_detected_after_reuse():
    """The ABA case OA exists for: page freed AND reallocated — the old
    snapshot must still fail validation."""
    pool = pp.pool_init(4)
    pool, pages, _ = pp.alloc_pages(pool, 2)
    snap = pp.snapshot_versions(pool, pages)
    pool = pp.free_pages(pool, pages)
    pool, again, _ = pp.alloc_pages(pool, 2)  # same physical pages (LIFO)
    assert set(np.asarray(again).tolist()) == set(np.asarray(pages).tolist())
    assert not bool(pp.validate_read(pool, pages, snap))


def test_oa_stale_snapshot_detected_after_free_realloc():
    """The same rule through the stateful allocator: free bumps the
    version, so a reader's pre-free snapshot can never match a re-granted
    page."""
    pool = pp.DevicePagePool(16, 4, ReleaseStrategy.MADVISE)
    ids, ok = pool.alloc(2)
    assert ok
    before = np.asarray(pool.snapshot(ids))
    pool.free(ids)
    again, ok = pool.alloc(2)
    assert ok and set(again) == set(ids)  # LIFO free list re-grants them
    after = np.asarray(pool.snapshot(ids))
    assert (after != before).all(), "free->realloc must be snapshot-visible"


@given(st.data())
@settings(**SETTINGS)
def test_pool_never_double_allocates(data):
    npages = data.draw(st.integers(4, 32))
    pool = pp.pool_init(npages)
    live: set[int] = set()
    for _ in range(data.draw(st.integers(1, 40))):
        if data.draw(st.booleans()) and live:
            k = data.draw(st.integers(1, len(live)))
            batch = [live.pop() for _ in range(k)]
            pool = pp.free_pages(pool, jnp.asarray(batch, jnp.int32))
        else:
            k = data.draw(st.integers(1, npages))
            pool, pages, ok = pp.alloc_pages(pool, k)
            got = [int(p) for p in np.asarray(pages) if p >= 0]
            if bool(ok):
                assert len(got) == k
                assert not (set(got) & live), "double allocation"
                live.update(got)
            else:
                assert not got
    assert int(pool.free_top) == npages - len(live)


@given(nfree=st.integers(1, 8))
@settings(**SETTINGS)
def test_versions_monotone(nfree):
    pool = pp.pool_init(8)
    pool, pages, _ = pp.alloc_pages(pool, 8)
    v0 = np.asarray(pp.snapshot_versions(pool, pages))
    for _ in range(nfree):
        pool = pp.free_pages(pool, pages[:2])
        pool, pages2, _ = pp.alloc_pages(pool, 2)
    v1 = np.asarray(pp.snapshot_versions(pool, pages))
    assert (v1 >= v0).all()
    assert (v1[:2] > v0[:2]).all()


@given(st.data())
@settings(**SETTINGS)
def test_superblock_interleavings_never_dup_or_leak_unmapped(data):
    """Any interleaving of alloc_pages_batch / free_pages /
    release_empty_superblocks / map_superblocks never duplicates a live page
    id and never hands out a page from an unmapped superblock — including
    MULTI-PAGE per-row grants (a chunked-prefill row can demand up to
    ``max_grow`` pages in one pop), whose rows must be satisfied
    all-or-nothing.  The per-superblock anchors (``sb_free``) are checked
    EXACTLY against a host mirror after every op."""
    npages = data.draw(st.integers(4, 24))
    K = data.draw(st.integers(1, 6))
    pool = pp.pool_init(npages, pages_per_superblock=K)
    K = pool.pages_per_superblock  # pool_init clamps K to the pool size
    S = pool.num_superblocks
    caps = [min(K, npages - s * K) for s in range(S)]
    live: set[int] = set()
    for _ in range(data.draw(st.integers(1, 25))):
        op = data.draw(st.sampled_from(["alloc", "free", "release", "map"]))
        if op == "alloc":
            B = data.draw(st.integers(1, 4))
            max_grow = data.draw(st.integers(1, 4))
            need = [data.draw(st.integers(0, max_grow)) for _ in range(B)]
            pool, grants, ok = pp.alloc_pages_batch(
                pool, jnp.asarray(need, jnp.int32), max_grow)
            g = np.asarray(grants)
            got = [int(p) for p in g.ravel() if p >= 0]
            mapped = set(np.flatnonzero(np.asarray(pool.sb_mapped)).tolist())
            assert len(got) == len(set(got)), "duplicate grant within batch"
            for p in got:
                assert p not in live, "double allocation of a live page"
                assert p // K in mapped, "grant from an unmapped superblock"
            for b in range(B):  # multi-page rows are all-or-nothing
                row = [int(p) for p in g[b] if p >= 0]
                assert len(row) in (0, need[b]), \
                    "partially satisfied multi-page row"
            if bool(ok):
                assert len(got) == sum(need), "ok=True but rows were starved"
            live.update(got)
        elif op == "free" and live:
            k = data.draw(st.integers(1, len(live)))
            batch = [live.pop() for _ in range(k)]
            pool = pp.free_pages(pool, jnp.asarray(batch, jnp.int32))
        elif op == "release":
            pool, _, _ = pp.release_empty_superblocks(
                pool,
                jnp.asarray(data.draw(st.integers(0, S)), jnp.int32),
                jnp.asarray(data.draw(st.integers(0, S)), jnp.int32))
        elif op == "map":
            pool, _, _ = pp.map_superblocks(
                pool, jnp.asarray(data.draw(st.integers(0, S)), jnp.int32))
        # live pages always sit in mapped superblocks (release only takes
        # EMPTY superblocks, which by definition hold no live page)
        mapped = set(np.flatnonzero(np.asarray(pool.sb_mapped)).tolist())
        for p in live:
            assert p // K in mapped, "release unmapped a live page"
        # the device anchors match the host mirror EXACTLY, superblock by
        # superblock: free count == capacity − live pages homed there
        live_in = [sum(1 for p in live if p // K == s) for s in range(S)]
        np.testing.assert_array_equal(
            np.asarray(pool.sb_free), [caps[s] - live_in[s] for s in range(S)],
            err_msg="device sb_free anchors diverged from the host mirror")
        expect_free = sum(caps[s] for s in mapped) - len(live)
        assert int(pool.free_top) == expect_free


@given(st.data())
@settings(**SETTINGS)
def test_share_unshare_free_release_interleavings(data):
    """Any interleaving of alloc / share_pages / unshare_pages / free_pages /
    release_empty_superblocks / map_superblocks keeps the refcount layer
    sound: a refcount never goes negative, a page with holders is never
    granted to a new owner, and a superblock containing any refcount > 0
    page can never be released (ISSUE invariants, pinned)."""
    npages = data.draw(st.integers(4, 20))
    K = data.draw(st.integers(2, 5))
    pool = pp.pool_init(npages, pages_per_superblock=K)
    K = pool.pages_per_superblock
    S = pool.num_superblocks
    refs: dict[int, int] = {}  # host model: page -> expected refcount
    for _ in range(data.draw(st.integers(1, 30))):
        op = data.draw(st.sampled_from(
            ["alloc", "share", "unshare", "free", "release", "map"]))
        live = sorted(refs)
        if op == "alloc":
            k = data.draw(st.integers(1, 4))
            pool, pages, ok = pp.alloc_pages(pool, k)
            got = [int(p) for p in np.asarray(pages) if p >= 0]
            for p in got:
                assert p not in refs, "granted a page that still has holders"
                refs[p] = 1
        elif op == "share" and live:
            batch = data.draw(st.lists(st.sampled_from(live), min_size=1,
                                       max_size=4))
            pool, ok = pp.share_pages(pool, jnp.asarray(batch, jnp.int32))
            assert bool(ok)
            for p in batch:
                refs[p] += 1
        elif op == "share":  # no live pages: sharing free ids must refuse
            pool, ok = pp.share_pages(pool, jnp.asarray([0], jnp.int32))
            assert not bool(ok)
        elif op in ("unshare", "free") and live:
            batch = data.draw(st.lists(st.sampled_from(live), min_size=1,
                                       max_size=4, unique=True))
            fn = pp.unshare_pages if op == "unshare" else pp.free_pages
            pool = fn(pool, jnp.asarray(batch, jnp.int32))
            for p in batch:
                refs[p] -= 1
                if refs[p] == 0:
                    del refs[p]
        elif op == "release":
            pool, _, _ = pp.release_empty_superblocks(
                pool, jnp.asarray(data.draw(st.integers(0, S)), jnp.int32),
                jnp.asarray(data.draw(st.integers(0, S)), jnp.int32))
        elif op == "map":
            pool, _, _ = pp.map_superblocks(
                pool, jnp.asarray(data.draw(st.integers(0, S)), jnp.int32))
        rc = np.asarray(pool.page_refcount)
        assert (rc >= 0).all(), "refcount went negative"
        for p in range(npages):
            assert rc[p] == refs.get(p, 0), "device/host refcount divergence"
        mapped = np.asarray(pool.sb_mapped)
        for p in refs:
            assert mapped[p // K], "released a superblock with refcount > 0"
    # extra decrefs of already-free pages clamp at zero (no corruption)
    if npages > 0:
        before = int(pool.free_top)
        pool = pp.unshare_pages(pool, jnp.arange(npages, dtype=jnp.int32))
        rc = np.asarray(pool.page_refcount)
        for p in range(npages):
            assert rc[p] == max(0, refs.get(p, 0) - 1)
        refs = {p: c - 1 for p, c in refs.items() if c > 1}
        assert int(pool.free_top) >= before


def test_append_and_gather_roundtrip():
    kv = pp.kv_pages_init(8, 4, 2, 8, dtype=jnp.float32)
    bt = jnp.array([[2, 5, -1, -1]], jnp.int32)
    lengths = jnp.array([0], jnp.int32)
    import jax
    for t in range(6):
        k = jnp.full((1, 2, 8), float(t + 1))
        v = jnp.full((1, 2, 8), float(-(t + 1)))
        kv = pp.append_kv(kv, bt, lengths, k, v)
        lengths = lengths + 1
    kf, vf = pp.gather_kv(kv, bt[0], 8)
    got = np.asarray(kf[:, 0, 0])
    assert got[:6].tolist() == [1, 2, 3, 4, 5, 6]
