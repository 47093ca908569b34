"""Device-side paged KV-cache pool with Optimistic-Access semantics.

This is the TPU-native adaptation of the paper (DESIGN.md §2):

- The KV page arrays are allocated ONCE for the process lifetime — freed
  pages stay addressable forever and gathers through stale block tables can
  never fault.  That is exactly the guarantee ``palloc`` gives OA on the
  host: *memory stays readable after free; contents are undefined*.
- Every page carries a **version counter** (bumped on free) and the pool a
  **global clock** (bumped on every reclamation batch) — the OA-VER warning
  channel.  A reader (a decode step that overlaps with scheduling) snapshots
  versions before launch and validates after: a mismatch means the page was
  reclaimed mid-flight, the result is discarded and the request restarts
  from a known-valid state — the OA read protocol, verbatim.
- Writes (appending a token's KV) are only ever issued to pages *pinned* by
  the scheduler for the in-flight batch — the hazard-pointer half of OA,
  enforced structurally.

Superblock structure (LRMalloc §2.3 / §3.2, device edition)
-----------------------------------------------------------
Pages are grouped into fixed-size **superblocks** and the free list is
two-level: one LIFO free list *per superblock* plus a per-superblock anchor
(free count + mapped bit) packed into device arrays.  A superblock's state
is derived from its anchor exactly as in LRMalloc Fig. 2:

    FULL     free == 0            (every page allocated)
    PARTIAL  0 < free < capacity
    EMPTY    free == capacity     (every page free)
    UNMAPPED released from circulation (the device analogue of handing the
             physical frames back to the OS — pages are not allocatable and
             their versions were bumped at release time)

Allocation prefers PARTIAL superblocks — fullest first — over EMPTY ones
(one-pass segmented pop over a priority ordering, still a single fused
dispatch, still sync-free), so frees coalesce into EMPTY superblocks
instead of fragmenting the arena.  ``release_empty_superblocks`` takes
EMPTY superblocks out of circulation (version bump catches any in-flight
optimistic reader of the released range, the OA warning channel again) and
``map_superblocks`` brings them back under pressure.  The CPU model
(``core/lrmalloc.py`` + ``core/vm.py``) and this device pool report release
behaviour through the same ``ReleaseStrategy`` vocabulary.

Reference-counted sharing (the hybrid-system claim, applied)
------------------------------------------------------------
The paper's thesis is that reclamation and allocation should be ONE
system, so memory freed by one component is safely reusable by another.
The refcount layer makes that real for KV pages: every page carries a
reference count (``page_refcount``) so several block tables — several
requests sharing a common prompt prefix — can reference the same physical
page at once.

- ``alloc`` grants a page with refcount 1 (sole owner).
- ``share_pages`` adds an owner (refcount += 1).  Sharing never bumps a
  version: the page's content stays valid for every holder.
- ``unshare_pages`` (== ``free_pages``) drops an owner.  Only the
  **zero-transition** returns the page to its superblock's LIFO free list
  — and THAT is the moment its version bumps and the clock ticks, so an
  in-flight optimistic reader of a fully-unshared page fails
  ``validate_and_commit`` exactly like a reader of a reclaimed node (the
  VBR-style version bump of Sheffi et al., applied per page).
- A page with refcount > 0 is never on a free list, so it can never be
  granted to a new owner and its superblock can never be EMPTY — hence
  ``release_empty_superblocks`` can never unmap a shared page (the guard
  is also enforced explicitly, belt and braces).

All state lives in a JAX pytree; all operations are pure and jit-able, so
the pool shards with the serving mesh (pages over 'data', heads over
'model') and the alloc/free path adds no host-device sync.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .allocator import AllocatorView
from .vm import ReleaseStrategy  # shared release vocabulary (host + device)

__all__ = [
    "PagePool", "DevicePagePool", "ReleaseStrategy", "pool_init",
    "SB_FULL", "SB_PARTIAL", "SB_EMPTY", "SB_UNMAPPED", "superblock_states",
    "alloc_pages", "alloc_pages_batch", "free_pages",
    "share_pages", "unshare_pages",
    "release_empty_superblocks", "map_superblocks",
    "snapshot_versions", "validate_and_commit", "validate_read",
    "kv_pages_init", "append_kv", "gather_kv",
]

#: default superblock granularity (pages); ``pool_init`` clamps to the pool
DEFAULT_PAGES_PER_SUPERBLOCK = 8

# superblock states (LRMalloc Fig. 2 plus the released state of §3.2)
SB_FULL, SB_PARTIAL, SB_EMPTY, SB_UNMAPPED = 0, 1, 2, 3


class PagePool(NamedTuple):
    """Device-side page pool state (a pure JAX pytree; see module docstring).

    OA contract: ``page_version`` only moves when a page is *reclaimed*
    (refcount zero-transition, or superblock release) — never on alloc or
    share — so a snapshot taken at grant time stays valid for exactly as
    long as the page has at least one owner.
    """

    sb_pages: jax.Array  # [S, K] int32 per-superblock LIFO free lists
    sb_free: jax.Array  # [S] int32 anchor: free pages per superblock
    sb_mapped: jax.Array  # [S] bool anchor: in circulation?
    page_version: jax.Array  # [num_pages] uint32 — bumped on free + release
    page_refcount: jax.Array  # [num_pages] int32 — owners (0 = free)
    clock: jax.Array  # [] uint32 — global reclamation clock (OA-VER)

    @property
    def num_pages(self) -> int:
        """Total pages in the arena (constant: palloc'd once)."""
        return self.page_version.shape[0]

    @property
    def num_superblocks(self) -> int:
        """Superblock count S (the last one may be ragged)."""
        return self.sb_pages.shape[0]

    @property
    def pages_per_superblock(self) -> int:
        """Superblock granularity K (pages per LIFO free list)."""
        return self.sb_pages.shape[1]

    @property
    def free_top(self) -> jax.Array:
        """Total allocatable pages (mapped superblocks only) — the flat-pool
        view the engine and tests reason with."""
        return _free_total(self)


def _capacities(pool: PagePool) -> jax.Array:
    """Per-superblock page capacity [S] (the last superblock may be ragged
    when ``num_pages % pages_per_superblock != 0``)."""
    S, K = pool.sb_pages.shape
    return jnp.minimum(K, pool.num_pages - jnp.arange(S, dtype=jnp.int32) * K)


def _free_total(pool: PagePool) -> jax.Array:
    return jnp.sum(jnp.where(pool.sb_mapped, pool.sb_free, 0)).astype(jnp.int32)


def superblock_states(pool: PagePool) -> jax.Array:
    """[S] int32 anchor states: SB_FULL/SB_PARTIAL/SB_EMPTY/SB_UNMAPPED."""
    cap = _capacities(pool)
    st = jnp.where(pool.sb_free == 0, SB_FULL,
                   jnp.where(pool.sb_free >= cap, SB_EMPTY, SB_PARTIAL))
    return jnp.where(pool.sb_mapped, st, SB_UNMAPPED).astype(jnp.int32)


def pool_init(num_pages: int,
              pages_per_superblock: int = DEFAULT_PAGES_PER_SUPERBLOCK) -> PagePool:
    """Build a fully-mapped pool: every page free (refcount 0), version 0."""
    K = max(1, min(pages_per_superblock, num_pages))
    S = -(-num_pages // K)
    lists = np.full((S, K), -1, np.int32)
    caps = np.minimum(K, num_pages - np.arange(S) * K)
    for s in range(S):
        c = int(caps[s])
        # LIFO top is index c-1; lowest page id on top so a fresh pool hands
        # out ascending ids within each superblock
        lists[s, :c] = s * K + np.arange(c - 1, -1, -1)
    return PagePool(
        sb_pages=jnp.asarray(lists),
        sb_free=jnp.asarray(caps, jnp.int32),
        sb_mapped=jnp.ones((S,), bool),
        page_version=jnp.zeros((num_pages,), jnp.uint32),
        page_refcount=jnp.zeros((num_pages,), jnp.int32),
        clock=jnp.zeros((), jnp.uint32),
    )


# ---------------------------------------------------------------------------
# allocation: one-pass segmented pop over a superblock priority ordering


def _scope(name: str):
    """Trace the body under ``jax.named_scope(name)``: every device op of
    a pool operation carries ``pool.<op>`` in its op name, whether it runs
    as the pool's own program or inline in a fused step.  A fresh scope
    per call: ``named_scope``'s object keeps the outer name stack on
    itself, so one shared as a decorator is unsafe when two threads
    trace at once."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def _alloc_order(pool: PagePool):
    """Priority ordering of superblocks for allocation.

    PARTIAL superblocks first (fullest first, i.e. fewest free pages — the
    LRMalloc anti-fragmentation policy: pack partials so frees coalesce into
    EMPTY superblocks), then EMPTY ones by index; FULL and UNMAPPED
    superblocks are excluded.  Returns (order [S], avail-in-order [S]).
    """
    S, K = pool.sb_pages.shape
    cap = _capacities(pool)
    fc = pool.sb_free
    allocatable = pool.sb_mapped & (fc > 0)
    partial = allocatable & (fc < cap)
    rank = jnp.where(partial, 0, jnp.where(allocatable, 1, 2)).astype(jnp.int32)
    big = (K + 1) * S
    sidx = jnp.arange(S, dtype=jnp.int32)
    key = rank * big + jnp.where(partial, fc, 0) * S + sidx
    order = jnp.argsort(key).astype(jnp.int32)
    avail = jnp.where(rank < 2, fc, 0)[order]
    return order, avail


def _segmented_pop_impl(pool: PagePool, total: jax.Array, max_total: int):
    """Pop ``total`` (<= free_top) pages across superblocks in priority
    order, in one fused pass.  Returns (pool, pages [max_total] int32 with
    −1 past ``total``)."""
    S, K = pool.sb_pages.shape
    order, avail = _alloc_order(pool)
    cum = jnp.cumsum(avail)
    total = jnp.minimum(total.astype(jnp.int32), cum[-1])
    j = jnp.arange(max_total, dtype=jnp.int32)
    seg = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    segc = jnp.minimum(seg, S - 1)
    sb = order[segc]
    prev = jnp.where(segc > 0, cum[jnp.maximum(segc - 1, 0)], 0)
    pos = pool.sb_free[sb] - 1 - (j - prev)  # LIFO: pop from the top
    pages = pool.sb_pages[sb, jnp.clip(pos, 0, K - 1)]
    pages = jnp.where(j < total, pages, -1).astype(jnp.int32)
    taken = jnp.clip(total - (cum - avail), 0, avail)
    # a granted page leaves the free list with exactly one owner
    pidx = jnp.where(pages >= 0, pages, pool.num_pages)
    refcount = pool.page_refcount.at[pidx].set(1, mode="drop")
    return pool._replace(sb_free=pool.sb_free.at[order].add(-taken),
                         page_refcount=refcount), pages


@_scope("pool.alloc")
def _alloc_pages_batch_impl(pool: PagePool, need: jax.Array, max_grow: int):
    """Traceable body of :func:`alloc_pages_batch` (reused inside fused jits)."""
    B = need.shape[0]
    need = jnp.clip(need.astype(jnp.int32), 0, max_grow)
    end = jnp.cumsum(need)  # [B]
    start = end - need
    # prefix satisfaction: a row is granted iff every row before it (in batch
    # order) was, and its own grant still fits.  Because ``end`` is monotone,
    # once the pool runs dry every later needy row fails too — so a single
    # segmented pop assigns a contiguous run of popped pages.
    sat = end <= _free_total(pool)
    ok = jnp.all(sat | (need == 0))
    total = jnp.sum(jnp.where(sat, need, 0))
    pool, popped = _segmented_pop_impl(pool, total, B * max_grow)
    j = jnp.arange(max_grow, dtype=jnp.int32)[None, :]
    take = (j < need[:, None]) & sat[:, None]
    lin = jnp.minimum(start[:, None] + j, B * max_grow - 1)
    grants = jnp.where(take, popped[lin], -1).astype(jnp.int32)
    return pool, grants, ok


@functools.partial(jax.jit, static_argnums=2, donate_argnums=0)
def alloc_pages_batch(pool: PagePool, need: jax.Array, max_grow: int = 1):
    """Grant pages for an entire batch's growth in ONE fused call.

    ``need`` [B] int32 — pages wanted per request this step (clipped to
    ``max_grow``).  Returns (pool, grants [B, max_grow] int32 (−1 = not
    granted), ok).  Grants are assigned greedily in batch order; on
    exhaustion the satisfied prefix KEEPS its pages (so the batch still makes
    progress) and ``ok`` is False so the scheduler can reclaim (preempt a
    victim) or remap released superblocks before the unsatisfied rows retry.
    Pages come from PARTIAL superblocks first (see :func:`_alloc_order`);
    UNMAPPED superblocks never serve grants.  One jitted dispatch, zero host
    syncs, for the whole batch.
    """
    return _alloc_pages_batch_impl(pool, need, max_grow)


@_scope("pool.alloc")
def _alloc_pages_impl(pool: PagePool, n: int):
    ok = _free_total(pool) >= n
    pool, pages = _segmented_pop_impl(
        pool, jnp.where(ok, n, 0).astype(jnp.int32), n)
    return pool, pages, ok


@functools.partial(jax.jit, static_argnums=1, donate_argnums=0)
def alloc_pages(pool: PagePool, n: int):
    """Pop ``n`` pages.  Returns (pool, pages [n] int32, ok).

    On exhaustion (ok=False) no state changes and pages are -1 — the caller
    (scheduler) must reclaim (preempt a victim) or remap released
    superblocks and retry, which mirrors the allocator's fill-from-heap /
    trigger-reclamation path.
    """
    return _alloc_pages_impl(pool, n)


# ---------------------------------------------------------------------------
# refcounted free/share: a page re-enters its HOME superblock's LIFO free
# list only on the refcount ZERO-TRANSITION


@_scope("pool.unshare")
def _unshare_pages_impl(pool: PagePool, pages: jax.Array) -> PagePool:
    """Traceable body of :func:`unshare_pages` (reused inside fused jits).

    Each valid entry drops one reference from its page.  The entry whose
    drop takes the count to zero pushes the page back onto its superblock's
    free list, bumps the page's version and arms the clock tick.  Duplicate
    entries within one batch each count as a drop; drops below zero clamp
    (a double-free of an already-free page is a no-op, not corruption).
    """
    pages = pages.reshape(-1).astype(jnp.int32)
    n = pages.shape[0]
    P = pool.num_pages
    S, K = pool.sb_pages.shape
    valid = pages >= 0
    pidx = jnp.where(valid, pages, P)
    rc0 = jnp.where(valid, pool.page_refcount[jnp.minimum(pidx, P - 1)], 0)
    # cnt_incl[i] = occurrences of pages[i] among valid entries 0..i — the
    # entry where the cumulative drop count reaches the old refcount is the
    # (unique) one that performs the zero-transition push
    i = jnp.arange(n)
    same = (pages[None, :] == pages[:, None]) & valid[None, :] & valid[:, None]
    cnt_incl = jnp.sum(same & (i[None, :] <= i[:, None]), axis=1).astype(jnp.int32)
    frees = valid & (rc0 > 0) & (cnt_incl == rc0)
    # total drops per page (clamped at the old count: no negative refcounts)
    drops = jnp.zeros((P + 1,), jnp.int32).at[pidx].add(
        valid.astype(jnp.int32))[:P]
    refcount = jnp.maximum(pool.page_refcount - drops, 0)
    # push only the zero-transition entries, packed per superblock
    sb = jnp.where(frees, pages // K, S)  # S = OOB row -> dropped scatter
    before = (sb[None, :] == sb[:, None]) & (i[None, :] < i[:, None]) & frees[None, :]
    occ = jnp.sum(before, axis=1).astype(jnp.int32)
    slot = pool.sb_free[jnp.minimum(sb, S - 1)] + occ
    sb_lists = pool.sb_pages.at[sb, slot].set(pages, mode="drop")
    freed = jnp.zeros((S,), jnp.int32).at[sb].add(
        frees.astype(jnp.int32), mode="drop")
    fidx = jnp.where(frees, pages, P)
    version = pool.page_version.at[fidx].add(1, mode="drop")
    # the warning fires only when something was actually reclaimed: a batch
    # of pure decrements (or all-(-1)) must not tick the clock (nor the
    # engine's host mirror)
    any_freed = jnp.any(frees)
    return pool._replace(
        sb_pages=sb_lists,
        sb_free=pool.sb_free + freed,
        page_version=version,
        page_refcount=refcount,
        clock=pool.clock + any_freed.astype(jnp.uint32),
    )


@functools.partial(jax.jit, donate_argnums=0)
def unshare_pages(pool: PagePool, pages: jax.Array) -> PagePool:
    """Drop one reference from each page (−1 entries ignored).

    Pages whose count hits ZERO re-enter their superblock's free list and
    fire the warning: the page's version bumps and the global clock ticks
    once per batch containing at least one zero-transition (one warning per
    reclamation batch — Alg. 1/2's single barrier).  Pages still referenced
    elsewhere just lose a reference: no version bump, so surviving holders'
    snapshots stay valid.  A batch with no zero-transition does NOT tick
    the clock."""
    return _unshare_pages_impl(pool, pages)


# free == unshare: with every grant starting at refcount 1, freeing a
# solely-owned page is exactly the zero-transition decref.  The alias keeps
# the paper-facing vocabulary ("retire/free") alongside the sharing one.
_free_pages_impl = _scope("pool.free")(_unshare_pages_impl)


@functools.partial(jax.jit, donate_argnums=0)
def free_pages(pool: PagePool, pages: jax.Array) -> PagePool:
    """Release the caller's reference on each page (−1 entries ignored).

    Alias of :func:`unshare_pages`: a page granted by ``alloc`` holds one
    reference, so for unshared pages this is the classic optimistic free —
    version bump + clock tick, the page immediately re-allocatable.  For
    pages with extra holders (``share_pages``) only the caller's reference
    is dropped."""
    return _free_pages_impl(pool, pages)


@_scope("pool.share")
def _share_pages_impl(pool: PagePool, pages: jax.Array):
    """Traceable body of :func:`share_pages` (reused inside fused jits)."""
    pages = pages.reshape(-1).astype(jnp.int32)
    P = pool.num_pages
    valid = pages >= 0
    pidx = jnp.where(valid, pages, P)
    rc = jnp.where(valid, pool.page_refcount[jnp.minimum(pidx, P - 1)], 1)
    # sharing a FREE page is a caller bug (it could be granted to someone
    # else concurrently): the increment is suppressed and ok goes False
    ok = jnp.all(rc > 0)
    inc = jnp.zeros((P + 1,), jnp.int32).at[pidx].add(
        (valid & (rc > 0)).astype(jnp.int32))[:P]
    return pool._replace(page_refcount=pool.page_refcount + inc), ok


@functools.partial(jax.jit, donate_argnums=0)
def share_pages(pool: PagePool, pages: jax.Array):
    """Add one reference to each LIVE page (−1 entries ignored).

    Returns (pool, ok) — ok is False if any entry named a free page (its
    increment is suppressed: a free page may be granted to a new owner at
    any moment, so sharing it would be a use-after-free in the making).
    Sharing bumps NO version and ticks NO clock: the page content stays
    valid for every holder, and in-flight optimistic readers are unharmed.
    Duplicate entries add one reference each."""
    return _share_pages_impl(pool, pages)


# ---------------------------------------------------------------------------
# physical release accounting (paper §3.2, device edition)


@_scope("pool.release")
def _release_empty_impl(pool: PagePool, max_release: jax.Array,
                        keep_mapped: jax.Array):
    S, K = pool.sb_pages.shape
    cap = _capacities(pool)
    # a page with refcount > 0 is never on a free list, so its superblock
    # can never be EMPTY — but the invariant "releasing a superblock with
    # any refcount > 0 page is impossible" is enforced explicitly too, so
    # even a corrupted anchor cannot unmap a referenced (shared) page
    page_sb_all = jnp.arange(pool.num_pages, dtype=jnp.int32) // K
    refs_in_sb = jnp.zeros((S,), jnp.int32).at[page_sb_all].add(
        (pool.page_refcount > 0).astype(jnp.int32))
    empty = pool.sb_mapped & (pool.sb_free >= cap) & (refs_in_sb == 0)
    mapped_count = jnp.sum(pool.sb_mapped.astype(jnp.int32))
    quota = jnp.clip(
        jnp.minimum(max_release, mapped_count - keep_mapped), 0, S)
    # release highest-indexed empties first so allocation (which prefers
    # low-indexed superblocks among equals) keeps the low region hot
    from_top = jnp.cumsum(empty[::-1].astype(jnp.int32))[::-1]
    release = empty & (from_top <= quota)
    version = pool.page_version + release[page_sb_all].astype(jnp.uint32)
    n_rel = jnp.sum(release.astype(jnp.int32))
    pages_rel = jnp.sum(jnp.where(release, cap, 0)).astype(jnp.int32)
    return (
        pool._replace(
            sb_mapped=pool.sb_mapped & ~release,
            page_version=version,
            clock=pool.clock + (n_rel > 0).astype(jnp.uint32),
        ),
        n_rel, pages_rel,
    )


@functools.partial(jax.jit, donate_argnums=0)
def release_empty_superblocks(pool: PagePool, max_release: jax.Array,
                              keep_mapped: jax.Array):
    """Take up to ``max_release`` EMPTY superblocks out of circulation while
    keeping at least ``keep_mapped`` superblocks mapped.

    The device analogue of handing an empty superblock's frames back to the
    OS (paper §3.2): released pages leave the free list (they can no longer
    be granted), every released page's version bumps — so any in-flight
    optimistic reader holding a snapshot over the released range fails OA
    validation, exactly like a reader of a reclaimed node — and the clock
    ticks once per non-empty release batch.  The KV arena itself stays
    allocated (palloc: reads through stale block tables never fault).

    Returns (pool, n_released [] int32, pages_released [] int32).  Only
    FULL==0-live (i.e. EMPTY) superblocks are eligible, so a release can
    never take a live page out from under a running request.
    """
    return _release_empty_impl(pool, max_release, keep_mapped)


@_scope("pool.map")
def _map_superblocks_impl(pool: PagePool, n: jax.Array):
    cap = _capacities(pool)
    unmapped = ~pool.sb_mapped
    rk = jnp.cumsum(unmapped.astype(jnp.int32))  # 1-based rank among unmapped
    take = unmapped & (rk <= n)
    n_map = jnp.sum(take.astype(jnp.int32))
    pages_map = jnp.sum(jnp.where(take, cap, 0)).astype(jnp.int32)
    return pool._replace(sb_mapped=pool.sb_mapped | take), n_map, pages_map


@functools.partial(jax.jit, donate_argnums=0)
def map_superblocks(pool: PagePool, n: jax.Array):
    """Bring up to ``n`` released superblocks back into circulation (lowest
    index first).  Their pages re-enter the free lists as an EMPTY
    superblock; versions were already bumped at release, so no stale
    snapshot can survive a release/remap cycle.  Returns (pool, n_mapped []
    int32, pages_mapped [] int32)."""
    return _map_superblocks_impl(pool, n)


# ---------------------------------------------------------------------------
# OA snapshot / validate (unchanged by the superblock refactor)


@_scope("pool.snapshot")
def _snapshot_impl(pool: PagePool, pages: jax.Array) -> jax.Array:
    return jnp.where(pages >= 0, pool.page_version[jnp.maximum(pages, 0)], 0)


@jax.jit
def snapshot_versions(pool: PagePool, pages: jax.Array) -> jax.Array:
    """Versions of ``pages`` (−1 entries read as 0) — the reader's LocalClock."""
    return _snapshot_impl(pool, pages)


@_scope("pool.validate")
def _validate_and_commit_impl(pool: PagePool, pages: jax.Array,
                              snapshot: jax.Array):
    cur = _snapshot_impl(pool, pages)
    return jnp.all(cur == snapshot, axis=-1), cur


@jax.jit
def validate_and_commit(pool: PagePool, pages: jax.Array, snapshot: jax.Array):
    """Fused per-row OA check + reader clock advance in ONE pass.

    ``pages`` [..., n]; ``snapshot`` [..., n] (the versions recorded when the
    rows were last known valid).  Returns (valid [...] bool — True iff no page
    in the row was reclaimed since the snapshot — and ``cur``, the freshly
    read versions, which become the next snapshot for rows that commit).
    Replaces the snapshot → compare → re-snapshot sequence (two full passes
    over ``page_version`` plus a host-side compare) the engine used per step.
    """
    return _validate_and_commit_impl(pool, pages, snapshot)


@jax.jit
def validate_read(pool: PagePool, pages: jax.Array, snapshot: jax.Array) -> jax.Array:
    """OA check: True iff none of ``pages`` were reclaimed since ``snapshot``.
    (A reclaim bumps the version BEFORE the page can be re-allocated — and a
    superblock release bumps it again BEFORE the range leaves circulation —
    so a stale optimistic read is always caught — the warning-before-free
    order of Alg. 1.)"""
    with jax.named_scope("pool.validate"):
        cur = jnp.where(pages >= 0,
                        pool.page_version[jnp.maximum(pages, 0)], 0)
        return jnp.all(cur == snapshot)


# ---------------------------------------------------------------------------
# KV page storage


def kv_pages_init(num_pages: int, page_size: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16):
    """The persistent KV arena: allocated once, never released (palloc).
    Layout: [num_pages, page_size, n_kv_heads, head_dim] for each of k/v.
    Superblock release is pure *accounting* on the pool — the arena keeps
    every page addressable so optimistic reads through released ranges stay
    safe (they fail validation instead of faulting)."""
    shape = (num_pages, page_size, n_kv_heads, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@functools.partial(jax.jit, donate_argnums=0)
def append_kv(kv, block_tables, lengths, k_new, v_new):
    """Write one new token's K/V for each sequence.

    kv: page arrays; block_tables [B, max_pages] int32 (−1 = unmapped);
    lengths [B] int32 current lengths (new token goes at position ``lengths``);
    k_new/v_new [B, n_kv_heads, head_dim].

    Only pages pinned in a live block table are written — the scheduler
    guarantees these are not concurrently freed (hazard-pointer discipline).
    """
    page_size = kv["k"].shape[1]
    B = lengths.shape[0]
    page_idx = lengths // page_size
    slot = lengths % page_size
    pages = jnp.take_along_axis(block_tables, page_idx[:, None], axis=1)[:, 0]
    valid = pages >= 0
    p = jnp.where(valid, pages, kv["k"].shape[0])  # OOB -> dropped
    k = kv["k"].at[p, slot].set(k_new, mode="drop")
    v = kv["v"].at[p, slot].set(v_new, mode="drop")
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# the stateful Allocator-protocol adapter (core.allocator.Allocator)


class DevicePagePool:
    """Stateful :class:`repro.core.allocator.Allocator` over the pure pool ops.

    Owns the :class:`PagePool` pytree (``state``) plus the host mirrors of
    the superblock anchors — mapped / released / remapped counts that the
    engine used to duplicate in ``EngineStats`` and private fields.  The
    mirrors move only at the explicit ``release``/``map`` sync points, so
    reading :meth:`view` never costs a device transfer; the hot path
    (``serving.paged_decode.fused_decode_step``) keeps threading the raw
    pytree through its fused dispatch and hands it back via ``state``.

    Versions bump on every zero-transition and release, and the serving
    stack's fused step validates every row's snapshot against them on
    every step — OA, exactly as the paper describes, with no free
    deferred.
    """

    def __init__(self, num_pages: int,
                 pages_per_superblock: int = DEFAULT_PAGES_PER_SUPERBLOCK,
                 release_strategy: ReleaseStrategy = ReleaseStrategy.MADVISE,
                 mesh=None):
        self.state = pool_init(num_pages, pages_per_superblock)
        if mesh is not None:
            # tensor-parallel serving: the whole pool pytree — superblock
            # anchors, free lists, versions, refcounts, the OA clock — is the
            # paper's SHARED metadata and replicates on every shard; the
            # per-shard half of the split is the KV arena payload, which is
            # not this class's concern (one logical pool, per-shard payloads)
            self.state = jax.device_put(
                self.state, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()))
        self.release_strategy = release_strategy
        self.superblocks_total = self.state.num_superblocks
        self.superblocks_mapped = self.superblocks_total
        self.superblocks_released = 0  # cumulative
        self.superblocks_remapped = 0  # cumulative
        self.pages_mapped = num_pages

    @property
    def num_pages(self) -> int:
        """Total pages in the arena (constant: palloc'd once)."""
        return self.state.num_pages

    @property
    def pages_per_superblock(self) -> int:
        """Release granularity (pages per superblock)."""
        return self.state.pages_per_superblock

    def alloc(self, n: int) -> tuple[list[int], bool]:
        """Pop ``n`` pages (refcount 1 each).  Returns ``(ids, ok)``; on
        exhaustion ``ok`` is False, nothing changes and ``ids`` is empty.
        An allowed sync point: the grant is materialised to host ints so
        the caller's bookkeeping stays device-free."""
        self.state, pages, ok = alloc_pages(self.state, n)
        pages_np, ok = jax.device_get((pages, ok))
        if not bool(ok):
            return [], False
        return [int(p) for p in pages_np], True

    def free(self, pages) -> None:
        """Drop one reference per page (−1 ignored); zero-transitions
        re-enter the free list with a version bump + one clock tick per
        batch.  Accepts host lists or device arrays (a block-table row) —
        no host transfer either way."""
        self.state = free_pages(self.state, jnp.asarray(pages, jnp.int32))

    def unshare(self, pages) -> None:
        """Alias of :meth:`free` (the refcount vocabulary)."""
        self.free(pages)

    def share(self, pages) -> bool:
        """Add one reference per live page; returns False (and suppresses
        the increment) if any id named a FREE page.  Syncs on the ok flag —
        sharing happens at admission, an allowed sync point."""
        self.state, ok = share_pages(self.state, jnp.asarray(pages, jnp.int32))
        return bool(ok)

    def release(self, keep_superblocks: int) -> tuple[int, int]:
        """Take EMPTY superblocks above the floor out of circulation
        (versions bump; the clock ticks once per non-empty batch).  Updates
        the anchor mirrors; returns ``(n_superblocks, n_pages)``.  A
        ``KEEP`` pool never releases (the paper's portable baseline)."""
        if self.release_strategy is ReleaseStrategy.KEEP:
            return 0, 0
        self.state, n_sb, n_pg = release_empty_superblocks(
            self.state, jnp.asarray(self.superblocks_total, jnp.int32),
            jnp.asarray(max(0, keep_superblocks), jnp.int32))
        got_sb, got_pg = (int(x) for x in jax.device_get((n_sb, n_pg)))
        self.superblocks_mapped -= got_sb
        self.superblocks_released += got_sb
        self.pages_mapped -= got_pg
        return got_sb, got_pg

    def map(self, n_superblocks: int) -> tuple[int, int]:
        """Bring up to ``n_superblocks`` released superblocks back into
        circulation (their versions were bumped at release, so no stale
        snapshot survives the cycle).  Returns ``(n_superblocks,
        n_pages)`` and updates the anchor mirrors."""
        if n_superblocks <= 0 or self.superblocks_mapped >= self.superblocks_total:
            return 0, 0
        self.state, n_sb, n_pg = map_superblocks(
            self.state, jnp.asarray(n_superblocks, jnp.int32))
        got_sb, got_pg = (int(x) for x in jax.device_get((n_sb, n_pg)))
        self.superblocks_mapped += got_sb
        self.superblocks_remapped += got_sb
        self.pages_mapped += got_pg
        return got_sb, got_pg

    def snapshot(self, pages):
        """Versions of ``pages`` (−1 reads as 0) as a device array — the OA
        reader's LocalClock; no host transfer."""
        return snapshot_versions(self.state, jnp.asarray(pages, jnp.int32))

    def view(self) -> AllocatorView:
        """Anchor introspection from the host mirrors (no device sync)."""
        return AllocatorView(
            superblocks_total=self.superblocks_total,
            superblocks_mapped=self.superblocks_mapped,
            superblocks_released=self.superblocks_released,
            superblocks_remapped=self.superblocks_remapped,
            pages_mapped=self.pages_mapped,
            pages_per_superblock=self.pages_per_superblock,
            release_strategy=self.release_strategy.value,
        )


def gather_kv(kv, block_table, max_len: int):
    """Optimistic gather of one sequence's KV as [max_len, Hkv, D] (reference
    path; the Pallas kernel does this page-at-a-time in VMEM).  Reads through
    freed pages are SAFE (arena is persistent) and their content is ignored
    after version validation fails."""
    page_size = kv["k"].shape[1]
    n = max_len // page_size
    pages = jnp.maximum(block_table[:n], 0)
    k = kv["k"][pages].reshape(n * page_size, *kv["k"].shape[2:])
    v = kv["v"][pages].reshape(n * page_size, *kv["v"].shape[2:])
    return k, v
