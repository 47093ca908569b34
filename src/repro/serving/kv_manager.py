"""KVCacheManager: page / refcount / superblock lifecycle for serving.

The middle layer of the serving stack (ARCHITECTURE.md):

    Scheduler (policy)  ->  KVCacheManager (mechanics)  ->  Allocator
                             ^ the ONLY layer that talks to the pool

Everything that touches the allocator protocol (``core.allocator``) or the
per-slot device arrays lives here: share/unshare batches with their clock
mirror, slot install/clear/release, the sharer and index-pin refcount
mirrors, physical release (shrink) and remap.  The layer makes NO policy
decisions — *when* to evict, whom to preempt, how big a chunk to run are
the scheduler's; *how* to do each of those without breaking the OA
invariants is this file.  The scheduler drives it with plain host types
(ints, lists, bools) so the cross-layer contract tests can substitute a
pure-host fake allocator (``tests/test_layering.py``).

Mirror discipline (the exactness contract): ``stats.warnings_fired`` is the
host mirror of the device pool's reclamation clock.  Every method here that
can cause a zero-transition free ticks it exactly once per device batch
that actually freed something — matching ``unshare_pages``' once-per-batch
rule — so ``warnings_fired == pool.clock`` holds after any interleaving
(tested per workload in the engine suites).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.allocator import Allocator
from .stats import EngineStats


class DeviceStepState(NamedTuple):
    """The persistent device-resident batch state, bundled for the runner.

    The runner treats every field as opaque (it forwards ``pool`` into the
    fused step without looking inside — the layering contract); the manager
    owns the fields' meaning: ``kv`` is the paged KV arena, ``pool`` the
    allocator's pytree, the rest the per-slot arrays documented on
    ``fused_decode_step``."""

    kv: dict
    pool: object
    block_tables: jax.Array
    snapshot: jax.Array
    lengths: jax.Array
    last_tok: jax.Array
    active: jax.Array
    prompt_buf: jax.Array
    prompt_len: jax.Array


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6))
def _install_slot(bt, snap, lengths, last, active, pbuf, plen,
                  slot, row, vers, start_len, prompt_row, prompt_n):
    """Install one slot's block-table row and its OA version snapshot (the
    baseline the fused step validates against); ``start_len`` is the
    committed length a shared prefix grants for free."""
    bt = bt.at[slot].set(row)
    snap = snap.at[slot].set(jnp.where(row >= 0, vers, 0).astype(jnp.uint32))
    lengths = lengths.at[slot].set(start_len)
    last = last.at[slot].set(0)
    active = active.at[slot].set(True)
    pbuf = pbuf.at[slot].set(prompt_row)
    plen = plen.at[slot].set(prompt_n)
    return bt, snap, lengths, last, active, pbuf, plen


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def _clear_slot(bt, snap, lengths, last, active, slot):
    """Discard a slot WITHOUT touching its pages (the caller has already
    freed them — or a racing reclaimer owns them)."""
    bt = bt.at[slot].set(-1)
    snap = snap.at[slot].set(0)
    lengths = lengths.at[slot].set(0)
    last = last.at[slot].set(0)
    active = active.at[slot].set(False)
    return bt, snap, lengths, last, active


class KVCacheManager:
    """Page lifecycle mechanics behind the scheduler (module docstring)."""

    def __init__(self, allocator: Allocator, *, kv, max_batch: int,
                 max_pages_per_seq: int, page_size: int, stats: EngineStats,
                 mesh=None):
        self.allocator = allocator
        self.kv = kv
        self.stats = stats
        self.page_size = page_size
        self.max_batch = max_batch
        self.max_pages_per_seq = max_pages_per_seq
        # tensor-parallel serving: per-slot arrays (block tables, snapshots,
        # lengths, prompt buffers) are the SHARED metadata of the split —
        # replicated on every shard of the mesh so the fused step's pool and
        # validation decisions are identical everywhere; only the KV arena
        # payload (built head-sharded by ``kv_storage_init``) is per-shard
        self._replicate = (
            jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
            if mesh is not None else None)
        B, M = max_batch, max_pages_per_seq
        self._bt = self._place(jnp.full((B, M), -1, jnp.int32))
        self._snap = self._place(jnp.zeros((B, M), jnp.uint32))
        self._len = self._place(jnp.zeros((B,), jnp.int32))
        self._last = self._place(jnp.zeros((B,), jnp.int32))
        self._active = self._place(jnp.zeros((B,), bool))
        self._prompt_cap = 16
        self._pbuf = self._place(jnp.zeros((B, self._prompt_cap), jnp.int32))
        self._plen = self._place(jnp.zeros((B,), jnp.int32))
        #: slot index -> the request object occupying it (None = free)
        self.slots: list = [None] * B
        #: page -> live slot references beyond the allocator's own refcount
        self.sharers: dict[int, int] = {}
        #: pages the prefix index holds a reference on — a LIVE view of the
        #: scheduler's page->entry dict (bound via :meth:`bind_index`), so
        #: the zero-transition predicates can never drift from the index
        self.index_pages = {}.keys()

    def _place(self, arr):
        """Replicate ``arr`` over the serving mesh (identity without one)."""
        return (jax.device_put(arr, self._replicate)
                if self._replicate is not None else arr)

    # -- step-state plumbing (the runner's side of the contract) -------------

    def step_state(self) -> DeviceStepState:
        """Bundle the device-resident batch state for one fused dispatch."""
        return DeviceStepState(self.kv, self.allocator.state, self._bt,
                               self._snap, self._len, self._last,
                               self._active, self._pbuf, self._plen)

    def install_state(self, st: DeviceStepState) -> None:
        """Thread the (donated, possibly still in-flight) state back in."""
        self.kv = st.kv
        self.allocator.state = st.pool
        (self._bt, self._snap, self._len, self._last) = (
            st.block_tables, st.snapshot, st.lengths, st.last_tok)

    # -- slot lifecycle (allowed sync points only) ---------------------------

    def free_slot_index(self) -> int:
        """Lowest unoccupied slot (caller checks occupancy beforehand)."""
        return self.slots.index(None)

    def row_pages(self, slot: int) -> list[int]:
        """The slot's mapped page ids, materialised to host ints (finish /
        donation are allowed sync points)."""
        row = np.asarray(jax.device_get(self._bt[slot]))
        return [int(p) for p in row]

    def _ensure_prompt_cap(self, n: int) -> None:
        if n <= self._prompt_cap:
            return
        cap = self._prompt_cap
        while cap < n:
            cap *= 2
        self._pbuf = jnp.pad(self._pbuf, ((0, 0), (0, cap - self._prompt_cap)))
        self._prompt_cap = cap

    def install_slot(self, slot: int, row: list[int], start_len: int,
                     prompt: list[int]) -> None:
        """Install ``row`` (page ids, −1 padding to the block-table width)
        into ``slot`` and snapshot the CURRENT version of every mapped page
        through the allocator protocol — the OA baseline."""
        self._ensure_prompt_cap(len(prompt))
        prow = np.zeros((self._prompt_cap,), np.int32)
        prow[: len(prompt)] = prompt
        bt_row = np.full((self.max_pages_per_seq,), -1, np.int32)
        bt_row[: len(row)] = row
        vers = jnp.asarray(self.allocator.snapshot(bt_row), jnp.uint32)
        (self._bt, self._snap, self._len, self._last, self._active,
         self._pbuf, self._plen) = _install_slot(
            self._bt, self._snap, self._len, self._last, self._active,
            self._pbuf, self._plen,
            jnp.asarray(slot, jnp.int32), jnp.asarray(bt_row), vers,
            jnp.asarray(start_len, jnp.int32),
            jnp.asarray(prow), jnp.asarray(len(prompt), jnp.int32))

    def clear_slot(self, slot: int) -> None:
        """Vacate a slot without freeing its pages (the caller freed them
        already, or a racing reclaimer owns them)."""
        (self._bt, self._snap, self._len, self._last,
         self._active) = _clear_slot(
            self._bt, self._snap, self._len, self._last, self._active,
            jnp.asarray(slot, jnp.int32))
        self.slots[slot] = None

    def release_slot(self, slot: int) -> None:
        """OPTIMISTIC release of a whole row: one reference dropped per
        mapped page (owned pages free with a version bump; shared ones just
        lose this holder), then the slot is cleared.  The caller accounts
        the mirror via :meth:`release_mirror`."""
        self.allocator.free(self._bt[slot])
        self.clear_slot(slot)

    def free_row(self, slot: int) -> None:
        """Free a row's page references WITHOUT clearing the slot (the
        external-reclaimer race hook: the scheduler still believes the slot
        runs, which is the point of the OA race test)."""
        self.allocator.free(self._bt[slot])

    def free_row_tail(self, slot: int, start: int) -> None:
        """Free only the row's pages at block-table positions >= ``start``
        (grants landed after a racing reclaim's watermark)."""
        self.allocator.free(self._bt[slot, start:])

    # -- refcount mirrors ----------------------------------------------------

    def sharer_count(self, page: int) -> int:
        """Live slot references on ``page`` (beyond the index's own)."""
        return self.sharers.get(page, 0)

    def inc_sharer(self, page: int) -> None:
        """A slot took a shared reference on ``page``."""
        self.sharers[page] = self.sharers.get(page, 0) + 1

    def dec_sharer(self, page: int) -> None:
        """A slot dropped its shared reference on ``page``."""
        c = self.sharers.get(page, 0)
        if c <= 1:
            self.sharers.pop(page, None)
        else:
            self.sharers[page] = c - 1

    def bind_index(self, pages: dict) -> None:
        """Adopt the prefix index's page->entry dict as the single source
        of index-held pages: the mirrors read a live key view of it, so a
        donate or evict updates both layers in one mutation (no shadow set
        to keep in lockstep)."""
        self.index_pages = pages.keys()

    def shared_distinct(self) -> int:
        """Distinct pages held shared (slots' shares ∪ the index) — each
        counted ONCE, the way release floors and admission guards bill."""
        return len(self.index_pages | set(self.sharers))

    def drop_ref_frees(self, page: int, was_shared: bool) -> bool:
        """Account one reference drop on ``page`` in the mirrors; True iff
        that drop is the zero-transition (the page actually frees)."""
        if was_shared:
            frees = (self.sharer_count(page) == 1
                     and page not in self.index_pages)
            self.dec_sharer(page)
            return frees
        return page not in self.index_pages  # owned: refcount 1 -> 0

    def release_mirror(self, shared_pages: list[int], owned: int) -> None:
        """Host mirror of a whole-row unshare (:meth:`release_slot`): owned
        pages hit zero, shared pages lose this holder — freeing only if it
        was the last AND the index holds no reference.  Ticks the clock
        mirror iff SOME page hit zero, exactly the device's rule."""
        freed_shared = sum(
            1 for p in shared_pages
            if self.sharers.get(p, 0) == 1 and p not in self.index_pages)
        if owned > 0 or freed_shared:
            self.stats.record_warning()
        for p in shared_pages:
            self.dec_sharer(p)
        self.stats.record_reclaimed(owned + freed_shared)

    # -- share / unshare / alloc mechanics -----------------------------------

    def share(self, pages: list[int]) -> None:
        """Grant slot references on resident ``pages`` (refcount += 1, no
        version moves).  A False from the allocator means the host index
        named a FREE page — an index/pool desync that must fail loudly here,
        not surface later as two requests corrupting one KV page."""
        ok = self.allocator.share(pages)
        assert ok, (
            f"prefix index named free page(s) among {pages} — host cache "
            f"mirrors diverged from the allocator")
        for p in pages:
            self.inc_sharer(p)

    def unshare_batch(self, pages: list[int], freed: int) -> None:
        """Drop one reference per page in ONE allocator batch; ``freed`` is
        the caller-computed zero-transition count (mirror predicates), which
        ticks the clock mirror once iff positive — the device's rule."""
        if not pages:
            return
        self.allocator.unshare(pages)
        if freed:
            self.stats.record_warning()
        self.stats.record_reclaimed(freed)

    def alloc_fresh(self) -> int | None:
        """One fresh page at refcount 1, or None when the pool is dry (the
        scheduler then remaps / evicts / preempts and retries)."""
        pages, ok = self.allocator.alloc(1)
        return pages[0] if ok else None

    # -- physical release / remap (paper §3.2) -------------------------------

    @property
    def mapped_pages(self) -> int:
        """Current allocatable capacity (free + held), from the anchors."""
        return self.allocator.view().pages_mapped

    def shrink(self, keep_superblocks: int) -> int:
        """Release every EMPTY superblock above the floor; a release batch
        bumps released versions and ticks the clock once (OA warning for
        in-flight readers of the range).  Returns superblocks released."""
        got_sb, _ = self.allocator.release(keep_superblocks)
        if got_sb > 0:
            self.stats.record_warning()
            self.stats.record_superblocks(self.allocator.view())
        return got_sb

    def remap_for(self, need_pages: int) -> bool:
        """Bring released superblocks back to cover ``need_pages`` more
        pages; True if any superblock was remapped.  Preferred over
        preemption: remapping costs no running request anything."""
        view = self.allocator.view()
        if need_pages <= 0 or view.superblocks_mapped >= view.superblocks_total:
            return False
        want = -(-need_pages // view.pages_per_superblock)
        got_sb, _ = self.allocator.map(want)
        if got_sb > 0:
            self.stats.record_superblocks(self.allocator.view())
        return got_sb > 0
