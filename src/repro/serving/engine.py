"""PagedServingEngine: the thin facade over the layered serving stack.

The engine used to be a 1,139-line monolith; it is now wiring plus
delegation over three modules with explicit contracts (ARCHITECTURE.md has
the diagram, ``tests/test_layering.py`` pins every arrow):

- :class:`repro.serving.scheduler.Scheduler` — continuous-batching POLICY
  (admission, Sarathi budgets, AIMD backoff, victims, prefix index,
  quiescence release).  Pure host logic; imports no jax.
- :class:`repro.serving.kv_manager.KVCacheManager` — page/refcount/
  superblock MECHANICS and host mirrors; the only layer that talks to the
  allocator (:class:`repro.core.pagepool.DevicePagePool`, one
  implementation of the unified ``core.allocator`` protocol).
- :class:`repro.serving.runner.ModelRunner` — the fused-dispatch EXECUTOR
  owning the ``fused_decode_step`` executables and the one-``device_get``-
  per-step invariant (tests/test_sync_free.py).

The OA story those layers implement end-to-end is unchanged — optimistic
free with version validation, hazard-pointer-style write pinning, physical
superblock release, refcounted prefix sharing with fused COW, chunked
prefill — see each module's docstring and PERF.md.  OA is the one
reclamation path: every fused step validates every row's snapshot, and no
free is deferred.  Data-parallel multi-pool serving stacks N of these
engines behind one router (``serving/parallel.py``); each replica is
exactly this facade.  The historical surface (``submit/step/run/shrink``,
``pool``, ``kv``, ``queue``, ``_admit`` …) delegates to the layer that
now owns it.
"""

from __future__ import annotations

import contextlib
import time
import types

import jax

from repro.core.chaos import ChaosAllocator, ChaosConfig
from repro.core.pagepool import DEFAULT_PAGES_PER_SUPERBLOCK, DevicePagePool
from repro.core.vm import ReleaseStrategy
from repro.kernels.ops import attention_grid_counter
from repro.launch.mesh import make_serving_mesh
from .kv_manager import KVCacheManager
from .paged_decode import kv_storage_init
from .runner import ModelRunner
from .scheduler import Request, Scheduler  # noqa: F401  (re-export)
from .spans import span
from .stats import EngineStats

# OA validates every step and defers no frees: there is never anything to
# drain (see PagedServingEngine.reclaim_policy)
_NO_DEFERRED_FREES = types.SimpleNamespace(drain_pending=lambda: False)


class PagedServingEngine:
    """Continuous-batching LM serving on the refcounted, versioned page pool
    (module docstring; knobs match the historical constructor)."""

    def __init__(self, cfg, params, *, num_pages: int, page_size: int,
                 max_batch: int = 8, max_pages_per_seq: int | None = None,
                 attn_impl: str | None = None, greedy: bool = True,
                 temperature: float = 1.0, seed: int = 0,
                 pages_per_superblock: int = DEFAULT_PAGES_PER_SUPERBLOCK,
                 release_strategy: ReleaseStrategy = ReleaseStrategy.MADVISE,
                 release_quiescence: int | str | None = None,
                 min_mapped_superblocks: int = 1,
                 prefix_cache: bool = False,
                 prefix_cache_pages: int | None = None,
                 prefill_chunk: int = 1,
                 token_budget: int | None = None,
                 grant_retry_limit: int = 8,
                 chaos: ChaosConfig | None = None,
                 speculative_k: int = 0,
                 drafter=None,
                 spec_probe_interval: int = 16,
                 classes: dict | None = None,
                 max_queue_depth: int | None = None,
                 victim_policy="youngest",
                 ladder=None,
                 clock=None,
                 device=None,
                 tensor_parallel: int = 1,
                 devices=None):
        self.cfg = cfg
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_batch = max_batch
        # tensor parallelism: a per-engine ('data','model') mesh of
        # ``tensor_parallel`` devices (the 'data' axis is size 1 — replica
        # parallelism composes OUTSIDE the engine, see serving/parallel.py).
        # Weights shard by param_specs(serving=True), the KV arena by the
        # paged-cache rule (Hkv over 'model'); the pool, block tables and
        # every other scalar of engine state replicate, so each shard makes
        # the identical alloc/free/validate decision — one logical pool,
        # per-shard payloads.
        self.tensor_parallel = int(tensor_parallel)
        if self.tensor_parallel > 1:
            if device is not None:
                raise ValueError(
                    "tensor_parallel > 1 takes a `devices` list, not a "
                    "single `device`")
            self.mesh = make_serving_mesh(self.tensor_parallel, devices)
        else:
            self.mesh = None
            if device is None and devices:
                device = devices[0]
        self.device = device
        ctx = (jax.default_device(device) if device is not None
               else contextlib.nullcontext())
        with ctx:
            if self.mesh is not None:
                from repro.sharding import rules
                self.params = jax.device_put(
                    params, rules.to_named(
                        rules.param_specs(cfg, params, self.mesh,
                                          serving=True),
                        self.mesh))
            else:
                self.params = (jax.device_put(params, device)
                               if device is not None else params)
            self.stats = EngineStats()
            allocator = DevicePagePool(num_pages, pages_per_superblock,
                                       release_strategy, mesh=self.mesh)
            if chaos is not None:
                # fault injection wraps the PROTOCOL, not the pool: the
                # whole stack above sees denials/perturbations through the
                # same Allocator surface it always talks to (core/chaos.py)
                allocator = ChaosAllocator(allocator, chaos)
            self.stats.record_superblocks(allocator.view())
            self.kv_manager = KVCacheManager(
                allocator,
                kv=kv_storage_init(cfg, num_pages, page_size,
                                   mesh=self.mesh),
                max_batch=max_batch,
                max_pages_per_seq=max_pages_per_seq or num_pages,
                page_size=page_size, stats=self.stats, mesh=self.mesh)
            self.runner = ModelRunner(
                cfg, self.params, attn_impl=attn_impl, greedy=greedy,
                temperature=temperature, seed=seed,
                mesh=self.mesh, device=device)
            # the attention kernel's grid, counted on the host each step
            self._attn_grid = attention_grid_counter(
                impl=self.runner.attn_impl, mesh=self.mesh,
                batch=max_batch,
                max_pages=self.kv_manager.max_pages_per_seq,
                page_size=page_size, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                itemsize=self.kv_manager.kv["k"].dtype.itemsize,
                n_layers=cfg.n_layers)
            self.scheduler = Scheduler(
                self.kv_manager, self.stats, num_pages=num_pages,
                page_size=page_size, max_batch=max_batch,
                prefix_cache=prefix_cache,
                prefix_cache_pages=prefix_cache_pages,
                prefill_chunk=prefill_chunk, token_budget=token_budget,
                release_quiescence=release_quiescence,
                min_mapped_superblocks=min_mapped_superblocks, engine=self,
                grant_retry_limit=grant_retry_limit, greedy=greedy,
                speculative_k=speculative_k, drafter=drafter,
                spec_probe_interval=spec_probe_interval,
                classes=classes,
                max_queue_depth=max_queue_depth,
                victim_policy=victim_policy, ladder=ladder, clock=clock,
                span=span)

    # -- scheduling (delegates to the policy layer) --------------------------

    def submit(self, prompt: list[int], max_new_tokens: int,
               deadline: float | None = None, cls: str = "interactive",
               block: bool = False) -> Request:
        """Queue a request (host-only; rejects degenerate and over-capacity
        inputs; ``deadline`` in relative seconds enables admission-time
        shedding — see :meth:`Scheduler.submit`).

        When ``cls``'s bounded admission queue is full the request comes
        back with state ``"rejected"`` (explicit backpressure).  With
        ``block=True`` the engine instead drives admit/step/maintain rounds
        until the queue drains enough to accept it — the caller blocks, the
        queue still never grows past its bound."""
        req = self.scheduler.submit(prompt, max_new_tokens,
                                    deadline=deadline, cls=cls)
        while block and req.state == "rejected":
            self.scheduler.admit()
            if not self.scheduler.running:
                raise MemoryError(
                    "blocking submit: queue full and nothing running — "
                    "the engine cannot make progress to drain it")
            self.step()
            self.scheduler.maintain()
            self.scheduler.requeue(req)
        return req

    def step(self, *, inject_preemption_of: Request | None = None) -> None:
        """One batched decode/prefill step: the scheduler plans the chunk,
        the runner executes ONE fused dispatch with ONE ``device_get``, the
        scheduler absorbs the results.  ``inject_preemption_of`` preempts
        that request after launch but before its results are consumed (the
        scheduler-overlap race; tests)."""
        if not self.scheduler.running:
            return
        C, budget, drafts = self._plan()
        res = self.runner.execute(self.kv_manager, chunk_size=C,
                                  budget=budget, drafts=drafts)
        self.scheduler.absorb(res, C, budget, inject_preemption_of,
                              drafts=drafts)

    def _plan(self):
        """The scheduler's plan for the next step, with the attention
        kernel's grid it implies counted into the stats."""
        C, budget, drafts = self.scheduler.plan_chunk()
        if self._attn_grid is not None:
            self.stats.record_attn_grid(*self._attn_grid(
                self.scheduler.attended_lengths(budget, drafts), C))
        return C, budget, drafts

    def launch_step(self):
        """Dispatch one step WITHOUT collecting its host transfer; returns a
        pending handle for :meth:`collect_step` (None when idle).  The
        data-parallel front end launches every replica before blocking on
        any — jax dispatch is async, so the fused steps overlap."""
        if not self.scheduler.running:
            return None
        C, budget, drafts = self._plan()
        return (self.runner.launch(self.kv_manager, chunk_size=C,
                                   budget=budget, drafts=drafts),
                C, budget, drafts)

    def collect_step(self, handle) -> None:
        """Collect a :meth:`launch_step` handle: the single ``device_get``,
        then the scheduler absorbs the results."""
        if handle is not None:
            pending, C, budget, drafts = handle
            self.scheduler.absorb(self.runner.collect(pending), C, budget,
                                  drafts=drafts)

    def run(self, max_steps: int = 10_000) -> EngineStats:
        """Drive admit/step/maintain until the queue drains (or max_steps);
        host work only at the allowed sync points."""
        t0 = time.time()
        for _ in range(max_steps):
            self.scheduler.admit()
            if not self.scheduler.running and not self.scheduler.queue:
                break
            if not self.scheduler.running:  # queue blocked on memory
                raise MemoryError("pool exhausted with empty running set")
            self.step()
            self.scheduler.maintain()
        if (self.scheduler.release_quiescence is not None
                and not self.scheduler._adaptive_release):
            # drain: park the now-idle superblocks.  Adaptive mode skips
            # this eager shrink — its point is to keep capacity mapped
            # across a regular burst cadence, releasing only when
            # maintain()'s learned threshold says the drain is genuine.
            self.shrink()
        self.stats.record_wall(time.time() - t0)
        return self.stats

    def stream(self, max_steps: int = 10_000):
        """Streaming drain: the same admit/step/maintain loop as
        :meth:`run`, but a GENERATOR yielding ``(request, new_tokens)``
        after every step that committed generated tokens — tokens reach the
        caller as steps complete instead of at drain end.  Structurally
        identical to :meth:`run` (one fused dispatch, one ``device_get``
        per step; yields are pure host reads of the mirrors), so the
        sync-free invariant holds with a streaming consumer attached."""
        t0 = time.time()
        emitted: dict[int, int] = {}  # rid -> tokens already yielded
        for _ in range(max_steps):
            self.scheduler.admit()
            if not self.scheduler.running and not self.scheduler.queue:
                break
            if not self.scheduler.running:  # queue blocked on memory
                raise MemoryError("pool exhausted with empty running set")
            watch = list(self.scheduler.running)
            self.step()
            for req in watch:
                # emit past the per-request high-water mark only: after a
                # preemption restart the row regenerates tokens the consumer
                # already saw (identical under greedy) — don't re-emit them
                seen = emitted.get(req.rid, 0)
                if len(req.generated) > seen:
                    yield req, req.generated[seen:]
                    emitted[req.rid] = len(req.generated)
            self.scheduler.maintain()
        if (self.scheduler.release_quiescence is not None
                and not self.scheduler._adaptive_release):
            self.shrink()
        self.stats.record_wall(time.time() - t0)

    def shrink(self, keep_superblocks: int | None = None) -> int:
        """Release every EMPTY superblock above the floor (maintenance sync
        point); returns the number released.  No-op under ``KEEP``."""
        return self.scheduler.shrink(keep_superblocks)

    def inject_external_reclaim(self, req: Request) -> None:
        """TEST/RACE HOOK — a reclaimer races the decode loop (see
        :meth:`Scheduler.inject_external_reclaim`)."""
        self.scheduler.inject_external_reclaim(req)

    # -- historical introspection surface (tests, examples, benchmarks) ------

    @property
    def pool(self):
        """The device pool pytree (the allocator's threaded state)."""
        return self.kv_manager.allocator.state

    @pool.setter
    def pool(self, state):
        """Install an externally transformed pool pytree (tests)."""
        self.kv_manager.allocator.state = state

    @property
    def kv(self):
        """The paged KV arena ({'k','v'} page arrays)."""
        return self.kv_manager.kv

    @property
    def queue(self):
        """Queued requests (scheduler-owned)."""
        return self.scheduler.queue

    @property
    def running(self):
        """Running requests (scheduler-owned)."""
        return self.scheduler.running

    @property
    def max_pages_per_seq(self) -> int:
        """Block-table width per slot (kv-manager-owned)."""
        return self.kv_manager.max_pages_per_seq

    @property
    def pages_per_superblock(self) -> int:
        """Release granularity of the device pool."""
        return self.kv_manager.allocator.pages_per_superblock

    @property
    def prefill_chunk(self) -> int:
        """Configured chunked-prefill width (scheduler-owned)."""
        return self.scheduler.prefill_chunk

    @property
    def prefix_cache(self) -> bool:
        """Whether refcounted prefix sharing is enabled."""
        return self.scheduler.prefix_cache

    @property
    def speculative_k(self) -> int:
        """Configured draft length K (0 = speculation off; scheduler-owned —
        the live AIMD cap is ``scheduler.spec_k_cap``)."""
        return self.scheduler.speculative_k

    @property
    def release_strategy(self) -> ReleaseStrategy:
        """The pool's physical-release strategy."""
        return self.kv_manager.allocator.release_strategy

    @property
    def reclaim_policy(self):
        """Read only by ``bench/engines/paged.py::Engine.unblock``: OA
        defers no frees, so ``drain_pending()`` has nothing to apply and
        returns False."""
        return _NO_DEFERRED_FREES

    # internal-but-stable hooks the test suites drive directly
    _HOOKS = {
        "_slots": lambda s: s.kv_manager.slots,
        "_bt": lambda s: s.kv_manager._bt,
        "_sharers": lambda s: s.kv_manager.sharers,
        "_cache_pages": lambda s: s.scheduler.index.pages,
        "_prefix_index": lambda s: s.scheduler.index.index,
        "_prefix_tail": lambda s: s.scheduler.index.tail,
        "_prompt_cap": lambda s: s.kv_manager._prompt_cap,
        "_chunk_budget_cap": lambda s: s.scheduler.chunk_budget_cap,
    }

    def __getattr__(self, name):
        hook = type(self)._HOOKS.get(name)
        if hook is None:
            raise AttributeError(name)
        return hook(self)

    @property
    def _warning_batches(self) -> int:
        # the clock mirror lives in stats now; tests still poke it directly
        return self.stats.warnings_fired

    @_warning_batches.setter
    def _warning_batches(self, v: int) -> None:
        self.stats.warnings_fired = v

    def _admit(self) -> None:
        return self.scheduler.admit()

    def _preempt(self, victim: Request) -> None:
        return self.scheduler.preempt(victim)

    def _maintain(self) -> None:
        return self.scheduler.maintain()

    def _evict_prefix(self, need_pages: int | None = None,
                      freeable_only: bool = True) -> int:
        return self.scheduler.index.evict(need_pages, freeable_only)
