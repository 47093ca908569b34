"""One ``PagedServingEngine`` on one chip, as the harness drives it.

An engine module is named by the ``builder`` of the configuration's (or the
mix's) ``engine`` settings, ``bench/engines/<builder>.py``, and defines
``build(arch, params, settings, traffic, devices)``, which returns an object
with the methods below.  The harness drives only these, so an engine of
another shape (replicas behind a router, a sharded engine) is a new module,
with no edit to the harness.

The round is the engine's public one, as ``launch/serve.py``'s trace
replay drives it: ``submit`` for the requests now due, ``admit``, ``step``,
``maintain``.  Nothing here re-implements a step.
"""

from __future__ import annotations

import jax

#: the engine's counters, read as deltas over the window
COUNTERS = ("steps", "tokens_committed", "prefix_tokens_reused",
            "prefix_hits", "preemptions", "chunked_steps", "cow_copies",
            "prefix_evictions")


class Engine:
    def __init__(self, engine):
        self.engine = engine  # what a test's ``engine_hook`` is given
        self.sched = engine.scheduler

    def submit(self, prompt: list, max_new: int):
        return self.engine.submit(prompt, max_new)

    def admit(self) -> None:
        self.sched.admit()

    def running(self) -> list:
        return list(self.sched.running)

    def queued(self) -> bool:
        return bool(self.sched.queue)

    def unblock(self) -> bool:
        """Apply frees the reclamation policy deferred, when queued work
        waits on memory and nothing runs; False if there were none."""
        return self.engine.reclaim_policy.drain_pending()

    def step(self) -> None:
        self.engine.step()

    def maintain(self) -> None:
        self.sched.maintain()

    def pages_in_use(self) -> int:
        return self.sched.distinct_pages_in_use()

    def counters(self) -> dict:
        return {k: getattr(self.engine.stats, k) for k in COUNTERS}

    def drain(self) -> None:
        self.engine.run()

    def warm_up_extra(self) -> None:
        """The allocator's ``share`` and ``free`` compile one program per
        length of the page list they are given, and the window frees and
        shares lists of every length a request can hold (a finished
        request's pages, an evicted prefix, a matched prefix).  Compile
        them all here, on a scratch pool of the engine's shape, with lists
        of -1 (ids the allocator ignores), so that nothing compiles inside
        the window.  The scratch pool is placed on the engine's device as
        the engine's is: a pool that is not committed to a device compiles
        programs of its own."""
        from repro.core.pagepool import DevicePagePool

        eng = self.engine
        scratch = DevicePagePool(eng.num_pages, eng.pages_per_superblock)
        scratch.state = jax.device_put(scratch.state, eng.device)
        for n in range(1, eng.max_pages_per_seq + 1):
            scratch.free([-1] * n)
            scratch.share([-1] * n)


def build(arch, params, settings: dict, traffic, devices) -> Engine:
    from repro.serving import PagedServingEngine, required_pages_per_seq

    ps = settings["page_size"]
    return Engine(PagedServingEngine(
        arch, params, num_pages=settings["num_pages"], page_size=ps,
        max_batch=settings["max_batch"],
        max_pages_per_seq=required_pages_per_seq(
            traffic.max_prompt_len(), traffic.max_new(), ps),
        prefill_chunk=settings["prefill_chunk"],
        prefix_cache=settings["prefix_cache"], device=devices[0]))
