"""Host spans and device scopes of the serving step (serving/spans.py).

A traced round of a toy engine records the scheduler's and the runner's
spans and the per-request ones, nested on the host thread as the engine
calls them; a scheduler built without a span factory records nothing and
runs the same; and the fused step's HLO carries the phase scopes
(``step.*``) and the allocator's (``pool.*``) in its op metadata.
"""

from __future__ import annotations

import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, reduced
from repro.core import ReleaseStrategy
from repro.core.pagepool import DevicePagePool
from repro.models import build_model
from repro.serving import (EngineStats, KVCacheManager, PagedServingEngine,
                           Scheduler, StepResult, spans)
from repro.serving.paged_decode import fused_decode_step

CFG = reduced(get_config("olmo-1b"))
OUTER = "test.round"  # stands in for the caller's own span


@pytest.fixture(scope="module")
def params():
    return build_model(CFG).init(jax.random.PRNGKey(0))


def _engine(params):
    return PagedServingEngine(CFG, params, num_pages=32, page_size=4,
                              max_batch=2, max_pages_per_seq=8,
                              prefill_chunk=4)


def _host_spans(log_dir) -> list:
    """(name, start, end, ids) of the program's spans and the test's."""
    path = next(log_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.split(".")[0] in ("sched", "runner", "req",
                                             "test"):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: v for k, v in ev.stats}))
    return out


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_traced_round_records_nested_spans(params, tmp_path):
    eng = _engine(params)
    eng.submit(list(range(1, 9)), 2)
    eng.run()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(OUTER):
            req = eng.submit(list(range(3, 12)), 3)
            eng.scheduler.admit()
            eng.step()
            eng.scheduler.maintain()
    finally:
        jax.profiler.stop_trace()
    got = _host_spans(tmp_path)
    names = [g[0] for g in got]
    for name in (spans.REQ_SUBMIT, spans.SCHED_ADMIT, spans.REQ_ADMIT,
                 spans.SCHED_PLAN, spans.RUNNER_LAUNCH,
                 spans.RUNNER_COLLECT, spans.SCHED_ABSORB,
                 spans.SCHED_MAINTAIN):
        assert name in names, (name, names)
    by = {g[0]: g for g in got}
    outer = by[OUTER]
    assert all(_inside(g, outer) for g in got if g[0] != OUTER)
    # the per-request spans share the request's id
    assert by[spans.REQ_SUBMIT][3]["rid"] == req.rid
    adm = by[spans.REQ_ADMIT][3]
    assert (adm["rid"], adm["slot"], adm["prefix"]) == (req.rid, req.slot, 0)
    assert _inside(by[spans.REQ_ADMIT], by[spans.SCHED_ADMIT])
    # one step: plan, launch, collect, absorb, in that order, disjoint
    order = [by[n] for n in (spans.RUNNER_LAUNCH, spans.RUNNER_COLLECT,
                             spans.SCHED_ABSORB, spans.SCHED_MAINTAIN)]
    plans = [g for g in got if g[0] == spans.SCHED_PLAN]
    assert len(plans) == 1  # plan_chunk
    seq = plans + order
    assert all(a[2] <= b[1] for a, b in zip(seq, seq[1:]))


class _StubRunner:
    """Every active row valid and committing one token; no grants."""

    def execute(self, kvm):
        B = kvm.max_batch
        active = np.asarray([kvm.slots[i] is not None for i in range(B)])
        return StepResult(
            tokens=np.full((B,), 7, np.int32), valid=active,
            grant_info=np.zeros((B,), np.int32),
            cow=np.zeros((B,), bool), adv=active.astype(np.int32),
            n_acc=np.zeros((B,), np.int32))


def _drive(sched, kvm, n_req=3) -> list:
    reqs = [sched.submit([1, 2, 3], 2) for _ in range(n_req)]
    runner = _StubRunner()
    for _ in range(20):
        sched.admit()
        if not sched.running and not sched.queue:
            break
        C, budget, _ = sched.plan_chunk()
        sched.absorb(runner.execute(kvm), C, budget)
        sched.maintain()
    return reqs


def _stack(**kw):
    stats = EngineStats()
    kvm = KVCacheManager(DevicePagePool(16, 4, ReleaseStrategy.MADVISE),
                         kv=None, max_batch=2, max_pages_per_seq=2,
                         page_size=8, stats=stats)
    return kvm, Scheduler(kvm, stats, num_pages=16, page_size=8,
                          max_batch=2, **kw)


def test_scheduler_without_span_factory_runs():
    kvm, sched = _stack()
    assert sched.span is spans.no_span
    reqs = _drive(sched, kvm)
    assert all(r.state == "finished" and r.generated == [7, 7]
               for r in reqs)


def test_injected_span_factory_sees_every_phase():
    seen = []

    def record(name, **ids):
        seen.append((name, ids))
        return spans.no_span(name)

    kvm, sched = _stack(span=record)
    reqs = _drive(sched, kvm, n_req=1)
    assert reqs[0].state == "finished"
    names = [n for n, _ in seen]
    assert names[:3] == [spans.REQ_SUBMIT, spans.SCHED_ADMIT,
                         spans.REQ_ADMIT]
    assert seen[2][1] == {"rid": reqs[0].rid}
    assert {spans.SCHED_PLAN, spans.SCHED_ABSORB,
            spans.SCHED_MAINTAIN} <= set(names)


def test_fused_step_hlo_carries_phase_and_pool_scopes(params):
    eng = _engine(params)
    st = eng.kv_manager.step_state()
    r = eng.runner
    hlo = fused_decode_step.lower(
        params, st.kv, st.pool, st.block_tables, st.snapshot, st.lengths,
        st.last_tok, st.active, st.prompt_buf, st.prompt_len, r._base_key,
        r._temperature, r._budget_one, None, None, cfg=CFG,
        impl=r.attn_impl, chunk_size=4).compile().as_text()
    parts = {p for name in re.findall(r'op_name="([^"]*)"', hlo)
             for p in name.split("/")}
    assert {"step.inputs", "step.grant", "step.layers", "step.unembed",
            "step.validate", "kv.cow", "layer.kv_write", "layer.attention",
            "layer.mlp", "pool.alloc", "pool.unshare",
            "pool.validate"} <= parts
