"""Serving engine: paged decode == dense baseline, preemption under
pressure, mid-flight reclamation (the OA race) caught by version check."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serving import PagedServingEngine

CFG = reduced(get_config("olmo-1b"))
MODEL = build_model(CFG)
PARAMS = MODEL.init(jax.random.PRNGKey(0))
PROMPTS = [[5, 9, 13], [7, 11], [3, 4, 5, 6]]


def dense_generate(prompt, n):
    cache = MODEL.init_cache(1, 64)
    toks = list(prompt)
    step = jax.jit(MODEL.decode_step)
    for pos in range(len(prompt) + n - 1):
        b = {"token": jnp.array([toks[pos]], jnp.int32),
             "pos": jnp.array([pos], jnp.int32)}
        logits, cache = step(PARAMS, cache, b)
        if pos >= len(prompt) - 1 and len(toks) < len(prompt) + n:
            toks.append(int(jnp.argmax(logits[0])))
    return toks[len(prompt):]


BASELINE = [dense_generate(p, 6) for p in PROMPTS]


def test_paged_matches_dense():
    eng = PagedServingEngine(CFG, PARAMS, num_pages=64, page_size=4,
                             max_batch=2, max_pages_per_seq=8)
    reqs = [eng.submit(p, 6) for p in PROMPTS]
    stats = eng.run()
    assert all(r.state == "finished" for r in reqs)
    for r, b in zip(reqs, BASELINE):
        assert r.generated == b
    assert stats.reader_restarts == 0  # no pressure, no races


def test_preemption_under_memory_pressure():
    eng = PagedServingEngine(CFG, PARAMS, num_pages=4, page_size=4,
                             max_batch=3, max_pages_per_seq=8)
    reqs = [eng.submit(p, 6) for p in PROMPTS]
    stats = eng.run()
    for r, b in zip(reqs, BASELINE):
        assert r.state == "finished" and r.generated == b
    assert stats.preemptions > 0
    assert stats.warnings_fired > 0  # frees tick the pool clock


def test_midflight_reclamation_is_caught():
    eng = PagedServingEngine(CFG, PARAMS, num_pages=64, page_size=4,
                             max_batch=2, max_pages_per_seq=8)
    r1 = eng.submit(PROMPTS[0], 6)
    r2 = eng.submit(PROMPTS[1], 6)
    eng._admit()
    eng.step(inject_preemption_of=r2)  # the OA race
    assert eng.stats.preemptions == 1
    eng.run()
    assert r1.generated == BASELINE[0]
    assert r2.generated == BASELINE[1]  # restarted, still correct


def test_external_reclaim_race_caught_by_version_check():
    """The OA race proper: a reclaimer frees a running request's pages while
    the scheduler still holds a valid-looking snapshot.  The next step's
    fused version check must discard the row (reader_restarts) and the
    request must restart and still finish correctly."""
    eng = PagedServingEngine(CFG, PARAMS, num_pages=64, page_size=4,
                             max_batch=2, max_pages_per_seq=8)
    r1 = eng.submit(PROMPTS[0], 6)
    r2 = eng.submit(PROMPTS[1], 6)
    eng._admit()
    eng.step()
    eng.inject_external_reclaim(r2)  # versions bump under a live snapshot
    eng.step()
    assert eng.stats.reader_restarts == 1
    assert r2.state == "queued" and r2.committed == 0  # known-valid root
    eng.run()
    assert r1.generated == BASELINE[0]
    assert r2.generated == BASELINE[1]  # restarted, still correct


def test_no_live_page_double_mapping():
    """Invariant: at any point, no page appears in two live block tables."""
    eng = PagedServingEngine(CFG, PARAMS, num_pages=5, page_size=4,
                             max_batch=3, max_pages_per_seq=8)
    reqs = [eng.submit(p, 6) for p in PROMPTS]
    for _ in range(200):
        eng._admit()
        if not eng.running and not eng.queue:
            break
        eng.step()
        live = [p for r in eng.running for p in r.pages]
        assert len(live) == len(set(live)), "page double-mapped"
    assert all(r.state == "finished" for r in reqs)


def test_pool_too_small_for_one_request_raises():
    eng = PagedServingEngine(CFG, PARAMS, num_pages=1, page_size=4,
                             max_batch=1, max_pages_per_seq=8)
    eng.submit(list(range(1, 10)), 8)  # needs >1 page
    with pytest.raises(MemoryError):
        eng.run()


def test_streaming_drain_yields_tokens_as_steps_complete():
    """``stream()`` is run() as a generator: tokens arrive incrementally
    (many yields, each a suffix of the final answer), the final outputs
    match the batch run exactly, and no token is ever emitted twice even
    across preemption restarts (the per-request high-water mark)."""
    eng = PagedServingEngine(CFG, PARAMS, num_pages=4, page_size=4,
                             max_batch=3, max_pages_per_seq=8)
    reqs = [eng.submit(p, 6) for p in PROMPTS]
    streamed = {r.rid: [] for r in reqs}
    yields = 0
    for req, new in eng.stream():
        assert new, "a yield always carries at least one new token"
        streamed[req.rid].extend(new)
        yields += 1
    assert yields > len(reqs)  # incremental, not one burst at drain end
    assert eng.stats.preemptions > 0  # tiny pool: restarts happened
    for r, b in zip(reqs, BASELINE):
        assert r.state == "finished"
        assert streamed[r.rid] == b == r.generated  # no dupes, no gaps


def test_blocking_submit_waits_out_a_full_queue():
    """With a bounded admission queue, ``submit(block=True)`` drives the
    engine until space frees instead of rejecting — the queue never
    exceeds its bound, and every request still finishes correctly."""
    eng = PagedServingEngine(CFG, PARAMS, num_pages=64, page_size=4,
                             max_batch=1, max_pages_per_seq=8,
                             max_queue_depth=2)
    first = eng.submit(PROMPTS[0], 6)
    queued = eng.submit(PROMPTS[1], 6)
    rejected = eng.submit(PROMPTS[2], 6)
    assert rejected.state == "rejected" and eng.stats.requests_rejected == 1
    blocked = eng.submit(PROMPTS[2], 6, block=True)  # drives steps inline
    assert blocked.state != "rejected"
    eng.run()
    for r, b in zip((first, queued, blocked), BASELINE):
        assert r.state == "finished" and r.generated == b


def test_randomized_workloads_always_finish_correctly():
    """Property-style sweep: random prompt/generation lengths and pool sizes
    — every request finishes, outputs match a fresh ample-memory engine, no
    page is ever double-mapped."""
    import numpy as np
    rnd = np.random.default_rng(0)
    for trial in range(4):
        n_req = int(rnd.integers(2, 6))
        reqs_spec = [(rnd.integers(1, 15, size=int(rnd.integers(1, 6))).tolist(),
                      int(rnd.integers(1, 8))) for _ in range(n_req)]
        max_need = max((len(p) + n + 3) // 4 for p, n in reqs_spec)
        pool = int(rnd.integers(max_need, max_need + 6))
        eng = PagedServingEngine(CFG, PARAMS, num_pages=pool, page_size=4,
                                 max_batch=3, max_pages_per_seq=max_need + 1)
        ample = PagedServingEngine(CFG, PARAMS, num_pages=64, page_size=4,
                                   max_batch=3, max_pages_per_seq=max_need + 1)
        rs = [eng.submit(p, n) for p, n in reqs_spec]
        ra = [ample.submit(p, n) for p, n in reqs_spec]
        for _ in range(500):
            eng._admit()
            if not eng.running and not eng.queue:
                break
            eng.step()
            live = [pg for r in eng.running for pg in r.pages]
            assert len(live) == len(set(live))
        ample.run()
        for r, a in zip(rs, ra):
            assert r.state == "finished", (trial, r.rid)
            assert r.generated == a.generated, (trial, r.rid)


@pytest.mark.parametrize("impl,page,max_pages,expect_ppcb", [
    ("interpret", 64, 3, 2), ("interpret", 16, 10, 8), ("ref", 16, 10, 0)],
    ids=["interpret-ppcb2", "interpret-kernel-choice", "ref"])
def test_attn_grid_counters_follow_row_lengths(impl, page, max_pages,
                                               expect_ppcb):
    """``attn_grid_blocks``/``attn_live_blocks`` count the kernel's grid
    over every layer: max_batch rows x ceil(max_pages / ppcb) blocks, and
    for each running row the blocks its KV length (committed + this step's
    chunk) reaches.  The kernel's own block is a lane width of keys, 128
    positions: 2 pages of 64 (a 3-slot table is 2 blocks, the second
    padded), 8 pages of 16 (10 slots, 2 blocks); the 150-token prompt
    reaches the second block on the fifth step.  The jnp reference runs no
    grid."""
    B, C = 3, 32
    eng = PagedServingEngine(CFG, PARAMS, num_pages=64, page_size=page,
                             max_batch=B, max_pages_per_seq=max_pages,
                             attn_impl=impl, prefill_chunk=C)
    for p in ([5, 9, 13, 2, 7], [7, 11], [3 + i % 97 for i in range(150)]):
        eng.submit(p, 5)
    crossed = False
    for _ in range(6):
        eng.scheduler.admit()
        before = [(len(r.prompt), r.committed) for r in eng.running]
        grid0, live0 = eng.stats.attn_grid_blocks, eng.stats.attn_live_blocks
        eng.step()
        eng.scheduler.maintain()
        if not expect_ppcb:
            assert (eng.stats.attn_grid_blocks, eng.stats.attn_live_blocks) \
                == (0, 0)
            continue
        span = expect_ppcb * page
        # a prefilling row appends min(C, what is left of its prompt) in a
        # C-wide step; a decoding row appends its one token
        lens = [c + (min(C, n - c) if c < n else 1) for n, c in before]
        crossed |= max(lens) > span
        assert eng.stats.attn_grid_blocks - grid0 == (
            CFG.n_layers * B * -(-max_pages // expect_ppcb))
        assert eng.stats.attn_live_blocks - live0 == (
            CFG.n_layers * sum(-(-n // span) for n in lens))
    assert crossed or not expect_ppcb
