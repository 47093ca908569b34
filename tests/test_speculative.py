"""Speculative multi-token decoding: draft-and-verify in the fused step.

Token exactness is the whole contract: greedy decoding with speculation ON
must produce byte-identical output to speculation OFF, for ANY drafter —
the drafts only ever change how many dispatches the tokens take, never
which tokens commit.  The accept scan is the sequence-axis twin of the
pool's OA ``validate_and_commit``: optimistic work (drafted tokens, their
KV appends) that fails validation is discarded, not undone — rejected
writes sit past the committed length and the next append overwrites them.

Covered here: exactness across mixed prefill/decode batches, COW/prefix-
shared rows and mid-draft finishes; exactness under an adversarial
(always-wrong) drafter; the non-greedy ``ValueError`` at ``submit()``; the
AIMD draft-cap backoff to ZERO (the plain executable) with probing; the
n-gram drafter's host semantics; and a hypothesis property test driving
variable per-row accepted counts (0..K) against the non-speculative oracle
with the refcount/clock host mirrors checked after every run.
"""

import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serving import NGramDrafter, PagedServingEngine

CFG = dataclasses.replace(reduced(get_config("olmo-1b")), n_layers=1)


@pytest.fixture(scope="module")
def params():
    return build_model(CFG).init(jax.random.PRNGKey(0))


def _engine(params, **kw):
    kw.setdefault("num_pages", 96)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_pages_per_seq", 24)
    return PagedServingEngine(CFG, params, **kw)


def _run(params, prompts, max_new, **kw):
    eng = _engine(params, **kw)
    reqs = [eng.submit(list(p), m) for p, m in zip(prompts, max_new)]
    stats = eng.run()
    assert all(r.state == "finished" for r in reqs)
    return [r.generated for r in reqs], stats, eng


class AlwaysWrongDrafter:
    """Adversarial drafter: proposes tokens far from anything the tiny
    model emits, so every draft is rejected — the worst case the exactness
    contract (and the AIMD floor-zero backoff) must absorb."""

    def propose(self, context, k):
        """k tokens offset far from the context's own vocabulary usage."""
        return [(context[-1] + 977 + j) % CFG.vocab for j in range(k)]


# ---------------------------------------------------------------------------
# token exactness


def test_spec_on_equals_off_simple(params):
    prompts = [[1, 2, 3, 4], [7, 11, 13], [5, 6, 7, 8, 9, 10]]
    base, sb, _ = _run(params, prompts, [12] * 3)
    spec, ss, _ = _run(params, prompts, [12] * 3, speculative_k=4)
    assert spec == base
    assert ss.tokens_accepted > 0  # speculation actually engaged
    assert ss.steps < sb.steps  # and saved dispatches


def test_spec_exact_on_mixed_prefill_decode_batches(params):
    """Prompts of very different lengths force steps whose batch mixes a
    chunk-prefilling row with drafting decode rows (ONE dispatch)."""
    prompts = [[1, 2, 3], list(range(1, 25)), [9, 9, 9, 9],
               list(range(3, 20))]
    base, _, _ = _run(params, prompts, [10] * 4, prefill_chunk=8)
    spec, ss, _ = _run(params, prompts, [10] * 4, prefill_chunk=8,
                       speculative_k=4)
    assert spec == base
    assert ss.spec_steps > 0


def test_spec_exact_with_token_budget(params):
    prompts = [list(range(1, 17)), list(range(2, 18))]
    base, _, _ = _run(params, prompts, [8] * 2, prefill_chunk=8,
                      token_budget=8)
    spec, _, _ = _run(params, prompts, [8] * 2, prefill_chunk=8,
                      token_budget=8, speculative_k=3)
    assert spec == base


def test_spec_exact_mid_draft_finish(params):
    """Rows whose generation budget is SMALLER than the draft window must
    land exactly on max_new: the scheduler caps each row's draft so full
    acceptance ends the request on the bonus token, never past it."""
    prompts = [[1, 2, 3, 4], [7, 11, 13]]
    for max_new in (1, 2, 3, 5):
        base, _, _ = _run(params, prompts, [max_new] * 2)
        spec, _, _ = _run(params, prompts, [max_new] * 2, speculative_k=6)
        assert spec == base
        assert all(len(g) == max_new for g in spec)


def test_spec_exact_with_prefix_sharing_and_cow(params):
    """COW/prefix-shared rows: two requests sharing a donated prefix decode
    with drafts on; sharing must not corrupt and outputs must match the
    speculation-off engine run over the same two rounds."""
    shared = list(range(1, 9))
    def rounds(**kw):
        eng = _engine(params, prefix_cache=True, **kw)
        r0 = eng.submit(shared, 4)
        eng.run()  # seed the prefix index
        assert r0.state == "finished"
        rs = [eng.submit(shared + [t], 10) for t in (11, 12)]
        stats = eng.run()
        assert all(r.state == "finished" for r in rs)
        assert eng.stats.prefix_hits >= 2
        assert eng.stats.warnings_fired == int(eng.pool.clock)
        return [r.generated for r in rs], stats
    base, _ = rounds()
    spec, ss = rounds(speculative_k=4)
    assert spec == base


def test_spec_exact_under_adversarial_drafter(params):
    """The drafter can be arbitrarily wrong — all-rejected drafts commit
    exactly one token per row per step, identical to plain decode."""
    prompts = [[1, 2, 3, 4], [5, 6, 7]]
    base, _, _ = _run(params, prompts, [8] * 2)
    spec, ss, _ = _run(params, prompts, [8] * 2, speculative_k=4,
                       drafter=AlwaysWrongDrafter())
    assert spec == base
    assert ss.tokens_accepted == 0


def test_spec_rows_never_write_into_shared_pages(params):
    """A drafting row's write page is never refcount-shared: COW divergence
    resolves during prefill, so by decode time the row owns its tail page —
    checked against the host mirrors after every step."""
    shared = list(range(1, 9))
    eng = _engine(params, prefix_cache=True, speculative_k=4)
    r0 = eng.submit(shared, 4)
    eng.run()
    rs = [eng.submit(shared + [t], 8) for t in (21, 22)]
    for _ in range(40):
        eng._admit()
        if not eng.running:
            break
        eng.step()
        for r in eng.running:
            if r.committed >= len(r.prompt):  # decoding (draft-eligible)
                assert (r.committed // eng.page_size) not in r.shared_chain
    assert all(r.state == "finished" for r in rs)


# ---------------------------------------------------------------------------
# sampling policy


def test_non_greedy_submit_raises(params):
    eng = _engine(params, speculative_k=4, greedy=False, temperature=0.7)
    with pytest.raises(ValueError, match="greedy"):
        eng.submit([1, 2, 3], 4)
    # speculation off: non-greedy submits fine
    eng2 = _engine(params, greedy=False, temperature=0.7)
    eng2.submit([1, 2, 3], 4)


def test_fused_step_rejects_non_greedy_speculation():
    from repro.serving.paged_decode import fused_decode_step
    with pytest.raises(ValueError, match="greedy"):
        fused_decode_step(None, None, None, None, None, None, None, None,
                          None, None, None, None, cfg=CFG, greedy=False,
                          speculative=True)


# ---------------------------------------------------------------------------
# AIMD draft cap


def test_aimd_backs_off_to_zero_and_probes(params):
    """Under an always-wrong drafter the K cap must fall to ZERO (drafting
    k=1 still pays the full wide executable) and only probe occasionally —
    the worst-case-overhead bound the benchmark gate measures."""
    eng = _engine(params, speculative_k=4, drafter=AlwaysWrongDrafter(),
                  spec_probe_interval=8)
    reqs = [eng.submit([1, 2, 3, 4], 24), eng.submit([5, 6, 7], 24)]
    eng.run()
    assert all(r.state == "finished" for r in reqs)
    assert eng.scheduler.spec_k_cap == 0
    # probes keep re-testing, but most steps ran the plain executable
    assert 0 < eng.stats.spec_steps < eng.stats.steps / 2
    assert eng.stats.accept_rate == 0.0


def test_aimd_reopens_after_probe(params):
    """A workload that turns self-predictive after a bad stretch re-opens
    the throttle through the probe path."""
    class FlipDrafter:
        def __init__(self):
            self.bad = True
            self.good = NGramDrafter()
        def propose(self, context, k):
            if self.bad:
                return [(context[-1] + 977 + j) % CFG.vocab
                        for j in range(k)]
            return self.good.propose(context, k)
    d = FlipDrafter()
    eng = _engine(params, speculative_k=4, drafter=d, spec_probe_interval=4)
    eng.submit([1, 2, 3, 4], 40)
    eng._admit()
    for _ in range(12):  # drive the cap to zero on bad drafts
        eng.step()
    assert eng.scheduler.spec_k_cap == 0
    d.bad = False
    for _ in range(20):
        if not eng.running:
            break
        eng.step()
    assert eng.scheduler.spec_k_cap > 0  # probe re-opened the throttle


# ---------------------------------------------------------------------------
# drafter host semantics


def test_ngram_drafter_prompt_lookup():
    d = NGramDrafter(max_ngram=3)
    # trigram [1,2,3] recurs: draft continues its earlier occurrence
    assert d.propose([1, 2, 3, 9, 1, 2, 3], 2) == [9, 1]
    # no recurrence at any n: nothing to propose
    assert d.propose([1, 2, 3, 4], 3) == []
    # unigram fallback: last token seen before -> copy what followed
    assert d.propose([5, 7, 5], 1) == [7]
    # k larger than the remaining continuation: proposal may be short
    assert d.propose([4, 4], 5) == [4]
    assert d.propose([3], 4) == []  # too short to look anything up
    assert d.propose([1, 2], 0) == []
    with pytest.raises(ValueError):
        NGramDrafter(max_ngram=0)


# ---------------------------------------------------------------------------
# property test: variable per-row accepted counts against the oracle

class ScriptedDrafter:
    """Proposes the ORACLE continuation corrupted at a scripted depth, so
    each call's accepted count is exactly ``min(depth, k, remaining)`` —
    hypothesis drives acceptance through 0..K deterministically."""

    def __init__(self, oracle: dict, depths: list[int]):
        self.oracle = oracle  # prompt tuple -> full greedy generation
        self.depths = list(depths)
        self.calls = 0

    def propose(self, context, k):
        """Oracle continuation with a wrong token at the scripted depth."""
        for p, gen in self.oracle.items():
            if (len(context) > len(p) and tuple(context[:len(p)]) == p
                    and context[len(p):] == gen[:len(context) - len(p)]):
                g = len(context) - len(p)
                cont = list(gen[g:g + k])
                if not cont:
                    return []
                depth = self.depths[self.calls % len(self.depths)]
                self.calls += 1
                if depth < len(cont):
                    cont[depth] = (cont[depth] + 977) % CFG.vocab
                return cont
        return []


def _property_body(params, data):
    """Rejected suffixes never corrupt state: for ANY per-call accept depth
    (0..K), outputs match the non-speculative oracle, lengths advance only
    by the accepted prefix (the committed mirror stays exact) and the
    refcount/clock host mirrors balance after the run."""
    n_req = data.draw(st.integers(1, 3))
    prompts = [data.draw(st.lists(st.integers(1, 3), min_size=2, max_size=6))
               for _ in range(n_req)]
    max_new = data.draw(st.integers(2, 8))
    k = data.draw(st.integers(1, 4))
    depths = data.draw(st.lists(st.integers(0, 4), min_size=1, max_size=6))

    base, _, _ = _run(params, prompts, [max_new] * n_req)
    oracle = {tuple(p): g for p, g in zip(prompts, base)}
    drafter = ScriptedDrafter(oracle, depths)
    eng = _engine(params, speculative_k=k, drafter=drafter,
                  prefix_cache=True)
    reqs = [eng.submit(list(p), max_new) for p in prompts]
    eng.run()
    assert all(r.state == "finished" for r in reqs)
    assert [r.generated for r in reqs] == base
    for r in reqs:  # lengths advanced by exactly the committed tokens
        assert r.committed == len(r.prompt) + max_new - 1
    # host mirrors balance: the reclamation clock and the refcounts agree
    assert eng.stats.warnings_fired == int(eng.pool.clock)
    rc = np.asarray(eng.pool.page_refcount)
    cached = len(eng._cache_pages)
    assert (rc > 0).sum() == cached  # only the prefix cache holds pages


@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.function_scoped_fixture])
@given(data=st.data())
def test_variable_accept_counts_match_oracle(params, data):
    _property_body(params, data)
