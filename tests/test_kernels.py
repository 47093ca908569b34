"""Pallas kernel sweeps: interpret-mode kernel vs pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import paged_attention
from repro.kernels.ref import paged_attention_chunked_ref, paged_attention_ref

CASES = [
    # (P, page, Hkv, D, Hq, B, max_pages)
    (16, 8, 2, 16, 4, 3, 4),      # GQA 2:1
    (8, 4, 1, 32, 8, 2, 3),       # MQA
    (32, 16, 4, 64, 4, 1, 2),     # MHA, single batch
    (16, 8, 2, 128, 16, 2, 4),    # TPU-aligned head_dim
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", CASES, ids=[str(c) for c in CASES])
def test_paged_attention_matches_ref(case, dtype):
    P, page, Hkv, D, Hq, B, maxp = case
    rng = jax.random.PRNGKey(hash(case) % 2**31)
    ks = jax.random.split(rng, 3)
    kv = {"k": jax.random.normal(ks[0], (P, page, Hkv, D), dtype),
          "v": jax.random.normal(ks[1], (P, page, Hkv, D), dtype)}
    q = jax.random.normal(ks[2], (B, Hq, D), dtype)
    # ragged: every sequence has a different length; some tables end in -1
    bt = np.full((B, maxp), -1, np.int32)
    lens = []
    rnd = np.random.default_rng(0)
    pool = rnd.permutation(P)
    used = 0
    for b in range(B):
        n = int(rnd.integers(1, maxp + 1))
        bt[b, :n] = pool[used : used + n]
        used += n
        lens.append(int(rnd.integers(1, n * page + 1)))
    bt = jnp.asarray(bt)
    lens = jnp.asarray(lens, jnp.int32)

    out = paged_attention(q, kv, bt, lens, impl="interpret")
    ref = paged_attention_ref(q, kv["k"], kv["v"], bt, lens)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol)


@pytest.mark.parametrize("ppcb", [1, 2, 4, None])
@pytest.mark.parametrize("case", CASES, ids=[str(c) for c in CASES])
def test_multi_page_blocks_match_ref(case, ppcb):
    """pages_per_compute_block tiling must be bit-identical (fp32 accum) to
    the single-page walk across the GQA/ragged/unmapped sweep — including
    max_pages not divisible by ppcb (padded with -1 slots).  None is the
    kernel's own block."""
    P, page, Hkv, D, Hq, B, maxp = case
    rng = jax.random.PRNGKey(hash(case) % 2**31)
    ks = jax.random.split(rng, 3)
    kv = {"k": jax.random.normal(ks[0], (P, page, Hkv, D), jnp.float32),
          "v": jax.random.normal(ks[1], (P, page, Hkv, D), jnp.float32)}
    q = jax.random.normal(ks[2], (B, Hq, D), jnp.float32)
    bt = np.full((B, maxp), -1, np.int32)
    rnd = np.random.default_rng(1)
    pool = rnd.permutation(P)
    used = 0
    lens = []
    for b in range(B):
        n = int(rnd.integers(1, maxp + 1))
        bt[b, :n] = pool[used : used + n]
        used += n
        lens.append(int(rnd.integers(1, n * page + 1)))
    bt = jnp.asarray(bt)
    lens = jnp.asarray(lens, jnp.int32)

    ref = paged_attention_ref(q, kv["k"], kv["v"], bt, lens)
    out = paged_attention(q, kv, bt, lens, impl="interpret",
                          pages_per_compute_block=ppcb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_multi_page_blocks_skip_unmapped_interior():
    """A fully-unmapped row must stay finite, and interior -1 entries past
    the live length must not perturb the result."""
    P, page, Hkv, D, Hq = 8, 4, 2, 16, 4
    rng = jax.random.PRNGKey(3)
    kv = {"k": jax.random.normal(rng, (P, page, Hkv, D), jnp.float32),
          "v": jax.random.normal(jax.random.fold_in(rng, 1),
                                 (P, page, Hkv, D), jnp.float32)}
    q = jax.random.normal(jax.random.fold_in(rng, 2), (2, Hq, D), jnp.float32)
    bt = jnp.array([[2, 5, -1, -1], [-1, -1, -1, -1]], jnp.int32)
    lens = jnp.array([6, 1], jnp.int32)
    ref = paged_attention_ref(q[:1], kv["k"], kv["v"], bt[:1], lens[:1])
    for ppcb in (1, 2, 4):
        out = paged_attention(q, kv, bt, lens, impl="interpret",
                              pages_per_compute_block=ppcb)
        np.testing.assert_allclose(np.asarray(out[:1]), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        assert bool(jnp.all(jnp.isfinite(out)))


def test_single_token_length():
    P, page, Hkv, D, Hq, B = 8, 8, 2, 16, 4, 2
    rng = jax.random.PRNGKey(1)
    kv = {"k": jax.random.normal(rng, (P, page, Hkv, D), jnp.float32),
          "v": jax.random.normal(rng, (P, page, Hkv, D), jnp.float32)}
    q = jax.random.normal(rng, (B, Hq, D), jnp.float32)
    bt = jnp.array([[0, -1], [3, -1]], jnp.int32)
    lens = jnp.array([1, 1], jnp.int32)
    out = paged_attention(q, kv, bt, lens, impl="interpret")
    ref = paged_attention_ref(q, kv["k"], kv["v"], bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kv_append_matches_reference(dtype):
    from repro.core.pagepool import append_kv, kv_pages_init
    from repro.kernels.kv_append import kv_append_pallas

    kv = kv_pages_init(8, 4, 2, 8, dtype=dtype)
    bt = jnp.array([[2, 5, -1, -1], [1, -1, -1, -1], [-1, -1, -1, -1]], jnp.int32)
    ln = jnp.array([5, 2, 0], jnp.int32)  # third sequence unmapped: skip write
    k_new = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 8), dtype)
    v_new = jax.random.normal(jax.random.PRNGKey(1), (3, 2, 8), dtype)
    ref = append_kv({k: v.copy() for k, v in kv.items()}, bt, ln, k_new, v_new)
    out = kv_append_pallas({k: v.copy() for k, v in kv.items()}, bt, ln,
                           k_new, v_new, page_size=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(out["k"], np.float32),
                                  np.asarray(ref["k"], np.float32))
    np.testing.assert_array_equal(np.asarray(out["v"], np.float32),
                                  np.asarray(ref["v"], np.float32))


def _chunked_case(case, C, seed=7):
    """Random chunked sweep instance: ragged lengths, chunks straddling page
    boundaries, rows finishing mid-chunk (chunk_lens < C)."""
    P, page, Hkv, D, Hq, B, maxp = case
    rng = jax.random.PRNGKey(seed)
    ks = jax.random.split(rng, 3)
    kv = {"k": jax.random.normal(ks[0], (P, page, Hkv, D), jnp.float32),
          "v": jax.random.normal(ks[1], (P, page, Hkv, D), jnp.float32)}
    q = jax.random.normal(ks[2], (B, C, Hq, D), jnp.float32)
    bt = np.full((B, maxp), -1, np.int32)
    rnd = np.random.default_rng(seed)
    pool = rnd.permutation(P)
    used = 0
    lens, cls = [], []
    for b in range(B):
        n = int(rnd.integers(1, maxp + 1))
        bt[b, :n] = pool[used : used + n]
        used += n
        ln = int(rnd.integers(1, n * page + 1))
        lens.append(ln)
        # rows finishing mid-chunk: some chunk_lens < C; a chunk of c live
        # queries ending at position ln-1 starts at ln-c — straddling a page
        # boundary whenever (ln - c) // page != (ln - 1) // page
        cls.append(int(rnd.integers(1, min(C, ln) + 1)))
    return (q, kv, jnp.asarray(bt), jnp.asarray(lens, jnp.int32),
            jnp.asarray(cls, jnp.int32))


@pytest.mark.parametrize("C", [1, 8, 16])
@pytest.mark.parametrize("ppcb", [1, 2, 4, None])
def test_chunked_matches_ref_sweep(C, ppcb):
    """Chunked Pallas kernel vs the chunked jnp oracle across C ∈ {1,8,16} ×
    ppcb ∈ {1,2,4, the kernel's own}: GQA, ragged lengths, unmapped slots,
    page-boundary straddles, rows finishing mid-chunk."""
    case = (16, 4, 2, 16, 4, 3, 6)  # page_size 4 < C: chunks straddle pages
    q, kv, bt, lens, cls = _chunked_case(case, C)
    ref = paged_attention_chunked_ref(q, kv["k"], kv["v"], bt, lens, cls)
    out = paged_attention(q, kv, bt, lens, impl="interpret",
                          pages_per_compute_block=ppcb, chunk_lens=cls)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_chosen_block_pads_a_ragged_table():
    """The kernel's own block at 16-token pages is 8 pages; a 20-slot table
    is then 3 blocks, the last padded with -1 slots.  At G=6, C=16 with
    rows finishing mid-chunk and rows whose live pages end inside the
    padded block, it still matches the oracle."""
    from repro.kernels.paged_attention import compute_block_pages

    P, page, Hkv, D, G, C, slots = 64, 16, 2, 128, 6, 16, 20
    assert compute_block_pages(page, slots, Hkv, G * C, D, 4) == 8
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    kv = {"k": jax.random.normal(ks[0], (P, page, Hkv, D), jnp.float32),
          "v": jax.random.normal(ks[1], (P, page, Hkv, D), jnp.float32)}
    q = jax.random.normal(ks[2], (3, C, Hkv * G, D), jnp.float32)
    # a row 19 pages long (into the padded third block), one ending in the
    # second block, one in the first; chunks of 16, 5 and 1 live queries
    lens = jnp.array([300, 130, 17], jnp.int32)
    cls = jnp.array([16, 5, 1], jnp.int32)
    perm = np.random.default_rng(5).permutation(P)
    bt = np.full((3, slots), -1, np.int32)
    used = 0
    for b, n in enumerate(-(-np.asarray(lens) // page)):
        bt[b, :n] = perm[used:used + n]
        used += n
    bt = jnp.asarray(bt)
    ref = paged_attention_chunked_ref(q, kv["k"], kv["v"], bt, lens, cls)
    out = paged_attention(q, kv, bt, lens, impl="interpret", chunk_lens=cls)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", CASES, ids=[str(c) for c in CASES])
def test_chunked_matches_ref_shapes(case):
    """The chunked sweep across the MQA/GQA/MHA shape matrix (C=8 fixed)."""
    q, kv, bt, lens, cls = _chunked_case(case, 8, seed=11)
    ref = paged_attention_chunked_ref(q, kv["k"], kv["v"], bt, lens, cls)
    out = paged_attention(q, kv, bt, lens, impl="interpret",
                          pages_per_compute_block=2, chunk_lens=cls)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_chunked_c1_equals_decode_path():
    """A C=1 chunk with chunk_lens=1 must reproduce the decode kernel (and
    the decode oracle) exactly — the chunk axis is a strict generalization."""
    P, page, Hkv, D, Hq, B, maxp = CASES[0]
    q, kv, bt, lens, cls = _chunked_case((P, page, Hkv, D, Hq, B, maxp), 1)
    dec = paged_attention(q[:, 0], kv, bt, lens, impl="interpret")
    chk = paged_attention(q, kv, bt, lens, impl="interpret", chunk_lens=cls)
    np.testing.assert_allclose(np.asarray(chk[:, 0]), np.asarray(dec),
                               atol=1e-6, rtol=1e-6)
    ref = paged_attention_ref(q[:, 0], kv["k"], kv["v"], bt, lens)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_chunked_causal_mask_matches_incremental_decode():
    """Ground truth for the in-chunk causal mask: appending C tokens and
    attending them in ONE chunked call must equal C sequential decode calls
    (append one token, attend, repeat) — the exact replacement the fused
    prefill step performs, across a page-boundary straddle."""
    P, page, Hkv, D, Hq, C = 8, 4, 2, 16, 4, 6
    rng = jax.random.PRNGKey(5)
    ks = jax.random.split(rng, 4)
    kv = {"k": jax.random.normal(ks[0], (P, page, Hkv, D), jnp.float32),
          "v": jax.random.normal(ks[1], (P, page, Hkv, D), jnp.float32)}
    qs = jax.random.normal(ks[2], (C, Hq, D), jnp.float32)
    bt = jnp.array([[3, 6, 1, -1]], jnp.int32)
    base = 2  # chunk spans positions 2..7: straddles the page-0/1 boundary
    # sequential: token t attends pos < base + t + 1
    seq = [paged_attention(qs[t][None], kv, bt,
                           jnp.array([base + t + 1], jnp.int32),
                           impl="interpret")[0]
           for t in range(C)]
    # chunked: one call, total length base + C, all C queries live
    out = paged_attention(qs[None], kv, bt,
                          jnp.array([base + C], jnp.int32),
                          impl="interpret",
                          chunk_lens=jnp.array([C], jnp.int32))[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.stack(seq)),
                               atol=2e-5, rtol=2e-5)


def test_stale_table_reads_are_safe_not_correct():
    """OA semantics: a block table pointing at reclaimed pages must produce
    *some* finite result (never fault) — correctness comes from the version
    check that discards it, not from the read itself."""
    P, page, Hkv, D, Hq, B = 8, 4, 1, 16, 2, 1
    kv = {"k": jnp.zeros((P, page, Hkv, D), jnp.float32),
          "v": jnp.zeros((P, page, Hkv, D), jnp.float32)}
    q = jnp.ones((B, Hq, D), jnp.float32)
    stale = jnp.array([[7, 7]], jnp.int32)  # double-mapped garbage
    out = paged_attention(q, kv, stale, jnp.array([8], jnp.int32),
                          impl="interpret")
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# speculative accept scan: fused primitive vs oracle


def test_speculative_accept_matches_ref_random():
    from repro.kernels.ops import speculative_accept
    from repro.kernels.ref import speculative_accept_ref

    rng = np.random.default_rng(0)
    for _ in range(20):
        B = int(rng.integers(1, 9))
        C = int(rng.integers(2, 9))
        # tiny alphabet so prefixes of every length actually occur
        tgt = rng.integers(0, 3, (B, C)).astype(np.int32)
        chunk = rng.integers(0, 3, (B, C)).astype(np.int32)
        dlens = rng.integers(0, C, (B,)).astype(np.int32)
        got = np.asarray(speculative_accept(jnp.asarray(tgt),
                                            jnp.asarray(chunk),
                                            jnp.asarray(dlens)))
        want = speculative_accept_ref(tgt, chunk, dlens)
        np.testing.assert_array_equal(got, want)
        assert (got <= dlens).all() and (got >= 0).all()


def test_speculative_accept_edge_cases():
    from repro.kernels.ops import speculative_accept
    from repro.kernels.ref import speculative_accept_ref

    # C=1: no draft slots at all -> always 0 accepted
    tgt = np.asarray([[5]], np.int32)
    chunk = np.asarray([[5]], np.int32)
    assert int(speculative_accept(jnp.asarray(tgt), jnp.asarray(chunk),
                                  jnp.asarray([0], np.int32))[0]) == 0
    # full acceptance: every draft equals the verifier's previous argmax
    tgt = np.asarray([[7, 7, 7, 9]], np.int32)
    chunk = np.asarray([[1, 7, 7, 7]], np.int32)
    d = np.asarray([3], np.int32)
    assert int(speculative_accept(jnp.asarray(tgt), jnp.asarray(chunk),
                                  jnp.asarray(d))[0]) == 3
    assert speculative_accept_ref(tgt, chunk, d)[0] == 3
    # first-mismatch truncation: later matches must NOT resurrect the prefix
    tgt = np.asarray([[7, 8, 7, 9]], np.int32)
    chunk = np.asarray([[1, 7, 7, 7]], np.int32)  # slot1 ok, slot2 mismatch
    assert int(speculative_accept(jnp.asarray(tgt), jnp.asarray(chunk),
                                  jnp.asarray(d))[0]) == 1
    assert speculative_accept_ref(tgt, chunk, d)[0] == 1


def test_grid_counter_counts_blocks_by_hand():
    """The host count of the kernel's grid: rows x blocks x layers, and
    for each live row the blocks its length reaches.  At 16-token pages
    the kernel's block is 8 pages (128 positions), so a 20-slot table is 3
    blocks; rows of 1, 128, 129 and 320 positions reach 1, 1, 2 and 3."""
    from repro.kernels.paged_attention import grid_counter

    count = grid_counter(batch=6, max_pages=20, page_size=16, n_kv_heads=2,
                         group=6, head_dim=128, n_layers=4)
    assert count([1, 128, 129, 320], 16) == (4 * 6 * 3, 4 * (1 + 1 + 2 + 3))
    assert count([], 1) == (4 * 6 * 3, 0)
