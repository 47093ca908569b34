"""Layer isolation in the fused step's flattened KV arena.

The layer loop holds the arena as one [L*P, page, Hkv, D] buffer and gives
layer l page ids offset by ``l*P``.  One ``fused_decode_step`` over L = 3
layers and a mixed batch must write exactly the (layer, page, slot)
positions its valid rows own, and nothing else:

- row 0 decodes one token;
- row 1 prefills a chunk that straddles three pages (two of them granted);
- row 2 is starved of its grant: its mapped page 0 must not change, in any
  layer (a masked write aimed one layer past its own would land on the next
  layer's page 0);
- row 3 decodes with unmapped (-1) entries in its block table.

Tokens and arena must equal, bit for bit, the step built on the loop as it
was before the arena was flattened: each layer's [P, page, Hkv, D] sliced
out, written and attended over, and the layers stacked back.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import pagepool as pp
from repro.models import build_model
from repro.models.layers import apply_norm, attention_qkv, mlp_apply
from repro.models.transformer import embed_tokens
from repro.serving import paged_decode
from repro.serving.paged_decode import fused_decode_step

CFG = dataclasses.replace(reduced(get_config("olmo-1b")), n_layers=3)
L, P, PS, B, M, C, CAP = 3, 8, 4, 4, 6, 8, 16


def _sliced_core(params, kv, block_tables, lengths, tokens, n_new, *, cfg,
                 impl="ref", write_ok=None, mesh=None):
    """The layer loop with each layer's KV sliced out of [L, P, ...]."""
    Bq, Cq = tokens.shape
    np_, page_size = kv["k"].shape[1:3]
    positions = lengths[:, None] + jnp.arange(Cq, dtype=jnp.int32)[None, :]
    x = embed_tokens(cfg, params["embed"], tokens, positions)
    pos_page = positions // page_size
    slot = positions % page_size
    pages = jnp.take_along_axis(
        block_tables, jnp.minimum(pos_page, M - 1), axis=1)
    wvalid = (jnp.arange(Cq)[None, :] < n_new[:, None]) & (pages >= 0) \
        & (pos_page < M) & write_ok[:, None]
    pidx = jnp.where(wvalid, pages, np_)
    total_len = lengths + n_new

    def layer(x, scanned):
        blk, kl, vl = scanned  # this layer's [P, page, Hkv, D]
        h = apply_norm(cfg, x, blk["ln1"])
        q, k, v = attention_qkv(cfg, h, blk["attn"], positions)
        kl = kl.at[pidx, slot].set(k, mode="drop")
        vl = vl.at[pidx, slot].set(v, mode="drop")
        att = paged_decode.paged_attention(
            q, {"k": kl, "v": vl}, block_tables, total_len, impl=impl,
            chunk_lens=n_new, mesh=mesh)
        x = x + att.reshape(Bq, Cq, -1) @ blk["attn"]["wo"]
        x = x + mlp_apply(cfg, apply_norm(cfg, x, blk["ln2"]), blk["mlp"])
        return x, (kl, vl)

    x, (ks, vs) = jax.lax.scan(layer, x, (params["blocks"], kv["k"], kv["v"]))
    x = apply_norm(cfg, x, params["final_norm"])
    return x, {"k": ks, "v": vs}


@pytest.fixture(scope="module")
def params():
    return build_model(CFG).init(jax.random.PRNGKey(3))


# (lengths, prompt_len, mapped pages) per row; the pool hands out pages
# 0..4 first, leaving 5, 6, 7: row 1 takes two, row 2 then finds one
ROWS = [(6, 4, [1, 2]), (2, 14, [3]), (1, 12, [0]), (3, 2, [4])]


def _inputs():
    """Fresh step inputs (the step donates them)."""
    pool, got, ok = pp.alloc_pages(pp.pool_init(P), 5)
    assert bool(ok) and np.asarray(got).tolist() == [0, 1, 2, 3, 4]
    bt = np.full((B, M), -1, np.int32)
    for r, (_, _, pages) in enumerate(ROWS):
        bt[r, :len(pages)] = pages
    rng = np.random.default_rng(11)
    shape = (L, P, PS, CFG.n_kv_heads, CFG.head_dim)
    kv = {n: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
          for n in ("k", "v")}
    prompt = rng.integers(1, CFG.vocab, (B, CAP)).astype(np.int32)
    return (kv, pool, jnp.asarray(bt), jnp.zeros((B, M), jnp.uint32),
            jnp.asarray([r[0] for r in ROWS], jnp.int32),
            jnp.asarray(rng.integers(1, CFG.vocab, B), jnp.int32),
            jnp.ones((B,), bool), jnp.asarray(prompt),
            jnp.asarray([r[1] for r in ROWS], jnp.int32),
            jax.random.PRNGKey(0), jnp.float32(1.0), jnp.int32(C))


def _step(core, ppcb):
    """``fused_decode_step`` traced over ``core``, its kernel calls at a
    compute block of ``ppcb`` pages (the kernel's own override)."""
    def step(*args, **kw):
        real = paged_decode._chunk_core, paged_decode.paged_attention
        paged_decode._chunk_core = core
        paged_decode.paged_attention = functools.partial(
            real[1], pages_per_compute_block=ppcb)
        try:
            return fused_decode_step.__wrapped__(*args, **kw)
        finally:
            paged_decode._chunk_core, paged_decode.paged_attention = real
    return jax.jit(step, static_argnames=("cfg", "impl", "chunk_size"))


@pytest.mark.parametrize("impl,ppcb", [("ref", 1), ("interpret", 1),
                                       ("interpret", 2)])
def test_flat_arena_writes_only_its_layers_pages(params, impl, ppcb):
    kw = dict(cfg=CFG, impl=impl, chunk_size=C)
    kv0 = {n: np.asarray(a, np.float32) for n, a in _inputs()[0].items()}
    out = _step(paged_decode._chunk_core, ppcb)(params, *_inputs(), **kw)
    ref = _step(_sliced_core, ppcb)(params, *_inputs(), **kw)

    kv, bt = out[0], np.asarray(out[2])
    valid, grant, adv = (np.asarray(out[i]) for i in (7, 8, 10))
    assert grant.tolist() == [0, 2, -1, 0]
    assert valid.tolist() == [True, True, False, True]
    # each valid row writes positions [lengths, lengths + adv)
    want = np.zeros((P, PS), bool)
    for r, (n0, _, _) in enumerate(ROWS):
        for pos in range(n0, n0 + adv[r]):
            want[bt[r, pos // PS], pos % PS] = True
    assert not want[0].any() and want.sum() == 1 + 8 + 1
    for n in ("k", "v"):
        changed = (np.asarray(kv[n], np.float32) != kv0[n]).any(axis=(3, 4))
        for l in range(L):
            assert (changed[l] == want).all(), (n, l, np.argwhere(changed[l]))

    for i, (a, b) in enumerate(zip(jax.tree.leaves(out),
                                   jax.tree.leaves(ref))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"output leaf {i}")
