"""Partial rotary: ``ArchConfig.rotary_pct`` < 1 turns only the leading
``int(head_dim * rotary_pct)`` dims of each query and key head.

- ``apply_rope`` at 0.5 leaves the trailing half of each head as it was and
  rotates the leading half exactly as a head of that width would be; at
  1.0 it is the whole-head formula, bit for bit.
- The serving engine at a small size of Nemotron-4's family (three query
  heads per kv head, LayerNorm with scale and bias, squared ReLU, an untied
  head, ``rotary_pct`` 0.5) prefills in chunks and then decodes through the
  paged cache, and agrees on logits with the plain float32 reference
  ``bench/models/nemotron_decoder.py`` on seeded random weights; the same
  engine rotating the whole head does not.
- olmo-1b's fused step (whole-head rotary) is the step built on the
  whole-head formula, bit for bit.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import pagepool as pp
from repro.models import build_model
from repro.models.layers import apply_rope, attention_qkv, rope_angles
from repro.serving import PagedServingEngine, paged_decode
from repro.serving.paged_decode import fused_decode_step

from bench import check
from bench.models import nemotron_decoder
from bench.run import check_layout

THETA = 1e4


def _whole_head_angles(positions, head_dim, theta):
    """The whole-head formula the program had before ``rotary_pct``."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def _whole_head_rope(x, sin, cos):
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    sin = sin[..., None, :]
    cos = cos[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _heads(dtype):
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 37, 3, 128))
    pos = jnp.arange(37, dtype=jnp.int32)[None, :] + jnp.array([[0], [900]])
    return x.astype(dtype), pos


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_half_rotary_turns_the_leading_half_only(dtype):
    x, pos = _heads(dtype)
    cfg = dataclasses.replace(get_config("nemotron-4-15b"), rotary_pct=0.5)
    assert cfg.rope_dim == 64
    y = apply_rope(x, *rope_angles(pos, cfg.rope_dim, THETA))
    np.testing.assert_array_equal(np.asarray(y[..., 64:]),
                                  np.asarray(x[..., 64:]))
    want = _whole_head_rope(x[..., :64], *_whole_head_angles(pos, 64, THETA))
    np.testing.assert_array_equal(np.asarray(y[..., :64]), np.asarray(want))
    assert not np.array_equal(np.asarray(y[..., :64]),
                              np.asarray(x[..., :64]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_whole_rotary_is_the_whole_head_formula(dtype):
    x, pos = _heads(dtype)
    cfg = get_config("olmo-1b")
    assert cfg.rotary_pct == 1.0 and cfg.rope_dim == cfg.head_dim == 128
    y = apply_rope(x, *rope_angles(pos, cfg.rope_dim, THETA))
    want = _whole_head_rope(x, *_whole_head_angles(pos, 128, THETA))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


def test_rotated_width_must_be_even():
    with pytest.raises(ValueError, match="even"):
        dataclasses.replace(get_config("nemotron-4-15b"), rotary_pct=0.2)


# ---------------------------------------------------------------------------
# the engine against the reference

#: Nemotron-4's family at a small size: 6 query heads over 2 kv heads of 64
#: dims, of which rotary turns 32.  At d_model 512 the attention scores
#: (about 4e-4 * d_model with the reference's 0.02 weights) are large
#: enough for the rotation to move the greedy choice; at 64 they are not,
#: and the whole-head engine serves the same tokens as the half-head one.
NEMO = dataclasses.replace(reduced(get_config("nemotron-4-15b")), n_heads=6,
                           n_kv_heads=2, d_model=512, head_dim=64,
                           d_ff=1024)
EPS = 1e-5  # the program's LayerNorm epsilon
WORDS = (2**31 + 5, 1)  # the seed's two words: weights for both sides
#: The widest logit gap allowed: the reference's best logit minus its logit
#: of the token the engine served, over every served position.  Greedy
#: bfloat16 serving parts from the float32 argmax only between logits
#: within bfloat16 rounding of each other.  Over three weight seeds at this
#: size the engine read 0 to 0.0064, the float8 control 0.036 to 0.117 and
#: the whole-head engine 0.040 to 0.159 (CPU): the limit lies 3x above the
#: first and 2x below the lowest of the others.
GAP_LIMIT = 0.02
#: (prompt length, tokens to generate): prompts that straddle pages and
#: chunks, one reaching 5 pages
REQUESTS = [(13, 10), (22, 8), (37, 12), (5, 9)]


def _model_dict(cfg) -> dict:
    keys = ("name", "family", "n_layers", "d_model", "n_heads", "n_kv_heads",
            "head_dim", "d_ff", "vocab", "vocab_padded", "norm_type",
            "mlp_type", "tie_embeddings", "use_rope", "rope_theta",
            "rotary_pct")
    return {k: getattr(cfg, k) for k in keys}


@pytest.fixture(scope="module")
def nemo_params():
    params = nemotron_decoder.make_params(_model_dict(NEMO), WORDS)
    check_layout(NEMO, params)
    return params


def _served(cfg, params, impl):
    """Greedy tokens of each request, prefilled 4 tokens a step and then
    decoded, through a 3-row engine of 8-token pages."""
    eng = PagedServingEngine(cfg, params, num_pages=32, page_size=8,
                             max_batch=3, attn_impl=impl, prefill_chunk=4)
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(1, cfg.vocab, n).tolist(), m)
            for n, m in REQUESTS]
    eng.run()
    assert all(r.state == "finished" for r in reqs)
    assert eng.stats.chunked_steps > 0
    return [check.Served(list(r.prompt), list(r.generated)) for r in reqs]


def _widest_gap(served) -> float:
    gaps, _ = check.reference_gaps(nemotron_decoder, _model_dict(NEMO), EPS,
                                   WORDS, served)
    assert sum(len(g) for g in gaps) == sum(m for _, m in REQUESTS)
    return float(max(np.max(g) for g in gaps))


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_engine_agrees_with_nemotron_reference(nemo_params, impl):
    gap = _widest_gap(_served(NEMO, nemo_params, impl))
    print(f"{impl}: widest logit gap {gap}")
    assert gap <= GAP_LIMIT


def test_whole_head_engine_fails_the_half_head_reference(nemo_params):
    whole = dataclasses.replace(NEMO, rotary_pct=1.0)
    gap = _widest_gap(_served(whole, nemo_params, "ref"))
    print(f"whole-head engine: widest logit gap {gap}")
    assert gap > GAP_LIMIT


# ---------------------------------------------------------------------------
# olmo-1b's step, unchanged

OLMO = reduced(get_config("olmo-1b"))
P, PS, B, M, C, CAP = 16, 4, 3, 6, 8, 16
#: (committed length, prompt length, mapped pages) per row: a decoding row,
#: a row prefilling from its first token, a row finishing its prompt
ROWS = [(6, 4, [0, 1]), (0, 14, []), (3, 5, [2])]


def _whole_head_qkv(cfg, x, p, positions):
    """``attention_qkv`` with the whole-head formula (olmo has no bias)."""
    B_, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B_, S, cfg.n_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(B_, S, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(B_, S, cfg.n_kv_heads, cfg.head_dim)
    sin, cos = _whole_head_angles(positions, cfg.head_dim, cfg.rope_theta)
    return _whole_head_rope(q, sin, cos), _whole_head_rope(k, sin, cos), v


def _step_inputs():
    """Fresh step inputs (the step donates them)."""
    pool, got, ok = pp.alloc_pages(pp.pool_init(P), 3)
    assert bool(ok)
    bt = np.full((B, M), -1, np.int32)
    for r, (_, _, pages) in enumerate(ROWS):
        bt[r, :len(pages)] = pages
    rng = np.random.default_rng(5)
    shape = (OLMO.n_layers, P, PS, OLMO.n_kv_heads, OLMO.head_dim)
    kv = {n: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
          for n in ("k", "v")}
    prompt = rng.integers(1, OLMO.vocab, (B, CAP)).astype(np.int32)
    return (kv, pool, jnp.asarray(bt), jnp.zeros((B, M), jnp.uint32),
            jnp.asarray([r[0] for r in ROWS], jnp.int32),
            jnp.asarray(rng.integers(1, OLMO.vocab, B), jnp.int32),
            jnp.ones((B,), bool), jnp.asarray(prompt),
            jnp.asarray([r[1] for r in ROWS], jnp.int32),
            jax.random.PRNGKey(0), jnp.float32(1.0), jnp.int32(C))


def _whole_head_step():
    """``fused_decode_step`` traced over the whole-head ``attention_qkv``."""
    def step(*args, **kw):
        real = paged_decode.attention_qkv
        paged_decode.attention_qkv = _whole_head_qkv
        try:
            return fused_decode_step.__wrapped__(*args, **kw)
        finally:
            paged_decode.attention_qkv = real
    return jax.jit(step, static_argnames=("cfg", "impl", "chunk_size"))


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_olmo_step_is_the_whole_head_step(impl):
    assert paged_decode.attention_qkv is attention_qkv
    params = build_model(OLMO).init(jax.random.PRNGKey(3))
    kw = dict(cfg=OLMO, impl=impl, chunk_size=C)
    out = fused_decode_step(params, *_step_inputs(), **kw)
    ref = _whole_head_step()(params, *_step_inputs(), **kw)
    assert np.asarray(out[7]).all()  # every row's step was valid
    for i, (a, b) in enumerate(zip(jax.tree.leaves(out),
                                   jax.tree.leaves(ref))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"output leaf {i}")
