"""Nemotron-4's decoder (arXiv:2402.16819): its plain float32 reference.

The blocks are ``dense_decoder``'s (pre-norm LayerNorm with scale and bias,
grouped-query causal attention, a squared-ReLU MLP with no gate, an untied
output head), except that rotary position embedding turns only the leading
``int(head_dim * rotary_pct)`` dims of each head, as the pairs
(i, i + rot/2); the other dims of every query and key head pass through
unrotated.  The configuration's ``model`` group gives ``rotary_pct``
beside ``dense_decoder``'s keys.

It imports nothing of ``repro``.  The weights are ``dense_decoder``'s
(``make_params`` for the engine, the same draws layer by layer here), and
so are the output head's statistics (``head_stats``) and the float8 control
(``quant="fp8"``).

Where it departs from the published model:

- the weights are random, drawn from the run's seed;
- LayerNorm's scale is stored as it multiplies; the released checkpoints
  store it zero-centred (LayerNorm1p, ``1 + gamma``), which is the same
  function;
- the paper does not state the rotary fraction: 0.5 is the family's
  released configs' (NeMo ``rotary_percentage``, Hugging Face
  ``partial_rotary_factor``);
- depth and vocabulary are what the configuration holds (one pipeline
  stage of the layers, one chip's slice of the vocabulary), and the logits
  are over that slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.dense_decoder import (  # noqa: F401  (the harness's API)
    SEQ_BUCKET,
    _attention,
    _embed_rows,
    _layer_f32,
    _linear,
    _mlp,
    _norm,
    _rope as _dense_rope,
    _spec,
    head_stats,
    make_params,
)


def _rope(x, theta, pct):
    """``dense_decoder``'s rotary embedding over the leading
    ``int(D * pct)`` dims of each head; the rest unchanged.  x [S, H, D];
    position = row index."""
    rot = int(x.shape[-1] * pct)
    return jnp.concatenate([_dense_rope(x[..., :rot], theta), x[..., rot:]],
                           -1)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _block(spec, eps, x, p, quant):
    m = dict(spec)
    s = x.shape[0]
    hq, hkv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    theta, pct = m["rope_theta"], m["rotary_pct"]
    h = _norm(m, eps, x, p["ln1"])
    q = _rope(_linear(h, p["attn"]["wq"], quant).reshape(s, hq, dh),
              theta, pct)
    k = _rope(_linear(h, p["attn"]["wk"], quant).reshape(s, hkv, dh),
              theta, pct)
    v = _linear(h, p["attn"]["wv"], quant).reshape(s, hkv, dh)
    att = _attention(q, k, v).reshape(s, hq * dh)
    x = x + _linear(att, p["attn"]["wo"], quant)
    return x + _mlp(m, _norm(m, eps, x, p["ln2"]), p["mlp"], quant)


def final_hidden(m: dict, eps: float, words, seqs: list,
                 quant: str | None = None) -> list:
    """For each token sequence, the hidden states after the last block
    (rows padded to a multiple of ``SEQ_BUCKET``), from a causal forward
    over the whole sequence, one layer's float32 weights on the device at a
    time."""
    spec = _spec(m)
    words = jnp.asarray(words, jnp.uint32)
    xs = []
    for seq in seqs:
        n = -(-len(seq) // SEQ_BUCKET) * SEQ_BUCKET
        toks = np.zeros(n, np.int32)
        toks[:len(seq)] = seq  # padding after the sequence: causal, unread
        xs.append(_embed_rows(spec, words, jnp.asarray(toks)))
    for layer in range(m["n_layers"]):
        p = _layer_f32(spec, words, layer)
        xs = [_block(spec, eps, x, p, quant) for x in xs]
        del p
    return xs
