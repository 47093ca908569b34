"""A dense decoder LM: its seeded weights, and its plain float32 reference.

The reference is written from the architecture's description, not from the
program: pre-norm blocks of rotary multi-head (or grouped-query) causal
attention and an MLP, a final norm, and an output head (the embedding
transposed where the two are tied).  It imports nothing of ``repro`` and
takes nothing that the program made: it draws its own weights from the
seed, layer by layer, and computes in float32 at
``jax.default_matmul_precision("highest")``.

Weights are drawn here, on the device, from the run's seed, in the type
they are served in (bfloat16), and handed to the engine in its parameter
layout (``make_params``).  The reference draws the same values again one
layer at a time (``layer_weights``), which is what lets it run after the
engine's state is freed, in less memory than the model.

The control (``quant="fp8"``) is the reference computed one precision below
the bfloat16 the configuration serves in: every linear layer's weights
(per output column) and inputs (per row) rounded to float8 e4m3 with a
scale, the rest as the reference.

The configuration's ``model`` group is read with the names of the
program's ``ArchConfig`` (``n_layers``, ``d_model``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``d_ff``, ``vocab``, ``norm_type``,
``mlp_type``, ``tie_embeddings``, ``rope_theta``); ``norm_eps`` sits beside
it.
"""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: rows of one padded sequence block: the reference compiles one program
#: per multiple of this, not one per sequence length
SEQ_BUCKET = 512
#: queries per attention block: bounds the score matrix to
#: heads x QUERY_BLOCK x length
QUERY_BLOCK = 1024
FP8_MAX = 448.0  # largest finite float8 e4m3fn


def _tag(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def base_key(words):
    """The key every weight derives from; ``words`` = the seed's two 32-bit
    words, as a [2] uint32 array."""
    words = jnp.asarray(words, jnp.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def _normal(key, name, shape, std):
    x = jax.random.normal(jax.random.fold_in(key, _tag(name)), shape,
                          jnp.float32) * std
    return x.astype(jnp.bfloat16)


def _norm_params(m, key, name):
    d = m["d_model"]
    if m["norm_type"] == "nonparam_ln":
        return {}
    if m["norm_type"] == "layernorm":
        one = jnp.ones((d,), jnp.float32)
        return {"scale": (one + _normal(key, name + "/scale", (d,), 0.1)
                          .astype(jnp.float32)).astype(jnp.bfloat16),
                "bias": _normal(key, name + "/bias", (d,), 0.1)}
    if m["norm_type"] == "rmsnorm":  # the program scales by (1 + scale)
        return {"scale": _normal(key, name + "/scale", (d,), 0.1)}
    raise ValueError(f"norm_type {m['norm_type']!r}")


def layer_weights(m: dict, key, layer) -> dict:
    """Layer ``layer``'s weights (bfloat16), in the program's layout."""
    d, hq, hkv, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                          m["head_dim"], m["d_ff"])
    k = jax.random.fold_in(jax.random.fold_in(key, _tag("layer")), layer)
    s_in, s_out = 0.02, 0.02 / math.sqrt(2 * m["n_layers"])
    mlp = {"w_up": _normal(k, "mlp/w_up", (d, ff), s_in),
           "w_down": _normal(k, "mlp/w_down", (ff, d), s_out)}
    if m["mlp_type"] in ("swiglu", "geglu"):
        mlp["w_gate"] = _normal(k, "mlp/w_gate", (d, ff), s_in)
    elif m["mlp_type"] != "relu2":
        raise ValueError(f"mlp_type {m['mlp_type']!r}")
    return {
        "ln1": _norm_params(m, k, "ln1"),
        "attn": {"wq": _normal(k, "attn/wq", (d, hq * hd), s_in),
                 "wk": _normal(k, "attn/wk", (d, hkv * hd), s_in),
                 "wv": _normal(k, "attn/wv", (d, hkv * hd), s_in),
                 "wo": _normal(k, "attn/wo", (hq * hd, d), s_out)},
        "ln2": _norm_params(m, k, "ln2"),
        "mlp": mlp,
    }


def embedding(m: dict, key):
    return _normal(key, "embed", (m["vocab"], m["d_model"]), 0.02)


def output_head(m: dict, key):
    """[d_model, vocab]: the embedding transposed where tied."""
    if m["tie_embeddings"]:
        return embedding(m, key).T
    return _normal(key, "lm_head", (m["d_model"], m["vocab"]), 0.02)


def _spec(m: dict) -> tuple:
    return tuple(sorted(m.items()))


@functools.partial(jax.jit, static_argnums=0)
def _make_params(spec, words):
    m = dict(spec)
    key = base_key(words)
    blocks = jax.lax.map(lambda layer: layer_weights(m, key, layer),
                         jnp.arange(m["n_layers"]))
    params = {"embed": {"tok": embedding(m, key)}, "blocks": blocks,
              "final_norm": _norm_params(m, key, "final_norm")}
    if not m["tie_embeddings"]:
        params["lm_head"] = output_head(m, key)
    return params


def make_params(m: dict, words) -> dict:
    """Every weight of the model, on the default device, in one compiled
    program, in the program's parameter layout."""
    return jax.block_until_ready(
        _make_params(_spec(m), jnp.asarray(words, jnp.uint32)))


# ---------------------------------------------------------------------------
# the plain reference


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _linear(x, w, quant):
    """x [..., k] @ w [k, n] in float32; with ``quant`` both operands are
    first rounded to float8 (x per row, w per output column)."""
    if quant == "fp8":
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(m, eps, x, p):
    if m["norm_type"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * (1.0 + p["scale"])
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    if m["norm_type"] == "layernorm":
        y = y * p["scale"] + p["bias"]
    return y


def _rope(x, theta):
    """Rotary embedding over the whole head, halves rotated as pairs
    (i, i + D/2).  x [S, H, D]; position = row index."""
    s, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal attention, q [S, Hq, D], k/v [S, Hkv, D]; query head h reads
    kv head h // (Hq / Hkv).  Blocks of queries bound the score matrix."""
    s, hq, dh = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    out = []
    for q0 in range(0, s, QUERY_BLOCK):
        q1 = min(s, q0 + QUERY_BLOCK)
        qb = q[q0:q1].reshape(q1 - q0, hkv, g, dh)
        sc = jnp.einsum("qhgd,khd->hgqk", qb, k[:q1],
                        precision=HIGHEST) / math.sqrt(dh)
        mask = (jnp.arange(q0, q1)[:, None] >= jnp.arange(q1)[None, :])
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v[:q1], precision=HIGHEST)
        out.append(o.reshape(q1 - q0, hq, dh))
    return jnp.concatenate(out, 0)


def _mlp(m, h, p, quant):
    if m["mlp_type"] == "relu2":
        a = jnp.square(jax.nn.relu(_linear(h, p["w_up"], quant)))
    elif m["mlp_type"] == "swiglu":
        a = (jax.nn.silu(_linear(h, p["w_gate"], quant))
             * _linear(h, p["w_up"], quant))
    else:  # geglu
        a = (jax.nn.gelu(_linear(h, p["w_gate"], quant), approximate=True)
             * _linear(h, p["w_up"], quant))
    return _linear(a, p["w_down"], quant)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _block(spec, eps, x, p, quant):
    m = dict(spec)
    s = x.shape[0]
    hq, hkv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = _norm(m, eps, x, p["ln1"])
    q = _rope(_linear(h, p["attn"]["wq"], quant).reshape(s, hq, dh),
              m["rope_theta"])
    k = _rope(_linear(h, p["attn"]["wk"], quant).reshape(s, hkv, dh),
              m["rope_theta"])
    v = _linear(h, p["attn"]["wv"], quant).reshape(s, hkv, dh)
    att = _attention(q, k, v).reshape(s, hq * dh)
    x = x + _linear(att, p["attn"]["wo"], quant)
    return x + _mlp(m, _norm(m, eps, x, p["ln2"]), p["mlp"], quant)


@functools.partial(jax.jit, static_argnums=0)
def _layer_f32(spec, words, layer):
    p = layer_weights(dict(spec), base_key(words), layer)
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


@functools.partial(jax.jit, static_argnums=0)
def _embed_rows(spec, words, tokens):
    return embedding(dict(spec), base_key(words))[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1, 6))
def _head(spec, eps, words, x, rows, targets, quant):
    """At each of ``rows`` of the final hidden states: the largest logit,
    its token, and the logit of ``targets`` (one token per row)."""
    m = dict(spec)
    key = base_key(words)
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     _norm_params(m, key, "final_norm"))
    w = output_head(m, key).astype(jnp.float32)
    lg = _linear(_norm(m, eps, x[rows], p), w, quant)
    tgt = jnp.take_along_axis(lg, targets[:, None], axis=1)[:, 0]
    return jnp.max(lg, -1), jnp.argmax(lg, -1).astype(jnp.int32), tgt


def final_hidden(m: dict, eps: float, words, seqs: list,
                 quant: str | None = None) -> list:
    """For each token sequence, the hidden states after the last block
    (rows padded to a multiple of :data:`SEQ_BUCKET`), from a causal
    forward over the whole sequence.  Layer by layer: only one layer's
    weights in float32 are on the device at a time."""
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


def head_stats(m: dict, eps: float, words, x, rows, targets,
               quant: str | None = None):
    """:func:`_head` on host arrays: (max logit, its token, target logit)
    at each row of ``rows`` (row i predicts token i + 1)."""
    rows = np.asarray(rows, np.int32)
    n = len(rows)
    pad = -(-n // SEQ_BUCKET) * SEQ_BUCKET
    rr = np.zeros(pad, np.int32)
    rr[:n] = rows
    tt = np.zeros(pad, np.int32)
    tt[:n] = targets
    out = _head(_spec(m), eps, jnp.asarray(words, jnp.uint32), x,
                jnp.asarray(rr), jnp.asarray(tt), quant)
    return tuple(np.asarray(a)[:n] for a in jax.device_get(out))
