"""Shared model layers: norms, RoPE, chunked-flash attention, MLP variants.

Everything is pure-functional JAX.  Attention never materializes an S×S
score matrix: training/prefill use an online-softmax scan over KV chunks
(flash attention expressed in jnp — the same math as the Pallas kernel in
``repro.kernels``, selectable via config), decode uses a single einsum over
the cache (scores are B×H×S, not S×S).

Dtype policy: parameters and activations bf16, softmax/accumulators fp32.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Norms


def rmsnorm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def nonparam_layernorm(x, eps=1e-5):
    """OLMo-style non-parametric LayerNorm (no learned scale/bias)."""
    return layernorm(x, None, None, eps)


def apply_norm(cfg, x, norm_params):
    if cfg.norm_type == "rmsnorm":
        return rmsnorm(x, norm_params["scale"])
    if cfg.norm_type == "layernorm":
        return layernorm(x, norm_params["scale"], norm_params["bias"])
    if cfg.norm_type == "nonparam_ln":
        return nonparam_layernorm(x)
    raise ValueError(cfg.norm_type)


def init_norm(cfg, d, dtype=jnp.bfloat16):
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return {}  # non-parametric


# ---------------------------------------------------------------------------
# RoPE


def rope_angles(positions, rot_dim, theta):
    """positions [*(pos)] -> (sin, cos) each [*(pos), rot_dim/2], fp32.

    ``rot_dim`` is the rotated width of a head (``cfg.rope_dim``): the whole
    head, or its leading part under partial rotary."""
    half = rot_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x, sin, cos):
    """x [..., H, D]; sin/cos broadcastable to [..., 1, R/2].

    Rotates the first R = 2 * sin.shape[-1] dims of each head as the pairs
    (i, i + R/2); dims [R, D) pass through unchanged (partial rotary)."""
    rot = 2 * sin.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], sin, cos), x[..., rot:]], -1)
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    sin = sin[..., None, :]
    cos = cos[..., None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(
        x.dtype
    )


# ---------------------------------------------------------------------------
# Attention (chunked flash for train/prefill; einsum for decode)


def _flash_mask(k_pos, q_pos, Sk, causal, window, prefix_len):
    """[Sq, chunk] bool validity mask."""
    mask = k_pos[None, :] < Sk  # KV padding
    if causal:
        cm = q_pos[:, None] >= k_pos[None, :]
        if prefix_len:
            cm = cm | (k_pos[None, :] < prefix_len)  # bidirectional prefix
        mask = mask & cm
    if window is not None:
        mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
    return mask


def _flash_chunks(x, chunk):
    B, S, H, D = x.shape
    n = (S + chunk - 1) // chunk
    pad = n * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return jnp.moveaxis(x.reshape(B, n, chunk, H, D), 1, 0), n


def _flash_fwd_scan(qg, k, v, cfgt):
    causal, chunk, window, q_offset, prefix_len = cfgt
    B, Sq, Hkv, G, D = qg.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    kc, nchunks = _flash_chunks(k, chunk)
    vc, _ = _flash_chunks(v, chunk)
    q_pos = q_offset + jnp.arange(Sq)

    def body(carry, inputs):
        m, l, acc = carry
        ci, kb, vb = inputs
        k_pos = ci * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        mask = _flash_mask(k_pos, q_pos, Sk, causal, window, prefix_len)
        s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(mask[None, :, None, None, :],
                      jnp.exp(s - m_safe[..., None]), 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        return (m_new, l_new, acc * alpha[..., None] + pv), None

    m0 = jnp.full((B, Sq, Hkv, G), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
    ks = (jnp.arange(nchunks), kc, vc)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), ks)
    l_safe = jnp.maximum(l, 1e-30)
    out = acc / l_safe[..., None]
    lse = jnp.where(l > 0, m + jnp.log(l_safe), -jnp.inf)
    return out, lse


@functools.lru_cache(maxsize=None)
def _flash_core(cfgt):
    """custom_vjp flash attention for one static config tuple.

    Forward saves only (q, k, v, out, lse) — the flash-2 residual set — and
    the backward re-derives per-chunk probabilities inside its own scan, so
    no S×S (or S×chunk stack) tensor is ever live.  This is what lets
    train_4k (1M tokens) and prefill_32k lower within HBM.
    """
    causal, chunk, window, q_offset, prefix_len = cfgt

    @jax.custom_vjp
    def core(qg, k, v):
        return _flash_fwd_scan(qg, k, v, cfgt)[0]

    def fwd(qg, k, v):
        out, lse = _flash_fwd_scan(qg, k, v, cfgt)
        return out, (qg, k, v, out, lse)

    def bwd(res, dout):
        qg, k, v, out, lse = res
        B, Sq, Hkv, G, D = qg.shape
        Sk = k.shape[1]
        scale = 1.0 / math.sqrt(D)
        kc, nchunks = _flash_chunks(k, chunk)
        vc, _ = _flash_chunks(v, chunk)
        q_pos = q_offset + jnp.arange(Sq)
        dout32 = dout.astype(jnp.float32)
        delta = jnp.sum(dout32 * out, axis=-1)  # [B,Sq,Hkv,G]
        lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)

        def body(dq, inputs):
            ci, kb, vb = inputs
            k_pos = ci * chunk + jnp.arange(chunk)
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _flash_mask(k_pos, q_pos, Sk, causal, window, prefix_len)
            p = jnp.where(mask[None, :, None, None, :],
                          jnp.exp(s - lse_safe[..., None]), 0.0)
            dv = jnp.einsum("bqhgk,bqhgd->bkhd", p, dout32)
            dp = jnp.einsum("bqhgd,bkhd->bqhgk", dout, vb,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta[..., None]) * scale
            dq = dq + jnp.einsum("bqhgk,bkhd->bqhgd", ds.astype(kb.dtype), kb,
                                 preferred_element_type=jnp.float32)
            dk = jnp.einsum("bqhgk,bqhgd->bkhd", ds, qg.astype(jnp.float32))
            return dq, (dk, dv)

        dq0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)
        ks = (jnp.arange(nchunks), kc, vc)
        dq, (dks, dvs) = jax.lax.scan(body, dq0, ks)
        unchunk = lambda x: jnp.moveaxis(x, 0, 1).reshape(B, nchunks * chunk, Hkv, D)[:, :Sk]
        return (dq.astype(qg.dtype),
                unchunk(dks).astype(k.dtype),
                unchunk(dvs).astype(v.dtype))

    core.defvjp(fwd, bwd)
    return core


def flash_attention(q, k, v, *, causal=True, chunk=512, window=None,
                    q_offset=0, prefix_len=0):
    """Online-softmax attention without S×S materialization (flash-2 math,
    memory-true backward via custom_vjp).

    q: [B, Sq, Hq, D]; k,v: [B, Sk, Hkv, D] with Hq % Hkv == 0 (GQA).
    ``window``: sliding-window size (None = full); ``prefix_len``: leading
    positions that attend bidirectionally (VLM image prefix); ``q_offset``:
    global position of q[0].  Returns [B, Sq, Hq, D] in q.dtype.
    """
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    core = _flash_core((causal, chunk, window, q_offset, prefix_len))
    out = core(qg, k, v)
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, window=None):
    """Single-token attention over a (possibly longer-than-valid) cache.

    q: [B, 1, Hq, D]; caches: [B, S, Hkv, D]; cache_len: [] or [B] valid length.
    Returns [B, 1, Hq, D].
    """
    B, _, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache, preferred_element_type=jnp.float32)
    s = s / math.sqrt(D)
    k_pos = jnp.arange(S)
    valid = k_pos[None, :] < jnp.reshape(cache_len, (-1, 1))  # [B or 1, S]
    if window is not None:
        valid = valid & (k_pos[None, :] >= jnp.reshape(cache_len, (-1, 1)) - window)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs


def mlp_apply(cfg, x, p):
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
        return h @ p["w_down"]
    if cfg.mlp_type == "geglu":  # gemma-family gated GELU
        h = jax.nn.gelu(x @ p["w_gate"], approximate=True) * (x @ p["w_up"])
        return h @ p["w_down"]
    if cfg.mlp_type == "gelu":
        h = jax.nn.gelu(x @ p["w_up"] + p.get("b_up", 0).astype(x.dtype), approximate=True)
        return h @ p["w_down"] + p.get("b_down", 0).astype(x.dtype)
    if cfg.mlp_type == "relu2":  # Nemotron-4 squared ReLU
        h = jnp.square(jax.nn.relu(x @ p["w_up"]))
        return h @ p["w_down"]
    raise ValueError(cfg.mlp_type)


def init_mlp(cfg, key, d, ff, dtype=jnp.bfloat16, bias=False):
    k1, k2, k3 = jax.random.split(key, 3)
    std_in, std_out = 0.02, 0.02 / math.sqrt(2 * max(cfg.n_layers, 1))
    p = {}
    if cfg.mlp_type in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(k1, (d, ff)) * std_in).astype(dtype)
        p["w_up"] = (jax.random.normal(k2, (d, ff)) * std_in).astype(dtype)
        p["w_down"] = (jax.random.normal(k3, (ff, d)) * std_out).astype(dtype)
    else:
        p["w_up"] = (jax.random.normal(k1, (d, ff)) * std_in).astype(dtype)
        p["w_down"] = (jax.random.normal(k3, (ff, d)) * std_out).astype(dtype)
        if bias:
            p["b_up"] = jnp.zeros((ff,), dtype)
            p["b_down"] = jnp.zeros((d,), dtype)
    return p


# ---------------------------------------------------------------------------
# Attention block params


def init_attention(cfg, key, dtype=jnp.bfloat16):
    d, Hq, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    std_in, std_out = 0.02, 0.02 / math.sqrt(2 * max(cfg.n_layers, 1))
    p = {
        "wq": (jax.random.normal(ks[0], (d, Hq * Dh)) * std_in).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, Hkv * Dh)) * std_in).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, Hkv * Dh)) * std_in).astype(dtype),
        "wo": (jax.random.normal(ks[3], (Hq * Dh, d)) * std_out).astype(dtype),
    }
    if cfg.attn_bias:  # qwen2-style QKV bias
        p["bq"] = jnp.zeros((Hq * Dh,), dtype)
        p["bk"] = jnp.zeros((Hkv * Dh,), dtype)
        p["bv"] = jnp.zeros((Hkv * Dh,), dtype)
    return p


def attention_qkv(cfg, x, p, positions):
    """Project to q/k/v with RoPE applied.  x [B,S,d] -> q [B,S,Hq,D], k/v."""
    B, S, _ = x.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.attn_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, Hq, Dh)
    k = k.reshape(B, S, Hkv, Dh)
    v = v.reshape(B, S, Hkv, Dh)
    if cfg.use_rope:
        sin, cos = rope_angles(positions, cfg.rope_dim, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    return q, k, v
