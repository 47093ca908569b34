"""Model family assembly: decoder LM (dense / MoE / prefix-VLM), encoder-
decoder (whisper), hybrid recurrent (RecurrentGemma), SSM (Mamba-2).

All families scan over stacked per-layer parameters (keeps HLO size and
compile time O(1) in depth — essential for 80-layer configs on a 512-device
SPMD partition) and support three entry points:

  forward(params, batch)             -> logits          (teacher forcing)
  prefill(params, batch, cache_size) -> (cache, logits) (inference prefill)
  decode_step(params, cache, batch)  -> (logits, cache) (one-token decode)

Decode caches support per-sequence write positions (``pos`` is a [B] vector)
so the paged/continuous-batching serving engine can drive ragged batches;
sliding-window archs use a rolling ring buffer of ``cache_size`` slots.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.sharding.rules import shard_seq

from . import moe as moe_lib
from . import rglru as rglru_lib
from . import ssm as ssm_lib
from .layers import (
    apply_norm,
    attention_qkv,
    decode_attention,
    flash_attention,
    init_attention,
    init_mlp,
    init_norm,
    mlp_apply,
)


def _stacked(init_fn, L, key):
    return jax.vmap(init_fn)(jax.random.split(key, L))


def _maybe_remat(cfg, fn):
    if cfg.remat == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False,
        )
    return fn


# ===========================================================================
# Embedding / unembedding


def init_embed(cfg, key, dtype=jnp.bfloat16):
    p = {"tok": (jax.random.normal(key, (cfg.vocab_padded, cfg.d_model)) * 0.02).astype(dtype)}
    if not cfg.use_rope:
        p["pos"] = (
            jax.random.normal(jax.random.fold_in(key, 1), (cfg.max_seq, cfg.d_model))
            * 0.02
        ).astype(dtype)
    return p


def embed_tokens(cfg, p, tokens, positions):
    x = jnp.take(p["tok"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    if not cfg.use_rope:
        x = x + jnp.take(p["pos"], positions, axis=0)
    return x


def unembed(cfg, params, x):
    w = params["embed"]["tok"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ w


# ===========================================================================
# Decoder-LM family (dense / MoE / prefix-VLM)


def init_decoder_block(cfg, key):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": init_norm(cfg, cfg.d_model),
        "attn": init_attention(cfg, ks[0]),
        "ln2": init_norm(cfg, cfg.d_model),
    }
    if cfg.moe:
        p["moe"] = moe_lib.init_moe(cfg, ks[1])
    else:
        p["mlp"] = init_mlp(cfg, ks[1], cfg.d_model, cfg.d_ff)
    return p


def _decoder_block_fwd(cfg, x, blk, positions, prefix_len, dropless=False):
    x = shard_seq(x)  # sequence-parallel residual stream (Megatron-SP)
    h = apply_norm(cfg, x, blk["ln1"])
    q, k, v = attention_qkv(cfg, h, blk["attn"], positions)
    # prefix_len > 0: leading (image) tokens attend bidirectionally
    att = flash_attention(
        q, k, v,
        causal=True,
        chunk=cfg.attn_chunk,
        window=cfg.sliding_window,
        prefix_len=prefix_len,
    )
    x = x + att.reshape(*x.shape[:2], -1) @ blk["attn"]["wo"]
    h2 = apply_norm(cfg, x, blk["ln2"])
    if cfg.moe:
        y, aux = moe_lib.moe_apply(cfg, h2, blk["moe"], dropless=dropless)
    else:
        y, aux = mlp_apply(cfg, h2, blk["mlp"]), jnp.zeros((), jnp.float32)
    return x + y, aux




def decoder_forward(cfg, params, batch, dropless=False):
    """-> (hidden [B,S,d], aux_loss). S includes the VLM prefix if present.

    ``dropless``: size MoE capacity so no assignment is dropped — the
    inference/teacher-forcing mode that matches prefill + decode_step
    exactly; the training loss keeps the capacity-bounded default."""
    tokens = batch["tokens"]
    B, St = tokens.shape
    prefix_len = 0
    positions = jnp.arange(St)[None, :]
    x = embed_tokens(cfg, params["embed"], tokens, positions)
    if cfg.prefix_tokens:
        prefix = batch["patches"].astype(x.dtype)  # [B, P, d] (stub frontend)
        prefix_len = prefix.shape[1]
        x = jnp.concatenate([prefix, x], axis=1)
    S = x.shape[1]
    pos_all = jnp.arange(S)

    def layer(x, blk):
        x, aux = _decoder_block_fwd(cfg, x, blk, pos_all, prefix_len,
                                    dropless=dropless)
        return x, aux

    x, auxs = jax.lax.scan(_maybe_remat(cfg, layer), x, params["blocks"])
    x = apply_norm(cfg, x, params["final_norm"])
    return x, jnp.sum(auxs)


def init_decoder_lm(cfg, key):
    ks = jax.random.split(key, 3)
    params = {
        "embed": init_embed(cfg, ks[0]),
        "blocks": _stacked(lambda k: init_decoder_block(cfg, k), cfg.n_layers, ks[1]),
        "final_norm": init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(ks[2], (cfg.d_model, cfg.vocab_padded)) * 0.02
        ).astype(jnp.bfloat16)
    return params


# -- decoder LM: prefill + decode ---------------------------------------------


def decoder_prefill(cfg, params, batch, cache_size):
    tokens = batch["tokens"]
    B, St = tokens.shape
    positions = jnp.arange(St)[None, :]
    x = embed_tokens(cfg, params["embed"], tokens, positions)
    prefix_len = 0
    if cfg.prefix_tokens:
        prefix = batch["patches"].astype(x.dtype)
        prefix_len = prefix.shape[1]
        x = jnp.concatenate([prefix, x], axis=1)
    S = x.shape[1]
    pos_all = jnp.arange(S)

    def layer(x, blk):
        x = shard_seq(x)
        h = apply_norm(cfg, x, blk["ln1"])
        q, k, v = attention_qkv(cfg, h, blk["attn"], pos_all)
        att = flash_attention(q, k, v, causal=True, chunk=cfg.attn_chunk,
                              window=cfg.sliding_window, prefix_len=prefix_len)
        x = x + att.reshape(B, S, -1) @ blk["attn"]["wo"]
        h2 = apply_norm(cfg, x, blk["ln2"])
        if cfg.moe:
            y, _ = moe_lib.moe_apply(cfg, h2, blk["moe"], dropless=True)
        else:
            y = mlp_apply(cfg, h2, blk["mlp"])
        return x + y, (k, v)

    x, (ks, vs) = jax.lax.scan(layer, x, params["blocks"])
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params, x[:, -1:, :]).astype(jnp.float32)
    pad = cache_size - S
    if pad >= 0:
        kc = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    else:  # sliding-window ring buffer smaller than the prompt
        kc = jax.vmap(lambda kv: _ring_align(kv, cache_size))(ks)
        vc = jax.vmap(lambda kv: _ring_align(kv, cache_size))(vs)
    cache = {"k": kc, "v": vc, "len": jnp.full((B,), S, jnp.int32)}
    return cache, logits


def decoder_decode_step(cfg, params, cache, batch):
    """batch: token [B] int32, pos [B] int32 (absolute position of the new
    token).  Ring-buffer semantics when cache_size < max position."""
    token, pos = batch["token"], batch["pos"]
    B = token.shape[0]
    W = cache["k"].shape[2]  # cache slots
    x = embed_tokens(cfg, params["embed"], token[:, None], pos[:, None])
    slot = pos % W
    cache_len = jnp.minimum(pos + 1, W)

    def layer(x, scanned):
        blk, kc, vc = scanned
        h = apply_norm(cfg, x, blk["ln1"])
        q, k, v = attention_qkv(cfg, h, blk["attn"], pos[:, None])
        kc = kc.at[jnp.arange(B), slot].set(k[:, 0])
        vc = vc.at[jnp.arange(B), slot].set(v[:, 0])
        window = cfg.sliding_window if W > (cfg.sliding_window or W) else None
        att = decode_attention(q, kc, vc, cache_len, window=window)
        x = x + att.reshape(B, 1, -1) @ blk["attn"]["wo"]
        h2 = apply_norm(cfg, x, blk["ln2"])
        if cfg.moe:
            y, _ = moe_lib.moe_apply(cfg, h2, blk["moe"], dropless=True)
        else:
            y = mlp_apply(cfg, h2, blk["mlp"])
        return x + y, (kc, vc)

    x, (kcs, vcs) = jax.lax.scan(layer, x, (params["blocks"], cache["k"], cache["v"]))
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params, x)[:, 0].astype(jnp.float32)
    return logits, {"k": kcs, "v": vcs, "len": cache["len"] + 1}


def decoder_init_cache(cfg, batch_size, cache_size, dtype=jnp.bfloat16):
    shape = (cfg.n_layers, batch_size, cache_size, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "len": jnp.zeros((batch_size,), jnp.int32),
    }


# ===========================================================================
# Encoder-decoder family (whisper)


def init_encdec(cfg, key):
    ks = jax.random.split(key, 6)

    def enc_block(k):
        kk = jax.random.split(k, 2)
        return {
            "ln1": init_norm(cfg, cfg.d_model),
            "attn": init_attention(cfg, kk[0]),
            "ln2": init_norm(cfg, cfg.d_model),
            "mlp": init_mlp(cfg, kk[1], cfg.d_model, cfg.d_ff, bias=True),
        }

    def dec_block(k):
        kk = jax.random.split(k, 3)
        return {
            "ln1": init_norm(cfg, cfg.d_model),
            "self_attn": init_attention(cfg, kk[0]),
            "ln2": init_norm(cfg, cfg.d_model),
            "cross_attn": init_attention(cfg, kk[1]),
            "ln3": init_norm(cfg, cfg.d_model),
            "mlp": init_mlp(cfg, kk[2], cfg.d_model, cfg.d_ff, bias=True),
        }

    return {
        "embed": init_embed(cfg, ks[0]),
        "enc_pos": (jax.random.normal(ks[1], (cfg.encoder_seq, cfg.d_model)) * 0.02).astype(jnp.bfloat16),
        "enc_blocks": _stacked(enc_block, cfg.encoder_layers, ks[2]),
        "enc_norm": init_norm(cfg, cfg.d_model),
        "blocks": _stacked(dec_block, cfg.n_layers, ks[3]),
        "final_norm": init_norm(cfg, cfg.d_model),
    }


def encoder_forward(cfg, params, frames):
    """frames [B, F, d] — precomputed conv-frontend embeddings (stub)."""
    x = frames.astype(jnp.bfloat16) + params["enc_pos"][None, : frames.shape[1], :]
    pos = jnp.arange(x.shape[1])

    def layer(x, blk):
        h = apply_norm(cfg, x, blk["ln1"])
        q, k, v = attention_qkv(cfg, h, blk["attn"], pos)
        att = flash_attention(q, k, v, causal=False, chunk=cfg.attn_chunk)
        x = x + att.reshape(*x.shape[:2], -1) @ blk["attn"]["wo"]
        h2 = apply_norm(cfg, x, blk["ln2"])
        return x + mlp_apply(cfg, h2, blk["mlp"]), None

    x, _ = jax.lax.scan(_maybe_remat(cfg, layer), x, params["enc_blocks"])
    return apply_norm(cfg, x, params["enc_norm"])


def _cross_attention(cfg, x, blk, enc_out):
    h = apply_norm(cfg, x, blk["ln2"])
    B, S, _ = h.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ blk["cross_attn"]["wq"]).reshape(B, S, Hq, Dh)
    k = (enc_out @ blk["cross_attn"]["wk"]).reshape(B, -1, Hkv, Dh)
    v = (enc_out @ blk["cross_attn"]["wv"]).reshape(B, -1, Hkv, Dh)
    att = flash_attention(q, k, v, causal=False, chunk=cfg.attn_chunk)
    return x + att.reshape(B, S, -1) @ blk["cross_attn"]["wo"]


def encdec_forward(cfg, params, batch):
    enc_out = encoder_forward(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.arange(S)[None, :]
    x = embed_tokens(cfg, params["embed"], tokens, positions)
    pos = jnp.arange(S)

    def layer(x, blk):
        h = apply_norm(cfg, x, blk["ln1"])
        q, k, v = attention_qkv(cfg, h, blk["self_attn"], pos)
        att = flash_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
        x = x + att.reshape(B, S, -1) @ blk["self_attn"]["wo"]
        x = _cross_attention(cfg, x, blk, enc_out)
        h2 = apply_norm(cfg, x, blk["ln3"])
        return x + mlp_apply(cfg, h2, blk["mlp"]), None

    x, _ = jax.lax.scan(_maybe_remat(cfg, layer), x, params["blocks"])
    x = apply_norm(cfg, x, params["final_norm"])
    return x, jnp.zeros((), jnp.float32)


def encdec_prefill(cfg, params, batch, cache_size):
    enc_out = encoder_forward(cfg, params, batch["frames"])
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.arange(S)[None, :]
    x = embed_tokens(cfg, params["embed"], tokens, positions)
    pos = jnp.arange(S)
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim

    def layer(x, blk):
        h = apply_norm(cfg, x, blk["ln1"])
        q, k, v = attention_qkv(cfg, h, blk["self_attn"], pos)
        att = flash_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
        x = x + att.reshape(B, S, -1) @ blk["self_attn"]["wo"]
        x = _cross_attention(cfg, x, blk, enc_out)
        ck = (enc_out @ blk["cross_attn"]["wk"]).reshape(B, -1, Hkv, Dh)
        cv = (enc_out @ blk["cross_attn"]["wv"]).reshape(B, -1, Hkv, Dh)
        h2 = apply_norm(cfg, x, blk["ln3"])
        return x + mlp_apply(cfg, h2, blk["mlp"]), (k, v, ck, cv)

    x, (ks, vs, cks, cvs) = jax.lax.scan(layer, x, params["blocks"])
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params, x[:, -1:, :]).astype(jnp.float32)
    pad = cache_size - S
    cache = {
        "k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "ck": cks,
        "cv": cvs,
        "len": jnp.full((B,), S, jnp.int32),
    }
    return cache, logits


def encdec_init_cache(cfg, batch_size, cache_size, dtype=jnp.bfloat16):
    L, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((L, batch_size, cache_size, Hkv, Dh), dtype),
        "v": jnp.zeros((L, batch_size, cache_size, Hkv, Dh), dtype),
        "ck": jnp.zeros((L, batch_size, cfg.encoder_seq, Hkv, Dh), dtype),
        "cv": jnp.zeros((L, batch_size, cfg.encoder_seq, Hkv, Dh), dtype),
        "len": jnp.zeros((batch_size,), jnp.int32),
    }


def encdec_decode_step(cfg, params, cache, batch):
    token, pos = batch["token"], batch["pos"]
    B = token.shape[0]
    W = cache["k"].shape[2]
    x = embed_tokens(cfg, params["embed"], token[:, None], pos[:, None])
    slot = pos % W
    cache_len = jnp.minimum(pos + 1, W)

    def layer(x, scanned):
        blk, kc, vc, ck, cv = scanned
        h = apply_norm(cfg, x, blk["ln1"])
        q, k, v = attention_qkv(cfg, h, blk["self_attn"], pos[:, None])
        kc = kc.at[jnp.arange(B), slot].set(k[:, 0])
        vc = vc.at[jnp.arange(B), slot].set(v[:, 0])
        att = decode_attention(q, kc, vc, cache_len)
        x = x + att.reshape(B, 1, -1) @ blk["self_attn"]["wo"]
        # cross attention over the precomputed encoder KV
        h2 = apply_norm(cfg, x, blk["ln2"])
        Hq, Dh = cfg.n_heads, cfg.head_dim
        cq = (h2 @ blk["cross_attn"]["wq"]).reshape(B, 1, Hq, Dh)
        catt = decode_attention(cq, ck, cv, jnp.full((B,), ck.shape[1], jnp.int32))
        x = x + catt.reshape(B, 1, -1) @ blk["cross_attn"]["wo"]
        h3 = apply_norm(cfg, x, blk["ln3"])
        return x + mlp_apply(cfg, h3, blk["mlp"]), (kc, vc)

    x, (kcs, vcs) = jax.lax.scan(
        layer, x, (params["blocks"], cache["k"], cache["v"], cache["ck"], cache["cv"])
    )
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params, x)[:, 0].astype(jnp.float32)
    return logits, {**cache, "k": kcs, "v": vcs, "len": cache["len"] + 1}


# ===========================================================================
# Hybrid family (RecurrentGemma: groups of rec, rec, local-attn)


def _init_hybrid_sublayer(cfg, key, kind):
    kk = jax.random.split(key, 2)
    mix = (
        rglru_lib.init_rglru(cfg, kk[0])
        if kind == "rec"
        else init_attention(cfg, kk[0])
    )
    return {
        "ln_mix": init_norm(cfg, cfg.d_model),
        "mix": mix,
        "ln_mlp": init_norm(cfg, cfg.d_model),
        "mlp": init_mlp(cfg, kk[1], cfg.d_model, cfg.d_ff),
    }


def init_hybrid(cfg, key):
    ks = jax.random.split(key, 6)
    n_groups = cfg.n_layers // 3
    n_tail = cfg.n_layers % 3  # trailing recurrent layers

    def group(k):
        kk = jax.random.split(k, 3)
        return {
            "rec1": _init_hybrid_sublayer(cfg, kk[0], "rec"),
            "rec2": _init_hybrid_sublayer(cfg, kk[1], "rec"),
            "attn": _init_hybrid_sublayer(cfg, kk[2], "attn"),
        }

    params = {
        "embed": init_embed(cfg, ks[0]),
        "groups": _stacked(group, n_groups, ks[1]),
        "final_norm": init_norm(cfg, cfg.d_model),
    }
    if n_tail:
        params["tail"] = _stacked(
            lambda k: _init_hybrid_sublayer(cfg, k, "rec"), n_tail, ks[2]
        )
    return params


def _hybrid_rec_fwd(cfg, x, sub):
    h = apply_norm(cfg, x, sub["ln_mix"])
    y, _ = rglru_lib.rglru_apply(cfg, h, sub["mix"])
    x = x + y
    h2 = apply_norm(cfg, x, sub["ln_mlp"])
    return x + mlp_apply(cfg, h2, sub["mlp"])


def _hybrid_attn_fwd(cfg, x, sub, pos):
    h = apply_norm(cfg, x, sub["ln_mix"])
    q, k, v = attention_qkv(cfg, h, sub["mix"], pos)
    att = flash_attention(q, k, v, causal=True, chunk=cfg.attn_chunk,
                          window=cfg.local_window)
    x = x + att.reshape(*x.shape[:2], -1) @ sub["mix"]["wo"]
    h2 = apply_norm(cfg, x, sub["ln_mlp"])
    return x + mlp_apply(cfg, h2, sub["mlp"])


def hybrid_forward(cfg, params, batch):
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(cfg, params["embed"], tokens, jnp.arange(S)[None, :])
    pos = jnp.arange(S)

    def group_fwd(x, g):
        x = shard_seq(x)
        x = _hybrid_rec_fwd(cfg, x, g["rec1"])
        x = _hybrid_rec_fwd(cfg, x, g["rec2"])
        x = _hybrid_attn_fwd(cfg, x, g["attn"], pos)
        return x, None

    x, _ = jax.lax.scan(_maybe_remat(cfg, group_fwd), x, params["groups"])
    if "tail" in params:
        def tail_fwd(x, sub):
            return _hybrid_rec_fwd(cfg, x, sub), None
        x, _ = jax.lax.scan(tail_fwd, x, params["tail"])
    x = apply_norm(cfg, x, params["final_norm"])
    return x, jnp.zeros((), jnp.float32)


def _ring_align(kv, W):
    """Last-W window of kv [B,S,...] placed into ring slots (slot = pos % W)."""
    S = kv.shape[1]
    if S < W:
        return jnp.pad(kv, ((0, 0), (0, W - S)) + ((0, 0),) * (kv.ndim - 2))
    last = kv[:, S - W :]
    return jnp.roll(last, S % W, axis=1)


def _hybrid_rec_prefill(cfg, x, sub):
    h = apply_norm(cfg, x, sub["ln_mix"])
    y, st = rglru_lib.rglru_apply(cfg, h, sub["mix"], return_state=True)
    x = x + y
    h2 = apply_norm(cfg, x, sub["ln_mlp"])
    return x + mlp_apply(cfg, h2, sub["mlp"]), st


def hybrid_prefill(cfg, params, batch, cache_size):
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(cfg, params["embed"], tokens, jnp.arange(S)[None, :])
    pos = jnp.arange(S)
    W = min(cache_size, cfg.local_window)

    def group_fwd(x, g):
        x, st1 = _hybrid_rec_prefill(cfg, x, g["rec1"])
        x, st2 = _hybrid_rec_prefill(cfg, x, g["rec2"])
        h = apply_norm(cfg, x, g["attn"]["ln_mix"])
        q, k, v = attention_qkv(cfg, h, g["attn"]["mix"], pos)
        att = flash_attention(q, k, v, causal=True, chunk=cfg.attn_chunk,
                              window=cfg.local_window)
        x = x + att.reshape(B, S, -1) @ g["attn"]["mix"]["wo"]
        hm = apply_norm(cfg, x, g["attn"]["ln_mlp"])
        x = x + mlp_apply(cfg, hm, g["attn"]["mlp"])
        return x, (st1["h"], st1["conv"], st2["h"], st2["conv"],
                   _ring_align(k, W), _ring_align(v, W))

    x, (h1, c1, h2_, c2, ks, vs) = jax.lax.scan(group_fwd, x, params["groups"])
    cache = {
        "h1": h1, "conv1": c1, "h2": h2_, "conv2": c2, "k": ks, "v": vs,
        "len": jnp.full((B,), S, jnp.int32),
    }
    if "tail" in params:
        def tail_fwd(x, sub):
            x, st = _hybrid_rec_prefill(cfg, x, sub)
            return x, (st["h"], st["conv"])
        x, (th, tc) = jax.lax.scan(tail_fwd, x, params["tail"])
        cache["th"], cache["tconv"] = th, tc
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params, x[:, -1:, :]).astype(jnp.float32)
    return cache, logits


def hybrid_init_cache(cfg, batch_size, cache_size, dtype=jnp.bfloat16):
    n_groups = cfg.n_layers // 3
    n_tail = cfg.n_layers % 3
    W = min(cache_size, cfg.local_window)
    dr = cfg.rnn_width
    cache = {
        "h1": jnp.zeros((n_groups, batch_size, dr), jnp.float32),
        "conv1": jnp.zeros((n_groups, batch_size, 3, dr), dtype),
        "h2": jnp.zeros((n_groups, batch_size, dr), jnp.float32),
        "conv2": jnp.zeros((n_groups, batch_size, 3, dr), dtype),
        "k": jnp.zeros((n_groups, batch_size, W, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((n_groups, batch_size, W, cfg.n_kv_heads, cfg.head_dim), dtype),
        "len": jnp.zeros((batch_size,), jnp.int32),
    }
    if n_tail:
        cache["th"] = jnp.zeros((n_tail, batch_size, dr), jnp.float32)
        cache["tconv"] = jnp.zeros((n_tail, batch_size, 3, dr), dtype)
    return cache


def _hybrid_rec_step(cfg, x, sub, h, conv):
    hin = apply_norm(cfg, x, sub["ln_mix"])
    y, st = rglru_lib.rglru_decode_step(cfg, hin, sub["mix"], {"h": h, "conv": conv})
    x = x + y
    h2 = apply_norm(cfg, x, sub["ln_mlp"])
    return x + mlp_apply(cfg, h2, sub["mlp"]), st["h"], st["conv"]


def hybrid_decode_step(cfg, params, cache, batch):
    token, pos = batch["token"], batch["pos"]
    B = token.shape[0]
    W = cache["k"].shape[2]
    x = embed_tokens(cfg, params["embed"], token[:, None], pos[:, None])
    slot = pos % W
    cache_len = jnp.minimum(pos + 1, W)

    def group_step(x, scanned):
        g, h1, c1, h2_, c2, kc, vc = scanned
        x, h1, c1 = _hybrid_rec_step(cfg, x, g["rec1"], h1, c1)
        x, h2_, c2 = _hybrid_rec_step(cfg, x, g["rec2"], h2_, c2)
        h = apply_norm(cfg, x, g["attn"]["ln_mix"])
        q, k, v = attention_qkv(cfg, h, g["attn"]["mix"], pos[:, None])
        kc = kc.at[jnp.arange(B), slot].set(k[:, 0])
        vc = vc.at[jnp.arange(B), slot].set(v[:, 0])
        att = decode_attention(q, kc, vc, cache_len)
        x = x + att.reshape(B, 1, -1) @ g["attn"]["mix"]["wo"]
        hm = apply_norm(cfg, x, g["attn"]["ln_mlp"])
        x = x + mlp_apply(cfg, hm, g["attn"]["mlp"])
        return x, (h1, c1, h2_, c2, kc, vc)

    x, (h1, c1, h2_, c2, kcs, vcs) = jax.lax.scan(
        group_step,
        x,
        (params["groups"], cache["h1"], cache["conv1"], cache["h2"],
         cache["conv2"], cache["k"], cache["v"]),
    )
    new = {**cache, "h1": h1, "conv1": c1, "h2": h2_, "conv2": c2,
           "k": kcs, "v": vcs, "len": cache["len"] + 1}
    if "tail" in params:
        def tail_step(x, scanned):
            sub, th, tc = scanned
            x, th, tc = _hybrid_rec_step(cfg, x, sub, th, tc)
            return x, (th, tc)
        x, (th, tc) = jax.lax.scan(tail_step, x, (params["tail"], cache["th"], cache["tconv"]))
        new["th"], new["tconv"] = th, tc
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params, x)[:, 0].astype(jnp.float32)
    return logits, new


# ===========================================================================
# SSM family (Mamba-2)


def init_ssm_lm(cfg, key):
    ks = jax.random.split(key, 2)

    def block(k):
        return {"ln1": init_norm(cfg, cfg.d_model), "ssm": ssm_lib.init_ssm(cfg, k)}

    return {
        "embed": init_embed(cfg, ks[0]),
        "blocks": _stacked(block, cfg.n_layers, ks[1]),
        "final_norm": init_norm(cfg, cfg.d_model),
    }


def ssm_forward(cfg, params, batch):
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(cfg, params["embed"], tokens, jnp.arange(S)[None, :])

    def layer(x, blk):
        x = shard_seq(x)
        h = apply_norm(cfg, x, blk["ln1"])
        return x + ssm_lib.ssd_apply(cfg, h, blk["ssm"], chunk=cfg.ssd_chunk), None

    x, _ = jax.lax.scan(_maybe_remat(cfg, layer), x, params["blocks"])
    x = apply_norm(cfg, x, params["final_norm"])
    return x, jnp.zeros((), jnp.float32)


def ssm_prefill(cfg, params, batch, cache_size):
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(cfg, params["embed"], tokens, jnp.arange(S)[None, :])

    def layer(x, blk):
        h = apply_norm(cfg, x, blk["ln1"])
        y, st = ssm_lib.ssd_apply(cfg, h, blk["ssm"], chunk=cfg.ssd_chunk,
                                  return_state=True)
        return x + y, (st["ssm"], st["conv"])

    x, (sts, cvs) = jax.lax.scan(layer, x, params["blocks"])
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params, x[:, -1:, :]).astype(jnp.float32)
    cache = {"ssm": sts, "conv": cvs, "len": jnp.full((B,), S, jnp.int32)}
    return cache, logits


def ssm_init_cache(cfg, batch_size, cache_size=0, dtype=jnp.float32):
    st = ssm_lib.ssd_decode_init(cfg, batch_size)
    return {
        "ssm": jnp.zeros((cfg.n_layers,) + st["ssm"].shape, jnp.float32),
        "conv": jnp.zeros((cfg.n_layers,) + st["conv"].shape, jnp.bfloat16),
        "len": jnp.zeros((batch_size,), jnp.int32),
    }


def ssm_decode_step(cfg, params, cache, batch):
    token, pos = batch["token"], batch["pos"]
    B = token.shape[0]
    x = embed_tokens(cfg, params["embed"], token[:, None], pos[:, None])

    def layer(x, scanned):
        blk, st, cv = scanned
        h = apply_norm(cfg, x, blk["ln1"])
        y, ns = ssm_lib.ssd_decode_step(cfg, h, blk["ssm"], {"ssm": st, "conv": cv})
        return x + y, (ns["ssm"], ns["conv"])

    x, (sts, cvs) = jax.lax.scan(layer, x, (params["blocks"], cache["ssm"], cache["conv"]))
    x = apply_norm(cfg, x, params["final_norm"])
    logits = unembed(cfg, params, x)[:, 0].astype(jnp.float32)
    return logits, {"ssm": sts, "conv": cvs, "len": cache["len"] + 1}
