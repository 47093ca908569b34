"""Paged decode + chunked-prefill step for decoder-LM families.

Same math as ``transformer.decoder_decode_step`` but the KV cache lives in
the versioned page pool: storage [L, P, page, Hkv, D], one block table per
sequence shared by all layers (vLLM layout).  Attention goes through
``repro.kernels.ops.paged_attention`` (Pallas on TPU, oracle on CPU).

Two entry points:

- ``paged_decode_step``: the bare model math — (logits, kv).  Kept for
  benchmarking the pre-fusion hot path and for callers that want logits.
- ``fused_decode_step``: the serving hot path, generalized over a **chunk
  axis**.  Page growth (batched pool alloc, now multi-page per row),
  next-token routing (prompt replay vs. last sample), KV append, attention,
  token selection (greedy or temperature sampling) and the OA
  snapshot/validate protocol all execute in ONE jitted dispatch, so the
  engine's only per-step host transfer is one ``device_get`` of five small
  [B] arrays — not logits [B, vocab] plus two version arrays.  This is the
  paper's amortization argument applied to the decode loop: the version
  check is cheap because it is batched and fused with the read it guards.

Chunked prefill (``chunk_size=C > 1``) applies the same argument along the
sequence axis: a row still replaying its prompt consumes up to C tokens per
dispatch — ONE grant covering every page the chunk touches (a C-token chunk
can straddle up to ``1 + ceil((C-1)/page_size)`` pages), ONE KV append for
all C positions, ONE attention pass with an in-chunk causal mask, and ONE
version validation — where the token-at-a-time path burned C full
dispatches and C validations.  Rows decode (1 token) and prefill (C tokens)
in the SAME step: ``chunk_budget`` (a traced scalar — no recompile) caps
the per-row chunk so the engine's scheduler can hold a Sarathi-style token
budget across mixed batches, and each row's live token count ``n_new`` is
computed on device from ``lengths``/``prompt_len``.  A row samples a next
token only when its chunk reaches the final prompt token (or it is already
decoding); rows finishing mid-chunk simply advance ``lengths`` by their
chunk length.

The pool is superblock-structured (``core/pagepool.py``): the batched grant
is a one-pass segmented pop that prefers PARTIAL superblocks and never
touches UNMAPPED (physically released) ones — the anchor walk happens
inside the same fused dispatch, so the anti-fragmentation and release
machinery costs the hot path zero extra host syncs.  Multi-page grants are
all-or-nothing per row (the allocator's prefix satisfaction): a starved row
keeps zero of its requested pages, its appends are masked, and the engine
sees ``grant_info == -1``.

Copy-on-write for shared prefix pages (the refcount layer, hot-path side):
a row whose next write lands in a page with refcount > 1 — a page it
shares with other requests and/or the engine's prefix cache — must not
write in place.  Only the FIRST page a chunk writes can be shared (pages
past the row's committed length are always unmapped), so the fused step
allocates the COW copy in the SAME batched grant that serves chunk growth,
copies the shared page's KV into it (a batched gather/scatter over the
arena, still inside the one dispatch), repoints the row's block table at
the copy and drops the row's reference on the original (``unshare``: no
version bump while other holders remain).  The engine learns what happened
from the per-row ``grant_info``/``cow`` fields in the step's single
``device_get``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec

from repro.core import pagepool as pp
from repro.kernels.ops import paged_attention, speculative_accept
from repro.models.layers import apply_norm, attention_qkv, mlp_apply
from repro.models.transformer import embed_tokens, unembed
from repro.sharding import rules


def kv_storage_init(cfg, num_pages: int, page_size: int, dtype=jnp.bfloat16,
                    mesh=None):
    """The persistent all-layer KV arena [L, P, page, Hkv, D] (palloc: pages
    stay addressable forever; stale reads validate, never fault).

    With ``mesh`` the arena is laid out by the paged-cache rule
    (``sharding.rules.cache_specs(paged=True)``): the KV-HEAD axis shards
    over 'model' so each shard holds ``Hkv/tp`` heads of every page — the
    pool's page ids stay meaningful on every shard.
    """
    shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    kv = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if mesh is not None:
        specs = rules.cache_specs(cfg, kv, mesh, paged=True)
        kv = jax.device_put(kv, rules.to_named(specs, mesh))
    return kv


def _tp_pin(mesh, kv, rest):
    """Pin the fused step's output layout under tensor parallelism: the KV
    arena keeps its head-sharded layout, everything else (pool anchors,
    block tables, snapshots, per-row results) stays replicated.  Explicit
    constraints — rather than trusting GSPMD propagation — keep the donated
    input/output layouts identical step over step (no silent re-layout, no
    doubled arena memory)."""
    rep = NamedSharding(mesh, PartitionSpec())
    tp = mesh.shape["model"]
    kv_spec = [None] * 5
    if kv["k"].shape[3] % tp == 0:
        kv_spec[3] = "model"
    kv_sh = NamedSharding(mesh, PartitionSpec(*kv_spec))
    kv = {n: jax.lax.with_sharding_constraint(a, kv_sh)
          for n, a in kv.items()}
    rest = jax.tree.map(
        lambda a: jax.lax.with_sharding_constraint(a, rep), rest)
    return kv, rest


def max_chunk_pages(chunk_size: int, page_size: int) -> int:
    """Most pages a ``chunk_size``-token append can touch: the chunk's first
    token may land on the last slot of a page, so C tokens straddle at most
    ``1 + ceil((C-1)/page_size)`` pages (== 1 for the decode case C=1)."""
    return 1 + (max(chunk_size, 1) - 1 + page_size - 1) // page_size


def _chunk_core(params, kv, block_tables, lengths, tokens, n_new, *, cfg,
                impl: str = "ref", write_ok=None, mesh=None):
    """Model math for a C-token chunk per row (C = 1 is plain decode).

    tokens [B, C] int32 — chunk inputs; position of tokens[b, j] is
    ``lengths[b] + j``.  n_new [B] int32 (1..C) — live tokens per row; KV
    appends for j >= n_new are masked, and the attention mask gives query j
    the causal horizon of its global position.  Returns (x [B, C, d_model]
    — final-normed hidden states, caller unembeds what it needs — and the
    updated kv).  ``write_ok`` [B] bool masks ALL of a row's appends (the
    starved-grant case: a denied row must not touch the shared page it
    failed to diverge from).

    The layer loop carries the arena whole, flattened to [L*P, page, Hkv, D]
    (merging the two leading axes is a bitcast): layer l's page p is row
    ``l*P + p``, so each layer offsets its page ids instead of slicing its
    [P, page, Hkv, D] out.  As carry it is updated in place; scanned as
    input and output it would be sliced out, restacked and copied back
    every step, three arena-sized copies each of K and V.
    """
    assert cfg.family in ("dense", "moe", "vlm"), "paged decode: decoder LMs only"
    B, C = tokens.shape
    L, P, page_size = kv["k"].shape[:3]
    M = block_tables.shape[1]
    positions = lengths[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    x = embed_tokens(cfg, params["embed"], tokens, positions)

    pos_page = positions // page_size
    slot = positions % page_size
    pages = jnp.take_along_axis(
        block_tables, jnp.minimum(pos_page, M - 1), axis=1)  # [B, C]
    # OOB row of the WHOLE flattened arena -> dropped write (``off + P``
    # would be the next layer's page 0)
    drop = L * P
    wvalid = (jnp.arange(C)[None, :] < n_new[:, None]) & (pages >= 0) \
        & (pos_page < M)
    if write_ok is not None:
        # rows denied this step's page grant must not append: a starved COW
        # row still points at the SHARED page it failed to diverge from, and
        # an in-place write there would corrupt every other holder's KV
        # without any version bump to warn them
        wvalid = wvalid & write_ok[:, None]
    total_len = lengths + n_new
    k_all = kv["k"].reshape(L * P, *kv["k"].shape[2:])
    v_all = kv["v"].reshape(L * P, *kv["v"].shape[2:])

    def layer(carry, scanned):
        x, k_all, v_all = carry  # k_all/v_all [L*P, page, Hkv, D]
        blk, l = scanned
        off = l * P
        with jax.named_scope("layer.kv_write"):
            h = apply_norm(cfg, x, blk["ln1"])
            q, k, v = attention_qkv(cfg, h, blk["attn"], positions)
            pidx = jnp.where(wvalid, pages + off, drop)
            k_all = k_all.at[pidx, slot].set(k, mode="drop")
            v_all = v_all.at[pidx, slot].set(v, mode="drop")
        with jax.named_scope("layer.attention"):
            # unmapped entries stay -1 (never read past the mask)
            bt = jnp.where(block_tables >= 0, block_tables + off, -1)
            att = paged_attention(
                q, {"k": k_all, "v": v_all}, bt, total_len, impl=impl,
                chunk_lens=n_new, mesh=mesh)
            x = x + att.reshape(B, C, -1) @ blk["attn"]["wo"]
        with jax.named_scope("layer.mlp"):
            h2 = apply_norm(cfg, x, blk["ln2"])
            if cfg.moe:
                from repro.models.moe import moe_apply
                # dropless: the serving path must compute the same
                # per-token function regardless of chunk width
                # (decode-parity contract)
                y, _ = moe_apply(cfg, h2, blk["moe"], dropless=True)
            else:
                y = mlp_apply(cfg, h2, blk["mlp"])
            return (x + y, k_all, v_all), None

    (x, k_all, v_all), _ = jax.lax.scan(
        layer, (x, k_all, v_all),
        (params["blocks"], jnp.arange(L, dtype=jnp.int32)))
    x = apply_norm(cfg, x, params["final_norm"])
    return x, {"k": k_all.reshape(kv["k"].shape),
               "v": v_all.reshape(kv["v"].shape)}


@functools.partial(jax.jit, static_argnames=("cfg", "impl"), donate_argnums=(1,))
def paged_decode_step(params, kv, block_tables, lengths, tokens, *, cfg,
                      impl: str = "ref"):
    """One token for every sequence.

    kv: {'k','v': [L, P, page, Hkv, D]} (donated, updated in place);
    block_tables [B, max_pages] int32; lengths [B] int32 (current length —
    the new token lands at position ``lengths``); tokens [B] int32.
    Returns (logits [B, vocab], kv).
    """
    ones = jnp.ones_like(lengths)
    x, kv = _chunk_core(params, kv, block_tables, lengths, tokens[:, None],
                        ones, cfg=cfg, impl=impl)
    logits = unembed(cfg, params, x)[:, 0].astype(jnp.float32)
    return logits, kv


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "impl", "greedy", "chunk_size", "speculative",
                     "mesh"),
    donate_argnums=(1, 2, 3, 4, 5, 6),
)
def fused_decode_step(params, kv, pool, block_tables, snapshot, lengths,
                      last_tok, active, prompt_buf, prompt_len, key,
                      temperature, chunk_budget=1, draft_toks=None,
                      draft_lens=None, *, cfg,
                      impl: str = "ref",
                      greedy: bool = True, chunk_size: int = 1,
                      speculative: bool = False, mesh=None):
    """The sync-free batched step: one dispatch, one host transfer — now
    covering up to ``chunk_size`` prompt tokens per prefilling row.

    Device-resident engine state (all donated, threaded step to step):
      kv            {'k','v': [L, P, page, Hkv, D]} — persistent KV arena
      pool          PagePool — versioned free list (OA warning channel)
      block_tables  [B, max_pages] int32, −1 = unmapped
      snapshot      [B, max_pages] uint32 — versions at last known-valid point
      lengths       [B] int32 — committed tokens per slot
      last_tok      [B] int32 — last sampled token (decode-phase input)
      active        [B] bool — slot occupancy mask (inactive rows frozen)
      prompt_buf    [B, cap] int32 / prompt_len [B] int32 — prompt replay
      key           PRNG key for sampling; temperature [] f32 (greedy=False)
      chunk_budget  [] int32 (traced — no recompile): per-row chunk cap this
                    step, the engine's Sarathi-style token-budget knob;
                    clipped to [1, chunk_size]
      draft_toks    [B, chunk_size−1] int32 (``speculative`` only) — per-row
                    optimistic draft tokens from the host-side drafter
      draft_lens    [B] int32 (``speculative`` only) — live drafts per row
                    (0..chunk_size−1); 0 = the row runs plain decode

    Speculative decoding (``speculative=True``, greedy only): a DECODING
    row's chunk carries its last committed token at slot 0 and up to C−1
    draft tokens after it, so the same chunked append + in-chunk-causal
    attention that serves prefill verifies all drafts in this ONE dispatch.
    The verifier's argmax at slot j is what the model would emit after the
    inputs up to j; an on-device accept scan
    (``repro.kernels.ops.speculative_accept``) finds the longest accepted
    draft prefix and the row commits ``n_acc + 1`` tokens — the accepted
    drafts plus the bonus token the verifier emitted at the accept point.
    Rejected slots' KV writes land past the committed length in pages the
    row already holds: they are simply never committed — the sequence-axis
    twin of the pool's OA discipline, where optimistic work that fails
    validation is discarded, not undone.  Prefilling rows in the same batch
    behave exactly as without speculation (mixed batches are one dispatch).

    Fused pipeline: (1) per-row chunk sizing — ``n_new = min(chunk_budget,
    prompt_len − lengths)`` for prefilling rows, 1 for decoding rows, so a
    mixed batch advances both in the same dispatch; (2) batched multi-page
    growth + copy-on-write — every page the chunk's append range
    ``[lengths, lengths + n_new)`` touches that is still unmapped gets a
    page from ONE prefix-granting batch allocation (per-row counts up to
    ``max_chunk_pages``), and a row whose first written page is SHARED
    (refcount > 1 — a prompt-prefix page granted by the engine's prefix
    cache) additionally gets a fresh page in the same grant, the shared
    page's KV is copied into it and the row's reference on the original is
    dropped (COW divergence); every granted page's version is folded into
    the snapshot; (3) input routing — prompt tokens while ``lengths <
    prompt_len``, else the previous sample; (4) model math (chunked KV
    append + chunked paged attention with the in-chunk causal mask);
    (5) on-device token selection from the chunk's LAST live position —
    meaningful only for rows whose chunk reaches the final prompt token
    (``samples``), which is every decoding row and exactly the prefilling
    rows completing this step; (6) ONE fused OA validation against the
    persistent snapshot covering all ``n_new`` tokens.  Rows fail
    validation if a page they read was reclaimed since its snapshot
    (version bump) or if their grant was starved; only valid rows advance
    ``lengths``/``last_tok``.

    Returns (kv, pool, block_tables, snapshot, lengths, last_tok,
    tokens [B] int32, valid [B] bool, grant_info [B] int32, cow [B] bool,
    adv [B] int32, n_acc [B] int32).  The engine does a single
    ``device_get`` of the last six.  ``n_acc`` is the accepted-draft count
    (always 0 without ``speculative``).  ``grant_info`` is the number of
    fresh pages granted to the row
    this step (0..max_chunk_pages), or −1 when the row needed pages but the
    pool is dry (the row is starved — it did not advance and the scheduler
    must reclaim/remap before it can; grants are all-or-nothing per row).
    ``cow`` flags rows whose first grant was a COW copy of a shared page
    (refcount handoff — the copy replaces, not extends, the row's
    footprint).  ``adv`` is how many tokens the row actually committed
    (0 for invalid rows; ``n_new`` for prefilling rows, ``n_acc + 1`` for
    speculative decode rows).
    """
    if speculative and not greedy:
        raise ValueError(
            "speculative=True requires greedy decoding: the accept scan "
            "compares the verifier's argmax, and lossless rejection "
            "sampling for temperature > 0 is not implemented")
    B = block_tables.shape[0]
    M = block_tables.shape[1]
    page_size = kv["k"].shape[2]
    num_pages = kv["k"].shape[1]
    C = max(int(chunk_size), 1)
    MG = max_chunk_pages(C, page_size)

    # (1) per-row chunk sizing (device-side: no host knowledge of lengths).
    # With speculation a DECODING row's chunk holds 1 + dlens tokens: its
    # last committed token plus the drafts to verify.
    with jax.named_scope("step.inputs"):
        budget = jnp.clip(jnp.asarray(chunk_budget, jnp.int32), 1, C)
        prefilling = lengths < prompt_len
        if speculative:
            dlens = jnp.where(active & ~prefilling,
                              jnp.clip(draft_lens, 0, C - 1),
                              0).astype(jnp.int32)
            decode_n = 1 + dlens
        else:
            decode_n = 1
        n_new = jnp.where(active & prefilling,
                          jnp.minimum(budget, prompt_len - lengths),
                          decode_n).astype(jnp.int32)

    # (2) batched multi-page growth + COW — one fused alloc_pages_batch for
    # every page the batch's chunks touch
    with jax.named_scope("step.grant"):
        rows = jnp.arange(B)
        p0 = lengths // page_size
        plast = (lengths + n_new - 1) // page_size
        koff = jnp.arange(MG, dtype=jnp.int32)
        pis = p0[:, None] + koff[None, :]  # [B, MG] candidate page slots
        in_range = (pis <= plast[:, None]) & (pis < M)
        cur = jnp.take_along_axis(block_tables, jnp.minimum(pis, M - 1),
                                  axis=1)
        cur0 = cur[:, 0]
        rc0 = pool.page_refcount[jnp.clip(cur0, 0, num_pages - 1)]
        # the chunk's FIRST written page is the only one that can be mapped
        # yet shared (pages past the committed length are unmapped): diverge
        # onto a private copy before the KV append below can touch it
        need_copy = active & (cur0 >= 0) & (rc0 > 1)
        need_slot = in_range & (cur < 0) & active[:, None]
        need_slot = need_slot | (need_copy[:, None] & (koff == 0)[None, :])
        need = jnp.sum(need_slot, axis=1).astype(jnp.int32)
        pool, grants, _ = pp._alloc_pages_batch_impl(pool, need, MG)
        # pack each row's grants onto its needing slots, in page order
        gidx = jnp.cumsum(need_slot, axis=1) - 1
        g = jnp.take_along_axis(grants, jnp.clip(gidx, 0, MG - 1), axis=1)
        g = jnp.where(need_slot, g, -1).astype(jnp.int32)
        grant_n = jnp.sum((g >= 0).astype(jnp.int32), axis=1)
        grant_ok = (need == 0) | (grant_n == need)  # all-or-nothing per row
        # COW: copy the shared page's KV into the fresh copy (whole-page
        # gather/scatter across all layers; OOB src/dst rows are dropped)
        cow = need_copy & (g[:, 0] >= 0)
        with jax.named_scope("kv.cow"):
            src = jnp.where(cow, cur0, num_pages)
            dst = jnp.where(cow, g[:, 0], num_pages)
            src_c = jnp.clip(src, 0, num_pages - 1)
            kv = {"k": kv["k"].at[:, dst].set(kv["k"][:, src_c],
                                              mode="drop"),
                  "v": kv["v"].at[:, dst].set(kv["v"][:, src_c],
                                              mode="drop")}
        # ...and drop the row's reference on the original (other holders
        # keep their versions valid; if this was the LAST reference the page
        # frees and its version bumps — correct either way, all in this
        # dispatch)
        pool = pp._unshare_pages_impl(pool, jnp.where(cow, cur0, -1))
        # install the grants and fold their versions into the snapshot
        pis_w = jnp.where(g >= 0, pis, M)  # column M = OOB: dropped scatter
        block_tables = block_tables.at[rows[:, None], pis_w].set(
            g, mode="drop")
        vers = pool.page_version[jnp.clip(g, 0, num_pages - 1)]
        snapshot = snapshot.at[rows[:, None], pis_w].set(
            vers.astype(jnp.uint32), mode="drop")
        grant_info = jnp.where(grant_ok, grant_n, -1).astype(jnp.int32)

    # (3) next input tokens: replay the prompt, then feed back the sample.
    # The position clamp is for DECODE rows' padded lanes (their positions
    # legitimately exceed the buffer — the where() discards them); admission
    # guarantees every real prompt position fits (engine.submit rejects
    # prompts beyond capacity instead of silently clamping).
    with jax.named_scope("step.inputs"):
        cap = prompt_buf.shape[1]
        pos = lengths[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        ppos = jnp.minimum(pos, cap - 1)
        ptok = jnp.take_along_axis(prompt_buf, ppos, axis=1)
        if speculative:
            # decode rows' chunk inputs: last committed token, then drafts
            gen_in = jnp.concatenate([last_tok[:, None], draft_toks], axis=1)
        else:
            gen_in = last_tok[:, None]
        tok_in = jnp.where(pos < prompt_len[:, None], ptok, gen_in)

    # (4) model math (starved rows' appends are masked — see _chunk_core)
    with jax.named_scope("step.layers"):
        x, kv = _chunk_core(
            params, kv, block_tables, lengths, tok_in, n_new, cfg=cfg,
            impl=impl, write_ok=grant_ok, mesh=mesh)

    # (5) on-device token selection.  Plain path: only the chunk's last
    # live position is unembedded — logits never leave the device.
    # Speculative path: EVERY chunk slot is unembedded, the argmax at slot j
    # is the verifier's verdict on draft j+1, and the accept scan turns the
    # per-slot verdicts into a committed prefix length (the sequence-axis
    # validate_and_commit).  The sampled token is the BONUS token from the
    # accept point (for prefilling rows: from the last live slot, as ever).
    with jax.named_scope("step.unembed"):
        if speculative:
            tgt = jnp.argmax(unembed(cfg, params, x).astype(jnp.float32),
                             axis=-1).astype(jnp.int32)  # [B, C]
            n_acc = speculative_accept(tgt, tok_in, dlens)
            sel = jnp.where(prefilling, jnp.clip(n_new - 1, 0, C - 1),
                            n_acc)
            nxt = jnp.take_along_axis(tgt, sel[:, None], axis=1)[:, 0]
            commit_n = jnp.where(prefilling, n_new,
                                 n_acc + 1).astype(jnp.int32)
        else:
            last_idx = jnp.clip(n_new - 1, 0, C - 1)
            xl = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)
            logits = unembed(cfg, params, xl)[:, 0].astype(jnp.float32)
            if greedy:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                nxt = jax.random.categorical(
                    key, logits / jnp.maximum(temperature, 1e-6), axis=-1
                ).astype(jnp.int32)
            n_acc = jnp.zeros_like(lengths)
            commit_n = n_new
        # a row's sample is a real next token only once its chunk reaches
        # the final prompt token (decode rows always; prefilling rows
        # exactly on the step their prompt completes)
        samples = (lengths + n_new) >= prompt_len

    # (6) fused OA validation: one pass over page_version for all C tokens.
    # Speculative rows advance by the ACCEPTED prefix only — the rejected
    # suffix's KV writes sit past the committed length in pages the row
    # already holds, and the next append simply overwrites them.  The pass
    # runs on every step; grant starvation is a separate allocation
    # outcome, folded in beside it.
    with jax.named_scope("step.validate"):
        valid_oa = pp._validate_and_commit_impl(pool, block_tables,
                                                snapshot)[0]
        valid = valid_oa & active & grant_ok
        adv = jnp.where(valid, commit_n, 0).astype(jnp.int32)
        lengths = lengths + adv
        last_tok = jnp.where(valid & samples, nxt, last_tok)
        if mesh is not None and mesh.shape.get("model", 1) > 1:
            # pin the TP layout on the way out: head-sharded arena,
            # replicated everything-else — by construction every shard ran
            # the identical pool/validation math, so the replicated outputs
            # agree bit-for-bit and the engine's single device_get pulls ONE
            # host-visible result
            kv, rest = _tp_pin(
                mesh, kv, (pool, block_tables, snapshot, lengths, last_tok,
                           nxt, valid, grant_info, cow, adv, n_acc))
            (pool, block_tables, snapshot, lengths, last_tok,
             nxt, valid, grant_info, cow, adv, n_acc) = rest
    return (kv, pool, block_tables, snapshot, lengths, last_tok,
            nxt, valid, grant_info, cow, adv, n_acc)
