"""Jit'd public wrappers for the kernels.

``impl`` selection:
- "pallas"    — pl.pallas_call compiled for TPU (the production path)
- "interpret" — same kernel body executed in Python on CPU (correctness)
- "ref"       — the pure-jnp oracle (the serving path on CPU)

:func:`resolve_attn_impl` is the one place the serving stack picks among
them from the platform its devices report.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .paged_attention import (grid_counter, paged_attention_pallas,
                              paged_attention_sharded)
from .ref import paged_attention_chunked_ref, paged_attention_ref


ATTN_IMPLS = ("pallas", "interpret", "ref")


def resolve_attn_impl(impl: str | None, platform: str) -> str:
    """The attention implementation for devices of ``platform``.

    ``None`` follows the platform: the compiled Pallas kernel on a TPU, the
    jnp oracle anywhere else.  An explicit choice is kept, except that
    ``"interpret"`` on a TPU is refused — it would run the kernel body in
    the Python interpreter on the host while the chip waits."""
    if impl is None:
        return "pallas" if platform == "tpu" else "ref"
    if impl not in ATTN_IMPLS:
        raise ValueError(f"unknown attention impl {impl!r}; expected one of "
                         f"{ATTN_IMPLS} or None")
    if impl == "interpret" and platform == "tpu":
        raise ValueError("attn impl 'interpret' runs the Pallas kernel in the "
                         "Python interpreter; on a TPU use 'pallas' (or None)")
    return impl


def paged_attention(q, kv, block_tables, lengths, *, impl: str = "ref",
                    pages_per_compute_block: int | None = None,
                    chunk_lens=None,
                    mesh=None):
    """Decode or chunked-prefill attention over the paged pool.

    q [B, Hq, D] (decode: one query per row) or [B, C, Hq, D] (chunk: C
    queries per row with in-chunk causal masking); kv {'k','v': [P, page,
    Hkv, D]}; block_tables [B, max_pages]; lengths [B] — the TOTAL valid KV
    length per row including any tokens the chunk just appended.
    ``chunk_lens`` [B] int32 is each row's live query count (rows finishing
    mid-chunk, decode rows in a mixed batch); None means every query slot is
    live.  Returns the same rank as q.

    ``pages_per_compute_block`` tiles the Pallas grid: each grid step fetches
    that many KV pages and runs one set of MXU dots over the combined
    (ppcb*page_size, Hkv, D) tile; None (the default) is the kernel's own
    choice from the call's shapes (ignored by the jnp reference).

    ``mesh`` (tensor-parallel serving): the jnp reference needs nothing —
    GSPMD partitions it from the head-sharded arena layout — but a
    ``pallas_call`` has no partitioning rule, so the pallas/interpret impls
    route through ``paged_attention_sharded`` (``shard_map`` per-shard head
    slabs) whenever the KV-head count divides the mesh's 'model' axis.
    """
    if q.ndim == 3:
        # decode form: one query per row, classic ``pos < lengths`` mask —
        # chunk_lens is meaningless here and is dropped in EVERY impl so
        # ref/interpret/pallas can never silently disagree
        chunk_lens = None
    if impl == "ref":
        if q.ndim == 3:
            return paged_attention_ref(q, kv["k"], kv["v"], block_tables,
                                       lengths)
        if chunk_lens is None:
            chunk_lens = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
        return paged_attention_chunked_ref(q, kv["k"], kv["v"], block_tables,
                                           lengths, chunk_lens)
    page_size = kv["k"].shape[1]
    n_kv_heads = kv["k"].shape[2]
    if (mesh is not None and mesh.shape.get("model", 1) > 1
            and n_kv_heads % mesh.shape["model"] == 0):
        return paged_attention_sharded(
            q, kv["k"], kv["v"], block_tables, lengths, mesh=mesh,
            page_size=page_size, n_kv_heads=n_kv_heads,
            pages_per_compute_block=pages_per_compute_block,
            interpret=(impl == "interpret"), chunk_lens=chunk_lens,
        )
    return paged_attention_pallas(
        q, kv["k"], kv["v"], block_tables, lengths,
        page_size=page_size, n_kv_heads=n_kv_heads,
        pages_per_compute_block=pages_per_compute_block,
        interpret=(impl == "interpret"), chunk_lens=chunk_lens,
    )


def attention_grid_counter(*, impl: str, mesh, batch: int, max_pages: int,
                           page_size: int, n_heads: int, n_kv_heads: int,
                           head_dim: int, itemsize: int, n_layers: int):
    """The host-side count of the kernel's grid (``grid_counter``) for
    :func:`paged_attention` calls of an engine's shapes, on the kv heads
    each launch holds (a shard's where it runs per shard); None where
    ``impl`` is the jnp reference, which runs no grid."""
    if impl == "ref":
        return None
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    local = n_kv_heads // tp if n_kv_heads % tp == 0 else n_kv_heads
    return grid_counter(batch=batch, max_pages=max_pages,
                        page_size=page_size, n_kv_heads=local,
                        group=n_heads // n_kv_heads, head_dim=head_dim,
                        itemsize=itemsize, n_layers=n_layers)


def speculative_accept(target_toks, chunk_toks, draft_lens):
    """On-device accept/reject scan for speculative decoding (fused-step
    building block; oracle: ``repro.kernels.ref.speculative_accept_ref``).

    The chunk axis carries a draft: slot 0's input is the row's last
    committed token and slots 1..dlens are optimistic draft tokens.  The
    verifier's greedy argmax at slot j (``target_toks[:, j]``) is what the
    model WOULD emit after the inputs up to slot j — so draft j+1 stands
    exactly when ``target_toks[:, j] == chunk_toks[:, j+1]``.  The longest
    accepted prefix is a cumulative-product scan over that match vector
    (the first mismatch zeroes everything after it), masked to each row's
    live draft count.  This is the sequence-axis version of the pool's
    ``validate_and_commit``: one vectorized validation pass decides how
    much optimistic work commits, and everything past the first failure is
    discarded without ever having blocked the optimistic path.

    target_toks [B, C] int32; chunk_toks [B, C] int32; draft_lens [B] int32
    (0..C−1).  Returns n_acc [B] int32 in [0, draft_lens].
    """
    C = target_toks.shape[1]
    j = jnp.arange(max(C - 1, 1), dtype=jnp.int32)[: C - 1]
    match = (target_toks[:, : C - 1] == chunk_toks[:, 1:]) \
        & (j[None, :] < draft_lens[:, None])
    return jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                   axis=1).astype(jnp.int32)
