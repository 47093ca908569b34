"""Pallas TPU kernel: decode + chunked-prefill attention over the versioned
page pool.

This is the compute hot-spot of the paper's device-side adaptation: the
optimistic reader.  It walks a sequence's block table in compute blocks of
``pages_per_compute_block`` KV pages, DMA'ing each page HBM→VMEM exactly
once and keeping the flash accumulator state (m, l, acc) in VMEM scratch —
the jnp reference path instead materializes the gathered [S, Hkv, D] cache
in HBM (2× traffic on the dominant term of the decode roofline; see
EXPERIMENTS.md §Perf).

The query side carries a **chunk axis**: q is [B, C, Hq, D] where C is the
chunk size (C = 1 is the decode special case — the [B, Hq, D] form is
accepted and squeezed back on return).  A chunked-prefill step appends C
prompt tokens and attends them all in ONE kernel launch; the paper's
amortize-the-validation argument applied along the sequence axis (one
dispatch, one OA validation for C tokens instead of C of each).

TPU mapping:
- grid = (batch, ceil(max_pages / pages_per_compute_block)); the block table
  rides in scalar-prefetch memory (SMEM) so the ``index_map`` can translate
  virtual page slots to physical page ids *before* the DMAs are issued — the
  pagemap lookup of LRMalloc, done by the DMA engine.
- Each grid step assembles a (ppcb*page_size, Hkv, D) KV tile from ``ppcb``
  independently-mapped pages (one BlockSpec per page within the block — the
  pages are scattered in the arena, so each needs its own translation), then
  issues ONE batched MXU dot per kv head for all C queries over the whole
  tile (bf16 operands, f32 accumulation).  Larger
  ``ppcb`` ⇒ fewer grid steps, fewer accumulator round-trips, larger dots —
  the same batching-of-validation amortization OA applies to reclamation.
- **In-chunk causal mask**: ``lengths[b]`` is the row's TOTAL valid KV
  length including this step's appended chunk; ``chunk_lens[b]`` (1..C) is
  how many of the C query slots are live.  Query j sits at global position
  ``lengths - chunk_lens + j`` and sees ``pos < lengths - chunk_lens + j +
  1``; padded slots (j >= chunk_lens — rows finishing mid-chunk, decode
  rows inside a mixed batch) fall back to the full ``pos < lengths`` view,
  staying finite while their output is discarded.
- ``pl.when`` skips the COMPUTE (dots, softmax accumulation, scratch
  round-trips) for blocks that are entirely past ``lengths[b]`` or fully
  unmapped (every table entry < 0).  Note the BlockSpec DMAs are still
  issued for skipped blocks — index_maps run regardless of kernel-body
  predicates — so ragged padding saves FLOPs and accumulator traffic, not
  HBM reads.
- Freed pages remain mapped in the persistent arena, so a stale block table
  entry fetches garbage *safely*; the scheduler's version check discards the
  result (OA semantics — reads validated after the fact).
- Block shapes: the wrapper lays q out head-major, (Hkv, G*C, D) per row,
  and hands the kernel each query row's causal limit as a (G*C, 1) column,
  so every in-kernel array keeps (rows, lanes) in its last two dims and the
  mask needs no vector reshape; D should be a multiple of 128.  At olmo-1b
  widths (Hkv 16, D 128) the chunk form fits v5e's 16 MiB scoped VMEM at
  every C the engine builds (``tests/test_tpu_compile.py``).

Weak spots the sweep tests cover: GQA grouping, ragged lengths mid-page,
unmapped (-1) table entries, page_size not dividing length, max_pages not
divisible by pages_per_compute_block (padded with -1 slots), chunks
straddling page boundaries, and rows finishing mid-chunk
(chunk_lens < C).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    # scalar-prefetch
    block_tables_ref,  # [B, nblocks*ppcb] (SMEM)
    lengths_ref,  # [B] (SMEM) — total valid length incl. the chunk
    # blocked inputs: q, per-row causal limits, ppcb k-page refs, ppcb v-page
    # refs
    q_ref,  # [1, Hkv, R, D] — R = G*C query rows per kv head
    limit_ref,  # [1, R, 1] int32 — each query row sees pos < limit
    *refs,
    page_size: int,
    ppcb: int,
):
    k_refs = refs[:ppcb]  # each [1, page, Hkv, D]
    v_refs = refs[ppcb : 2 * ppcb]
    o_ref = refs[2 * ppcb]  # [1, Hkv, R, D]
    m_ref, l_ref, acc_ref = refs[2 * ppcb + 1 :]  # VMEM scratch, head-major

    b = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)
    span = ppcb * page_size

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-page mapped bits from SMEM as int32 SCALARS — they drive both the
    # skip predicate and the position mask (an unmapped page inside the
    # block contributes nothing).  Kept scalar on purpose: Mosaic cannot
    # reshape a stacked bool vector into a mask.
    mapped = [(block_tables_ref[b, i * ppcb + j] >= 0).astype(jnp.int32)
              for j in range(ppcb)]
    start = i * span
    block_live = (start < lengths_ref[b]) & (functools.reduce(
        jnp.maximum, mapped) > 0)

    @pl.when(block_live)
    def _compute():
        q = q_ref[0]  # [Hkv, R, D]
        k = jnp.concatenate([r[0] for r in k_refs], axis=0)  # [span, Hkv, D]
        v = jnp.concatenate([r[0] for r in v_refs], axis=0)
        R, D = q.shape[1], q.shape[2]
        # one batched MXU dot per kv head over all R query rows; operands
        # stay in their storage dtype, accumulation is f32
        s = jnp.einsum("hrd,phd->hrp", q, k,
                       preferred_element_type=jnp.float32)
        s = s * (1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32)))

        # [R, span] mask: the row's causal horizon (computed by the wrapper)
        # AND the page of each position being mapped
        t = jax.lax.broadcasted_iota(jnp.int32, (R, span), 1)
        page_ok = jnp.zeros((R, span), jnp.int32)
        for j in range(ppcb):
            in_page = (t >= j * page_size) & (t < (j + 1) * page_size)
            page_ok = jnp.where(in_page, mapped[j], page_ok)
        live = ((start + t) < limit_ref[0]) & (page_ok > 0)
        live = live[None]  # [1, R, span], broadcast over kv heads
        s = jnp.where(live, s, -jnp.inf)

        m_prev = m_ref[...]  # [Hkv, R, 1]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(live, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("hrp,phd->hrd", p, v.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "n_kv_heads", "pages_per_compute_block",
                     "interpret"),
)
def paged_attention_pallas(q, k_pages, v_pages, block_tables, lengths, *,
                           page_size: int, n_kv_heads: int,
                           pages_per_compute_block: int = 1,
                           interpret: bool, chunk_lens=None):
    """q [B, Hq, D] (decode) or [B, C, Hq, D] (chunk) -> same shape back.

    ``lengths`` is the total valid KV length per row (including the chunk's
    appended tokens); ``chunk_lens`` [B] int32 gives each row's live query
    count for the in-chunk causal mask (None = every slot live — for the
    decode form that is the classic single-query mask).  ``interpret`` runs
    the kernel body in the Pallas interpreter (CPU tests) instead of
    compiling it with Mosaic.  See the module docstring for layout rules.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, C, Hq, D = q.shape
    Hkv = n_kv_heads
    G = Hq // Hkv
    R = G * C
    if chunk_lens is None:
        chunk_lens = jnp.full((B,), C, jnp.int32)
    ppcb = max(int(pages_per_compute_block), 1)
    max_pages = block_tables.shape[1]
    nblocks = -(-max_pages // ppcb)
    if nblocks * ppcb != max_pages:
        block_tables = jnp.pad(
            block_tables, ((0, 0), (0, nblocks * ppcb - max_pages)),
            constant_values=-1)

    # head-major query rows: row g*C + j of kv head h is query slot j of
    # head h*G + g, so each kv head's dot covers all of its G*C queries
    qt = q.reshape(B, C, Hkv, G, D).transpose(0, 2, 3, 1, 4).reshape(
        B, Hkv, R, D)
    # in-chunk causal horizon per row: query j sits at global position
    # lengths - chunk_lens + j and sees pos < that position + 1; padded
    # slots (j >= chunk_lens) clamp to the full pos < lengths view
    qpos = (lengths - chunk_lens)[:, None] + jnp.arange(C, dtype=jnp.int32)
    limit = jnp.minimum(qpos + 1, lengths[:, None]).astype(jnp.int32)
    limit = jnp.broadcast_to(limit[:, None, :], (B, G, C)).reshape(B, R, 1)

    def page_map(j):
        # each of the block's ppcb pages gets its own virtual→physical
        # translation (they are scattered in the arena)
        def m(b, i, bt, ln):
            return (jnp.maximum(bt[b, i * ppcb + j], 0), 0, 0, 0)
        return m

    kv_spec = lambda j: pl.BlockSpec((1, page_size, Hkv, D), page_map(j))
    row_spec = pl.BlockSpec((1, Hkv, R, D), lambda b, i, bt, ln: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nblocks),
        in_specs=(
            [row_spec,
             pl.BlockSpec((1, R, 1), lambda b, i, bt, ln: (b, 0, 0))]
            + [kv_spec(j) for j in range(ppcb)]
            + [kv_spec(j) for j in range(ppcb)]
        ),
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, D), jnp.float32),
        ],
    )
    kern = functools.partial(_kernel, page_size=page_size, ppcb=ppcb)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, qt, limit,
      *([k_pages] * ppcb), *([v_pages] * ppcb))
    out = out.reshape(B, Hkv, G, C, D).transpose(0, 3, 1, 2, 4).reshape(
        B, C, Hq, D)
    return out[:, 0] if squeeze else out


def paged_attention_sharded(q, k_pages, v_pages, block_tables, lengths, *,
                            mesh, page_size: int, n_kv_heads: int,
                            pages_per_compute_block: int = 1,
                            interpret: bool, chunk_lens=None):
    """Tensor-parallel Pallas dispatch: ``shard_map`` over the mesh's 'model'
    axis, one kernel launch per shard on its LOCAL head slab.

    GSPMD cannot partition a ``pallas_call`` (no partitioning rule), so the
    TP serving path wraps the kernel manually: q shards its ``Hq`` axis and
    the KV arena its ``Hkv`` axis (both kv-head-major, so GQA groups never
    straddle shards — q reshapes to ``[C, Hkv, G, D]`` inside the kernel),
    while block tables / lengths / chunk_lens ride in replicated.  Attention
    is embarrassingly parallel over KV-head groups: no collective here — the
    cross-shard ``psum`` happens at the row-parallel ``wo`` matmul the
    caller runs on the sharded output.  ``n_kv_heads`` is the GLOBAL count;
    it must divide the 'model' axis size (callers fall back to the unsharded
    kernel otherwise).
    """
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["model"]
    if n_kv_heads % tp != 0:
        raise ValueError(f"n_kv_heads={n_kv_heads} not divisible by tp={tp}")
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, C = q.shape[:2]
    if chunk_lens is None:
        chunk_lens = jnp.full((B,), C, jnp.int32)
    heads = P(None, None, "model", None)  # q [B,C,Hq,D] / kv [P,page,Hkv,D]
    rep = P()

    def local(bt, ln, cl, qs, ks, vs):
        return paged_attention_pallas(
            qs, ks, vs, bt, ln, page_size=page_size,
            n_kv_heads=n_kv_heads // tp,
            pages_per_compute_block=pages_per_compute_block,
            interpret=interpret, chunk_lens=cl)

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(rep, rep, rep, heads, heads, heads),
        out_specs=heads, check_vma=False,
    )(block_tables, lengths, chunk_lens, q, k_pages, v_pages)
    return out[:, 0] if squeeze else out
