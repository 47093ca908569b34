"""Pallas TPU kernel: batched KV append into the paged pool.

The serving engine's second hot spot: writing one token's K/V for every
running sequence into its block-table-addressed page slot.  The jnp path
(`pagepool.append_kv`) lowers to a scatter that on TPU reads-modifies-writes
whole pages; this kernel DMAs exactly one (n_kv_heads, head_dim) row per
sequence, with the page id and intra-page slot resolved from scalar-prefetch
memory (the pagemap-in-SMEM trick shared with the paged-attention kernel).

Writes go only to scheduler-pinned pages (the hazard-pointer half of OA):
a -1 page id (preempted mid-batch) skips the write entirely rather than
faulting — freed pages must never be written, only read.

Grid: (B,).  Block mapping: the kv page arrays are indexed by the page id
for sequence b; the output aliases the input (in-place page update).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(pages_ref, slots_ref, k_new_ref, v_new_ref, k_ref, v_ref,
            ko_ref, vo_ref, *, page_size: int):
    b = pl.program_id(0)
    slot = slots_ref[b]
    live = pages_ref[b] >= 0

    # copy-through (grid steps own distinct pages; aliasing elides the copy
    # on the real backend, interpret mode needs the explicit assignment)
    ko_ref[...] = k_ref[...]
    vo_ref[...] = v_ref[...]

    @pl.when(live)
    def _write():
        ko_ref[0, slot] = k_new_ref[0]
        vo_ref[0, slot] = v_new_ref[0]


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def kv_append_pallas(kv, block_tables, lengths, k_new, v_new, *,
                     page_size: int, interpret: bool):
    """kv {'k','v': [P, page, Hkv, D]}; block_tables [B, max_pages];
    lengths [B] (new token position); k_new/v_new [B, Hkv, D]."""
    B = lengths.shape[0]
    P, page, Hkv, D = kv["k"].shape
    page_idx = lengths // page_size
    slots = (lengths % page_size).astype(jnp.int32)
    pages = jnp.take_along_axis(block_tables, page_idx[:, None], axis=1)[:, 0]

    def page_map(b, pg, sl):
        return (jnp.maximum(pg[b], 0), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hkv, D), lambda b, pg, sl: (b, 0, 0)),
            pl.BlockSpec((1, Hkv, D), lambda b, pg, sl: (b, 0, 0)),
            pl.BlockSpec((1, page, Hkv, D), page_map),
            pl.BlockSpec((1, page, Hkv, D), page_map),
        ],
        out_specs=[
            pl.BlockSpec((1, page, Hkv, D), page_map),
            pl.BlockSpec((1, page, Hkv, D), page_map),
        ],
    )
    kern = functools.partial(_kernel, page_size=page_size)
    ko, vo = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(kv["k"].shape, kv["k"].dtype),
            jax.ShapeDtypeStruct(kv["v"].shape, kv["v"].dtype),
        ],
        input_output_aliases={4: 0, 5: 1},  # indices include prefetch scalars
        interpret=interpret,
    )(pages, slots, k_new, v_new, kv["k"], kv["v"])
    return {"k": ko, "v": vo}
