"""The paged-attention kernel compiled for a described TPU v5e at olmo-1b
widths (Hq = Hkv = 16, D = 128, bf16, 16-token pages, 2048-page arena).

Nothing runs: the TPU compiler installed beside JAX compiles for a chip that
is described, not attached, and refuses what the chip would refuse — Mosaic
layouts it cannot lower, more scoped VMEM than the kernel may use.  Each
case is an executable shape the serving engine builds: decode (C=1), chunked
prefill (C=16), a speculative chunk (K=8 drafts, C=9) and a compute block of
two pages.  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_attention_pallas

HEADS, HEAD_DIM, PAGE, PAGES, BATCH, MAX_PAGES = 16, 128, 16, 2048, 8, 40


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("chunk,ppcb", [(1, 1), (16, 1), (9, 1), (1, 2),
                                        (16, 2)],
                         ids=["decode", "prefill-chunk16", "spec-chunk9",
                              "decode-ppcb2", "prefill-chunk16-ppcb2"])
def test_paged_attention_compiles_for_v5e(one_chip, chunk, ppcb):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q_shape = ((BATCH, HEADS, HEAD_DIM) if chunk == 1
               else (BATCH, chunk, HEADS, HEAD_DIM))
    pages = sds((PAGES, PAGE, HEADS, HEAD_DIM), jnp.bfloat16)
    chunk_lens = None if chunk == 1 else sds((BATCH,), jnp.int32)

    def attend(q, k, v, bt, lengths, cl):
        return paged_attention_pallas(
            q, k, v, bt, lengths, page_size=PAGE, n_kv_heads=HEADS,
            pages_per_compute_block=ppcb, interpret=False, chunk_lens=cl)

    compiled = jax.jit(attend).lower(
        sds(q_shape, jnp.bfloat16), pages, pages,
        sds((BATCH, MAX_PAGES), jnp.int32), sds((BATCH,), jnp.int32),
        chunk_lens).compile()
    assert "tpu_custom_call" in compiled.as_text()
