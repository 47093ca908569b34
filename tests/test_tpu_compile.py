"""The paged-attention kernel compiled for a described TPU v5e at olmo-1b
widths (Hq = Hkv = 16, D = 128, bf16, 16-token pages, 2048-page arena).

Nothing runs: the TPU compiler installed beside JAX compiles for a chip that
is described, not attached, and refuses what the chip would refuse — Mosaic
layouts it cannot lower, more scoped VMEM than the kernel may use.  Each
case is an executable shape the serving engine builds: decode (C=1), chunked
prefill (C=16), a speculative chunk (K=8 drafts, C=9) and a compute block of
two pages; and, at each benchmark cell's shapes (olmo-1b; nemotron-4-15b's
eight kv heads of six query heads each), decode and C=16 at the block the
kernel chooses itself, which must fit the scoped VMEM.

The fused serving step, compiled the same way with a small depth and arena
(4 layers, 512 pages), must keep the KV arena whole through the layer loop.
Held as the loop's carry, the arena is updated in place; scanned as the
loop's input and output, it would be sliced out a layer at a time,
restacked, and copied whole into the donated output, every step.  The guard
reads that off the compiled program: its temporaries stay below one layer's
K slice, and no ``copy`` or ``dynamic-slice`` produces a buffer of the
arena's shape or of one layer's slice.

nemotron-4-15b's step is compiled the same way at its benchmark
configuration's own sizes (``bench/configs/nemotron-4-15b.stage.json``: 8
layers, six query heads per kv head, half-head rotary, 5120 pages, 16 rows
of 288 block-table slots, a 4096-token prompt buffer): weights, arena and
temporaries fit one v5e, and the arena stays whole there too.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so these compiles stay in this
one file.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import (
    BLOCK_POSITIONS, VMEM_BUDGET, block_vmem_bytes, compute_block_pages,
    paged_attention_pallas)

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
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    try:
        yield topo
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


#: (kv heads, query heads per kv head, block-table slots, arena pages) of
#: each benchmark cell's kernel call: olmo-1b's 32 rows of 160 slots over a
#: 2560-page arena, nemotron-4-15b's 16 rows of 288 over 5120; each
#: arena is flattened over the layers, as the fused step hands it over
CELL_SHAPES = {"olmo-1b": (16, 1, 160, 16 * 2560, 32),
               "nemotron-4-15b": (8, 6, 288, 8 * 5120, 16)}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
@pytest.mark.parametrize("chunk", [1, 16], ids=["decode", "chunk16"])
def test_chosen_block_compiles_at_cell_shapes(one_chip, cell, chunk):
    """The kernel at its own block (``pages_per_compute_block=None``) for
    each cell's shapes: one lane width of keys a grid step, and it fits the
    scoped VMEM (the compile refuses a kernel that does not)."""
    hkv, g, slots, arena, batch = CELL_SHAPES[cell]
    assert compute_block_pages(PAGE, slots, hkv, g * chunk,
                               HEAD_DIM) * PAGE >= BLOCK_POSITIONS

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hq = hkv * g
    q_shape = ((batch, hq, HEAD_DIM) if chunk == 1
               else (batch, chunk, hq, HEAD_DIM))
    pages = sds((arena, PAGE, hkv, HEAD_DIM), jnp.bfloat16)
    chunk_lens = None if chunk == 1 else sds((batch,), jnp.int32)

    def attend(q, k, v, bt, lengths, cl):
        return paged_attention_pallas(
            q, k, v, bt, lengths, page_size=PAGE, n_kv_heads=hkv,
            interpret=False, chunk_lens=cl)

    compiled = jax.jit(attend).lower(
        sds(q_shape, jnp.bfloat16), pages, pages,
        sds((batch, slots), jnp.int32), sds((batch,), jnp.int32),
        chunk_lens).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_compute_block_pages_rule():
    """A lane width of keys at 16-token pages (8 pages), capped at the
    table's slots, one page where pages are a lane wide already, and halved
    under the VMEM budget for many query rows per kv head."""
    assert compute_block_pages(16, 288, 8, 96, 128) == 8
    assert compute_block_pages(16, 160, 16, 1, 128) == 8
    assert compute_block_pages(16, 5, 16, 16, 128) == 5
    assert compute_block_pages(128, 64, 8, 6, 128) == 1
    # 16 kv heads of 8 x 16 query rows: 8 pages pass the budget, 4 do not
    assert block_vmem_bytes(8, 16, 16, 128, 128) > VMEM_BUDGET
    assert block_vmem_bytes(4, 16, 16, 128, 128) <= VMEM_BUDGET
    assert compute_block_pages(16, 288, 16, 128, 128) == 4
    # no block fits 8 x 64 rows: one page, the least there is
    assert compute_block_pages(16, 288, 8, 8 * 64, 128) == 1


LAYERS, ARENA_PAGES, PROMPT_CAP = 4, 512, 64


def _big_copies(hlo: str, shapes) -> list[str]:
    """Instructions of ``hlo`` that are a ``copy`` or ``dynamic-slice`` with a
    bf16 result of one of ``shapes``."""
    dims = "|".join(",".join(map(str, s)) for s in shapes)
    pat = re.compile(r"=\s*bf16\[(?:" + dims + r")\]\S*\s+"
                     r"(?:copy|dynamic-slice)\(")
    return [ln.strip() for ln in hlo.splitlines() if pat.search(ln)]


@pytest.mark.parametrize("chunk", [1, 16], ids=["decode", "chunk16"])
def test_fused_step_keeps_arena_whole(one_chip, chunk):
    from repro.configs import get_config
    from repro.core.pagepool import pool_init
    from repro.models import build_model
    from repro.serving.paged_decode import fused_decode_step

    cfg = dataclasses.replace(get_config("olmo-1b"), n_layers=LAYERS)
    hkv, hd = cfg.n_kv_heads, cfg.head_dim

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        build_model(cfg).init, jax.random.PRNGKey(0)))
    arena = (LAYERS, ARENA_PAGES, PAGE, hkv, hd)
    kv = sds(arena, jnp.bfloat16)
    pool = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                        jax.eval_shape(lambda: pool_init(ARENA_PAGES)))
    i32 = jnp.int32
    compiled = fused_decode_step.lower(
        params, {"k": kv, "v": kv}, pool, sds((BATCH, MAX_PAGES), i32),
        sds((BATCH, MAX_PAGES), jnp.uint32), sds((BATCH,), i32),
        sds((BATCH,), i32), sds((BATCH,), bool),
        sds((BATCH, PROMPT_CAP), i32), sds((BATCH,), i32),
        sds((2,), jnp.uint32), sds((), jnp.float32), sds((), i32), None,
        None, cfg=cfg, impl="pallas", greedy=True,
        chunk_size=chunk).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo

    layer_slice = (ARENA_PAGES, PAGE, hkv, hd)
    slice_bytes = ARENA_PAGES * PAGE * hkv * hd * 2  # one layer's K: 32 MiB
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < slice_bytes, f"temporaries {temp / 2**20:.1f} MiB"
    big = _big_copies(hlo, [arena, (LAYERS * ARENA_PAGES, PAGE, hkv, hd),
                            layer_slice, (1, *layer_slice)])
    assert not big, "\n".join(big[:6])


#: what one v5e chip may hold: 16 GB, less what the runtime keeps
CHIP_BYTES = 14.5 * 2**30
NEMOTRON = (pathlib.Path(__file__).resolve().parents[1] / "bench" / "configs"
            / "nemotron-4-15b.stage.json")
#: the batch mix's longest request: prompt cap and output cap
NEMOTRON_PROMPT, NEMOTRON_NEW = 4096, 512


@pytest.mark.parametrize("chunk", [1, 16], ids=["decode", "chunk16"])
def test_nemotron_step_fits_and_keeps_arena_whole(one_chip, chunk):
    from repro.configs.base import ArchConfig
    from repro.core.pagepool import pool_init
    from repro.models import build_model
    from repro.serving import required_pages_per_seq
    from repro.serving.paged_decode import fused_decode_step

    spec = json.loads(NEMOTRON.read_text())
    cfg = ArchConfig(**spec["model"])
    eng = spec["engine"]
    assert cfg.n_heads // cfg.n_kv_heads == 6 and cfg.rope_dim == 64
    B, P, ps = eng["max_batch"], eng["num_pages"], eng["page_size"]
    M = required_pages_per_seq(NEMOTRON_PROMPT, NEMOTRON_NEW, ps)
    L, hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        build_model(cfg).init, jax.random.PRNGKey(0)))
    arena = (L, P, ps, hkv, hd)
    kv = sds(arena, jnp.bfloat16)
    pool = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                        jax.eval_shape(lambda: pool_init(P)))
    i32 = jnp.int32
    compiled = fused_decode_step.lower(
        params, {"k": kv, "v": kv}, pool, sds((B, M), i32),
        sds((B, M), jnp.uint32), sds((B,), i32), sds((B,), i32),
        sds((B,), bool), sds((B, NEMOTRON_PROMPT), i32), sds((B,), i32),
        sds((2,), jnp.uint32), sds((), jnp.float32), sds((), i32), None,
        None, cfg=cfg, impl="pallas", greedy=True,
        chunk_size=chunk).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"nemotron-4-15b C={chunk}: arguments {m.argument_size_in_bytes} "
          f"temporaries {m.temp_size_in_bytes} bytes")
    assert total < CHIP_BYTES, f"{total / 2**30:.2f} GiB"
    layer_slice = (P, ps, hkv, hd)
    big = _big_copies(hlo, [arena, (L * P, ps, hkv, hd), layer_slice,
                            (1, *layer_slice)])
    assert not big, "\n".join(big[:6])
