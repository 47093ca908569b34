"""Each cell's fused step, compiled for a described TPU v5e at the cell's
own sizes: the decode (C=1) and prefill-chunk (C=16) programs, with the
compiled Pallas attention (for nemotron-4-15b that is G = 6 query heads per
kv head), and the memory the chip has to hold for them.

Nothing runs.  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench.traffic import Traffic
from conftest import ROOT

#: what one v5e chip may hold: 16 GB, less what the runtime keeps
CHIP_BYTES = 14.5 * 2**30


#: (configuration, traffic mix): the cells of BENCHMARK.json, and those
#: whose files are here but whose proof on the chip is still to come
CELLS = [("olmo-1b", "chat"), ("nemotron-4-15b", "batch"),
         ("olmo-1b", "agent")]


def test_every_cell_is_rehearsed():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert {(w["config"], w["traffic"]) for w in spec["workloads"]
            if w["chips"] == 1} <= set(CELLS)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _prompt_cap(n: int) -> int:
    cap = 16  # the engine's prompt buffer starts at 16 and doubles
    while cap < n:
        cap *= 2
    return cap


@pytest.mark.parametrize("chunk", [1, 16], ids=["decode", "chunk16"])
@pytest.mark.parametrize("config,mix", CELLS,
                         ids=[f"{c}.{m}" for c, m in CELLS])
def test_fused_step_compiles_and_fits(one_chip, config, mix, chunk):
    from repro.core.pagepool import pool_init
    from repro.models import build_model
    from repro.serving import required_pages_per_seq
    from repro.serving.paged_decode import fused_decode_step

    from bench import common
    from bench.run import arch_config

    cfg = common.load_json(ROOT / "bench" / "configs" / f"{config}.json")
    mixd = common.load_json(ROOT / "bench" / "traffic" / f"{mix}.json")
    eng = {**cfg["engine"], **mixd.get("engine", {})}
    arch = arch_config(cfg["model"])
    tr = Traffic(mixd, 1, 30.0, arch.vocab)
    B, P, ps = eng["max_batch"], eng["num_pages"], eng["page_size"]
    M = required_pages_per_seq(tr.max_prompt_len(), tr.max_new(), ps)
    cap = _prompt_cap(tr.max_prompt_len())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        build_model(arch).init, jax.random.PRNGKey(0)))
    kv = sds((arch.n_layers, P, ps, arch.n_kv_heads, arch.head_dim),
             jnp.bfloat16)
    pool = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                        jax.eval_shape(lambda: pool_init(P)))
    i32 = jnp.int32
    compiled = fused_decode_step.lower(
        params, {"k": kv, "v": kv}, pool, sds((B, M), i32),
        sds((B, M), jnp.uint32), sds((B,), i32), sds((B,), i32),
        sds((B,), bool), sds((B, cap), i32), sds((B,), i32),
        sds((2,), jnp.uint32), sds((), jnp.float32), sds((), i32), None,
        None, sds((), bool), cfg=arch, impl="pallas", greedy=True,
        chunk_size=chunk).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes
    print(f"{config}.{mix} C={chunk}: arguments {m.argument_size_in_bytes} "
          f"temporaries {m.temp_size_in_bytes} bytes")
    assert total < CHIP_BYTES, f"{total / 2**30:.2f} GiB"
