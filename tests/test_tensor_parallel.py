"""Tensor-parallel serving: layout rules, TP=1 vs TP=2 parity, memory.

The multi-device cases run in SUBPROCESSES with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so the main test
process keeps seeing 1 device (pinned by ``test_tests_see_one_device``).

What the parity subprocess pins down (the tentpole's correctness claim):

- greedy tokens are IDENTICAL between TP=1 and TP=2 on a mixed workload
  (chunked prefill + decode + speculative drafts + prefix sharing);
- the gathered KV arena contents match (generation at random init is
  nearly input-independent, so token equality alone would not catch a
  misindexed head slab — the arena values do);
- per-device KV bytes exactly halve at TP=2 (the head axis shards) while
  the pool metadata / block tables stay replicated — the paper's split of
  shared metadata vs per-shard payloads.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.models import build_model
from repro.sharding import rules


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def _run_subprocess(prog: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------- layout


def test_cache_specs_dense_vs_paged():
    """The same k/v leaf name takes DIFFERENT rules by layout: dense caches
    [L,B,S,Hkv,Dh] shard the sequence axis, the paged arena [L,P,page,Hkv,Dh]
    shards the KV-head axis (pages must stay whole on every shard so the
    block-table gather is local and pool decisions replicate)."""
    mesh = _FakeMesh({"data": 1, "model": 2})
    cfg = get_config("olmo-1b")
    dense = {"k": jax.ShapeDtypeStruct((2, 4, 8, 4, 16), jnp.bfloat16),
             "v": jax.ShapeDtypeStruct((2, 4, 8, 4, 16), jnp.bfloat16)}
    ds = rules.cache_specs(cfg, dense, mesh)
    assert tuple(ds["k"])[2] == "model" and tuple(ds["k"])[3] is None
    paged = {"k": jax.ShapeDtypeStruct((2, 16, 2, 4, 16), jnp.bfloat16),
             "v": jax.ShapeDtypeStruct((2, 16, 2, 4, 16), jnp.bfloat16)}
    ps = rules.cache_specs(cfg, paged, mesh, paged=True)
    for leaf in ("k", "v"):
        spec = tuple(ps[leaf]) + (None,) * 5
        assert spec[3] == "model", spec
        assert all(spec[i] is None for i in (0, 1, 2, 4)), spec


def test_cache_specs_paged_nondivisible_replicates():
    """Hkv=3 does not divide tp=2: the arena must fall back to full
    replication (never a wrong layout), and the engine keeps working."""
    mesh = _FakeMesh({"data": 1, "model": 2})
    cfg = get_config("olmo-1b")
    paged = {"k": jax.ShapeDtypeStruct((2, 16, 2, 3, 16), jnp.bfloat16),
             "v": jax.ShapeDtypeStruct((2, 16, 2, 3, 16), jnp.bfloat16)}
    ps = rules.cache_specs(cfg, paged, mesh, paged=True)
    for leaf in ("k", "v"):
        assert all(p is None for p in tuple(ps[leaf])), ps[leaf]


def _assert_specs_divisible(cfg, params, mesh):
    flat_p = jax.tree.leaves(params)
    specs = rules.param_specs(cfg, params, mesh, serving=True)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        for dim, part in zip(leaf.shape, tuple(spec) + (None,) * 9):
            if part is None:
                continue
            axes = (part,) if isinstance(part, str) else tuple(part)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert dim % n == 0, (cfg.name, leaf.shape, spec)


@settings(max_examples=40, deadline=None)
@given(arch=st.sampled_from(list(ARCH_IDS)),
       tp=st.sampled_from([1, 2, 3, 4, 5, 6, 7, 8, 12, 16, 48]),
       data=st.sampled_from([1, 2, 3, 8]))
def test_param_specs_never_nondivisible(arch, tp, data):
    """Property: for ANY (arch, mesh shape), param_specs never emits a
    sharded dim the mesh axis product does not divide — fallback to
    replication is the contract, crashing device_put is a bug."""
    mesh = _FakeMesh({"data": data, "model": tp})
    cfg = get_config(arch)
    params = jax.eval_shape(
        lambda c=cfg: build_model(c).init(jax.random.PRNGKey(0)))
    _assert_specs_divisible(cfg, params, mesh)


# ------------------------------------------------------- sharded kernel


_KERNEL_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.kernels.ops import paged_attention
from repro.launch.mesh import make_serving_mesh

rng = np.random.default_rng(7)
B, Hq, Hkv, D, P_, page = 3, 8, 4, 16, 12, 4
kv = {"k": jnp.asarray(rng.standard_normal((P_, page, Hkv, D)), jnp.float32),
      "v": jnp.asarray(rng.standard_normal((P_, page, Hkv, D)), jnp.float32)}
tables = jnp.asarray(rng.permutation(P_)[: B * 3].reshape(B, 3), jnp.int32)
lengths = jnp.asarray([5, 12, 9], jnp.int32)
out = {}
mesh = make_serving_mesh(2)
for qshape in ((B, Hq, D), (B, 2, Hq, D)):
    q = jnp.asarray(rng.standard_normal(qshape), jnp.float32)
    ref = paged_attention(q, kv, tables, lengths, impl="ref")
    got = paged_attention(q, kv, tables, lengths, impl="interpret", mesh=mesh)
    out[f"err_{len(qshape)}d"] = float(jnp.max(jnp.abs(ref - got)))
    out[f"shards_{len(qshape)}d"] = len(got.sharding.device_set)
print(json.dumps(out))
"""


def test_sharded_kernel_matches_ref():
    """``paged_attention_sharded`` (shard_map per-shard head slabs, needed
    because pallas_call has no GSPMD rule) must agree with the jnp oracle in
    both decode [B,Hq,D] and chunk [B,C,Hq,D] forms, and its output must
    actually live on both shards."""
    out = _run_subprocess(_KERNEL_PROG)
    assert out["err_3d"] < 1e-5, out
    assert out["err_4d"] < 1e-5, out
    assert out["shards_3d"] == 2 and out["shards_4d"] == 2, out


# ----------------------------------------------------------- TP parity


_PARITY_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serving import PagedServingEngine

CFG = reduced(get_config("olmo-1b"))
params = build_model(CFG).init(jax.random.PRNGKey(0))
PROMPTS = [[5, 7, 11, 13], [5, 7, 11, 13], [3, 1, 4, 1, 5], [2, 2, 2],
           [9, 8, 7, 6, 5, 4], [1, 2, 3, 1, 2, 3, 1, 2]]


def dev_bytes(tree):
    return sum(
        int(np.prod(l.sharding.shard_shape(l.shape))) * l.dtype.itemsize
        for l in jax.tree.leaves(tree))


def run(tp):
    eng = PagedServingEngine(CFG, params, num_pages=64, page_size=2,
                             max_batch=4, prefix_cache=True, speculative_k=2,
                             prefill_chunk=4, tensor_parallel=tp)
    reqs = [eng.submit(p, 8) for p in PROMPTS]
    eng.run()
    # second wave AFTER the first drained: its donated prefixes are now in
    # the refcounted index, so these admissions take the sharing path
    reqs += [eng.submit([5, 7, 11, 13, 99], 8),
             eng.submit([5, 7, 11, 13, 98], 8)]
    eng.run()
    st = eng.kv_manager.step_state()
    assert all(r.state == "finished" for r in reqs)
    return ([list(r.generated) for r in reqs],
            np.asarray(st.kv["k"], dtype=np.float32),
            np.asarray(st.kv["v"], dtype=np.float32),
            np.asarray(st.lengths), np.asarray(st.block_tables),
            dev_bytes(st.kv), dev_bytes(eng.params), eng, st)


t1, k1, v1, len1, bt1, kvb1, pb1, e1, st1 = run(1)
t2, k2, v2, len2, bt2, kvb2, pb2, e2, st2 = run(2)
out = {
    "tokens_equal": t1 == t2,
    "k_allclose": bool(np.allclose(k1, k2, atol=2e-2, rtol=2e-2)),
    "v_allclose": bool(np.allclose(v1, v2, atol=2e-2, rtol=2e-2)),
    "lengths_equal": bool((len1 == len2).all()),
    "tables_equal": bool((bt1 == bt2).all()),
    "kv_bytes_tp1": kvb1, "kv_bytes_tp2": kvb2,
    "param_bytes_tp1": pb1, "param_bytes_tp2": pb2,
    "kv_spec_tp2": str(st2.kv["k"].sharding.spec),
    "pool_replicated": len(e2.pool.clock.sharding.device_set) == 2
                       and str(e2.pool.clock.sharding.spec)
                       == "PartitionSpec()",
    "prefix_hits": e2.stats.prefix_hits,
    "spec_accepted": e2.stats.tokens_accepted,
}
print(json.dumps(out))
"""


def test_tp2_matches_tp1_token_exact():
    """TP=2 must be a pure layout change: same greedy tokens, same KV arena
    contents (bf16 tolerance for psum reassociation), same lengths and block
    tables, on a workload exercising chunked prefill + speculative decoding
    + prefix sharing simultaneously."""
    out = _run_subprocess(_PARITY_PROG)
    assert out["tokens_equal"], out
    assert out["k_allclose"] and out["v_allclose"], out
    assert out["lengths_equal"] and out["tables_equal"], out
    assert out["prefix_hits"] >= 1, out  # workload truly exercised sharing
    assert out["spec_accepted"] > 0, out  # ... and accepted drafts


def test_tp2_shards_kv_and_weights():
    """Per-device KV bytes halve EXACTLY at TP=2 (head axis shards, page and
    slot axes never do) and per-device weight bytes shrink; pool metadata
    (the OA clock) stays replicated across both shard devices."""
    out = _run_subprocess(_PARITY_PROG)
    assert out["kv_bytes_tp2"] * 2 == out["kv_bytes_tp1"], out
    assert out["param_bytes_tp2"] < out["param_bytes_tp1"], out
    assert "model" in out["kv_spec_tp2"], out
    assert out["pool_replicated"], out


# ------------------------------------------------------------ 2D fleet


_FLEET_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serving import DataParallelEngine

CFG = reduced(get_config("olmo-1b"))
params = build_model(CFG).init(jax.random.PRNGKey(0))
fleet = DataParallelEngine(CFG, params, replicas=2, tensor_parallel=2,
                           num_pages=32, page_size=2, max_batch=4,
                           prefix_cache=True)
rng = np.random.default_rng(1)
reqs = [fleet.submit(list(map(int, rng.integers(1, 500, 5))), 6)
        for _ in range(8)]
fleet.run()
meshes = [e.mesh for e in fleet.replicas]
out = {
    "finished": sum(r.state == "finished" for r in reqs),
    "disjoint": not (set(d.id for d in meshes[0].devices.flat)
                     & set(d.id for d in meshes[1].devices.flat)),
    "mesh_shapes": [dict(m.shape) for m in meshes],
}
print(json.dumps(out))
"""


def test_2d_fleet_replica_times_tensor():
    """replicas=2 x tp=2 on 4 devices: every engine gets its own DISJOINT
    ('data','model') mesh slice and the fleet drains the workload."""
    out = _run_subprocess(_FLEET_PROG)
    assert out["finished"] == 8, out
    assert out["disjoint"], out
    assert all(s == {"data": 1, "model": 2} for s in out["mesh_shapes"]), out


def test_fleet_rejects_insufficient_devices():
    from repro.configs import get_config, reduced
    from repro.serving import DataParallelEngine
    cfg = reduced(get_config("olmo-1b"))
    params = jax.eval_shape(
        lambda: build_model(cfg).init(jax.random.PRNGKey(0)))
    with pytest.raises(RuntimeError, match="devices"):
        DataParallelEngine(cfg, params, replicas=2, tensor_parallel=2,
                           num_pages=16, page_size=2)  # 1 CPU device only


def test_engine_rejects_device_with_tp():
    from repro.serving import PagedServingEngine
    cfg = get_config("olmo-1b")
    with pytest.raises((ValueError, RuntimeError)):
        PagedServingEngine(cfg, {}, num_pages=16, page_size=2,
                           tensor_parallel=2, device=jax.devices()[0])
