"""Sharding rules + dry-run machinery on a small forced-device mesh.

Runs in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main test process keeps seeing 1 device.
"""

import json
import os
import subprocess
import sys

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, input_specs, SHAPES
from repro.models import build_model
from repro.sharding import rules


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_param_specs_cover_all_archs():
    mesh = _FakeMesh({"data": 16, "model": 16})
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        model = build_model(cfg)
        params = jax.eval_shape(lambda m=model: m.init(jax.random.PRNGKey(0)))
        specs = rules.param_specs(cfg, params, mesh)
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for leaf, spec in zip(flat_p, flat_s):
            assert len(spec) <= len(leaf.shape)
            for dim, part in zip(leaf.shape, tuple(spec) + (None,) * 9):
                if part == "model":
                    assert dim % 16 == 0, (arch, leaf.shape, spec)
                elif isinstance(part, tuple):
                    n = 1
                    for a in part:
                        n *= mesh.shape[a]
                    assert dim % n == 0, (arch, leaf.shape, spec)


def test_opt_specs_add_data_sharding():
    mesh = _FakeMesh({"data": 16, "model": 16})
    cfg = get_config("qwen2-72b")
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    ospecs = rules.opt_specs(cfg, params, mesh)
    assert set(ospecs.keys()) == {"m", "v", "step"}
    # at least the big moments carry a data axis (ZeRO-1)
    flat = jax.tree.leaves(ospecs["m"], is_leaf=lambda x: isinstance(x, P))
    has_data = sum(
        any(p == ("data",) or p == "data" or (isinstance(p, tuple) and "data" in p)
            for p in spec)
        for spec in flat)
    assert has_data > len(flat) // 2


def test_batch_specs_divisibility_fallback():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    cfg = get_config("mamba2-780m")
    import jax.numpy as jnp
    b1 = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32)}
    s1 = rules.batch_specs(cfg, b1, mesh)
    assert tuple(s1["tokens"])[0] == ("pod", "data")
    b2 = {"tokens": jax.ShapeDtypeStruct((1, 128), jnp.int32)}
    s2 = rules.batch_specs(cfg, b2, mesh)
    assert tuple(s2["tokens"])[0] is None  # B=1: replicate, don't crash


_SUBPROCESS_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
from repro.configs import get_config, reduced, SHAPES, input_specs, decode_cache_size
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_smoke_mesh
from repro.models import build_model
import dataclasses

out = {}
mesh = make_smoke_mesh((2, 4), ("data", "model"))
for arch in ("olmo-1b", "mixtral-8x7b", "mamba2-780m"):
    cfg = reduced(get_config(arch))
    # make reduced dims divide the smoke mesh (model=4)
    model = build_model(cfg)
    for shape_name in ("train_4k", "decode_32k"):
        sh = SHAPES[shape_name]
        sh = dataclasses.replace(sh, seq_len=64, global_batch=4)
        with jax.set_mesh(mesh):
            lowered = lower_cell(cfg, model, sh, mesh)
            compiled = lowered.compile()
        out[f"{arch}/{shape_name}"] = compiled.memory_analysis().temp_size_in_bytes
print(json.dumps(out))
"""


def test_lower_and_compile_on_smoke_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run([sys.executable, "-c", _SUBPROCESS_PROG],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(out) == 6
    assert all(v >= 0 for v in out.values())


def test_tests_see_one_device():
    assert len(jax.devices()) == 1
