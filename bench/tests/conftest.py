"""CPU tests of the benchmark itself: ``pytest bench/tests``.

They run on the CPU at toy sizes (the engine's attention follows the
platform and takes the jnp reference path there).  ``tiny_root`` builds a
stand-in checkout: a ``BENCHMARK.json`` with toy cells of the three traffic
kinds, their configuration and mixes, and a copy of the metric readers,
found by name as the real ones are.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

#: peaks for the CPU runs: any positive numbers do, nothing is timed
CPU_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
#: the toy cells' limit on the widest logit gap: the engine reads 4e-4 to
#: 2e-3 on the seeds of these tests and the float8 control 1e-2 to 2e-2
TINY_GAP_LIMIT = 5e-3

TINY_MODELS = {
    # nemotron's family: GQA (3 query heads per kv head), LayerNorm, relu2,
    # untied output head
    "tiny": dict(name="tiny", family="dense", n_layers=2, d_model=64,
                 n_heads=6, n_kv_heads=2, head_dim=16, d_ff=128, vocab=512,
                 vocab_padded=512, norm_type="layernorm", mlp_type="relu2",
                 tie_embeddings=False, use_rope=True, rope_theta=10000.0,
                 attn_bias=False, remat="none", fsdp=False),
    # olmo's family: MHA, non-parametric LayerNorm, SwiGLU, tied embeddings
    "tinyolmo": dict(name="tinyolmo", family="dense", n_layers=2,
                     d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                     d_ff=128, vocab=512, vocab_padded=512,
                     norm_type="nonparam_ln", mlp_type="swiglu",
                     tie_embeddings=True, use_rope=True, rope_theta=10000.0,
                     attn_bias=False, remat="none", fsdp=False),
}


def _mixes() -> dict:
    length = lambda mean, cap: {"mean": mean, "cap": cap, "sigma": 0.6,
                                "tail_frac": 0.1, "tail_scale": 4.0}
    return {
        "tinychat": {"kind": "open", "why": "toy", "rate_rps": 8.0,
                     "lead_in_s": 1.0, "shape_seed": 1,
                     "prompt": length(24, 64), "output": length(8, 16)},
        "tinybatch": {"kind": "closed", "why": "toy", "clients": 4,
                      "requests_per_client": 20, "lead_in_s": 1.0,
                      "shape_seed": 2, "prompt": length(24, 64),
                      "output": length(8, 16)},
        "tinyagent": {"kind": "sessions", "why": "toy", "rate_sps": 2.0,
                      "lead_in_s": 1.0, "shape_seed": 3, "turns": 3,
                      "think_mean_s": 0.2,
                      "system_prompts": [{"tokens": 64, "weight": 1.0}],
                      "message": length(8, 16), "output": length(4, 8),
                      "engine": {"max_batch": 4}},
    }


def make_root(path: pathlib.Path) -> pathlib.Path:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [("tiny.chat", "tiny", "tinychat"),
             ("tiny.batch", "tiny", "tinybatch"),
             ("tiny.agent", "tiny", "tinyagent"),
             ("tinyolmo.chat", "tinyolmo", "tinychat")]
    spec["configs"] = [{"name": n, "source": "toy",
                        "file": f"bench/configs/{n}.json", "reduced": [],
                        "why": "toy"} for n in TINY_MODELS]
    spec["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                          "why": "toy"} for n, c, t in cells]
    serve = ["tiny.chat", "tiny.agent", "tinyolmo.chat"]
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "workloads" in m:
                m["workloads"] = (["tiny.batch"] if m["name"].endswith(
                    ".batch") else serve)
    (path / "bench" / "configs").mkdir(parents=True)
    (path / "bench" / "traffic").mkdir(parents=True)
    for where in ("metrics", "engines"):
        shutil.copytree(ROOT / "bench" / where, path / "bench" / where)
    for gen in (ROOT / "bench" / "traffic").glob("*.py"):
        shutil.copy(gen, path / "bench" / "traffic")
    (path / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    for name, model in TINY_MODELS.items():
        cfg = {"name": name, "source": "toy", "reference": "dense_decoder",
               "dtype": "bfloat16", "model": model, "norm_eps": 1e-5,
               "reduced": {}, "assumed": {}, "deployment": "toy",
               "engine": {"builder": "paged", "num_pages": 256, "page_size": 8, "max_batch": 4,
                          "prefill_chunk": 4, "prefix_cache": True},
               "correct": {"max_logit_gap": TINY_GAP_LIMIT,
                           "min_tokens": 32}}
        (path / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg, indent=1))
    for name, mix in _mixes().items():
        (path / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    return path


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> pathlib.Path:
    return make_root(tmp_path_factory.mktemp("checkout"))


def run_tiny(root, workload: str, seed: int = 2**31 + 12345, **kw) -> dict:
    import jax

    from bench import run

    return run.run_cell(workload, seed, kw.pop("seconds", 3.0),
                        kw.pop("traced", False), devices=jax.devices(),
                        root=root, peaks=CPU_PEAKS, **kw)
