"""``chip_smoke.py`` rehearsed on the CPU, and the platform choice of the
attention implementation.

The smoke's phase functions take their sizes and attention implementation
as arguments, so these tests drive the same code paths the chip runs at a
toy size, with the Pallas kernel in interpret mode.  The four-chip phase
runs on four virtual CPU devices in a subprocess (the main test process
must keep seeing one device).
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.configs import get_config, reduced
from repro.kernels.ops import resolve_attn_impl
from repro.models import build_model

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    num_pages=64, page_size=4, max_batch=4, prefill_chunk=4, requests=4,
    prompt_min=8, prompt_max=16, max_new=4, shared_prefix=8, spec_k=2,
    parity_after=3, fleet_prompt=8, fleet_max_new=4)


@pytest.fixture(scope="module")
def model():
    cfg = reduced(get_config("olmo-1b"))
    return cfg, build_model(cfg).init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("impl,platform,want", [
    (None, "tpu", "pallas"),
    (None, "cpu", "ref"),
    (None, "gpu", "ref"),
    ("ref", "tpu", "ref"),
    ("pallas", "tpu", "pallas"),
    ("interpret", "cpu", "interpret"),
])
def test_attn_impl_follows_platform(impl, platform, want):
    assert resolve_attn_impl(impl, platform) == want


@pytest.mark.parametrize("impl", ["interpret", "flash"])
def test_attn_impl_refuses_interpret_on_tpu_and_unknown_names(impl):
    with pytest.raises(ValueError):
        resolve_attn_impl(impl, "tpu")


def test_engine_on_cpu_defaults_to_reference(model):
    cfg, params = model
    from repro.serving import PagedServingEngine
    eng = PagedServingEngine(cfg, params, num_pages=8, page_size=4,
                             max_batch=2)
    assert eng.runner.attn_impl == "ref"


@pytest.mark.parametrize("phase", ["mixed", "prefix", "speculative"])
def test_smoke_phase_runs_at_toy_size(model, phase):
    cfg, params = model
    fn = getattr(chip_smoke, f"phase_{phase}")
    rep = fn(cfg, params, TINY, attn_impl="interpret")
    assert rep["steps"] > 0 and rep["tokens"] > 0
    if phase == "mixed":
        assert rep["attn_impl"] == "interpret"
        assert 0 < rep["chunked_steps"] < rep["steps"]
        assert max(rep["parity_max_abs_err"].values()) <= chip_smoke.ATTN_TOL
    if phase == "speculative":
        assert rep["spec_steps"] > 0


_FOUR_CHIPS = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import jax
import chip_smoke
from repro.configs import get_config, reduced
from repro.models import build_model
cfg = reduced(get_config("olmo-1b"))
params = build_model(cfg).init(jax.random.PRNGKey(0))
s = chip_smoke.Sizes(**json.loads(sys.argv[2]))
out = chip_smoke.phase_four_chips(cfg, params, s, jax.devices(),
                                  attn_impl="interpret")
print("RESULT " + json.dumps(out))
"""


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run(
        [sys.executable, "-c", _FOUR_CHIPS, str(ROOT),
         json.dumps(dataclasses.asdict(TINY))],
        capture_output=True, text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    line = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT ")]
    out = json.loads(line[-1][len("RESULT "):])
    assert out["fleet4"]["tokens_same"] == TINY.requests
    assert out["fleet4"]["devices_used"] == 4
    kv_err = out["tp4"]["kv_rel_l2"]
    assert len(kv_err) == 2  # one entry per layer of the reduced model
    assert kv_err[0] <= chip_smoke.TP_LAYER0_TOL
    assert max(kv_err) <= chip_smoke.TP_REL_TOL


def test_smoke_without_a_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         cwd=str(ROOT), timeout=300)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert '"ok"' not in res.stdout
