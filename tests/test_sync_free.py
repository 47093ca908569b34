"""The sync-free invariant of the serving hot path (PERF.md).

A steady-state decode step must perform at most ONE host transfer — the
single ``device_get`` of ([B] tokens, [B] valid, [B] grant-info).  The pre-PR
engine did O(pages) transfers per step: logits [B, vocab], two version
snapshots, a ``bool(ok)`` per allocated page, plus per-request block-table
re-uploads.  This test instruments every device→host entry point (device_get
and the implicit ArrayImpl conversions np.asarray/bool/int/float trigger)
and counts top-level transfer events across a window of steady-state steps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serving import PagedServingEngine

CFG = reduced(get_config("olmo-1b"))


@pytest.fixture(scope="module")
def params():
    return build_model(CFG).init(jax.random.PRNGKey(0))


class _TransferCounter:
    """Counts top-level host-transfer events.  A reentrancy guard keeps one
    logical transfer (device_get internally invoking __array__, etc.) from
    counting more than once."""

    def __init__(self):
        self.count = 0
        self._inside = False

    def wrap(self, fn):
        def wrapped(*args, **kwargs):
            if self._inside:
                return fn(*args, **kwargs)
            self.count += 1
            self._inside = True
            try:
                return fn(*args, **kwargs)
            finally:
                self._inside = False
        return wrapped


def _instrument(monkeypatch, counter):
    import jax._src.array as jarray

    monkeypatch.setattr(jax, "device_get", counter.wrap(jax.device_get))
    for name in ("__array__", "__bool__", "__int__", "__float__", "__index__"):
        orig = getattr(jarray.ArrayImpl, name, None)
        if orig is not None:
            monkeypatch.setattr(jarray.ArrayImpl, name, counter.wrap(orig))


def test_steady_state_step_is_single_transfer(monkeypatch, params):
    eng = PagedServingEngine(CFG, params, num_pages=32, page_size=4,
                             max_batch=2, max_pages_per_seq=8)
    eng.submit(list(range(1, 5)), 14)
    eng.submit(list(range(2, 6)), 14)
    eng._admit()
    for _ in range(3):  # compile + settle; page growth included
        eng.step()
    counter = _TransferCounter()
    _instrument(monkeypatch, counter)
    nsteps = 6
    for _ in range(nsteps):
        eng.step()  # window crosses a page boundary (growth steps included)
    assert counter.count <= nsteps, (
        f"{counter.count} host transfers across {nsteps} steady-state steps "
        f"(sync-free hot path allows at most 1 per step)")


def test_steady_state_single_transfer_with_prefix_cache(monkeypatch, params):
    """Sharing must not cost the hot path anything: with the prefix cache on
    and a resident prefix being shared, steady-state decode is still one
    transfer per step (matching/sharing happen at admission, donation at
    finish — the allowed sync points)."""
    eng = PagedServingEngine(CFG, params, num_pages=32, page_size=4,
                             max_batch=2, max_pages_per_seq=8,
                             prefix_cache=True)
    r0 = eng.submit(list(range(1, 9)), 4)
    eng.run()  # seed the prefix index
    assert r0.state == "finished"
    eng.submit(list(range(1, 9)) + [11], 14)  # shares the donated prefix
    eng.submit(list(range(1, 9)) + [12], 14)
    eng._admit()
    assert eng.stats.prefix_hits >= 1
    for _ in range(3):
        eng.step()
    counter = _TransferCounter()
    _instrument(monkeypatch, counter)
    nsteps = 6
    for _ in range(nsteps):
        eng.step()
    assert counter.count <= nsteps, (
        f"{counter.count} host transfers across {nsteps} steady-state steps "
        f"with prefix sharing active (allowed at most 1 per step)")


def test_mixed_prefill_decode_step_is_single_transfer(monkeypatch, params):
    """Chunked prefill must not cost the hot path anything either: a step
    whose batch MIXES a decoding row with a chunk-prefilling row (the
    C>1 executable: multi-page grants, chunked KV append, in-chunk causal
    attention, one fused validation) is still one ``device_get`` per step.
    The chunk budget rides a host→device scalar upload, never a download."""
    eng = PagedServingEngine(CFG, params, num_pages=64, page_size=4,
                             max_batch=2, max_pages_per_seq=12,
                             prefill_chunk=4)
    ra = eng.submit(list(range(1, 5)), 30)
    eng._admit()
    for _ in range(6):  # ra finishes its prompt and decodes
        eng.step()
    assert ra.committed >= len(ra.prompt)
    eng.submit(list(range(2, 40)), 8)  # long prompt: prefills in chunks
    eng._admit()
    eng.step()  # compile the mixed (C>1) executable outside the window
    counter = _TransferCounter()
    _instrument(monkeypatch, counter)
    nsteps = 4
    for _ in range(nsteps):
        prefilling = sum(1 for r in eng.running
                         if r.committed < len(r.prompt))
        assert prefilling >= 1, "window must contain prefill work"
        assert len(eng.running) - prefilling >= 1, "and a decoding row"
        eng.step()
    assert counter.count <= nsteps, (
        f"{counter.count} host transfers across {nsteps} mixed "
        f"prefill/decode steps (sync-free hot path allows at most 1 per step)")


def test_speculative_step_is_single_transfer(monkeypatch, params):
    """Speculation must not cost the hot path anything: a drafting step
    commits up to K+1 tokens but is still ONE fused dispatch and ONE
    ``device_get`` — the draft tokens ride a host→device upload and the
    accept/reject scan runs on device, its result landing in the same
    six-array transfer every step already pays."""
    eng = PagedServingEngine(CFG, params, num_pages=64, page_size=4,
                             max_batch=2, max_pages_per_seq=12,
                             speculative_k=4)
    # self-repetitive prompts keep the n-gram drafter proposing every step
    eng.submit([1, 2, 3, 1, 2, 3, 1, 2], 40)
    eng.submit([5, 6, 5, 6, 5, 6], 40)
    eng._admit()
    for _ in range(4):  # prefill + compile both executables, settle AIMD-K
        eng.step()
    assert eng.scheduler.spec_k_cap > 0, "drafting must be live in the window"
    counter = _TransferCounter()
    _instrument(monkeypatch, counter)
    nsteps = 6
    for _ in range(nsteps):
        eng.step()
    assert counter.count <= nsteps, (
        f"{counter.count} host transfers across {nsteps} speculative decode "
        f"steps (sync-free hot path allows at most 1 per step)")
    assert eng.stats.tokens_accepted > 0, "window must contain accepted drafts"


def test_steady_state_single_transfer_with_ladder_engaged(monkeypatch,
                                                          params):
    """Overload response must not cost the hot path anything: with the
    degradation ladder ENGAGED (tiny queue soft limit keeps the pressure
    signal pinned high), every rung is pure host policy — chunk ceiling,
    draft cap, cache eviction, queue shedding all turn knobs the scheduler
    already owns — so steady-state decode is still one ``device_get`` per
    step."""
    from repro.serving import LadderConfig
    eng = PagedServingEngine(CFG, params, num_pages=32, page_size=4,
                             max_batch=2, max_pages_per_seq=8,
                             prefix_cache=True,
                             ladder=LadderConfig(high_water=0.5,
                                                 low_water=0.1,
                                                 engage_after=1,
                                                 release_after=50,
                                                 queue_soft_limit=1))
    eng.submit(list(range(1, 5)), 14)
    eng.submit(list(range(2, 6)), 14)
    # backlog beyond max_batch keeps queue pressure above high_water
    backlog = [eng.submit(list(range(3, 7)), 4, cls="background")
               for _ in range(4)]
    eng._admit()
    for _ in range(4):  # compile + settle; ladder climbs during these
        eng.step()
    assert eng.scheduler.ladder.level >= 1, "ladder must be engaged"
    counter = _TransferCounter()
    _instrument(monkeypatch, counter)
    nsteps = 6
    for _ in range(nsteps):
        eng.step()
    assert counter.count <= nsteps, (
        f"{counter.count} host transfers across {nsteps} steps with the "
        f"degradation ladder engaged (allowed at most 1 per step)")
    assert eng.stats.degradation_level >= 1
    del backlog


_TP_PROG = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serving import PagedServingEngine

CFG = reduced(get_config("olmo-1b"))
params = build_model(CFG).init(jax.random.PRNGKey(0))
eng = PagedServingEngine(CFG, params, num_pages=32, page_size=4,
                         max_batch=2, max_pages_per_seq=8, tensor_parallel=2)
eng.submit(list(range(1, 5)), 14)
eng.submit(list(range(2, 6)), 14)
eng._admit()
for _ in range(3):  # compile + settle
    eng.step()


class Counter:
    def __init__(self):
        self.count = 0
        self._inside = False

    def wrap(self, fn):
        def wrapped(*args, **kwargs):
            if self._inside:
                return fn(*args, **kwargs)
            self.count += 1
            self._inside = True
            try:
                return fn(*args, **kwargs)
            finally:
                self._inside = False
        return wrapped


import jax._src.array as jarray
counter = Counter()
jax.device_get = counter.wrap(jax.device_get)
for name in ("__array__", "__bool__", "__int__", "__float__", "__index__"):
    orig = getattr(jarray.ArrayImpl, name, None)
    if orig is not None:
        setattr(jarray.ArrayImpl, name, counter.wrap(orig))
nsteps = 6
for _ in range(nsteps):
    eng.step()
print(json.dumps({"transfers": counter.count, "nsteps": nsteps,
                  "devices": len(jax.devices())}))
"""


def test_steady_state_single_transfer_under_tensor_parallel():
    """Tensor parallelism must not cost the hot path anything: the fused
    step's outputs are REPLICATED on every shard, so the single
    ``device_get`` of (tokens, valid, grant-info) stays one logical transfer
    even with the weights and KV arena sharded over a 2-device 'model' axis.
    Runs in a subprocess (forced host devices; the main process is pinned to
    1 device by tests/test_sharding.py::test_tests_see_one_device)."""
    import json
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run([sys.executable, "-c", _TP_PROG],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["devices"] == 2
    assert out["transfers"] <= out["nsteps"], (
        f"{out['transfers']} host transfers across {out['nsteps']} "
        f"steady-state TP=2 steps (sync-free hot path allows at most 1 "
        f"per step)")


def test_steady_state_results_still_correct(params):
    """The instrumented path above must not be a different code path: the
    same workload, run normally, matches a per-request dense result."""
    eng = PagedServingEngine(CFG, params, num_pages=32, page_size=4,
                             max_batch=2, max_pages_per_seq=8)
    r1 = eng.submit(list(range(1, 5)), 6)
    r2 = eng.submit(list(range(2, 6)), 6)
    eng.run()
    solo = []
    for prompt in (list(range(1, 5)), list(range(2, 6))):
        e = PagedServingEngine(CFG, params, num_pages=32, page_size=4,
                               max_batch=1, max_pages_per_seq=8)
        r = e.submit(prompt, 6)
        e.run()
        solo.append(r.generated)
    assert r1.generated == solo[0]
    assert r2.generated == solo[1]
