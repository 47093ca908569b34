"""A whole run of a toy cell on the CPU, everything after the look for a
chip: the last line's schema, the float32 reference against the engine, the
control, and the timed path broken in each way a serving cell can be."""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import pytest

from conftest import TINY_GAP_LIMIT, run_tiny


@pytest.fixture(scope="module")
def chat(tiny_root):
    return run_tiny(tiny_root, "tiny.chat", control=True)


def test_last_line_schema(chat):
    line = json.loads(json.dumps(chat))
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "checks"  # the numbers compared come last
    assert line["device"]["platform"] == jax.devices()[0].platform
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {"setup_s", "ttft_p75_s", "itl_p95_ms",
                                    "output_tok_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and math.isfinite(m["value"])
        assert m["value"] > 0
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("cell", ["tiny.chat", "tiny.batch", "tiny.agent",
                                  "tinyolmo.chat"])
def test_engine_agrees_with_reference(tiny_root, chat, cell):
    res = chat if cell == "tiny.chat" else run_tiny(tiny_root, cell)
    gap = res["checks"]["max_logit_gap"]
    assert res["correct"], res["checks"]
    assert gap["value"] <= TINY_GAP_LIMIT
    assert res["checks"]["tokens_compared"]["value"] >= 32


def test_control_fails_the_limit(chat):
    """The float8 control, put through the run's own comparison, comes out
    not correct: it reads above the limit and three times the engine or
    more; the engine reads below the limit."""
    engine = chat["checks"]["max_logit_gap"]["value"]
    control = chat["control"]["checks"]["max_logit_gap"]["value"]
    print(f"engine {engine} control {control}")
    assert chat["correct"] and not chat["control"]["correct"]
    assert control > TINY_GAP_LIMIT
    assert control >= 3 * engine
    assert engine < TINY_GAP_LIMIT


def _alter_tokens(eng):
    """A token altered where it is produced: the step's host results carry
    the next token id, not the one the model chose."""
    runner = eng.runner
    collect = runner.collect
    vocab = eng.cfg.vocab

    def altered(pending):
        res = collect(pending)
        return res._replace(tokens=(res.tokens + 1) % vocab)

    runner.collect = altered


def _state_unchanged(eng):
    """A step that returns its state unchanged: the KV the step wrote is
    thrown away (a copy of the arena from before the step is put back; the
    step donates the one it was given)."""
    runner = eng.runner
    launch = runner.launch

    def keep(kvm, **kw):
        kv = jax.tree.map(jnp.copy, kvm.kv)
        out = launch(kvm, **kw)
        kvm.kv = kv
        return out

    runner.launch = keep


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged],
                         ids=["token-altered", "state-unchanged"])
def test_broken_timed_path_is_not_correct(tiny_root, fault):
    res = run_tiny(tiny_root, "tiny.chat", engine_hook=fault)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > TINY_GAP_LIMIT
