"""nemotron-4-15b's reference (``bench/models/nemotron_decoder.py``) through
the harness at a toy size: a configuration of the family (GQA with three
query heads per kv head, LayerNorm, squared ReLU, an untied head) with
rotary over half of each head, run by ``run_cell`` under the closed-loop
mix.  The engine agrees with the reference within the toy limit, and the
float8 control built from the same reference does not.  The chunked
prefill's reader finds prompt tokens in the cell's record."""

from __future__ import annotations

import json

import pytest

from conftest import TINY_GAP_LIMIT, TINY_MODELS, make_root, run_tiny


@pytest.fixture(scope="module")
def nemo(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("checkout"))
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "tiny.json").read_text())
    cfg["name"] = "tinynemo"
    cfg["reference"] = "nemotron_decoder"
    cfg["model"] = {**TINY_MODELS["tiny"], "name": "tinynemo",
                    "rotary_pct": 0.5}
    (bench / "configs" / "tinynemo.json").write_text(json.dumps(cfg))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tinynemo", "source": "toy",
                            "file": "bench/configs/tinynemo.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "tinynemo.batch", "config": "tinynemo",
                              "traffic": "tinybatch", "chips": 1,
                              "why": "toy"})
    for m in spec["per_layer"]:
        if m["name"].endswith(".batch"):
            m["workloads"].append("tinynemo.batch")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def batch(nemo):
    return run_tiny(nemo, "tinynemo.batch", control=True)


def test_engine_agrees_with_nemotron_reference(batch):
    gap = batch["checks"]["max_logit_gap"]["value"]
    print(f"engine {gap}")
    assert batch["correct"], batch["checks"]
    assert gap <= TINY_GAP_LIMIT
    assert batch["checks"]["tokens_compared"]["value"] >= 32


def test_float8_control_is_not_correct(batch):
    engine = batch["checks"]["max_logit_gap"]["value"]
    control = batch["control"]["checks"]["max_logit_gap"]["value"]
    print(f"engine {engine} control {control}")
    assert not batch["control"]["correct"]
    assert control > TINY_GAP_LIMIT and control >= 3 * engine


class _ReadOnKeep(dict):
    """``run_cell``'s ``keep``, reading a metric the moment it is handed the
    run: the run lets go of its requests after the window's metrics are
    read, and this reader is a per-layer one, read only in traced runs."""

    def __init__(self, reader):
        super().__init__()
        self.reader = reader

    def update(self, **kw):
        super().update(**kw)
        self["value"] = self.reader.read(kw["run"])


def test_prefill_reader_counts_prompt_tokens(nemo):
    """``prefill_tok_s.batch`` counts the window's committed prompt
    positions: some, and no more than all the tokens the steps committed."""
    from bench import common

    keep = _ReadOnKeep(common.load_module(nemo, "metrics",
                                          "prefill_tok_s.batch"))
    run_tiny(nemo, "tinynemo.batch", keep=keep)
    run = keep["run"]
    _, steps = run.window_steps()
    committed = sum(n for s in steps for _, _, n in s.rows) / run.close
    print(f"prefill {keep['value']} of {committed} tokens/s committed")
    assert 0 < keep["value"] < committed
