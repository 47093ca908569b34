"""The harness finds a configuration, a traffic mix and a metric by the name
in BENCHMARK.json: a new one is a new file and a new entry, with no edit to
any file the benchmark has.  And BENCHMARK.json itself keeps to the
benchmark's contract."""

from __future__ import annotations

import json
import re
import shutil

import pytest

from conftest import ROOT, make_root, run_tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


#: an arrival process of its own: ``size`` requests at once every ``gap_s``
BURST = """
from bench.traffic import Ask, lengths


class Generator:
    def __init__(self, traffic, shape):
        self.traffic = traffic
        mix = traffic.mix
        t, due = -traffic.lead_in_s, []
        while t < traffic.window_s:
            due += [t] * mix["size"]
            t += mix["gap_s"]
        p = lengths(shape, len(due), mix["prompt"])
        o = lengths(shape, len(due), mix["output"])
        self.schedule = list(zip(due, p.tolist(), o.tolist()))

    def first(self):
        return [Ask(d, self.traffic.tokens(i, n), m, i)
                for i, (d, n, m) in enumerate(self.schedule)]

    def after(self, ask, generated, t):
        return []

    def prefixes(self):
        return []

    def max_prompt_len(self):
        return self.traffic.mix["prompt"]["cap"]

    def max_new(self):
        return self.traffic.mix["output"]["cap"]
"""

#: an engine module of its own: the paged engine, counting its builds
COUNTING = """
BUILT = []
_paged_build = build


def build(*args, **kw):
    BUILT.append(1)
    return _paged_build(*args, **kw)
"""


def test_new_config_traffic_and_metric_are_files_and_entries(tmp_path):
    root = make_root(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "tiny.json").read_text())
    cfg["name"] = cfg["model"]["name"] = "dummy"
    cfg["model"]["d_ff"] = 96
    cfg["engine"]["builder"] = "counting"
    (bench / "configs" / "dummy.json").write_text(json.dumps(cfg))
    (bench / "engines" / "counting.py").write_text(
        (bench / "engines" / "paged.py").read_text() + COUNTING)
    (bench / "traffic" / "burst.py").write_text(BURST)
    mix = json.loads((bench / "traffic" / "tinychat.json").read_text())
    del mix["rate_rps"]
    mix.update(kind="burst", size=3, gap_s=0.5)
    (bench / "traffic" / "dummymix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "dummy_requests.py").write_text(
        "def read(run):\n    return len(run.record.due_in_window())\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dummy", "source": "toy",
                            "file": "bench/configs/dummy.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "dummy.mix", "config": "dummy",
                              "traffic": "dummymix", "chips": 1,
                              "why": "toy"})
    spec["end_to_end"].append({"name": "dummy_requests", "unit": "requests",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["dummy.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    res = run_tiny(root, "dummy.mix")
    assert res["correct"]
    assert res["metrics"]["dummy_requests"]["value"] == res["attempted"] == 18
    from bench import common

    assert common.load_module(root, "engines", "counting").BUILT == [1]
    after = {p: p.read_bytes() for p in before}
    assert after == before  # no file the benchmark had was edited


def test_every_name_has_its_file():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = ROOT / "bench"
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert (bench / "models" / f"{cfg['reference']}.py").is_file()
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["correct"]["max_logit_gap"] > 0
    for w in spec["workloads"]:
        mix = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
        assert (bench / "traffic" / f"{mix['kind']}.py").is_file()
        config = next(c for c in spec["configs"] if c["name"] == w["config"])
        engine = {**json.loads((ROOT / config["file"]).read_text())["engine"],
                  **mix.get("engine", {})}
        assert (bench / "engines" / f"{engine['builder']}.py").is_file()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert (bench / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_benchmark_json_keeps_to_the_contract():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"] for c in spec["configs"]}
    assert {w["config"] for w in cells.values()} == configs
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and len(c["why"]) <= 200
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in cells
    for m in spec["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert "workloads" not in moved or w in moved["workloads"]
    for w in cells:  # every cell reports set-up, one more, one per-layer
        reported = [m for m in spec["end_to_end"]
                    if w in m.get("workloads", [w])]
        assert len(reported) >= 2
        assert any(w in m.get("workloads", [w]) for m in spec["per_layer"])
