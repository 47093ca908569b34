"""The traffic generator: the same seed gives the same requests; every seed
gets the same work in the same order, and only other token ids."""

from __future__ import annotations

import json

import pytest

from bench.traffic import Traffic
from conftest import ROOT

MIXES = ["chat", "batch", "agent"]
BIG = 2**31 + 7  # the driver's seeds need more than 32 signed bits


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def _work(tr):
    """What the mix's shape seed drew: sizes, arrivals, think times."""
    return tr.gen.schedule


def _arrivals(tr):
    return [a.due for a in tr.first()] if tr.kind != "closed" else []


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = (Traffic(_mix(name), BIG, 30.0, 50304) for _ in range(2))
    assert _work(a) == _work(b) and _arrivals(a) == _arrivals(b)
    assert a.tokens(5, 100) == b.tokens(5, 100)


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_work_other_tokens(name):
    a = Traffic(_mix(name), BIG, 30.0, 50304)
    b = Traffic(_mix(name), 2**40 + 3, 30.0, 50304)
    assert _work(a) == _work(b) and _arrivals(a) == _arrivals(b)
    if a.kind != "closed":
        assert sum(0 <= t < 30.0 for t in _arrivals(a)) > 0
    assert a.tokens(5, 100) != b.tokens(5, 100)


def test_open_mix_sizes_follow_the_mix():
    mix = _mix("chat")
    tr = Traffic(mix, BIG, 30.0, 50304)
    asks = tr.first()
    assert max(len(a.prompt) for a in asks) <= mix["prompt"]["cap"]
    assert max(a.max_new for a in asks) <= mix["output"]["cap"]
    assert len(asks) == round(mix["rate_rps"] * 30.0) + round(
        mix["rate_rps"] * mix["lead_in_s"])
    assert all(0 <= t < 50304 for t in tr.tokens(1, 1000))


def test_closed_client_sends_next_when_last_finishes():
    tr = Traffic(_mix("batch"), BIG, 30.0, 50304)
    first = tr.first()
    assert len(first) == tr.mix["clients"]
    nxt = tr.after(first[1], [7, 8], 12.5)
    assert [a.due for a in nxt] == [12.5]
    assert nxt[0].tag == 1 + tr.mix["clients"]


def test_session_turn_carries_prompt_and_answer():
    tr = Traffic(_mix("agent"), BIG, 30.0, 50304)
    ask = tr.first()[0]
    assert ask.prompt[:8192] == tr.prefixes()[0]
    nxt = tr.after(ask, [7, 8], 3.0)
    assert nxt[0].prompt[:len(ask.prompt) + 2] == ask.prompt + [7, 8]
    assert nxt[0].due > 3.0
