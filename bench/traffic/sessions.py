"""Agent sessions: sessions arrive at ``rate_sps``; each picks one of the
``system_prompts`` by weight and runs ``turns`` turns.  A turn's prompt is
the previous turn's prompt and answer plus a new ``message``; it asks for
``output`` lengths, and the next turn is due an exponential think time
(mean ``think_mean_s``) after the answer lands (see ``bench/traffic.py``)."""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.traffic import Ask, lengths, segments


@dataclasses.dataclass
class Turn:
    message_len: int  # the user message (turn 1) or tool result appended
    max_new: int
    think_s: float  # after this turn's answer lands, before the next is due


@dataclasses.dataclass
class Session:
    due: float
    system: int  # which system prompt
    turns: list


class Generator:
    def __init__(self, traffic, shape):
        mix = traffic.mix
        self.traffic = traffic
        due = segments(traffic, shape, mix["rate_sps"])
        n, k = len(due), mix["turns"]
        msg = lengths(shape, n * k, mix["message"]).reshape(n, k)
        out = lengths(shape, n * k, mix["output"]).reshape(n, k)
        think = shape.exponential(mix["think_mean_s"], (n, k))
        weights = np.array([s["weight"] for s in mix["system_prompts"]],
                           np.float64)
        system = shape.choice(len(weights), n, p=weights / weights.sum())
        self.schedule = [
            Session(float(due[i]), int(system[i]),
                    [Turn(int(msg[i, t]), int(out[i, t]), float(think[i, t]))
                     for t in range(k)]) for i in range(n)]
        self.system_prompts = [traffic.tokens(("system", i), s["tokens"])
                               for i, s in enumerate(mix["system_prompts"])]

    def _turn(self, i: int, turn: int, due: float, head: list) -> Ask:
        s = self.schedule[i]
        t = s.turns[turn]
        msg = self.traffic.tokens(("turn", i, turn), t.message_len)
        return Ask(due, head + msg, t.max_new, (i, turn))

    def first(self) -> list[Ask]:
        return [self._turn(i, 0, s.due, self.system_prompts[s.system])
                for i, s in enumerate(self.schedule)]

    def after(self, ask, generated, t) -> list[Ask]:
        i, turn = ask.tag
        turns = self.schedule[i].turns
        if turn + 1 >= len(turns):
            return []
        due = t + turns[turn].think_s
        return [self._turn(i, turn + 1, due,
                           list(ask.prompt) + list(generated))]

    def prefixes(self) -> list:
        return self.system_prompts

    def max_prompt_len(self) -> int:
        m = self.traffic.mix
        sys_max = max(s["tokens"] for s in m["system_prompts"])
        k = m["turns"]
        return sys_max + k * m["message"]["cap"] + (k - 1) * m["output"]["cap"]

    def max_new(self) -> int:
        return self.traffic.mix["output"]["cap"]
