"""Closed-loop clients: ``clients`` callers, each with a queue of
``requests_per_client`` requests, sending the next the moment the last has
finished (see ``bench/traffic.py``)."""

from __future__ import annotations

from bench.traffic import Ask, lengths


class Generator:
    def __init__(self, traffic, shape):
        mix = traffic.mix
        self.traffic = traffic
        self.clients = mix["clients"]
        n = self.clients * mix["requests_per_client"]
        p = lengths(shape, n, mix["prompt"])
        o = lengths(shape, n, mix["output"])
        #: (prompt length, max_new) of every request; client c sends
        #: requests c, c + clients, c + 2 clients, ...
        self.schedule = [(int(a), int(b)) for a, b in zip(p, o)]

    def _ask(self, i: int, due: float) -> list[Ask]:
        if i >= len(self.schedule):
            return []
        n, m = self.schedule[i]
        return [Ask(due, self.traffic.tokens(i, n), m, i)]

    def first(self) -> list[Ask]:
        return [a for c in range(self.clients)
                for a in self._ask(c, -self.traffic.lead_in_s)]

    def after(self, ask, generated, t) -> list[Ask]:
        return self._ask(ask.tag + self.clients, t)

    def prefixes(self) -> list:
        return []

    def max_prompt_len(self) -> int:
        return self.traffic.mix["prompt"]["cap"]

    def max_new(self) -> int:
        return self.traffic.mix["output"]["cap"]
