"""Open-loop requests: arrivals at the mix's ``rate_rps``, each with a
prompt of ``prompt`` lengths and asking for ``output`` lengths (see
``bench/traffic.py``)."""

from __future__ import annotations

from bench.traffic import Ask, lengths, segments


class Generator:
    def __init__(self, traffic, shape):
        mix = traffic.mix
        self.traffic = traffic
        due = segments(traffic, shape, mix["rate_rps"])
        p = lengths(shape, len(due), mix["prompt"])
        o = lengths(shape, len(due), mix["output"])
        #: (due, prompt length, max_new) of every request, in order
        self.schedule = [(float(t), int(a), int(b))
                         for t, a, b in zip(due, p, o)]

    def first(self) -> list[Ask]:
        return [Ask(due, self.traffic.tokens(i, n), m, i)
                for i, (due, n, m) in enumerate(self.schedule)]

    def after(self, ask, generated, t) -> list[Ask]:
        return []

    def prefixes(self) -> list:
        return []

    def max_prompt_len(self) -> int:
        return self.traffic.mix["prompt"]["cap"]

    def max_new(self) -> int:
        return self.traffic.mix["output"]["cap"]
