"""Traffic: a mix is a data file, ``bench/traffic/<mix>.json``, whose
``kind`` names the generator that reads it, ``bench/traffic/<kind>.py``.
This module finds that generator by name, gives it the run's token ids,
and holds what the generators share.

Three generators are here:

- ``open``: independent users.  Arrivals come at a fixed mean rate,
  whether or not the engine keeps up, and each request is timed from the
  moment it was due.
- ``closed``: ``clients`` callers, each of which sends its next request
  the moment its last one has finished (offline batches).
- ``sessions``: agent sessions that arrive open-loop.  Every session shares
  one of a few long system prompts; each turn's prompt is the previous
  turn's prompt, its answer and a new message, and the next turn is due a
  think time after the answer lands.

A generator module defines ``Generator(traffic, shape_rng)`` with
``first()`` (the requests due from the start), ``after(ask, generated, t)``
(the requests a finished one leads to, its answer landing at ``t``),
``prefixes()`` (prompts the traffic needs in the prefix cache before it
starts), ``max_prompt_len()``, ``max_new()`` and ``schedule`` (the sizes
and arrivals it drew).  A new arrival process is a new module and a mix
that names it, with no edit here.

Every seed gets the same work, in the same order.  The sizes, the gaps
between arrivals and the think times are drawn once from the mix's own
``shape_seed``; the run's seed draws only the prompts' token ids (and the
weights, elsewhere).  Dealing the same sizes out in another order per seed
was tried first: at the chat cell's rate a window holds some thirty
requests, and which of them were long moved ``output_tok_s`` by 20% and
the TTFT tail by 16% between seeds, against 1-3% between two runs of one
seed (PERF.md).  Arrivals are drawn per segment (lead-in, window), so the
window holds a fixed number of them.

The length distribution is a copy of ``serving/traffic.py``'s
``synthesize_trace``: a lognormal body (sigma 0.6) with a ``tail_frac``
share stretched by ``tail_scale``, clipped to the cap.
"""

from __future__ import annotations

import dataclasses
import pathlib
import zlib

import numpy as np

from bench import common


def lengths(rng: np.random.Generator, n: int, spec: dict) -> np.ndarray:
    """``n`` lengths from ``spec`` (``mean``, ``cap`` and optionally
    ``sigma``, ``tail_frac``, ``tail_scale``, ``min``)."""
    mu = np.log(max(spec["mean"], 1))
    sigma = spec.get("sigma", 0.6)
    out = np.empty(n, np.int64)
    for i in range(n):
        scale = (spec.get("tail_scale", 4.0)
                 if rng.random() < spec.get("tail_frac", 0.1) else 1.0)
        x = scale * float(rng.lognormal(mu, sigma))
        out[i] = int(max(spec.get("min", 1), min(spec["cap"], round(x))))
    return out


def arrivals(rng, start: float, end: float, rate: float) -> np.ndarray:
    """``round(rate * (end - start))`` arrival times in ``[start, end)``:
    a Poisson process conditioned on its count (sorted uniform points)."""
    n = int(round(rate * (end - start)))
    return np.sort(rng.uniform(start, end, max(n, 0)))


def segments(traffic, rng, rate: float) -> np.ndarray:
    """Arrivals at ``rate`` over the lead-in and, apart, over the window."""
    return np.concatenate([
        arrivals(rng, -traffic.lead_in_s, 0.0, rate),
        arrivals(rng, 0.0, traffic.window_s, rate)])


@dataclasses.dataclass
class Ask:
    """One request a client sends: when it is due (seconds from the
    window's opening; the lead-in is negative), its prompt, how many tokens
    it asks for, and what its generator needs to know when it finishes."""

    due: float
    prompt: list
    max_new: int
    tag: object = None


def hash_words(s) -> int:
    """A stable 32-bit word for a stream name (Python's ``hash`` of a str
    changes from process to process)."""
    if isinstance(s, (int, np.integer)):
        return int(s) % 2**32
    return zlib.crc32(str(s).encode())


class Traffic:
    """The requests of one run of one mix (module docstring)."""

    def __init__(self, mix: dict, seed: int, window_s: float, vocab: int,
                 root: pathlib.Path = common.ROOT):
        self.mix = mix
        self.kind = mix["kind"]
        self.vocab = vocab
        self.seed = seed
        self.window_s = float(window_s)
        self.lead_in_s = float(mix["lead_in_s"])
        self.gen = common.load_module(root, "traffic", self.kind).Generator(
            self, np.random.default_rng(mix["shape_seed"]))

    def tokens(self, stream, n: int) -> list[int]:
        """``n`` token ids of the configuration's vocabulary, from the run's
        seed and the stream's name (a request index, a system prompt...)."""
        words = ([hash_words(s) for s in stream]
                 if isinstance(stream, tuple) else [int(stream)])
        rng = np.random.default_rng(
            [self.seed % 2**32, self.seed // 2**32, 2, *words])
        return rng.integers(0, self.vocab, n).tolist()

    def first(self) -> list[Ask]:
        return self.gen.first()

    def after(self, ask: Ask, generated: list, t: float) -> list[Ask]:
        return self.gen.after(ask, generated, t)

    def prefixes(self) -> list[list[int]]:
        return self.gen.prefixes()

    def max_prompt_len(self) -> int:
        return self.gen.max_prompt_len()

    def max_new(self) -> int:
        return self.gen.max_new()
