"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the engine finished
(drawn from the seed, the longest always in it) is run through the
configuration's plain float32 reference: each prompt with the tokens that
were served for it.  At every served position the reference's best logit
is compared with the logit it gives the served token.  The widest such gap
is the number compared; greedy decoding in bfloat16 differs from the
float32 argmax only where two logits lie within rounding of each other, so
a sound engine keeps it small, and a wrong KV entry, a wrong attention
result or an altered token opens it wide.

The control (``control_gaps``) is the same reference computed one precision
below the configuration's (float8 linear layers): at each of the same
positions it picks its own best token, and the gap that token has under the
float32 reference is read.  The limit lies between the two (PERF.md gives
the readings it was set from).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np


@dataclasses.dataclass
class Served:
    """A finished request, reduced to what the comparison needs."""

    prompt: list
    tokens: list


def sample(served: list, seed: int, max_requests: int,
           min_tokens: int) -> list:
    """The longest finished request, then others in an order drawn from the
    seed, until ``min_tokens`` served tokens or ``max_requests``."""
    if not served:
        return []
    longest = max(range(len(served)),
                  key=lambda i: len(served[i].prompt) + len(served[i].tokens))
    rng = np.random.default_rng([seed % 2**32, seed // 2**32, 3])
    order = [longest] + [int(i) for i in rng.permutation(len(served))
                         if i != longest]
    out, n = [], 0
    for i in order:
        if len(out) >= max_requests or n >= min_tokens:
            break
        out.append(served[i])
        n += len(served[i].tokens)
    return out


def _inputs(s: Served):
    """The sequence fed to the reference and the rows that predict each
    served token (row i predicts token i + 1)."""
    seq = list(s.prompt) + list(s.tokens[:-1])
    rows = [len(s.prompt) - 1 + i for i in range(len(s.tokens))]
    return seq, rows


def reference_gaps(ref, m: dict, eps: float, words, reqs: list):
    """Per request: the gap between the reference's best logit and the
    served token's, at each served position; and the reference's final
    hidden states (reused by :func:`control_gaps`)."""
    with jax.default_matmul_precision("highest"):
        seqs, rows = zip(*[_inputs(s) for s in reqs])
        hidden = ref.final_hidden(m, eps, words, list(seqs))
        gaps = []
        for h, r, s in zip(hidden, rows, reqs):
            best, _, tgt = ref.head_stats(m, eps, words, h, r, s.tokens)
            gaps.append(best - tgt)
    return gaps, hidden


def control_gaps(ref, m: dict, eps: float, words, reqs: list, hidden):
    """The control (module docstring): the float32 reference's gap of the
    token that the float8 reference puts first, at the same positions."""
    with jax.default_matmul_precision("highest"):
        seqs, rows = zip(*[_inputs(s) for s in reqs])
        low = ref.final_hidden(m, eps, words, list(seqs), quant="fp8")
        gaps = []
        for h, hl, r in zip(hidden, low, rows):
            _, pick, _ = ref.head_stats(m, eps, words, hl, r,
                                        np.zeros(len(r), np.int32),
                                        quant="fp8")
            best, _, tgt = ref.head_stats(m, eps, words, h, r, pick)
            gaps.append(best - tgt)
    return gaps


def verdict(gaps: list, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit."""
    n = int(sum(len(g) for g in gaps))
    widest = float(max((float(np.max(g)) for g in gaps if len(g)),
                       default=float("inf")))
    checks = {
        "max_logit_gap": {"value": widest,
                          "limit": limits["max_logit_gap"]},
        "tokens_compared": {"value": n, "limit": limits["min_tokens"]},
    }
    ok = widest <= limits["max_logit_gap"] and n >= limits["min_tokens"]
    return ok, checks
