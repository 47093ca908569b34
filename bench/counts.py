"""Operations and bytes of the work a step needs, computed from the
harness's record of each step (the rows' lengths, the tokens each row
committed, the pages rows share), never from the program's own grid.

Attention, per layer and per row that committed ``n`` tokens on top of
``c`` already committed: query j (position c + j) attends to c + j + 1
keys, two multiply-adds (q.k and p.v) of ``n_heads * head_dim`` each, so

    flops = 4 * n_heads * head_dim * (n * c + n * (n + 1) / 2).

Bytes, per layer: each distinct KV page the step's rows read (K and V of
``page_size`` tokens of every kv head), counted once however many rows
share it, plus the rows' queries and outputs.  A page is read whole,
since that is the unit the cache stores.

The model, per token processed: two operations per weight of the blocks
(embedding lookup not counted), plus the attention above, plus the output
head for each token delivered to a client.  Rows that committed nothing
(a restarted or starved row) count nothing, and neither does padding.
"""

from __future__ import annotations

import bisect

BF16 = 2


def block_params(m: dict) -> int:
    """Weights of one block (norms left out: d-sized)."""
    d, hq, hkv, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                          m["head_dim"], m["d_ff"])
    attn = d * (hq + 2 * hkv) * hd + hq * hd * d
    mlp = (3 if m["mlp_type"] in ("swiglu", "geglu") else 2) * d * ff
    return attn + mlp


def attention_flops(m: dict, c: int, n: int) -> float:
    """One layer, one row: ``n`` new queries on top of ``c`` keys."""
    return 4.0 * m["n_heads"] * m["head_dim"] * (n * c + n * (n + 1) / 2)


def _shared_at(chains: dict, rid: int, step: int) -> tuple:
    """The (block index, page id) pairs row ``rid`` shared at ``step``."""
    hist = chains.get(rid)
    if not hist:
        return ()
    i = bisect.bisect_right([h[0] for h in hist], step) - 1
    return hist[i][1] if i >= 0 else ()


def step_attention(m: dict, page_size: int, rows: list, chains: dict,
                   step: int) -> tuple[float, float]:
    """(flops, bytes) of one step's attention, all layers."""
    flops = 0.0
    private = 0
    shared: set = set()
    qo = 0
    for rid, c, n in rows:
        if n <= 0:
            continue
        flops += attention_flops(m, c, n)
        pages = -(-(c + n) // page_size)
        mine = [pid for idx, pid in _shared_at(chains, rid, step)
                if idx < pages]
        shared.update(mine)
        private += pages - len(mine)
        qo += n
    page_bytes = 2 * page_size * m["n_kv_heads"] * m["head_dim"] * BF16
    nbytes = ((private + len(shared)) * page_bytes
              + 2 * qo * m["n_heads"] * m["head_dim"] * BF16)
    layers = m["n_layers"]
    return flops * layers, nbytes * layers


def attention_work(m: dict, page_size: int, steps: list, first_index: int,
                   chains: dict) -> list[tuple[float, float]]:
    """(flops, bytes) of each of ``steps`` (the step log's entries from
    ``first_index`` on)."""
    return [step_attention(m, page_size, s.rows, chains, first_index + i)
            for i, s in enumerate(steps)]


def model_flops(m: dict, steps: list, tokens_out: int) -> float:
    """Operations of the model over ``steps``: blocks and attention for
    every token committed, the output head for each token delivered."""
    per_token = 2.0 * m["n_layers"] * block_params(m)
    total = 0.0
    for s in steps:
        for _, c, n in s.rows:
            if n > 0:
                total += per_token * n + m["n_layers"] * attention_flops(
                    m, c, n)
    return total + 2.0 * m["d_model"] * m["vocab"] * tokens_out
