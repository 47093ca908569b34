"""The kernel's and the model's counts against shapes worked by hand."""

from __future__ import annotations

from bench import counts
from bench.harness import StepLog

M = dict(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
         d_ff=64, vocab=100, mlp_type="relu2")
PAGE = 4
# (rid, committed before, committed by the step)
ROWS = [(1, 6, 1),   # decode at position 6: 7 keys, pages 0-1
        (2, 0, 4),   # a 4-token prefill chunk from 0: 1 page
        (3, 8, 1),   # decode at 8 over pages 0-2, pages 0-1 shared
        (4, 8, 1),   # the same shared prefix pages, its own page 2
        (5, 3, 0)]   # restarted this step: nothing committed, not counted
CHAINS = {3: [(0, ((0, 100), (1, 101)))], 4: [(0, ((0, 100), (1, 101)))]}


def test_attention_flops_per_row():
    # 4 * heads * head_dim * (n * c + n (n + 1) / 2) = 128 * (...)
    assert counts.attention_flops(M, 6, 1) == 128 * 7
    assert counts.attention_flops(M, 0, 4) == 128 * 10
    assert counts.attention_flops(M, 8, 1) == 128 * 9


def test_step_attention_counts_shared_pages_once():
    flops, nbytes = counts.step_attention(M, PAGE, ROWS, CHAINS, 0)
    # per layer: 896 + 1280 + 1152 + 1152 = 4480 flops
    assert flops == 2 * 4480
    # pages read: 2 + 1 + (1 own + 2 shared) + (1 own + the same 2) = 7;
    # a page is K and V of 4 tokens x 2 kv heads x 8 x 2 B = 256 B;
    # q and out: 7 tokens x 4 heads x 8 x 2 B x 2 = 896 B
    assert nbytes == 2 * (7 * 256 + 896)


def test_copy_on_write_ends_the_sharing():
    # from step 5 on, row 4 holds its own copy of block 1
    chains = dict(CHAINS)
    chains[4] = [(0, ((0, 100), (1, 101))), (5, ((0, 100),))]
    _, before = counts.step_attention(M, PAGE, ROWS, chains, 4)
    _, after = counts.step_attention(M, PAGE, ROWS, chains, 5)
    assert after - before == 2 * 256


def test_model_flops():
    # block weights: attention 32*(4+2*2)*8 + 4*8*32 = 3072, MLP 2*32*64
    # = 4096; two flops per weight per layer per token committed (7),
    # attention as above, the output head for 3 tokens delivered
    steps = [StepLog(0.0, 0.01, ROWS)]
    want = 2 * 2 * 7168 * 7 + 2 * 4480 + 2 * 32 * 100 * 3
    assert counts.model_flops(M, steps, 3) == want
