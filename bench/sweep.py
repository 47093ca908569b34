"""Find an open-loop cell's knee: run its traffic at several fixed rates, one
window each, in one process.

    python bench/sweep.py --workload olmo-1b.chat --seconds 51 --rates 0.4 0.8 1.2

For each rate one JSON line: the offered rate and the output tokens per
second it offers (the rate times the mean output length of the mix's
schedule), what the window delivered, the time to first token and the
queue wait at several percentiles, the requests due, and how full the
batch and the arena ran.  Then one line with the knee: the highest rate
up to which, from each rate tried to the next, the share of the offered
tokens that the window delivered falls by less than ``1 - TRACK`` (a
lead-in shorter than the longest request keeps the share under 1 at any
load, and each rate's schedule has its own long requests) and the queue
wait's 95th percentile stays within ``GROW_S`` of the lowest rate's.  A
cell runs at a fixed rate below the knee, written into its traffic file
(PERF.md records the sweep).  The benchmark's own runs never search for a
rate.
"""

from __future__ import annotations

import argparse
import json
import time

from run import run_cell  # noqa: E402  (bench/run.py sets up sys.path)

from bench import common, readers  # noqa: E402
from bench.harness import percentile  # noqa: E402

TRACK = 0.85
GROW_S = 1.0


def knee(rows: list) -> float | None:
    """The knee of sweep rows (module docstring)."""
    rows = sorted(rows, key=lambda r: r["rate"])
    wait0 = rows[0]["queue_wait_s"][95]
    best, last = None, None
    for r in rows:
        share = r["metrics"]["output_tok_s"] / r["offered_tok_s"]
        if ((last is not None and share < TRACK * last)
                or r["queue_wait_s"][95] > wait0 + GROW_S):
            break
        best, last = r["rate"], share
    return best


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    cell, _, mix = common.find_cell(args.workload)
    key = "rate_rps" if mix["kind"] == "open" else "rate_sps"
    devices = common.require_tpu(cell["chips"])
    common.enable_cache()
    rows = []
    for rate in args.rates:
        kept: dict = {}
        res = run_cell(args.workload, args.seed, args.seconds, False,
                       devices=devices, t0=time.perf_counter(),
                       mix_override={key: rate}, keep=kept)
        run, traffic = kept["run"], kept["traffic"]
        outs = [a.max_new for a in traffic.first()]
        ttft, queue = readers.ttft_waits(run), readers.queue_waits(run)
        rows.append({
            "rate": rate, "correct": res["correct"],
            "due": res["attempted"],
            "offered_tok_s": rate * sum(outs) / len(outs) if outs else None,
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "ttft_s": {q: percentile(ttft, q) for q in (50, 75, 95)},
            "queue_wait_s": {q: percentile(queue, q) for q in (50, 75, 95)},
            "step_ms": readers.step_ms(run),
            **readers.occupancy(run)})
        print(json.dumps(rows[-1]), flush=True)
    k = knee(rows)
    print(json.dumps({"knee": k, "rate_0.8": None if k is None
                      else round(0.8 * k, 2)}), flush=True)


if __name__ == "__main__":
    main()
