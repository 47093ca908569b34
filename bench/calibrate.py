"""Readings that set the limit of ``correct``: a cell's widest logit gap
over many seeds, for the engine and for the control, in one process.

    python bench/calibrate.py --workload <cell> --seconds 15 --seeds 101 102 ...

Each seed makes its own weights and traffic and drives the cell's timed
path for a short window at the cell's own load, as a benchmark run does;
the sample of served requests is then compared with the float32 reference
(the engine's reading) and, at the same positions, the float8 control's
first choice is read against the same reference and put through the same
comparison and limits (the control's reading, and its ``correct``, which
has to come out false).
One JSON line per seed on standard output.  The benchmark's own runs do
not run this.
"""

from __future__ import annotations

import argparse
import json
import time

from run import run_cell  # noqa: E402  (bench/run.py sets up sys.path)

from bench import common  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell, _, _ = common.find_cell(args.workload)
    devices = common.require_tpu(cell["chips"])
    common.enable_cache()
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = run_cell(args.workload, seed, args.seconds, False,
                       devices=devices, t0=t0, control=True)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "max_logit_gap": res["checks"]["max_logit_gap"]["value"],
            "tokens_compared": res["checks"]["tokens_compared"]["value"],
            "control_correct": res["control"]["correct"],
            "control_max_logit_gap":
                res["control"]["checks"]["max_logit_gap"]["value"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)


if __name__ == "__main__":
    main()
