# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import argparse
import importlib
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gate(gates: list, name: str, actual, threshold, passed: bool) -> None:
    """Record one acceptance gate (actual vs threshold) and print its
    verdict line.  Every gate lands in ``gates`` so a failing run can end
    with ONE summary table of all of them instead of stopping at the
    first miss."""
    passed = bool(passed)
    gates.append({"gate": name, "actual": actual, "threshold": threshold,
                  "pass": passed})
    print(f"check,{name},{'PASS' if passed else 'FAIL'}")


def _checks(all_rows, crashed=()) -> bool:
    """Paper-claim checks (the reproduction's acceptance tests).  Each gate
    only fires when its benchmark's rows are present, so ``--check`` can run
    a subset.  ``crashed`` names suite modules that raised instead of
    producing rows — each becomes a failed gate.  On any failure the full
    actual-vs-threshold table is printed before returning False."""
    import collections
    by = collections.defaultdict(dict)
    for r in all_rows:
        if "threads" in r:
            by[(r["bench"], r["threads"])][r["method"]] = r

    gates: list[dict] = []
    print("# paper-claim checks")
    for label in crashed:
        _gate(gates, f"{label}: benchmark completes", "raised", "completes",
              False)
    for (bench, t), methods in by.items():
        if bench.startswith("list5k_50i50r") and {"OA-BIT", "OA-VER"} <= methods.keys():
            bit = methods["OA-BIT"]["warnings_fired"]
            _gate(gates, f"{bench}/t{t}: OA-VER fires <= warnings of OA-BIT",
                  methods["OA-VER"]["warnings_fired"], f"<= {bit}",
                  methods["OA-VER"]["warnings_fired"] <= bit)
        if bench.startswith("ht") and "OA" in methods and "OA-VER" in methods:
            _gate(gates, f"{bench}/t{t}: allocator-backed OA avoids recycling phases",
                  methods["OA-VER"]["recycling_phases"], "== 0",
                  methods["OA-VER"]["recycling_phases"] == 0)
        if bench.startswith("ht10k_50i50r") and "OA" in methods:
            _gate(gates, f"{bench}/t{t}: pooled OA pays recycling phases",
                  methods["OA"]["recycling_phases"], "> 0",
                  methods["OA"]["recycling_phases"] > 0)
    dw = {r["method"]: r for r in all_rows if r["bench"] == "dwcas_on_reclaimed"}
    if {"madvise", "shared_remap"} <= dw.keys():
        _gate(gates,
              f"dwcas leak: madvise leaks ({dw['madvise']['leaked_kib']}KiB) "
              f"but shared_remap does not ({dw['shared_remap']['leaked_kib']}KiB)",
              f"madvise={dw['madvise']['leaked_kib']}KiB,"
              f"shared_remap={dw['shared_remap']['leaked_kib']}KiB",
              "madvise > 100KiB and shared_remap < 64KiB",
              dw["madvise"]["leaked_kib"] > 100
              and dw["shared_remap"]["leaked_kib"] < 64)

    sp = [r for r in all_rows
          if r["bench"] == "decode_throughput" and r["method"] == "speedup"]
    if sp:
        x = sp[0]["speedup_x"]
        _gate(gates, f"decode_throughput: sync-free engine >=1.5x legacy "
              f"(got {x}x)", x, ">= 1.5", x >= 1.5)

    # chunked-prefill gates (BENCH_prefill.json): one dispatch must cover C
    # prompt tokens — structurally fewer dispatches to the first token AND
    # an end-to-end throughput win on the long-prompt workload
    pf = [r for r in all_rows
          if r["bench"] == "prefill_throughput" and r["method"] == "speedup"]
    if pf:
        x, tr = pf[0]["speedup_x"], pf[0]["ttft_dispatch_ratio"]
        _gate(gates, f"prefill_throughput: chunked TTFT <= 1/4 the dispatches "
              f"of token-at-a-time (got ratio {tr})", tr, "<= 0.25", tr <= 0.25)
        _gate(gates, f"prefill_throughput: chunked prefill >=1.5x gen "
              f"tokens/sec (got {x}x)", x, ">= 1.5", x >= 1.5)

    # speculative-decoding gates (BENCH_speculative.json): drafting must
    # pay on self-predictive text AND stay near-free when every draft is
    # wrong — the AIMD cap collapsing to zero (the plain executable) is
    # what the worst-case bound measures
    sv = [r for r in all_rows
          if r["bench"] == "speculative" and r["method"] == "speedup"]
    if sv:
        x, wr = sv[0]["speedup_x"], sv[0]["worst_case_ratio"]
        _gate(gates, f"speculative: >=2.0x decode tokens/sec on repetitive "
              f"text at batch 8 (got {x}x)", x, ">= 2.0", x >= 2.0)
        _gate(gates, f"speculative: <=10% regression under an always-wrong "
              f"drafter on random text (got ratio {wr})", wr, ">= 0.9",
              wr >= 0.9)

    # prefix-sharing gates (BENCH_prefix.json): the refcounted cache must
    # pay for itself on the shared-system-prompt workload
    pc = [r for r in all_rows
          if r["bench"] == "prefix_cache" and r["method"] == "speedup"]
    if pc:
        x, ar = pc[0]["speedup_x"], pc[0]["alloc_ratio"]
        _gate(gates, f"prefix_cache: sharing >=1.3x gen tokens/sec "
              f"(got {x}x)", x, ">= 1.3", x >= 1.3)
        _gate(gates, f"prefix_cache: >=30% fewer page allocations "
              f"(got ratio {ar})", ar, "<= 0.7", ar <= 0.7)

    # data-parallel multi-pool gates (BENCH_parallel.json): replicas must
    # genuinely overlap (a serialized fleet scores ~1.0x) and stay
    # sync-free.  The speedup bar is calibrated: >=1.6x absolute whenever
    # the host itself can scale >=2x (the model-only ceiling measured in
    # the same round), else >=80% of whatever parallel capacity the host
    # proves able to deliver — the no-architectural-serialization claim.
    mp = [r for r in all_rows
          if r["bench"] == "multi_pool" and r["method"] == "speedup"]
    if mp:
        x, thr = mp[0]["speedup_2x"], mp[0]["gate_threshold"]
        _gate(gates, f"multi_pool: 2 replicas >=min(1.6, 0.8x host ceiling "
              f"{mp[0]['ceiling_2x']}x) aggregate tokens/sec "
              f"(got {x}x, threshold {thr}x)", x, f">= {thr}",
              bool(mp[0]["gate_pass"]) and x >= thr)
        _gate(gates, "multi_pool: per-replica sync-free invariant in fleet "
              "mode", bool(mp[0]["sync_free_ok"]), "True",
              bool(mp[0]["sync_free_ok"]))

    # tensor-parallel gates (BENCH_tensor_parallel.json): sharding must be
    # a pure layout change.  Per-device weight+KV bytes at TP=2 must reach
    # the memory point of TP (<= 0.6x, exact from shard shapes); greedy
    # tokens must be IDENTICAL to TP=1; the hot path stays sync-free (the
    # fused step's outputs are replicated, one device_get); throughput is
    # judged against the model-only TP ceiling measured in the same round
    # (host-simulated shards share cores — no absolute speedup expected).
    tpb = [r for r in all_rows
           if r["bench"] == "tensor_parallel" and r["method"] == "speedup"]
    if tpb:
        r = tpb[0]
        _gate(gates, f"tensor_parallel: per-device bytes at TP=2 <= "
              f"{r['memory_gate']}x TP=1 (got {r['memory_ratio']}x)",
              r["memory_ratio"], f"<= {r['memory_gate']}",
              bool(r["memory_gate_pass"]))
        _gate(gates, f"tensor_parallel: TP=2 tokens/sec >= min(0.8, 0.8x "
              f"host TP ceiling {r['ceiling_ratio']}x) of TP=1 "
              f"(got {r['tp_ratio']}x, threshold {r['gate_threshold']}x)",
              r["tp_ratio"], f">= {r['gate_threshold']}",
              bool(r["gate_pass"]) and r["tp_ratio"] >= r["gate_threshold"])
        _gate(gates, "tensor_parallel: greedy TP=2 tokens identical to TP=1",
              bool(r["token_exact_ok"]), "True", bool(r["token_exact_ok"]))
        _gate(gates, "tensor_parallel: sync-free invariant at TP=2",
              bool(r["sync_free_ok"]), "True", bool(r["sync_free_ok"]))

    # chaos / self-healing gates (BENCH_chaos.json): the reference fault
    # schedule (10% grant denials + one replica kill mid-run) must keep
    # goodput within budget with zero lost or corrupted requests, and the
    # hot path must stay sync-free WITH the fault schedule active
    cg = [r for r in all_rows
          if r["bench"] == "chaos_goodput" and r["method"] == "goodput"]
    if cg:
        r = cg[0]
        _gate(gates, f"chaos_goodput: goodput >= {r['gate_threshold']}x "
              f"fault-free under the reference fault schedule "
              f"(got {r['goodput_ratio']}x)", r["goodput_ratio"],
              f">= {r['gate_threshold']}",
              r["goodput_ratio"] >= r["gate_threshold"])
        _gate(gates, f"chaos_goodput: zero lost / zero corrupted requests "
              f"(lost={r['lost']}, corrupted={r['corrupted']}, "
              f"migrated={r['requests_migrated']})",
              f"lost={r['lost']},corrupted={r['corrupted']}", "0/0",
              r["lost"] == 0 and r["corrupted"] == 0)
        _gate(gates, "chaos_goodput: sync-free invariant under injected "
              "faults", bool(r["sync_free_ok"]), "True",
              bool(r["sync_free_ok"]))

    # overload / tail-latency gates (BENCH_traffic.json): under the
    # reference bursty trace the interactive class must hold its p99 TTFT
    # SLO (strict-priority admission through bursts), every arrival must be
    # accounted for (finished / shed / rejected — never lost), and under
    # sustained 2x overload the degradation ladder + bounded queues must
    # keep goodput within budget instead of collapsing
    tf = [r for r in all_rows
          if r["bench"] == "traffic" and r["method"] == "tail_latency"]
    if tf:
        r = tf[0]
        _gate(gates, f"traffic: interactive p99 TTFT within SLO on the "
              f"reference bursty trace (got {r['interactive_p99_ttft_s']}s, "
              f"SLO {r['slo_ttft_s']}s)", r["interactive_p99_ttft_s"],
              f"<= {r['slo_ttft_s']}",
              r["interactive_p99_ttft_s"] <= r["slo_ttft_s"])
        _gate(gates, f"traffic: zero lost requests across reference + "
              f"overload phases (got {r['lost']})", r["lost"], "== 0",
              r["lost"] == 0)
        _gate(gates, f"traffic: goodput >= {r['gate_threshold']}x capacity "
              f"under sustained 2x overload (got {r['goodput_ratio']}x, "
              f"ladder peak {r['degradation_level_peak']}, "
              f"sheds {r['ladder_sheds']})", r["goodput_ratio"],
              f">= {r['gate_threshold']}",
              r["goodput_ratio"] >= r["gate_threshold"])

    mr = [r for r in all_rows if r["bench"] == "memory_release"]
    for r in mr:
        # every released persistent superblock (64 KiB) must actually leave
        # the resident set under madvise/shared_remap — and must NOT under keep
        expect_kib = r["superblocks_released"] * 64
        freed_kib = r["peak_kib"] - r["after_reclaim_kib"]
        if r["method"] in ("madvise", "shared_remap"):
            passed = freed_kib >= 0.9 * expect_kib and expect_kib > 0
            thr = f">= {0.9 * expect_kib}KiB"
        else:  # keep
            passed = freed_kib <= 0.1 * max(expect_kib, 1)
            thr = f"<= {0.1 * max(expect_kib, 1)}KiB"
        _gate(gates, f"memory_release/{r['method']} freed {freed_kib}KiB of "
              f"{expect_kib}KiB released superblocks", freed_kib, thr, passed)

    # device-pool watermark gates (BENCH_release.json, the device Fig. 3)
    mrd = {r["method"]: r for r in all_rows
           if r["bench"] == "memory_release_device"}
    if "madvise" in mrd:
        r = mrd["madvise"]
        _gate(gates, f"memory_release_device: mapped watermark follows load "
              f"({r['after_drain_mapped_pages']}/{r['peak_mapped_pages']} pages "
              f"after drain = {r['watermark_ratio']} <= 0.25)",
              r["watermark_ratio"], "<= 0.25",
              r["watermark_ratio"] <= 0.25 and r["superblocks_released"] > 0)
        _gate(gates, f"memory_release_device: bursts remap "
              f"({r['superblocks_remapped']} superblocks) instead of "
              f"preempting ({r['preemptions']})",
              f"remapped={r['superblocks_remapped']},"
              f"preemptions={r['preemptions']}",
              "remapped > 0 and preemptions == 0",
              r["superblocks_remapped"] > 0 and r["preemptions"] == 0)
    if "keep" in mrd:
        _gate(gates, f"memory_release_device/keep: closed pool stays mapped "
              f"(ratio {mrd['keep']['watermark_ratio']})",
              mrd["keep"]["watermark_ratio"], ">= 0.99",
              mrd["keep"]["watermark_ratio"] >= 0.99)

    failed = [g for g in gates if not g["pass"]]
    if failed:
        # one summary table, every gate, actual vs threshold — a failing
        # run reports the WHOLE picture instead of dying at the first miss
        print(f"\n# gate summary: {len(failed)}/{len(gates)} FAILED")
        print("status,gate,actual,threshold")
        for g in gates:
            print(f"{'PASS' if g['pass'] else 'FAIL'},{g['gate']},"
                  f"{g['actual']},{g['threshold']}")
    return not failed


#: (module, label) of every suite, in run order; ``--check`` runs the
#: BENCH-gated subset
SUITE = [
    ("linked_list", "fig4_linked_list"),
    ("hash_table", "fig5_fig6_hash_table"),
    ("memory_release", "fig3_memory_release"),
    ("memory_release_device", "fig3_device_memory_release"),
    ("paged_attention_bench", "device_paged_attention"),
    ("decode_throughput", "decode_throughput"),
    ("prefix_cache", "prefix_cache_sharing"),
    ("prefill_throughput", "chunked_prefill"),
    ("speculative", "speculative_decoding"),
    ("multi_pool", "data_parallel_multi_pool"),
    ("tensor_parallel", "tensor_parallel_serving"),
    ("chaos_goodput", "chaos_goodput_self_healing"),
    ("traffic", "traffic_tail_latency"),
]
CHECK_SUITE = [m for m in SUITE if m[0] not in (
    "linked_list", "hash_table", "memory_release", "paged_attention_bench")]


def _run_suite(module: str, quick: bool) -> list | None:
    """Run one suite in a child process of its own and return its rows
    (None if it raised).  This parent never touches JAX, so a suite — or a
    child that suite starts — can take the accelerator: a process that has
    initialised a JAX backend holds the chip until it exits."""
    with tempfile.TemporaryDirectory() as d:
        out = os.path.join(d, "rows.json")
        res = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--suite", module,
             "--emit", out] + ([] if quick else ["--paper-scale"]),
            cwd=ROOT)
        if res.returncode != 0:
            return None
        with open(out) as f:
            return json.load(f)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper-scale", action="store_true",
                    help="full node counts / thread counts (slow)")
    ap.add_argument("--check", action="store_true",
                    help="CI gate: run only the BENCH_*.json emitters (quick "
                         "mode) and validate their thresholds")
    ap.add_argument("--suite", help=argparse.SUPPRESS)
    ap.add_argument("--emit", help=argparse.SUPPRESS)
    args = ap.parse_args()
    quick = not args.paper_scale

    if args.suite:  # child: one suite, rows to --emit
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        mod = importlib.import_module(f"benchmarks.{args.suite}")
        rows = mod.run(quick=quick)
        with open(args.emit, "w") as f:
            json.dump(rows, f, default=float)
        return

    all_rows = []
    crashed = []
    for module, label in CHECK_SUITE if args.check else SUITE:
        print(f"# {label}", flush=True)
        rows = _run_suite(module, quick)
        if rows is None:  # a crashing suite is a failed gate, not the end
            # of the run — the others still report actual numbers
            print(f"# {label} CRASHED (traceback above)", flush=True)
            crashed.append(label)
            continue
        all_rows.extend(rows)
        for r in rows:
            name = f"{r['bench']}/{r['method']}" + (
                f"/t{r['threads']}" if "threads" in r else "")
            us = r.get("us_per_call", "")
            derived = {k: v for k, v in r.items()
                       if k not in ("bench", "method", "threads", "us_per_call")}
            print(f"{name},{us},{json.dumps(derived, default=float)}", flush=True)

    if not _checks(all_rows, crashed):
        sys.exit(1)


if __name__ == "__main__":
    main()
