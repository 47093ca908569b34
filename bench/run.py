"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``, and so are the engine module the configuration names
(``bench/engines/<builder>.py``), the generator the mix names
(``bench/traffic/<kind>.py``) and each metric's reader
(``bench/metrics/<name>.py``); nothing here is particular to one of them.
The run:

1. refuses to go on without a TPU (and without as many chips as the cell
   asks for): it exits non-zero and prints no result;
2. makes the weights on the chip from ``--seed``, builds the engine with
   the configuration's settings, compiles (or loads from ``.jax_cache``)
   and warms up every program the traffic will use, and starts the
   traffic a lead-in before the window, so that the window opens on a
   loaded engine: all of that is ``setup_s``;
3. measures for ``--seconds`` (with ``--trace 1`` under the profiler, and
   the per-layer metrics are then reported instead of the end-to-end
   ones);
4. reads the chip's peak memory, frees the engine, and compares a sample
   of the served requests with the plain float32 reference
   (``bench/check.py``): that decides ``correct``;
5. prints the numbers compared beside their limits as its last lines on
   standard error, and one JSON object as the last line of standard
   output.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import weakref  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
# the TPU runtime's logs stay inside the checkout (its default is /tmp)
os.environ.setdefault("TPU_LOG_DIR", str(_ROOT / ".bench_out" / "tpu_logs"))

import jax  # noqa: E402
import repro  # noqa: E402,F401  (the system under test must be beside us)

from bench import check, common, readers  # noqa: E402
from bench import trace as trace_lib  # noqa: E402
from bench.harness import Driver, warm_up  # noqa: E402
from bench.peaks import peaks as peaks_of  # noqa: E402
from bench.traffic import Traffic  # noqa: E402

#: the paged-attention kernel's operations in the device trace (the
#: ``pallas_call`` has no ``name=``; the name is the one its wrapper gives)
PAGED_ATTN_KERNEL = ["paged_attention"]  # %paged_attention_pallas.<n>
#: how many finished requests the comparison takes at most, and the served
#: tokens it aims for
CHECK_REQUESTS = 8
CHECK_TOKENS = 384


def arch_config(model: dict):
    from repro.configs.base import ArchConfig

    arch = ArchConfig(**model)
    if arch.vocab_padded != arch.vocab:
        raise ValueError(f"vocab {arch.vocab} is not a multiple of 128: the "
                         f"program would serve {arch.vocab_padded} columns")
    return arch


def check_layout(arch, params) -> None:
    """The weights the benchmark made have the program's layout."""
    from repro.models import build_model

    want = jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the benchmark's weights do not have the program's "
                         "parameter layout")


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             devices, root: pathlib.Path = common.ROOT, t0: float = T0,
             peaks: dict | None = None, engine_hook=None,
             control: bool = False, mix_override: dict | None = None,
             keep: dict | None = None) -> dict:
    """Everything after the look for a chip (module docstring); returns
    the result line.  ``engine_hook`` may alter the engine before the
    traffic starts (the tests break the timed path with it); ``control``
    also puts the control's reading on the same requests through the same
    comparison (``bench/calibrate.py``), which decides nothing here; ``mix_override``
    replaces parameters of the traffic mix and ``keep`` receives the run's
    record (``bench/sweep.py``)."""
    spec = common.load_json(root / "BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    centry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    mix = common.load_json(root / "bench" / "traffic" /
                           f"{cell['traffic']}.json")
    mix = {**mix, **(mix_override or {})}
    cfg = common.load_json(root / centry["file"])
    model, eps = cfg["model"], cfg["norm_eps"]
    ref = importlib.import_module(f"bench.models.{cfg['reference']}")
    chips = cell["chips"]
    used = devices[:chips]
    peaks = peaks or peaks_of(used[0].device_kind)
    compile_log = common.CompileLog()
    words = common.seed_key_words(seed)

    arch = arch_config(model)
    with jax.default_device(used[0]):
        params = ref.make_params(model, words)
    check_layout(arch, params)
    common.log(f"params ready at {time.perf_counter() - t0:.3f}s")
    eng_cfg = {**cfg["engine"], **mix.get("engine", {})}
    traffic = Traffic(mix, seed, seconds, model["vocab"], root=root)
    eng = common.load_module(root, "engines", eng_cfg["builder"]).build(
        arch, params, eng_cfg, traffic, used)
    if engine_hook is not None:
        engine_hook(eng.engine)
    warm_up(eng, traffic)
    common.log(f"warm-up done at {time.perf_counter() - t0:.3f}s; "
               f"compiles so far {len(compile_log.events)} "
               f"({sum(s for _, s in compile_log.events):.3f}s)")
    driver = Driver(eng, traffic, seconds, compile_log)
    driver.start()
    driver.lead_in()
    setup_s = time.perf_counter() - t0
    trace_dir = common.OUT_DIR / "trace"
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
        record = driver.window()
    if traced:
        jax.profiler.stop_trace()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)

    reduced = None
    if traced:
        events = trace_lib.load_events(jax.profiler.ProfileData.from_file(
            trace_lib.find_xplane(str(trace_dir))))
        reduced = trace_lib.reduce(events, chips, PAGED_ATTN_KERNEL)
    run = readers.RunData(record=record, model=model,
                          page_size=eng_cfg["page_size"], chips=chips,
                          peaks=peaks, setup_s=setup_s, trace=reduced,
                          engine=eng_cfg)
    if keep is not None:
        keep.update(run=run, traffic=traffic)
    metrics = {}
    for m in common.metrics_for_spec(spec, workload, traced):
        value = common.load_module(root, "metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    due = record.due_in_window()
    failed = sum(1 for r in due if r.req.state in ("rejected", "shed"))
    late = sorted(record.late)
    common.log(
        f"window: {len(record.window_steps())} steps, {len(due)} requests "
        f"due, {run.tokens_delivered()} tokens delivered, close at "
        f"{record.close_t:.3f}s; generator late p95 "
        f"{late[int(0.95 * (len(late) - 1))] if late else 0.0:.6f}s; "
        f"compiles in window {len(record.compiles_in_window)} "
        f"{sorted(set(record.compiles_in_window))}; counters "
        f"{ {k: record.counters_close[k] - record.counters_open[k] for k in record.counters_open} }; "
        f"occupancy {readers.occupancy(run)}")

    # the comparison runs on what the window served, after the engine's
    # state is freed: a request holds its engine, so drop both
    served = [check.Served(list(r.req.prompt), list(r.req.generated))
              for r in record.recs
              if r.req.state == "finished"
              and len(r.req.generated) == r.max_new]
    for r in record.recs:
        r.req = None
    gone = weakref.ref(eng.engine)
    del eng, driver, params
    gc.collect()
    if gone() is not None:
        raise RuntimeError("the engine is still referenced after the window")
    jax.clear_caches()  # unload the engine's programs and what they reserve
    limits = cfg["correct"]
    t_check = time.perf_counter()
    reqs = check.sample(served, seed, CHECK_REQUESTS, CHECK_TOKENS)
    with jax.default_device(used[0]):
        gaps, hidden = check.reference_gaps(ref, model, eps, words, reqs)
        low = (check.control_gaps(ref, model, eps, words, reqs, hidden)
               if control else None)
    del hidden
    ok, checks = check.verdict(gaps, limits)
    common.log(f"reference over {len(reqs)} requests in "
               f"{time.perf_counter() - t_check:.3f}s")
    dev = {"platform": used[0].platform, "kind": used[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(ok), "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    if low is not None:
        c_ok, c_checks = check.verdict(low, limits)
        result["control"] = {"correct": bool(c_ok), "checks": c_checks}
    result["checks"] = checks
    for name, c in checks.items():
        common.log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, _, _ = common.find_cell(args.workload)
    devices = common.require_tpu(cell["chips"])
    common.log(f"compile cache: {common.enable_cache()}")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), devices=devices)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
