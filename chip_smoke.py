"""Bring-up smoke: the paged serving engine on a TPU at olmo-1b's widths.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: replica fleet and TP=4

One chip: olmo-1b at its published widths (16 layers, d_model 2048, 16
heads, d_ff 8192, vocab 50304; random bf16 weights from a fixed seed) serves
three passes through :class:`PagedServingEngine` on a 2048-page arena of
16-token pages (4 GiB of KV beside 2.4 GB of weights):

1. mixed traffic — 16 requests with 128-512-token prompts and 32 new tokens
   each, chunked prefill (C=16) beside decode (C=1) in one batch of 8; in the
   middle of the run the compiled Pallas attention is checked against the jnp
   reference on the live arena and block tables;
2. prefix sharing — requests that share a 256-token system prompt;
3. speculative decoding — n-gram drafts of up to 4 tokens verified in the
   speculative executable.

Every pass must finish every request and keep the host clock mirror exact
(``stats.warnings_fired == pool.clock``).  ``--four-chips`` runs only the
multi-chip paths — ``DataParallelEngine(replicas=4)`` and
``PagedServingEngine(tensor_parallel=4)`` — each against the one-chip engine
on the same requests.

The script refuses to run without a TPU (no CPU fallback) and prints, as its
last line, ``{"ok": true, "device": {...}}`` only when every check passed.
The phase functions take their sizes and attention implementation as
arguments, so the CPU tests drive them at a toy size.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import weakref
from importlib import metadata

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels.ops import paged_attention  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import (DataParallelEngine, PagedServingEngine,  # noqa: E402
                           required_pages_per_seq)

#: Pallas-vs-reference attention tolerance (absolute and relative).  Both
#: paths read the same bf16 arena; the kernel multiplies bf16 operands with
#: f32 accumulation where the reference upcasts first, and both round the
#: output to bf16 (eps 2**-8 = 3.9e-3).  It is the bf16 tolerance the
#: interpret-mode sweeps in tests/test_kernels.py hold the kernel to.
ATTN_TOL = 2e-2
#: TP=4 against one chip, as relative L2 error of the live KV arena (every
#: layer) and of attention over the last layer.  Sharding re-associates
#: each row-parallel contraction (attention ``wo``, MLP down-projection)
#: into four partial sums that are rounded to bf16 (u = 2**-8) before they
#: are added, two per layer.  Independent roundings grow like a random
#: walk, sqrt(2 * 16) * u = 2.2e-2 at the last of 16 layers; the bound is
#: about twice that.  A wrong head layout or a dropped shard gives errors
#: of order 1, already at layer 0 (below).
TP_REL_TOL = 5e-2
#: TP=4 against one chip at layer 0 alone.  Its K and V are column-parallel
#: projections of replicated embeddings: no sharded reduction comes before
#: them, so they agree to one bf16 rounding.
TP_LAYER0_TOL = 4e-3
#: the seed of the random weights, prompts and parity queries
SEED = 0


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One smoke workload.  The defaults are the chip's; tests shrink them."""

    num_pages: int = 2048
    page_size: int = 16
    max_batch: int = 8
    prefill_chunk: int = 16
    requests: int = 16
    prompt_min: int = 128
    prompt_max: int = 512
    max_new: int = 32
    shared_prefix: int = 256
    spec_k: int = 4
    parity_after: int = 24  # steps of pass 1 before the parity check
    fleet_prompt: int = 64  # equal-length prompts of the four-chip phase
    fleet_max_new: int = 16

    @property
    def pages_per_seq(self) -> int:
        # one block-table width for every pass: no pass compiles a new shape
        return required_pages_per_seq(self.prompt_max,
                                      self.max_new + self.spec_k,
                                      self.page_size)


class CompileLog:
    """Backend compile durations by function name, from JAX's monitoring
    events (a persistent-cache hit is timed the same way, so a warm cache
    shows as shorter compiles)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **kw):
        if event == self.EVENT:
            self.events.append((str(kw.get("fun_name", "?")), float(secs)))

    def since(self, mark: int) -> list[tuple[str, float]]:
        return self.events[mark:]


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu() -> list:
    """Print the devices and versions; exit non-zero unless JAX sees a TPU."""
    devs = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__} libtpu={libtpu}")
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{devs[0].platform!r}); this smoke has no CPU fallback",
              file=sys.stderr)
        sys.exit(2)
    return devs


def init_params(cfg, seed: int):
    """Random bf16 weights from ``seed``, built on the default device in one
    compiled program."""
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def engine_kwargs(s: Sizes, attn_impl) -> dict:
    return dict(num_pages=s.num_pages, page_size=s.page_size,
                max_batch=s.max_batch, max_pages_per_seq=s.pages_per_seq,
                prefill_chunk=s.prefill_chunk, attn_impl=attn_impl)


def random_prompts(rng, n: int, lo: int, hi: int, vocab: int,
                   first_len: int | None = None) -> list[list[int]]:
    """``n`` prompts of ``lo..hi`` tokens; the first one ``first_len`` long,
    so the engine's prompt buffer reaches its final width at the first
    admission (one compiled shape per executable)."""
    lens = rng.integers(lo, hi + 1, n)
    if first_len is not None:
        lens[0] = first_len
    return [rng.integers(0, vocab, int(k)).tolist() for k in lens]


def check_served(eng, reqs, label: str) -> None:
    """Every request finished and the clock mirror is exact."""
    done = sum(r.state == "finished" for r in reqs)
    if done != len(reqs):
        raise AssertionError(f"{label}: {done}/{len(reqs)} requests finished")
    engines = getattr(eng, "replicas", [eng])
    for e in engines:
        clock = int(e.pool.clock)
        if e.stats.warnings_fired != clock:
            raise AssertionError(
                f"{label}: clock mirror {e.stats.warnings_fired} != pool "
                f"clock {clock}")


def _rel_l2(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def attention_parity(eng, impl: str, seed: int) -> dict:
    """Attention over the engine's LIVE arena and block tables (last layer):
    ``impl`` against the jnp reference, decode form (C=1) and chunk form
    (C = the engine's prefill chunk).  Raises past :data:`ATTN_TOL`;
    returns the worst absolute error of each form."""
    kvm = eng.kv_manager
    bt, lengths = kvm._bt, kvm._len
    live = np.asarray(jax.device_get(lengths)) > 0
    if not live.any():
        raise AssertionError("parity: no live row to check")
    cfg = eng.cfg
    layer = cfg.n_layers - 1
    kv = {"k": eng.kv["k"][layer], "v": eng.kv["v"][layer]}
    B, C = eng.max_batch, max(eng.prefill_chunk, 1)
    kq, kc = jax.random.split(jax.random.PRNGKey(seed))
    q1 = jax.random.normal(kq, (B, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    qc = jax.random.normal(kc, (B, C, cfg.n_heads, cfg.head_dim),
                           jnp.bfloat16)
    chunk_lens = jnp.clip(lengths, 1, C).astype(jnp.int32)
    out = {}
    for name, q, cl in (("decode", q1, None), ("chunk", qc, chunk_lens)):
        got = paged_attention(q, kv, bt, lengths, impl=impl, chunk_lens=cl,
                              mesh=eng.mesh)
        ref = paged_attention(q, kv, bt, lengths, impl="ref", chunk_lens=cl)
        got = np.asarray(jax.device_get(got), np.float32)[live]
        ref = np.asarray(jax.device_get(ref), np.float32)[live]
        np.testing.assert_allclose(got, ref, atol=ATTN_TOL, rtol=ATTN_TOL,
                                   err_msg=f"{impl} vs ref, {name} form")
        out[name] = float(np.max(np.abs(got - ref)))
    return out


def _pass_report(label, eng, compiles, t0) -> dict:
    st = eng.stats
    rep = {"steps": st.steps, "chunked_steps": st.chunked_steps,
           "spec_steps": st.spec_steps, "tokens": st.tokens_committed,
           "wall_s": time.perf_counter() - t0}
    log(f"[{label}] steps={rep['steps']} chunked_steps={rep['chunked_steps']}"
        f" spec_steps={rep['spec_steps']} tokens={rep['tokens']} "
        f"wall_s={rep['wall_s']:.3f} warnings={st.warnings_fired} "
        f"preemptions={st.preemptions}")
    for name, secs in compiles:
        if secs >= 0.5:
            log(f"[{label}] compile {name}: {secs:.3f}s")
    return rep


def phase_mixed(cfg, params, s: Sizes, *, attn_impl=None, seed: int = 0,
                compile_log: CompileLog | None = None) -> dict:
    """Pass 1: chunked prefill beside decode, with the mid-run parity check."""
    rng = np.random.default_rng(seed)
    mark = len(compile_log.events) if compile_log else 0
    t0 = time.perf_counter()
    eng = PagedServingEngine(cfg, params, **engine_kwargs(s, attn_impl))
    prompts = random_prompts(rng, s.requests, s.prompt_min, s.prompt_max,
                             cfg.vocab, first_len=s.prompt_max)
    reqs = [eng.submit(p, s.max_new) for p in prompts]
    eng.run(max_steps=s.parity_after)
    parity = attention_parity(eng, eng.runner.attn_impl, seed)
    log(f"[mixed] attention {eng.runner.attn_impl} vs ref on the live arena "
        f"after {eng.stats.steps} steps: max_abs_err decode="
        f"{parity['decode']:.3e} chunk={parity['chunk']:.3e} "
        f"(tol {ATTN_TOL})")
    eng.run()
    check_served(eng, reqs, "mixed")
    if not (0 < eng.stats.chunked_steps < eng.stats.steps):
        raise AssertionError(
            f"mixed: expected both the C={s.prefill_chunk} and the C=1 "
            f"executable to run (chunked {eng.stats.chunked_steps} of "
            f"{eng.stats.steps} steps)")
    rep = _pass_report("mixed", eng,
                       compile_log.since(mark) if compile_log else [], t0)
    rep["parity_max_abs_err"] = parity
    rep["attn_impl"] = eng.runner.attn_impl
    return rep


def phase_prefix(cfg, params, s: Sizes, *, attn_impl=None, seed: int = 1,
                 compile_log: CompileLog | None = None) -> dict:
    """Pass 2: requests sharing a system prompt through the prefix cache."""
    rng = np.random.default_rng(seed)
    mark = len(compile_log.events) if compile_log else 0
    t0 = time.perf_counter()
    eng = PagedServingEngine(cfg, params, prefix_cache=True,
                             **engine_kwargs(s, attn_impl))
    shared = rng.integers(0, cfg.vocab, s.shared_prefix).tolist()
    n = max(s.requests // 2, 2)
    tails = random_prompts(rng, n, 8, max(8, s.prompt_max - s.shared_prefix),
                           cfg.vocab,
                           first_len=s.prompt_max - s.shared_prefix)
    # the first request puts the system prompt in the cache; the rest, sent
    # together after it, share its pages
    reqs = [eng.submit(shared + tails[0], s.max_new // 2)]
    eng.run()
    reqs += [eng.submit(shared + t, s.max_new // 2) for t in tails[1:]]
    eng.run()
    check_served(eng, reqs, "prefix")
    if eng.stats.prefix_hits == 0:
        raise AssertionError("prefix: no request reused the shared prefix")
    rep = _pass_report("prefix", eng,
                       compile_log.since(mark) if compile_log else [], t0)
    rep["prefix_hits"] = eng.stats.prefix_hits
    rep["prefix_tokens_reused"] = eng.stats.prefix_tokens_reused
    log(f"[prefix] hits={eng.stats.prefix_hits} tokens_reused="
        f"{eng.stats.prefix_tokens_reused} cow_copies={eng.stats.cow_copies}")
    return rep


def phase_speculative(cfg, params, s: Sizes, *, attn_impl=None,
                      seed: int = 2,
                      compile_log: CompileLog | None = None) -> dict:
    """Pass 3: speculative decoding.  Prompts repeat a short random phrase,
    so the n-gram drafter has something to propose."""
    rng = np.random.default_rng(seed)
    mark = len(compile_log.events) if compile_log else 0
    t0 = time.perf_counter()
    eng = PagedServingEngine(cfg, params, speculative_k=s.spec_k,
                             **engine_kwargs(s, attn_impl))
    n = max(s.requests // 2, 2)
    reqs = []
    for i in range(n):
        phrase = rng.integers(0, cfg.vocab, 8).tolist()
        length = s.prompt_max if i == 0 else int(
            rng.integers(s.prompt_min, s.prompt_max + 1))
        prompt = (phrase * (length // 8 + 1))[:length]
        reqs.append(eng.submit(prompt, s.max_new))
    eng.run()
    check_served(eng, reqs, "speculative")
    if eng.stats.spec_steps == 0:
        raise AssertionError("speculative: the speculative executable never "
                             "ran")
    rep = _pass_report("speculative", eng,
                       compile_log.since(mark) if compile_log else [], t0)
    rep["accept_rate"] = eng.stats.accept_rate
    log(f"[speculative] drafted={eng.stats.tokens_drafted} accepted="
        f"{eng.stats.tokens_accepted} accept_rate={eng.stats.accept_rate:.3f}")
    return rep


def _fleet_prompts(cfg, s: Sizes, seed: int) -> list[list[int]]:
    """Equal-length prompts: every row of every engine runs the same step
    sequence, so per-row results are comparable across engines."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, s.fleet_prompt).tolist()
            for _ in range(s.requests)]


def phase_four_chips(cfg, params, s: Sizes, devices, *, attn_impl=None,
                     seed: int = 3,
                     compile_log: CompileLog | None = None) -> dict:
    """The multi-chip paths against the one-chip engine on the same requests:
    a 4-replica fleet must emit the same tokens; a TP=4 engine must hold its
    live KV arena within :data:`TP_LAYER0_TOL` of one chip at layer 0 and
    within :data:`TP_REL_TOL` at every layer, and attention over it within
    :data:`TP_REL_TOL`."""
    if len(devices) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices; have "
                           f"{len(devices)}")
    devices = list(devices[:4])
    prompts = _fleet_prompts(cfg, s, seed)
    # one executable shape per engine (C=1): the fleet compiles it once per
    # chip, and the comparison needs no other
    kw = engine_kwargs(dataclasses.replace(s, prefill_chunk=1), attn_impl)
    out = {}
    mark = len(compile_log.events) if compile_log else 0

    # the one-chip reference, driven in lockstep with the TP=4 engine until
    # mid-prefill (identical schedules: same block tables and lengths)
    t0 = time.perf_counter()
    one = PagedServingEngine(cfg, params, device=devices[0], **kw)
    tp = PagedServingEngine(cfg, params, tensor_parallel=4, devices=devices,
                            **kw)
    one_reqs = [one.submit(p, s.fleet_max_new) for p in prompts]
    tp_reqs = [tp.submit(p, s.fleet_max_new) for p in prompts]
    mid = max(2, s.fleet_prompt // 2)
    one.run(max_steps=mid)
    tp.run(max_steps=mid)
    for name in ("_bt", "_len"):
        a = np.asarray(jax.device_get(getattr(one.kv_manager, name)))
        b = np.asarray(jax.device_get(getattr(tp.kv_manager, name)))
        if not np.array_equal(a, b):
            raise AssertionError(f"tp4: {name} diverged from one chip")
    bt = np.asarray(jax.device_get(one.kv_manager._bt))
    lens = np.asarray(jax.device_get(one.kv_manager._len))
    pages = np.asarray(sorted({int(p) for p in bt[lens > 0].ravel()
                               if p >= 0}))
    live = [{n: np.asarray(jax.device_get(e.kv[n][:, pages]), np.float32)
             for n in ("k", "v")} for e in (tp, one)]
    kv_err = [max(_rel_l2(live[0][n][i], live[1][n][i]) for n in ("k", "v"))
              for i in range(cfg.n_layers)]
    layer = cfg.n_layers - 1
    tp_parity = attention_parity(tp, tp.runner.attn_impl, seed)
    key = jax.random.PRNGKey(seed + 1)
    q = jax.random.normal(key, (s.max_batch, cfg.n_heads, cfg.head_dim),
                          jnp.bfloat16)

    def last_layer_attention(e):
        kvl = {"k": e.kv["k"][layer], "v": e.kv["v"][layer]}
        return np.asarray(jax.device_get(paged_attention(
            q, kvl, e.kv_manager._bt, e.kv_manager._len,
            impl=e.runner.attn_impl, mesh=e.mesh)), np.float32)[lens > 0]

    att_err = _rel_l2(last_layer_attention(tp), last_layer_attention(one))
    log(f"[tp4] vs one chip after {mid} steps: kv rel_l2 by layer "
        f"{' '.join(f'{x:.3e}' for x in kv_err)} (layer 0 tol "
        f"{TP_LAYER0_TOL}, all layers tol {TP_REL_TOL}); attention rel_l2="
        f"{att_err:.3e} (tol {TP_REL_TOL}); sharded {tp.runner.attn_impl} "
        f"vs ref max_abs_err decode={tp_parity['decode']:.3e} "
        f"chunk={tp_parity['chunk']:.3e}")
    if (kv_err[0] > TP_LAYER0_TOL or max(kv_err) > TP_REL_TOL
            or att_err > TP_REL_TOL):
        raise AssertionError(
            f"tp4: kv rel_l2 layer 0 {kv_err[0]:.3e} (tol {TP_LAYER0_TOL}), "
            f"worst layer {max(kv_err):.3e}, attention {att_err:.3e} (tol "
            f"{TP_REL_TOL})")
    one.run()
    tp.run()
    check_served(one, one_reqs, "one-chip")
    check_served(tp, tp_reqs, "tp4")
    ref_tokens = [r.output_tokens for r in one_reqs]
    same = sum(r.output_tokens == t for r, t in zip(tp_reqs, ref_tokens))
    log(f"[tp4] requests with tokens identical to one chip: "
        f"{same}/{len(prompts)} (reported, not required: bf16 "
        f"re-association can flip a near-tie argmax)")
    out["tp4"] = {"kv_rel_l2": kv_err, "attention_rel_l2": att_err,
                  "parity_max_abs_err": tp_parity, "tokens_same": same}
    # a request holds its engine (``Request._engine``): drop the requests
    # too, or both arenas stay on the chips beside the fleet's
    engines = [weakref.ref(one), weakref.ref(tp)]
    del one, tp, one_reqs, tp_reqs
    gc.collect()
    if any(e() is not None for e in engines):
        raise AssertionError("four-chips: the one-chip or TP=4 engine is "
                             "still referenced; its arena would stay on "
                             "the chips")
    log("[four-chips] bytes in use before the fleet: " + " ".join(
        str((d.memory_stats() or {}).get("bytes_in_use")) for d in devices))

    fleet = DataParallelEngine(cfg, params, replicas=4, devices=devices, **kw)
    fleet_reqs = [fleet.submit(p, s.fleet_max_new) for p in prompts]
    fleet.run()
    check_served(fleet, fleet_reqs, "fleet4")
    used = sorted({str(r._engine.device) for r in fleet_reqs})
    same = sum(r.output_tokens == t for r, t in zip(fleet_reqs, ref_tokens))
    log(f"[fleet4] replicas on {used}; requests with tokens identical to one "
        f"chip: {same}/{len(prompts)}")
    if same != len(prompts):
        raise AssertionError(f"fleet4: {len(prompts) - same} requests' "
                             f"tokens differ from the one-chip engine")
    out["fleet4"] = {"tokens_same": same, "devices_used": len(used)}
    del fleet
    gc.collect()
    out["wall_s"] = time.perf_counter() - t0
    for name, secs in (compile_log.since(mark) if compile_log else []):
        if secs >= 0.5:
            log(f"[four-chips] compile {name}: {secs:.3f}s")
    return out


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the replica-fleet and TP=4 paths on four "
                         "chips, each against the one-chip engine")
    args = ap.parse_args(argv)

    devs = require_tpu()
    cache_dir = enable_compile_cache()
    log(f"compile cache: {cache_dir}")
    compile_log = CompileLog()
    cfg = get_config("olmo-1b")
    log(f"model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} (published widths)")
    t0 = time.perf_counter()
    params = init_params(cfg, SEED)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"params: {n_params} bf16 on {devs[0]} in "
        f"{time.perf_counter() - t0:.3f}s")
    s = Sizes()
    if args.four_chips:
        if len(devs) < 4:
            print(f"chip_smoke: --four-chips needs 4 chips; JAX sees "
                  f"{len(devs)}", file=sys.stderr)
            sys.exit(2)
        phase_four_chips(cfg, params, s, devs, seed=SEED + 3,
                         compile_log=compile_log)
    else:
        log(f"pool: {s.num_pages} pages x {s.page_size} tokens = "
            f"{2 * cfg.n_layers * s.num_pages * s.page_size * cfg.n_kv_heads * cfg.head_dim * 2 / 2**30:.2f} GiB "
            f"KV arena; max_batch={s.max_batch} "
            f"prefill_chunk={s.prefill_chunk}")
        rep = phase_mixed(cfg, params, s, seed=SEED,
                          compile_log=compile_log)
        if rep["attn_impl"] != "pallas":
            raise AssertionError(f"attention ran {rep['attn_impl']!r}, not "
                                 f"the compiled Pallas kernel")
        gc.collect()
        phase_prefix(cfg, params, s, seed=SEED + 1,
                     compile_log=compile_log)
        gc.collect()
        phase_speculative(cfg, params, s, seed=SEED + 2,
                          compile_log=compile_log)
    total = sum(secs for _, secs in compile_log.events)
    log(f"compile total: {total:.3f}s over {len(compile_log.events)} "
        f"programs")
    for d in devs[: 4 if args.four_chips else 1]:
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        log(f"peak_bytes_in_use {d}: {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
