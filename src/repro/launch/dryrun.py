# The dry-run needs 512 placeholder host devices so jax.make_mesh can build
# the production mesh.  MUST run before any other import — jax locks the
# device count at first init.  Never set this globally: smoke tests and
# benchmarks must see 1 device.
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (  # noqa: E402
    ARCH_IDS,
    SHAPES,
    cell_supported,
    decode_cache_size,
    get_config,
    input_specs,
)
from repro.launch.hlo_analysis import analyze  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import AdamWConfig, adamw_init  # noqa: E402
from repro.sharding import rules  # noqa: E402


def _named(tree_specs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def lower_cell(cfg, model, shape, mesh, *, grad_compression="none"):
    """Build + lower the step function for one (arch, shape) cell."""
    params_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pshard = _named(
        rules.param_specs(cfg, params_sds, mesh, serving=(shape.kind == "decode")),
        mesh)
    batch_sds = input_specs(cfg, shape)
    bshard = _named(rules.batch_specs(cfg, batch_sds, mesh), mesh)

    if shape.kind == "train":
        opt_sds = jax.eval_shape(adamw_init, params_sds)
        oshard = _named(rules.opt_specs(cfg, params_sds, mesh), mesh)
        step = make_train_step(model, AdamWConfig(), grad_compression=grad_compression)
        jitted = jax.jit(
            step,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1),
        )
        return jitted.lower(params_sds, opt_sds, batch_sds)

    if shape.kind == "prefill":
        step = make_prefill_step(model, shape.seq_len)
        cache_sds = jax.eval_shape(step, params_sds, batch_sds)[0]
        cshard = _named(rules.cache_specs(cfg, cache_sds, mesh), mesh)
        jitted = jax.jit(step, in_shardings=(pshard, bshard),
                         out_shardings=(cshard, None))
        return jitted.lower(params_sds, batch_sds)

    # decode: one new token against a cache of decode_cache_size slots
    cache_size = decode_cache_size(cfg, shape)
    cache_sds = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, cache_size)
    )
    cshard = _named(rules.cache_specs(cfg, cache_sds, mesh), mesh)
    step = make_decode_step(model)
    jitted = jax.jit(
        step,
        in_shardings=(pshard, cshard, bshard),
        out_shardings=(None, cshard),
        donate_argnums=(1,),
    )
    return jitted.lower(params_sds, cache_sds, batch_sds)


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             save_hlo: bool = False, grad_compression: str = "none") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "grad_compression": grad_compression}
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        model = build_model(cfg)
        t0 = time.time()
        with jax.set_mesh(mesh):
            lowered = lower_cell(cfg, model, shape, mesh,
                                 grad_compression=grad_compression)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        txt = compiled.as_text()
        hlo = analyze(txt, n_shards_hint=mesh.shape["model"])
        rec.update(
            status="ok",
            lower_seconds=round(t_lower, 2),
            compile_seconds=round(t_compile, 2),
            memory=dict(
                argument_bytes=ma.argument_size_in_bytes,
                output_bytes=ma.output_size_in_bytes,
                temp_bytes=ma.temp_size_in_bytes,
                alias_bytes=ma.alias_size_in_bytes,
                peak_bytes_est=ma.argument_size_in_bytes + ma.temp_size_in_bytes,
            ),
            cost_analysis_raw={
                "flops": ca.get("flops", 0.0),
                "bytes_accessed": ca.get("bytes accessed", 0.0),
            },
            hlo=hlo,
            hlo_text_bytes=len(txt),
        )
        if save_hlo:
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(
                out_dir, f"{arch}_{shape_name}_{mesh_name}.hlo.txt"), "w") as f:
                f.write(txt)
    except Exception as e:  # a failure here is a bug in the system
        rec.update(status="error", error=repr(e), traceback=traceback.format_exc())
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run: lower+compile "
                                 "every (arch x shape) on the production mesh")
    ap.add_argument("--arch", default="all", help=f"one of {ARCH_IDS} or 'all'")
    ap.add_argument("--shapes", default="all",
                    help=f"comma list of {list(SHAPES)} or 'all'")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out-dir", default="artifacts/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--grad-compression", default="none", choices=["none", "bf16"])
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shapes == "all" else args.shapes.split(",")
    os.makedirs(args.out_dir, exist_ok=True)

    for arch in archs:
        for shape_name in shapes:
            mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
            tag = f"{arch}_{shape_name}_{mesh_name}"
            if args.grad_compression != "none":
                tag += f"_gc{args.grad_compression}"
            path = os.path.join(args.out_dir, tag + ".json")
            if os.path.exists(path):
                print(f"[dryrun] {tag}: exists, skipping")
                continue
            print(f"[dryrun] {tag}: lowering...", flush=True)
            rec = run_cell(arch, shape_name, args.multi_pod, args.out_dir,
                           save_hlo=args.save_hlo,
                           grad_compression=args.grad_compression)
            with open(path, "w") as f:
                json.dump(rec, f, indent=2)
            status = rec["status"]
            extra = ""
            if status == "ok":
                extra = (f"compile={rec['compile_seconds']}s "
                         f"peak={rec['memory']['peak_bytes_est']/2**30:.2f}GiB/dev "
                         f"dotTFLOP={rec['hlo']['dot_flops']/1e12:.3f} "
                         f"coll={rec['hlo']['collective_bytes_total']/2**30:.3f}GiB")
            elif status == "error":
                extra = rec["error"][:200]
            else:
                extra = rec["reason"][:80]
            print(f"[dryrun] {tag}: {status} {extra}", flush=True)


if __name__ == "__main__":
    main()
