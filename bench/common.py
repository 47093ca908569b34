"""What every part of the benchmark shares: where things are, how a cell,
a configuration and a traffic mix are found by name, and the chip checks.

``require_tpu`` and ``CompileLog`` are copies of the bring-up smoke's
(``chip_smoke.py``), kept here so that no later change to the program can
move the yardstick.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from importlib import metadata

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: JAX's persistent compilation cache: a fixed path inside the checkout, so
#: that the second run of a cell finds every program the first one built
CACHE_DIR = ROOT / ".jax_cache"
#: traces and other run output that is too large for standard output
OUT_DIR = ROOT / ".bench_out"


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(name: str) -> tuple[dict, dict, dict]:
    """The cell ``name`` of ``BENCHMARK.json``, its configuration entry and
    its traffic parameters (``bench/traffic/<traffic>.json``)."""
    spec = benchmark()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = configs[cell["config"]]
    traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    return cell, config, traffic


_LOADED: dict = {}


def load_module(root: pathlib.Path, where: str, name: str):
    """``bench/<where>/<name>.py`` of the checkout ``root``, found by its
    name: an engine, a traffic generator, a metric's reader.  Each file is
    loaded once per process."""
    path = (pathlib.Path(root) / "bench" / where / f"{name}.py").resolve()
    if path in _LOADED:
        return _LOADED[path]
    if not path.is_file():
        raise FileNotFoundError(f"no {where} module {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{where}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    _LOADED[path] = mod
    return mod


def metrics_for_spec(spec: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell_name`` reports: its end-to-end metrics
    with ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def seed_key_words(seed: int) -> tuple[int, int]:
    """A seed of any size up to 64 bits as two 32-bit words."""
    if seed < 0 or seed >= 2**64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    return seed % 2**32, seed // 2**32


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_tpu(chips: int) -> list:
    """The devices JAX sees; exit non-zero unless they are at least
    ``chips`` TPUs.  There is no CPU fallback."""
    import jax

    devs = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__} libtpu={libtpu}")
    if devs[0].platform != "tpu":
        log(f"bench: no TPU found (JAX platform is {devs[0].platform!r}); "
            f"the benchmark has no CPU fallback")
        sys.exit(2)
    if len(devs) < chips:
        log(f"bench: the cell needs {chips} chips; JAX sees {len(devs)}")
        sys.exit(2)
    return devs


class CompileLog:
    """Backend compile durations by function name, from JAX's monitoring
    events (a persistent-cache hit is timed the same way, so a warm cache
    shows as shorter compiles)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **kw):
        if event == self.EVENT:
            self.events.append((str(kw.get("fun_name", "?")), float(secs)))

    def since(self, mark: int) -> list[tuple[str, float]]:
        return self.events[mark:]


def enable_cache() -> str:
    """Keep JAX's persistent compilation cache at :data:`CACHE_DIR`, for
    every program however quickly it compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)
