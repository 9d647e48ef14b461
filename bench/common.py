"""What every cell shares: the spec, seeds, the compile cache, the device
and the result line.

Nothing here imports JAX at module level, so the harness can read its
files and refuse a bad checkout before the accelerator is touched.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

#: Reading of the host clock when the harness was imported: the start of
#: ``setup_s`` (interpreter start-up before it is a few tens of ms).
T_START = time.perf_counter()


class SetupError(RuntimeError):
    """The run cannot start: no accelerator, a missing file, a bad name."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(spec_: dict, name: str) -> dict:
    for w in spec_["workloads"]:
        if w["name"] == name:
            return w
    raise SetupError(f"no workload named {name!r} in BENCHMARK.json")


def config_file(spec_: dict, name: str) -> dict:
    for c in spec_["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise SetupError(f"no config named {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> dict:
    path = BENCH / "workloads" / f"{name}.json"
    if not path.is_file():
        raise SetupError(f"no traffic mix {path}")
    return load_json(path)


@functools.lru_cache(maxsize=None)
def model_module(arch: str):
    """The family module ``bench/models/<arch>.py`` of the model whose
    configuration file names ``arch`` first in ``architectures``: its
    sizes, program config, weight shapes, reference and work (the names
    ``bench/models/InternLM2ForCausalLM.py`` lists). Loaded once."""
    path = BENCH / "models" / f"{arch}.py"
    if not path.is_file():
        raise SetupError(f"no model module bench/models/{arch}.py for "
                         f"architecture {arch!r}")
    return load_file(path, f"bench_model_{arch}")


def load_file(path, name: str):
    """The module in file ``path``, imported under ``name``."""
    spec_ = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod


def metrics_for(spec_: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: with ``trace`` its per-layer metrics,
    else its end-to-end ones. A metric without a ``workloads`` key is
    reported where the end-to-end metric it moves (or, end to end, every
    cell) is."""
    e2e = [m for m in spec_["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec_["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and ("workloads" in m or m["moves"] in moved)]


def seeds(seed: int, n: int) -> list[int]:
    """``n`` independent 31-bit seeds from one seed of any size."""
    import numpy as np

    ss = np.random.SeedSequence(int(seed))
    return [int(s) for s in ss.generate_state(n, np.uint32) >> 1]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one. Every
    program is cached, however quick to compile, so a second run of a cell
    compiles nothing."""
    import jax

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; anything else is a SetupError."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise SetupError(f"{chips} chips asked for, {len(devs)} found")
    return devs[:chips]


def device_report(devs) -> dict:
    import jax

    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts backend compilations (not persistent-cache hits) while
    ``on``; the window must see none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.on = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, name, _dur, **_kw):
        if self.on and name == self.EVENT:
            self.count += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(result: dict, checks: dict) -> dict:
    """Print the checks as the last lines of stderr and the result as the
    last line of stdout, with the checks under the key that comes last."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return line
