"""CPU fixtures of the benchmark's tests: tiny runs of each runner.

Run from the repository root:
``JAX_PLATFORMS=cpu python -m pytest -q bench/tests``.
"""
from __future__ import annotations

import os
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: Tiny stand-ins for the cells' sizes: the same runners, configuration
#: keys and checks, at a size the CPU runs in seconds (a model's own from
#: its family module's ``TINY``).
TINY_SERVE = dict(slots=4, cache_len=64, prompt_pad=32, warmup_max_new=4,
                  check_requests=3, trace_seconds=1,
                  prompt_len=dict(dist="lognormal", median=12, sigma=0.5,
                                  min=4, max=32),
                  output_len=dict(dist="lognormal", median=8, sigma=0.5,
                                  min=4, max=24))
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_run(cell_name: str, *, seed: int = 2 ** 33 + 5, seconds: float = 2,
             trace: bool = False, chips: int | None = None):
    """A ``bench.run.Run`` of ``cell_name`` shrunk to CPU size, without
    the look for a chip."""
    import jax

    from bench import common, run as R

    spec = common.spec()
    try:
        cell = dict(common.cell(spec, cell_name))
    except common.SetupError:
        # a cell whose files are here but which BENCHMARK.json does not
        # list yet: <config>.<traffic>, four chips for a distributed mix
        config_name, traffic_name = cell_name.split(".", 1)
        mix = common.traffic_file(traffic_name)
        cell = {"name": cell_name, "config": config_name,
                "traffic": traffic_name,
                "chips": 4 if mix.get("distributed") else 1}
    config = dict(common.config_file(spec, cell["config"]))
    traffic = dict(common.traffic_file(cell["traffic"]))
    limits = common.load_json(common.BENCH / "limits" / f"{cell_name}.json")
    if traffic["kind"] == "serve_backlog":
        config.update(common.model_module(config["architectures"][0]).TINY)
        traffic.update(TINY_SERVE)
    else:
        config["n_per_chip"] = 4096
        traffic["trace_seconds"] = 0.5
    listed = common.metrics_for(spec, cell_name, trace)
    readers = {m["name"]: R.reader(m["name"]) if trace else None
               for m in listed}
    devs = jax.devices()[:chips or cell["chips"]]
    return R.Run(spec, cell, config, traffic, limits, seed=seed,
                 seconds=seconds, trace=trace, devices=devs,
                 readers=readers, peaks=FAKE_PEAKS)


@pytest.fixture(autouse=True, scope="session")
def _no_persistent_cache():
    """The tests compile for the CPU; keep them out of the chip's cache."""
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
