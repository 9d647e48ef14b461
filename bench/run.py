"""Run one benchmark cell on the chips of this machine.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration, traffic mix and limits are found by the names
in ``BENCHMARK.json``: ``bench/configs/<config>.json``,
``bench/workloads/<traffic>.json`` and ``bench/limits/<cell>.json``; each
per-layer metric is read by ``bench/metrics/<metric>.py``, and a model
configuration's family by ``bench/models/<architectures[0]>.py``. The
traffic file's ``kind`` picks the runner: ``serve_backlog``
(``bench/serve.py``) or ``sort`` (``bench/sort.py``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (with ``--trace 0`` the cell's end-to-end
metrics, with ``--trace 1`` its per-layer ones, from a profiler trace of
a slice of the window), ``device`` and, last, ``checks``: every number
compared with its limit. The run fails, and prints no result, when it
finds no TPU, fewer chips than the cell asks for, or no program to run
(``src/repro`` beside ``bench/``).
"""
from __future__ import annotations

import argparse
import os
import pathlib
import shutil
import sys
import tempfile
import time

from bench import common
from bench.common import BENCH, ROOT, SetupError


def reader(name: str):
    """The ``read(ctx)`` function of per-layer metric ``name``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SetupError(f"no reader {path} for per-layer metric {name!r}")
    return common.load_file(path, f"bench_metric_{name}").read


class Tracer:
    """Profiler over a slice of the window: starts at the ``start_at``-th
    step (a decode step, or a sort) and stops ``seconds`` later."""

    def __init__(self, seconds: float, start_at: int = 2):
        self.seconds = seconds
        self.start_at = start_at
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.running = False
        self.window_s = None
        self._t0 = 0.0

    def on_step(self, step: int) -> None:
        if not self.running and self.window_s is None \
                and step >= self.start_at:
            self.start()
        elif self.running and self.elapsed() >= self.seconds:
            self.stop()

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.running = True
        self._t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self._t0

    def stop(self) -> None:
        import jax

        self.window_s = self.elapsed()
        jax.profiler.stop_trace()
        self.running = False

    def load(self):
        from bench.trace import Trace

        found = sorted(pathlib.Path(self.dir).rglob("*.xplane.pb"))
        try:
            # a window too short to reach the slice leaves nothing to read
            return Trace.load(found[-1]) if found else Trace({}, {}, [])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Context:
    """What a per-layer reader sees."""

    def __init__(self, trace, window_s, layer, peaks):
        from bench.trace import NAMES

        self.trace = trace
        self.window_s = window_s
        self.layer = layer
        self.peaks = peaks
        self.names = NAMES
        self.devices = trace.devices


class Run:
    """One run of one cell: its inputs, and ``finish``, which turns what a
    runner measured into the result line."""

    def __init__(self, spec, cell, config, traffic, limits, *, seed,
                 seconds, trace, devices, readers, peaks):
        self.spec, self.cell = spec, cell
        self.config, self.traffic, self.limits = config, traffic, limits
        self.seed, self.seconds = seed, seconds
        self.devices = devices
        self.readers = readers
        self.peaks = peaks
        self.compiles = common.CompileCounter()
        self.tracer = Tracer(traffic["trace_seconds"]) if trace else None
        self.result = None

    def finish(self, e2e, layer, checks, *, attempted, failed, device):
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        units = {m["name"]: m["unit"]
                 for m in self.spec["end_to_end"] + self.spec["per_layer"]}
        out = {"correct": correct, "attempted": attempted,
               "failed": failed, "device": device}
        if self.tracer is None:
            out["metrics"] = {m: {"value": e2e[m], "unit": units[m]}
                              for m in self.readers}
        else:
            trace = self.tracer.load()
            ctx = Context(trace, self.tracer.window_s or 0.0, layer,
                          self.peaks)
            metrics = {}
            for name, read in self.readers.items():
                value = read(ctx)
                if value is not None:
                    metrics[name] = {"value": value, "unit": units[name]}
            out["metrics"] = metrics
            device["busy_s"] = trace.mean_busy_s()
            device["window_s"] = self.tracer.window_s or 0.0
            out["breakdown"] = {"device_ops": trace.top_ops(10),
                                "idle_gaps": trace.idle_gaps(10)}
        self.result = common.emit(out, checks)


def peaks(kind: str) -> dict:
    """Published peaks of a device kind; an unknown kind is an error."""
    table = common.load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SetupError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def prepare(argv=None, *, require_tpu: bool = True):
    """Everything a run needs, checked before the device is touched."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = common.spec()
    cell = common.cell(spec, args.workload)
    config = common.config_file(spec, cell["config"])
    traffic = common.traffic_file(cell["traffic"])
    limits_path = BENCH / "limits" / f"{cell['name']}.json"
    if not limits_path.is_file():
        raise SetupError(f"no limits {limits_path}")
    limits = common.load_json(limits_path)
    if config["kind"] == "model":
        # a family without a module stops here, before the device is touched
        common.model_module(config["architectures"][0])
    listed = common.metrics_for(spec, cell["name"], bool(args.trace))
    readers = ({m["name"]: reader(m["name"]) for m in listed} if args.trace
               else {m["name"]: None for m in listed})
    if not (ROOT / "src" / "repro").is_dir():
        raise SetupError(f"no program: {ROOT / 'src' / 'repro'} is missing")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    # libtpu logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    common.enable_compile_cache()
    devices = (common.tpu_devices(cell["chips"]) if require_tpu
               else jax.devices()[:cell["chips"]])
    return Run(spec, cell, config, traffic, limits, seed=args.seed,
               seconds=args.seconds, trace=bool(args.trace),
               devices=devices, readers=readers,
               peaks=peaks(devices[0].device_kind) if require_tpu else None)


def runner(run):
    from bench import serve, sort

    kinds = {"serve_backlog": serve, "sort": sort}
    kind = run.traffic["kind"]
    if kind not in kinds:
        raise SetupError(f"no runner for traffic kind {kind!r}")
    return kinds[kind]


def main(argv=None) -> int:
    try:
        run = prepare(argv)
        drv = runner(run)
    except SetupError as e:
        common.log(f"bench.run: {e}")
        return 2
    drv.run(run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
