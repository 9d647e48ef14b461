"""The benchmark's files: every name in BENCHMARK.json resolves to a file,
the run refuses a machine without a TPU, and a new cell or metric needs
only new files."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import common, run as R

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = common.spec()


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics] + [w["name"] for w in
                                            SPEC["workloads"]]
    names += [c["name"] for c in SPEC["configs"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(m["name"] for m in metrics)) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_files_load(cell):
    config = common.config_file(SPEC, cell["config"])
    traffic = common.traffic_file(cell["traffic"])
    limits = common.load_json(common.BENCH / "limits"
                              / f"{cell['name']}.json")
    assert config["kind"] == ("model" if traffic["kind"] == "serve_backlog"
                              else "sort")
    assert all(isinstance(v, (int, float)) for v in limits.values())
    assert cell["chips"] in (1, 4)
    e2e = common.metrics_for(SPEC, cell["name"], trace=False)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert common.metrics_for(SPEC, cell["name"], trace=True)


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_every_reader_found_by_name(metric):
    assert callable(R.reader(metric["name"]))


def _bench_run(cwd, *args, env=None):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(
        [sys.executable, "-m", "bench.run", *args], cwd=cwd, env=full,
        capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_no_tpu_no_result():
    proc = _bench_run(common.ROOT, "--workload",
                      "sortkv_f32_i32.local_1chip", "--seed",
                      str(2 ** 33 + 1), "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and _no_result(proc)
    assert "no TPU" in proc.stderr


def _checkout(tmp_path):
    """BENCHMARK.json and bench/ alone."""
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_bench_alone_fails(tmp_path):
    root = _checkout(tmp_path)
    proc = _bench_run(root, "--workload", "sortkv_f32_i32.local_1chip",
                      "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and _no_result(proc)
    assert "no program" in proc.stderr


@pytest.mark.parametrize("with_reader", [True, False])
def test_new_cell_and_metric_need_only_new_files(tmp_path, with_reader):
    """A cell of a new traffic mix with a new per-layer metric: new files
    and new entries only. With every file there the run gets as far as
    the look for a chip; without the reader it stops at the name."""
    root = _checkout(tmp_path)
    os.symlink(common.ROOT / "src", root / "src")
    bench = root / "bench"
    mix = json.loads((bench / "workloads" / "decode_backlog.json")
                     .read_text())
    mix["slots"] = 32
    (bench / "workloads" / "decode_half.json").write_text(json.dumps(mix))
    (bench / "limits" / "internlm2_1_8b.decode_half.json").write_text(
        (bench / "limits" / "internlm2_1_8b.decode_backlog.json")
        .read_text())
    if with_reader:
        (bench / "metrics" / "queue_depth.py").write_text(
            "def read(ctx):\n    return None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({
        "name": "internlm2_1_8b.decode_half", "config": "internlm2_1_8b",
        "traffic": "decode_half", "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "queue_depth", "unit": "requests", "better": "lower",
        "source": "program_counter", "layer": "engine scheduler",
        "moves": "serve_tok_s", "workloads": ["internlm2_1_8b.decode_half"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    proc = _bench_run(root, "--workload", "internlm2_1_8b.decode_half",
                      "--seed", "3", "--seconds", "1", "--trace", "1")
    assert proc.returncode != 0 and _no_result(proc)
    want = "no TPU" if with_reader else "no reader"
    assert want in proc.stderr, proc.stderr[-2000:]
