"""The sort runner end to end at a tiny size on the CPU: a sound run is
correct, a run with its timed path broken underneath is not, and the
bfloat16 control fails the check."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from bench import sort
from bench.common import ROOT
from bench.tests.conftest import tiny_run

CELL = "sortkv_f32_i32.local_1chip"


def test_sound_run_is_correct():
    run = tiny_run(CELL)
    sort.run(run)
    out = run.result
    assert out["correct"] and out["attempted"] > 0
    assert set(out["metrics"]) == {"sort_gbps", "setup_s"}
    assert list(out)[-1] == "checks"


def _altered(fn, mesh):
    """An answer altered where it is produced: two keys swapped."""
    def wrong(k, v):
        ok, ov = fn(k, v)
        return ok.at[:2].set(ok[1::-1]), ov
    return wrong


def _unchanged(fn, mesh):
    """A sort that hands back its input as it got it."""
    def same(k, v):
        fn(k, v)
        return k, v
    return same


def _half(fn, mesh):
    """Half of the pairs left out: the first half sorted, the rest kept."""
    def half(k, v):
        h = k.shape[0] // 2
        sk, sv = fn(k[:h], v[:h])
        return k.at[:h].set(sk), v.at[:h].set(sv)
    return half


@pytest.mark.parametrize("fault", [_altered, _unchanged, _half])
def test_broken_path_is_not_correct(fault):
    run = tiny_run(CELL, seconds=0.5)
    sort.run(run, program_hook=fault)
    assert run.result["correct"] is False, run.result["checks"]


def test_control_fails():
    import jax

    from bench import reference

    run = tiny_run(CELL)
    k = jax.random.normal(jax.random.PRNGKey(3), (4096,))
    ck, cv = reference.bf16_sort(k)
    got = sort.check_output(k, [(ck, cv, 4096)], 0, device=run.devices[0])
    assert got["keys_wrong"] > run.limits["keys_wrong"]
    assert got["pairs_wrong"] == 0


FOUR = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
jax.config.update("jax_enable_compilation_cache", False)
from bench import sort
from bench.tests.conftest import tiny_run
run = tiny_run("sortkv_f32_i32.sihsort_4chip", seconds=0.5)
out = {}
w = sort.sort_window(run)
out["sound"] = sort.readings(w, run.devices)

def no_exchange(fn, mesh):
    # each rank sorts its own shard; nothing crosses between chips
    from jax.sharding import PartitionSpec as P
    from repro import core as ak
    from repro.core import compat
    from repro.core.distributed import ShardedSort

    def local(k, v):
        sk, sv = ak.merge_sort_by_key(k, v)
        n = k.shape[0]
        return ShardedSort(sk, sv, jax.numpy.full((1,), n),
                           jax.numpy.zeros((1,), jax.numpy.int32),
                           jax.numpy.zeros((1, 4), jax.numpy.int32))
    return jax.jit(compat.shard_map(
        local, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=ShardedSort(P("data"), P("data"), P("data"), P("data"),
                              P("data")), check_vma=False))

w = sort.sort_window(run, program_hook=no_exchange)
out["no_exchange"] = sort.readings(w, run.devices)
print(json.dumps(out))
"""


def test_four_ranks_sound_and_without_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", FOUR, str(ROOT)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["sound"] == {"keys_wrong": 0, "pairs_wrong": 0,
                            "overflow": 0}
    assert got["no_exchange"]["keys_wrong"] > 0
