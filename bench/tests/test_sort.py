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
    got = sort.check_output(k, [(ck, cv, 4096)], 0, devices=run.devices)
    assert got["keys_wrong"] > run.limits["keys_wrong"]
    assert got["pairs_wrong"] == 0


FOUR = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
jax.config.update("jax_enable_compilation_cache", False)
from bench import sort
from bench.tests.conftest import tiny_run


def per_rank(body):
    # each rank hands back body(its shard); nothing crosses between chips
    def hook(fn, mesh):
        from jax.sharding import PartitionSpec as P
        from repro.core import compat
        from repro.core.distributed import ShardedSort

        def local(k, v):
            sk, sv = body(k, v)
            n = k.shape[0]
            return ShardedSort(sk, sv, jax.numpy.full((1,), n),
                               jax.numpy.zeros((1,), jax.numpy.int32),
                               jax.numpy.zeros((1, 4), jax.numpy.int32))
        return jax.jit(compat.shard_map(
            local, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=ShardedSort(P("data"), P("data"), P("data"),
                                  P("data"), P("data")), check_vma=False))
    return hook


def no_exchange(k, v):
    from repro import core as ak
    return ak.merge_sort_by_key(k, v)


def altered(fn, mesh):
    # two keys of rank 0's output swapped where they are produced
    def wrong(k, v):
        out = fn(k, v)
        return out._replace(values=out.values.at[:2].set(out.values[1::-1]))
    return jax.jit(wrong)


def half(fn, mesh):
    # half of the pairs left out: SIHSort of the first half alone
    return lambda k, v: fn(k[:k.shape[0] // 2], v[:v.shape[0] // 2])


hooks = {"sound": None, "no_exchange": per_rank(no_exchange),
         "unchanged": per_rank(lambda k, v: (k, v)), "altered": altered,
         "half": half}
out = {}
for name, hook in hooks.items():
    run = tiny_run("sortkv_f32_i32_2e26.sihsort_4chip", seconds=0.5)
    sort.run(run, program_hook=hook)
    out[name] = {"correct": run.result["correct"],
                 **{k: c["value"] for k, c in run.result["checks"].items()}}
# the control as bench.control reads it: the bfloat16-keyed reference
# sort cut into one part per rank, in the program's place
from bench import control
run = tiny_run("sortkv_f32_i32_2e26.sihsort_4chip")
out["control"] = control._sort(run, [], [run.seed])[0]["control"]
# one output checked rank by rank on four chips, and all on one
from bench import reference
k = jax.random.normal(jax.random.PRNGKey(5), (4096,))
ck, cv = reference.bf16_sort(k)
parts = [(ck[r * 1024:(r + 1) * 1024], cv[r * 1024:(r + 1) * 1024], 1024)
         for r in range(4)]
out["apart"] = sort.check_output(k, parts, 0, devices=run.devices)
out["one_chip"] = sort.check_output(k, parts, 0, devices=run.devices[:1])
out["limits"] = run.limits
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four():
    """A tiny run of the four-chip cell on four virtual CPU devices,
    sound and with each fault the distributed path can have."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", FOUR, str(ROOT)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_four_ranks_sound_and_without_exchange(four):
    assert four["sound"] == {"correct": True, "keys_wrong": 0,
                             "pairs_wrong": 0, "overflow": 0}
    assert four["no_exchange"]["keys_wrong"] > 0
    assert four["no_exchange"]["correct"] is False


@pytest.mark.parametrize("fault", ["unchanged", "altered", "half"])
def test_four_ranks_broken_path_is_not_correct(four, fault):
    assert four[fault]["correct"] is False, four[fault]


def test_four_ranks_control_fails(four):
    assert four["control"]["keys_wrong"] > four["limits"]["keys_wrong"]
    assert four["control"]["pairs_wrong"] == 0


def test_four_ranks_checked_apart_read_as_on_one_chip(four):
    """Each rank checked on its own chip counts what one chip counts."""
    assert four["apart"] == four["one_chip"]
    assert four["apart"]["keys_wrong"] > 0
