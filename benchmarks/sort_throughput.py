"""Sort-throughput gate — counted launches, modelled HBM traffic, GB/s.

The paper's headline number is sorting throughput; the thing that decides
it on-device is how many kernel launches and full-array HBM round-trips the
network makes. This benchmark pins both, *counted not estimated*:

  * launches: ``sort_kernel`` increments a counter per ``pl.pallas_call``;
    tracing the sort under ``jax.eval_shape`` counts exactly the launches
    one execution performs (no execution needed);
  * the hyper-fused network (``sort_hyper=m``, default 3, tail-absorbing)
    is compared against the seed-equivalent layout (``sort_hyper=0``: one
    launch per cross stage + a separate in-block finish per k-phase);
  * sorted-output equality vs ``np.sort`` is asserted in the same run;
  * counted launches are cross-checked against the closed form
    ``sort_kernel.cross_launches`` (the DESIGN.md §2a formula).

HBM traffic model (per launch the kernel streams every block in once and
out once): ``2 · n · itemsize`` bytes. The seed network ADDITIONALLY paid
``3 · n · itemsize`` per cross stage for the ``_merge_pair_halves``
recombine (read both duplicated outputs + write the merged array) — that
pass is gone, outputs are written through the kernel's own BlockSpecs with
``input_output_aliases``; the model reports what it would have cost.

Gates (also asserted when run under ``benchmarks.run --quick`` in CI): the
fused network must issue ≤ half the launches of the seed layout, and the
distributed entry (``run_distributed``) pins ONE all_to_all per sihsort
call plus a merge finish that launches strictly fewer kernels than the
full re-sort it replaced. Every run appends a row to ``BENCH_sort.json``
so later PRs have a trajectory to diff against.

Throughput reporting: GB/s used for gating is modelled-bytes at the
modelled HBM rate. Wall-clock is recorded but informational — on this
container it times CPU interpret mode (dividing it as device time is how
the seed recorded 0.0025 GB/s), flagged per entry as ``interpret``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import common as KC
from repro.kernels import sort_kernel as SK

# ONE source for the modelled device rates: throughput for GATING is
# modelled-bytes / modelled-time at the cost model's rates — wall-clock
# from CPU interpret mode is *informational only* (dividing it as if it
# were device time is how the seed recorded 0.0025 GB/s).
from benchmarks.cost import HBM as HBM_BYTES_S
from benchmarks.cost import LAUNCH as COLLECTIVE_LATENCY_S

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(REPO, "BENCH_sort.json")


def _host_mesh_env(nranks: int) -> dict:
    """Environment for a child that simulates an ``nranks`` mesh on fake
    host devices. Pinned to the CPU on purpose: this process may already
    hold the accelerator, and the child's counts need no chip."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={nranks}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return env


def _count_launches(n: int, dtype, hyper: int) -> int:
    """Trace-time launch count of one n-element sort at hyper order m."""
    x = jax.ShapeDtypeStruct((n,), dtype)
    with KC.tuning_scope(sort_hyper=hyper):
        SK.reset_launch_count()
        # fresh lambda per count: eval_shape caches on function identity
        jax.eval_shape(lambda a: SK.bitonic_sort(a), x)
        return SK.launch_count()


def _hbm_model(n: int, itemsize: int, launches: int, merge_stages: int = 0):
    """Bytes moved: every launch streams the array in and out once; each
    (removed) merge pass read two full-size kernel outputs and wrote the
    recombined array."""
    return 2 * n * itemsize * launches + 3 * n * itemsize * merge_stages


def _cross_stage_count(n: int, block: int) -> int:
    """Number of cross-block stages of the full network (the merge passes
    the seed paid)."""
    total = max(KC.next_pow2(n), block)
    stages, k = 0, 2 * block
    while k <= total:
        stages += (k // block).bit_length() - 1
        k *= 2
    return stages


def run(n: int = 2**20, dtype=jnp.float32, repeats: int = 3,
        hyper: int | None = None, json_path: str | None = BENCH_JSON):
    """Returns benchmark rows [(name, us, derived), ...]; asserts the gate."""
    hyper = SK.HYPER_ORDER if hyper is None else hyper
    itemsize = jnp.dtype(dtype).itemsize
    block = SK.SORT_BLOCK

    fused = _count_launches(n, dtype, hyper)
    seed = _count_launches(n, dtype, 0)
    assert fused == SK.cross_launches(n, hyper=hyper), "count != closed form"
    assert seed == SK.cross_launches(n, hyper=0), "count != closed form"
    # THE GATE: fusion must never lose, and must at least halve the launch
    # count once there are enough cross phases for windows to bite (n >=
    # 4 blocks; below that both layouts are 1-3 launches and the ratio is
    # meaningless — a 2-block sort is 2 fused vs 3 seed launches).
    assert fused <= seed, (
        f"fused network regressed: {fused} launches vs seed {seed}"
    )
    if n >= 4 * block:
        assert 2 * fused <= seed, (
            f"fused network regressed: {fused} launches vs seed {seed}"
        )

    merge_stages = _cross_stage_count(n, block)
    hbm_fused = _hbm_model(n, itemsize, fused)
    hbm_seed = _hbm_model(n, itemsize, seed, merge_stages)

    # Correctness + wall time in the same run (jit of the interpret-mode
    # kernels compiles to real XLA on CPU; on TPU this is the real kernel).
    rng = np.random.default_rng(0)
    x_host = (rng.normal(size=n) * 1000).astype(jnp.dtype(dtype).name)
    x = jnp.asarray(x_host)

    def timed(m):
        with KC.tuning_scope(sort_hyper=m):
            fn = jax.jit(lambda a: SK.bitonic_sort(a))
            out = jax.block_until_ready(fn(x))  # warm/compile
            t0 = time.perf_counter()
            for _ in range(repeats):
                jax.block_until_ready(fn(x))
            dt = (time.perf_counter() - t0) / repeats
        return out, dt

    out_fused, t_fused = timed(hyper)
    np.testing.assert_array_equal(np.asarray(out_fused), np.sort(x_host))
    _, t_seed = timed(0)

    # GATING throughput = modelled bytes at modelled HBM rate: the effective
    # sort rate (2n useful bytes / time the modelled traffic takes on HBM).
    # Wall-clock stays a row field but is informational — on this container
    # it times CPU interpret mode, not the device the model describes.
    interpret = KC.interpret_mode()
    t_model_fused = hbm_fused / HBM_BYTES_S + fused * COLLECTIVE_LATENCY_S
    t_model_seed = hbm_seed / HBM_BYTES_S + seed * COLLECTIVE_LATENCY_S
    gbps_model = 2 * n * itemsize / t_model_fused / 1e9
    gbps_wall = 2 * n * itemsize / t_fused / 1e9
    rows = [
        (
            f"sort_throughput.fused_m{hyper}.n{n}",
            t_fused * 1e6,
            f"{gbps_model:.1f}GB/s(modelled) launches={fused} "
            f"modelled_hbm={hbm_fused / 1e6:.1f}MB "
            f"wallclock={gbps_wall:.4f}GB/s(interpret={interpret})",
        ),
        (
            f"sort_throughput.seed_m0.n{n}",
            t_seed * 1e6,
            f"launches={seed} modelled_hbm={hbm_seed / 1e6:.1f}MB "
            f"(incl. {merge_stages} merge passes, now deleted)",
        ),
        (
            "sort_throughput.gate",
            0.0,
            f"fused/seed launches = {fused}/{seed} "
            f"{'<= 1/2' if n >= 4 * block else '(no-lose, tiny n)'}: PASS; "
            f"np.sort equality: PASS",
        ),
    ]

    if json_path:
        append_json(json_path, {
            "n": n,
            "dtype": str(jnp.dtype(dtype)),
            "hyper": hyper,
            "launches_fused": fused,
            "launches_seed": seed,
            "cross_stages": merge_stages,
            "modelled_hbm_bytes_fused": hbm_fused,
            "modelled_hbm_bytes_seed": hbm_seed,
            "modelled_s_fused": t_model_fused,
            "modelled_s_seed": t_model_seed,
            "gbps_modelled": gbps_model,
            "wallclock_s_fused": t_fused,
            "wallclock_s_seed": t_seed,
            "gbps_wallclock_informational": gbps_wall,
            "interpret": interpret,
            "equal_to_npsort": True,
            "backend": jax.default_backend(),
        })
    return rows


# Child script for the multi-device entry: forcing a fake 8-device host
# platform needs XLA_FLAGS set before jax initialises, so the measurement
# runs in a subprocess and reports one JSON line. Everything in it is
# COUNTED by tracing (jaxpr collectives, pallas_call launches) — no
# execution, so full-size n stays cheap on the CPU container.
_DISTRIBUTED_CHILD = """
import json, sys
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import core as ak
from repro.core import compat
from repro.core.distributed import exchange_capacity
from repro.kernels import merge_kernel as MK
from repro.kernels import sort_kernel as SK

n, nranks, cf = int(sys.argv[1]), int(sys.argv[2]), float(sys.argv[3])
n_local = n // nranks
# THE capacity rule, not a copy: the counted finish describes exactly the
# buffer sihsort exchanges
cap = exchange_capacity(n_local, nranks, cf, dtypes=[jnp.float32])
buffer = nranks * cap
mesh = compat.make_mesh((nranks,), ("data",))
x = jax.ShapeDtypeStruct((n,), jnp.float32)

def counts_for(exchange):
    fn = compat.shard_map(
        lambda xl: ak.sihsort(xl, axis_name="data", capacity_factor=cf,
                              exchange=exchange).values,
        mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False,
    )
    return ak.count_collectives(fn, x)

def finish_launches(fn, *args):
    SK.reset_launch_count()
    jax.eval_shape(fn, *args)
    return SK.launch_count()

buf = jax.ShapeDtypeStruct((buffer,), jnp.float32)
cnts = jax.ShapeDtypeStruct((nranks,), jnp.int32)
merge_launches = finish_launches(
    lambda a, c: MK.kway_merge(a, nranks, counts=c), buf, cnts)
resort_launches = finish_launches(lambda a: SK.bitonic_sort(a), buf)

print(json.dumps({
    "collectives": counts_for("all_to_all"),
    "collectives_ring": counts_for("ring"),
    "cap": cap, "buffer": buffer,
    "finish_launches_merge": merge_launches,
    "finish_launches_resort": resort_launches,
    "merge_closed_form": MK.merge_launches(buffer, nranks),
    "resort_closed_form": SK.cross_launches(buffer),
}))
"""


def run_distributed(n: int = 2**20, nranks: int = 8,
                    capacity_factor: float = 2.0,
                    json_path: str | None = BENCH_JSON):
    """Multi-device (host-platform-simulated) sihsort gate.

    Counted in a subprocess with ``nranks`` fake devices: collective rounds
    per sihsort call (jaxpr inspection) and Pallas launches of the finish
    stage (merge vs the PR-2 full re-sort baseline). Gates, asserted here
    and re-run by the CI bench-smoke job:

      * exactly ONE all_to_all per call (the fused exchange — the seed
        paid 3); the ring variant issues 0 all_to_alls, nranks-1 ppermutes;
      * the merge finish launches strictly fewer kernels than the full
        re-sort of the same capacity buffer;
      * both counts match their closed forms.

    Modelled HBM + interconnect bytes/times come from
    ``benchmarks/cost.py::sihsort_cost`` and land in ``BENCH_sort.json``.
    """
    proc = subprocess.run(
        [sys.executable, "-c", _DISTRIBUTED_CHILD,
         str(n), str(nranks), str(capacity_factor)],
        env=_host_mesh_env(nranks), capture_output=True, text=True,
        timeout=600,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"distributed child failed:\n{proc.stdout}\n{proc.stderr}"
        )
    rec = json.loads(proc.stdout.strip().splitlines()[-1])

    coll = rec["collectives"]
    ring = rec["collectives_ring"]
    merge_l, resort_l = (
        rec["finish_launches_merge"], rec["finish_launches_resort"]
    )
    # THE GATES
    assert coll.get("all_to_all") == 1, f"fused exchange regressed: {coll}"
    assert ring.get("all_to_all", 0) == 0, ring
    assert ring.get("ppermute") == nranks - 1, ring
    assert merge_l < resort_l, (
        f"merge finish must beat the full re-sort: {merge_l} vs {resort_l}"
    )
    assert merge_l == rec["merge_closed_form"], "count != closed form"
    assert resort_l == rec["resort_closed_form"], "count != closed form"

    from benchmarks import cost

    n_bytes = n // nranks * 4  # per-rank f32 buffer
    direct = cost.sihsort_cost(n_bytes, nranks, link=cost.ICI)
    staged = cost.sihsort_cost(n_bytes, nranks, link=cost.HOST)
    speedup = staged["t_total_s"] / direct["t_total_s"]
    # finish-stage HBM model: 2 passes of the capacity buffer per launch
    hbm_merge = 2 * rec["buffer"] * 4 * merge_l
    hbm_resort = 2 * rec["buffer"] * 4 * resort_l

    rows = [
        (
            f"sort_throughput.sihsort.n{n}.p{nranks}",
            direct["t_total_s"] * 1e6,
            f"collectives={{a2a:{coll.get('all_to_all')},"
            f"pmax:{coll.get('pmax')},psum:{coll.get('psum')}}} "
            f"finish_launches={merge_l}(merge)/{resort_l}(re-sort) "
            f"modelled_hbm={hbm_merge / 1e6:.1f}MB "
            f"direct_vs_staged={speedup:.2f}x",
        ),
        (
            "sort_throughput.sihsort.gate",
            0.0,
            f"1 all_to_all: PASS; merge<re-sort launches "
            f"({merge_l}<{resort_l}): PASS; ring={nranks - 1} ppermutes: "
            f"PASS",
        ),
    ]
    if json_path:
        append_json(json_path, {
            "entry": "sihsort_distributed",
            "n": n,
            "nranks": nranks,
            "capacity_factor": capacity_factor,
            "cap": rec["cap"],
            "collectives": coll,
            "collectives_ring": ring,
            "finish_launches_merge": merge_l,
            "finish_launches_resort": resort_l,
            "modelled_hbm_bytes_merge_finish": hbm_merge,
            "modelled_hbm_bytes_resort_finish": hbm_resort,
            "modelled_interconnect_bytes": direct["wire_bytes"],
            "modelled_s_direct": direct["t_total_s"],
            "modelled_s_staged": staged["t_total_s"],
            "direct_vs_staged_speedup": speedup,
            "backend": jax.default_backend(),
        })
    return rows


# Child for the heterogeneous co-sort gate: a deliberately skewed mesh —
# forced jnp ranks beside pallas ranks on the fake 8-device host platform —
# actually EXECUTES the co-sort (bitwise equality and received-row counts
# cannot be traced), so n stays modest; the partition weights are resolved
# at the production anchor size where the modelled jnp/pallas skew is real.
_HETERO_CHILD = """
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from repro import core as ak
from repro.launch import mesh as LM

backends = tuple(sys.argv[1].split(","))
n, n_model, cf = int(sys.argv[2]), int(sys.argv[3]), float(sys.argv[4])
nranks = len(backends)

# throughput-proportional weights from the scheduler's own resolution
# path: no cache attached here, so every rank falls back to the
# deterministic model (sources == "model" on every machine)
weights, sources = LM.hetero_rank_weights(backends, n_model)

rng = np.random.default_rng(0)
x_host = rng.lognormal(0.0, 2.0, size=n).astype(np.float32)
x = jnp.asarray(x_host)

hm = LM.make_hetero_mesh(backends)
res = LM.co_sort(x, hm, weights=weights, capacity_factor=cf)
out = np.asarray(ak.collect_sorted(res))
ref_single = np.asarray(ak.merge_sort(x))  # single-rank reference sort

counts = np.asarray(res.count).reshape(-1)
caps = ak.exchange_capacities(n // nranks, nranks, cf, weights=weights)
ak.assert_no_overflow(res, weights=weights)

def traced(xl):
    return ak.sihsort_sharded(xl, hm.mesh, hm.axis_name,
                              rank_backends=backends, rank_weights=weights,
                              capacity_factor=cf)

print(json.dumps({
    "weights": [float(w) for w in weights],
    "sources": list(sources),
    "counts": [int(c) for c in counts],
    "caps": [int(c) for c in caps],
    "overflow": int(np.asarray(res.overflow).sum()),
    "equal_single_rank": bool(np.array_equal(out, ref_single)),
    "equal_npsort": bool(np.array_equal(out, np.sort(x_host))),
    "collectives": ak.count_collectives(
        traced, jax.ShapeDtypeStruct((n,), jnp.float32)),
}))
"""


def run_hetero(n: int = 2**16, n_model: int = 2**20,
               backends: tuple = ("jnp", "jnp") + ("pallas",) * 6,
               capacity_factor: float = 2.0,
               json_path: str | None = BENCH_JSON):
    """Heterogeneous co-sort gate — uniform vs throughput-proportional
    partitioning on a deliberately skewed mesh (jnp ranks beside pallas
    ranks, simulated on the fake multi-device host platform).

    The child EXECUTES the co-sort with model-resolved weights; asserted
    here (and re-run by the CI ``hetero-smoke`` job):

      * sorted output bitwise equal to the single-rank reference sort
        (and np.sort);
      * per-rank received-row counts within 10% of the throughput-weighted
        targets ``n * w_r`` — the splitters actually cut proportionally;
      * zero overflow under the ragged per-destination capacities, and the
        counts conserve every input row;
      * still exactly ONE all_to_all (weights add no collective when
        static);
      * modelled makespan (``benchmarks/cost.py``, per-rank bandwidths at
        the production anchor ``n_model``) of the proportional cut ≥1.3×
        lower than the uniform cut.
    """
    nranks = len(backends)
    env = _host_mesh_env(nranks)
    proc = subprocess.run(
        [sys.executable, "-c", _HETERO_CHILD, ",".join(backends),
         str(n), str(n_model), str(capacity_factor)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"hetero child failed:\n{proc.stdout}\n{proc.stderr}"
        )
    rec = json.loads(proc.stdout.strip().splitlines()[-1])

    weights = np.asarray(rec["weights"])
    counts = np.asarray(rec["counts"])
    # THE GATES: correctness first
    assert rec["equal_single_rank"], "co-sort != single-rank reference"
    assert rec["equal_npsort"], "co-sort != np.sort"
    assert rec["overflow"] == 0, rec
    assert counts.sum() == n, (counts.sum(), n)
    targets = n * weights
    assert (np.abs(counts - targets) <= 0.10 * targets).all(), (
        f"received rows {counts} not within 10% of targets {targets}"
    )
    assert rec["collectives"].get("all_to_all") == 1, rec["collectives"]
    # the weights must actually be skewed (the mesh is mixed on purpose)
    assert weights.max() / weights.min() > 1.5, weights

    from benchmarks import cost

    n_bytes = n_model * 4  # per-rank f32 shard at the production anchor
    uniform, prop, gain = cost.hetero_partition_gain(
        n_bytes, backends, weights=weights
    )
    # THE GATE: proportional cuts must beat uniform by >=1.3x makespan
    assert gain >= 1.3, (
        f"proportional partitioning gained only {gain:.2f}x over uniform"
    )

    rows = [
        (
            f"sort_throughput.hetero.n{n}.p{nranks}",
            prop["t_total_s"] * 1e6,
            f"backends={'/'.join(backends)} "
            f"weights={np.round(weights, 3).tolist()} "
            f"makespan uniform={uniform['t_total_s'] * 1e6:.1f}us "
            f"proportional={prop['t_total_s'] * 1e6:.1f}us "
            f"gain={gain:.2f}x",
        ),
        (
            "sort_throughput.hetero.gate",
            0.0,
            f"bitwise==single-rank: PASS; rows within 10% of weighted "
            f"targets: PASS; overflow=0: PASS; 1 all_to_all: PASS; "
            f"makespan gain {gain:.2f}x >= 1.3x: PASS",
        ),
    ]
    if json_path:
        entry = {
            "entry": "sort_hetero",
            "n": n,
            "n_model": n_model,
            "nranks": nranks,
            "backends": list(backends),
            "capacity_factor": capacity_factor,
            "weights": rec["weights"],
            "weight_sources": rec["sources"],
            "received_rows": rec["counts"],
            "caps": rec["caps"],
            "overflow": rec["overflow"],
            "equal_single_rank": rec["equal_single_rank"],
            "collectives": rec["collectives"],
            "modelled_makespan_s_uniform": uniform["t_total_s"],
            "modelled_makespan_s_proportional": prop["t_total_s"],
            "makespan_gain": gain,
            "backend": jax.default_backend(),
        }
        # fully deterministic (model weights, counted collectives, seeded
        # keys): an entry identical to the last recorded one adds no
        # trajectory information — skip it, same idiom as autotune_rows
        last = None
        if os.path.exists(json_path):
            try:
                with open(json_path) as f:
                    prev = [e for e in json.load(f)["entries"]
                            if e.get("entry") == "sort_hetero"]
                last = prev[-1] if prev else None
            except (json.JSONDecodeError, OSError, KeyError, TypeError,
                    IndexError):
                last = None
        if entry != last:
            append_json(json_path, entry)
    return rows


def append_json(path: str, entry: dict) -> None:
    """Append one entry to a ``{"schema": 1, "entries": [...]}`` trajectory
    file (shared by BENCH_sort.json and BENCH_autotune.json — one idiom,
    one reader)."""
    doc = {"schema": 1, "entries": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (json.JSONDecodeError, OSError):
            pass
    doc.setdefault("entries", []).append(entry)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    for name, us, derived in run() + run_distributed() + run_hetero():
        print(f"{name},{us:.1f},{derived}")
