"""SIHSort demo — the paper's §IV multi-node sort on a device mesh.

Sorts over a 1-D mesh of every device JAX sees (MPI-rank stand-ins): the
chips of a TPU host, or — on the CPU — 8 host devices, which this script
requests before JAX starts (the flag touches only the CPU platform). It
sorts several distributions + a key/payload pair, prints the per-rank
balance the interpolated-histogram splitters achieve, the *counted*
per-call collective rounds (one fused all_to_all), and the modelled
interconnect-cost breakdown — direct vs host-staged transfer, mirroring
the paper's 4.93× GPUDirect economics. Everything runs in this one
process.

    PYTHONPATH=src python examples/distributed_sort.py
    PYTHONPATH=src python examples/distributed_sort.py --hetero

``--hetero`` appends the heterogeneous co-processing demo (DESIGN.md
§12): two jnp ranks beside Pallas ranks in ONE collective mesh,
splitters cut throughput-proportionally so the slow ranks receive fewer
keys — makespan follows the fastest partition, not the slowest rank.
"""
import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

# benchmarks/ (the cost model) lives at the repo root, next to examples/
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import core as ak  # noqa: E402
from repro.core import compat  # noqa: E402

nranks = len(jax.devices())
mesh = compat.make_mesh((nranks,), ("data",))
rng = np.random.default_rng(0)
n = nranks * 65_536

print(f"devices (MPI-rank stand-ins): {len(jax.devices())}")
print(f"global elements: {n:,}\n")

for dist, data in [
    ("normal", rng.normal(size=n).astype(np.float32)),
    ("skewed lognormal", rng.lognormal(0, 2, size=n).astype(np.float32)),
    ("int32", rng.integers(-10**6, 10**6, size=n).astype(np.int32)),
]:
    res = ak.sihsort_sharded(jnp.asarray(data), mesh, "data",
                             capacity_factor=2.0)
    out = np.asarray(ak.collect_sorted(res))
    counts = np.asarray(res.count).reshape(-1)
    assert np.array_equal(out, np.sort(data))
    print(f"{dist:18s} sorted ✓  balance {counts.min():6d}..{counts.max():6d}"
          f"  (ideal {n // nranks})  overflow {int(np.asarray(res.overflow).sum())}")

# key/payload — the data-pipeline global shuffle building block
keys = rng.normal(size=n).astype(np.float32)
payload = np.arange(n, dtype=np.int32)
res = ak.sihsort_sharded(jnp.asarray(keys), mesh, "data",
                         payload=jnp.asarray(payload), capacity_factor=2.0)
vals = np.asarray(res.values).reshape(nranks, -1)
pays = np.asarray(res.payload).reshape(nranks, -1)
cnt = np.asarray(res.count).reshape(-1)
got_k = np.concatenate([vals[r, :cnt[r]] for r in range(nranks)])
got_p = np.concatenate([pays[r, :cnt[r]] for r in range(nranks)])
assert np.array_equal(keys[got_p], got_k)
print("\nkey/payload co-sort ✓ — every pair survived the exchange intact")

# -- communication contract, counted not claimed --------------------------
from jax.sharding import PartitionSpec as P  # noqa: E402

from benchmarks import cost  # noqa: E402
from repro.launch.mesh import axis_domain  # noqa: E402

spec = jax.ShapeDtypeStruct((n,), jnp.float32)
cc = ak.count_collectives(
    compat.shard_map(
        lambda xl: ak.sihsort(xl, axis_name="data").values,
        mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False,
    ),
    spec,
)
print(f"\ncollectives per sihsort call (jaxpr-counted): {cc}")
print("  -> ONE fused all_to_all ships values + payload + counts "
      "(the seed paid 3)")

# -- modelled interconnect economics (paper Fig 5 / §IV-A) ----------------
nb = (n // nranks) * 4  # per-rank f32 bytes
# the sorted axis's interconnect domain picks the link this mesh pays
# ('data' -> ici; a 'pod'-axis sort would pay the staged host rate); both
# domains are shown for the direct-vs-staged comparison
domain = axis_domain("data")
links = {"ici": cost.ICI, "host": cost.HOST}
direct = cost.sihsort_cost(nb, nranks, link=links["ici"])
staged = cost.sihsort_cost(nb, nranks, link=links["host"])
this_mesh = direct if domain == "ici" else staged
ring = cost.sihsort_cost(nb, nranks, link=links["host"], exchange="ring")
print(f"\nmodelled cost breakdown per rank ({nb / 1e6:.1f} MB, "
      f"'data' axis domain: {domain}):")
for name, t in [("direct (ICI)", direct), ("staged (host)", staged)]:
    print(f"  {name:14s} local {t['t_local_s'] * 1e6:7.1f}us  "
          f"comm {t['t_comm_s'] * 1e6:7.1f}us  "
          f"merge {t['t_merge_s'] * 1e6:7.1f}us  "
          f"total {t['t_total_s'] * 1e6:7.1f}us")
speedup = staged["t_total_s"] / direct["t_total_s"]
print(f"  this mesh pays the {domain} rate: "
      f"{this_mesh['t_total_s'] * 1e6:.1f}us/call")
print(f"  direct vs staged: {speedup:.2f}x "
      f"(paper: 4.93x with GPUDirect — interconnect decides viability)")
print(f"  ring-on-host overlap hides "
      f"{ring['overlap_saved_s'] * 1e6:.1f}us of wire time per call")

# -- heterogeneous co-processing (DESIGN.md §12) ---------------------------
# jnp ranks working BESIDE Pallas ranks on one problem: the mesh
# stays an ordinary 1-D jax mesh, the per-rank backend assignment lowers
# to lax.switch on axis_index, and the splitters are cut in proportion to
# each rank's throughput (autotune cache when compatible, cost model
# otherwise) so the slow ranks stop gating the makespan.
if "--hetero" in sys.argv[1:] and nranks < 3:
    print(f"\nheterogeneous co-sort needs >= 3 devices; {nranks} exist")
elif "--hetero" in sys.argv[1:]:
    from repro.launch import mesh as LM  # noqa: E402

    backends = ("jnp", "jnp") + ("pallas",) * (nranks - 2)
    hm = LM.make_hetero_mesh(backends)
    # weights anchored at the production shard size the weights describe;
    # the demo sorts a smaller array so interpret-mode stays snappy
    w, srcs = LM.hetero_rank_weights(backends, 2**20)
    nh = 2**16
    xh = jnp.asarray(rng.lognormal(0, 2, size=nh).astype(np.float32))
    res = LM.co_sort(xh, hm, weights=w, capacity_factor=2.0)
    ak.assert_no_overflow(res, weights=w)
    out = np.asarray(ak.collect_sorted(res))
    assert np.array_equal(out, np.sort(np.asarray(xh)))
    counts = np.asarray(res.count).reshape(-1)
    print(f"\nheterogeneous co-sort (2 jnp + {nranks - 2} pallas ranks):")
    for r, (b, wr, c) in enumerate(zip(backends, w, counts)):
        bar = "#" * max(int(60 * c / counts.max()), 1)
        print(f"  rank {r}  {b:6s} w={wr:.3f} ({srcs[r][:5]})  "
              f"recv {c:6d}  {bar}")
    print(f"  sorted ✓ bitwise == np.sort; overflow "
          f"{int(np.asarray(res.overflow).sum())}")
    uni, prop, gain = cost.hetero_partition_gain(2**20 * 4, backends,
                                                 weights=w)
    print(f"  modelled makespan: uniform {uni['t_total_s'] * 1e6:.0f}us "
          f"-> proportional {prop['t_total_s'] * 1e6:.0f}us "
          f"({gain:.2f}x)")
