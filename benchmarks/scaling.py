"""Paper Figures 1-3 — weak & strong scaling of the distributed sort.

MPI ranks -> fake host devices (subprocess per device count, since jax locks
the count at init). Measures wall-time of the jit'd SIHSort across rank
counts for the paper's two regimes:

  weak   — fixed data per rank (Fig 1: 0.1 MB & 10 MB; Fig 2: 1 GB in the
           paper, scaled down for a CPU container),
  strong — fixed total data divided over ranks (Fig 3).

The local sorter is swappable (--sorter jnp|pallas), reproducing the
paper's AK-vs-Thrust local-sorter comparison within one codebase. Derived
column: sorted GB/s (the paper's throughput metric).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

_WORKER = """
import json, time
import numpy as np, jax, jax.numpy as jnp
from repro import core as ak
from repro.core import compat

cfg = json.loads({cfg!r})
n_per = cfg["n_per_rank"]
ndev = cfg["ndev"]
mesh = compat.make_mesh((ndev,), ("data",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=ndev * n_per).astype(np.float32))

def run(xx):
    return ak.sihsort_sharded(xx, mesh, "data", capacity_factor=2.0,
                              backend=cfg["backend"])

res = run(x)  # warmup + compile
jax.block_until_ready(res.values)
ts = []
for _ in range(cfg["repeats"]):
    t0 = time.perf_counter()
    res = run(x)
    jax.block_until_ready(res.values)
    ts.append(time.perf_counter() - t0)
overflow = int(np.asarray(res.overflow).sum())
print("RESULT " + json.dumps({{"mean_s": float(np.mean(ts)),
                               "std_s": float(np.std(ts)),
                               "overflow": overflow}}))
"""


def _run_worker(ndev, n_per_rank, backend="jnp", repeats=3):
    cfg = json.dumps({"n_per_rank": n_per_rank, "ndev": ndev,
                      "backend": backend, "repeats": repeats})
    # the worker simulates an ndev mesh on fake host devices: pinned to the
    # CPU on purpose (this process may already hold the accelerator)
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    code = textwrap.dedent(_WORKER).format(cfg=cfg)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError("no RESULT line:\n" + proc.stdout)


def run(mode="weak", n_per_rank=65_536, total=524_288,
        devcounts=(1, 2, 4, 8), backend="jnp"):
    """Returns rows (name, us_per_call, derived)."""
    rows = []
    for ndev in devcounts:
        npr = n_per_rank if mode == "weak" else total // ndev
        r = _run_worker(ndev, npr, backend=backend)
        nbytes = ndev * npr * 4
        gbps = nbytes / r["mean_s"] / 1e9
        rows.append((
            f"fig_scaling.{mode}.{backend}.ranks{ndev}",
            r["mean_s"] * 1e6,
            f"{gbps:.3f}GB/s overflow={r['overflow']}",
        ))
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["weak", "strong"], default="weak")
    ap.add_argument("--backend", default="jnp")
    ap.add_argument("--n-per-rank", type=int, default=65_536)
    ap.add_argument("--total", type=int, default=524_288)
    args = ap.parse_args()
    for name, us, derived in run(args.mode, args.n_per_rank, args.total,
                                 backend=args.backend):
        print(f"{name},{us:.1f},{derived}")
