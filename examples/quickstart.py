"""Quickstart: the AK primitive suite in 60 seconds.

Mirrors the paper's §II-B tour — every primitive, both backends, plus the
Algorithm 3 `foreachindex` copy kernel.

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --paged --page-size 4
    PYTHONPATH=src python examples/quickstart.py --paged --chaos 7

``--paged`` appends a serving vignette: the block-pool paged KV cache
(DESIGN.md §8a) decoding token-identically to the contiguous engine while
holding fewer resident cache bytes per live token. ``--chaos SEED`` (with
``--paged``) re-runs that vignette under a seeded fault plan with an
undersized pool (DESIGN.md §9): injected failures are absorbed by
supervised retries and preempt-and-recompute, and the surviving tokens
still match the contiguous reference bit for bit. ``--deadline`` /
``--queue-cap`` add the latency/admission bounds to the same run.

``--trace PATH`` exports the telemetry walkthrough's span buffer as
Perfetto/Chrome-trace JSON — open it at https://ui.perfetto.dev to see
nested ``ak.*`` primitive spans carrying the backend and size of each
dispatch (DESIGN.md §11). Without the flag the walkthrough still runs and
writes to a temp file.

``--co-sort`` appends the heterogeneous co-processing vignette
(DESIGN.md §12): jnp-on-CPU ranks beside Pallas ranks co-sorting ONE
array on a mixed-backend mesh, splitters cut throughput-proportionally.
Runs ``examples/distributed_sort.py --hetero`` on 8 fake host devices.
"""
import argparse

import jax.numpy as jnp
import numpy as np

from repro import core as ak

_ap = argparse.ArgumentParser()
_ap.add_argument("--paged", action="store_true",
                 help="also run the paged-KV-cache serving vignette")
_ap.add_argument("--page-size", type=int, default=4,
                 help="tokens per KV page for the vignette")
_ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                 help="re-run the paged vignette under a seeded fault "
                      "plan (implies preemption + supervised retries)")
_ap.add_argument("--deadline", type=int, default=None,
                 help="per-request deadline (engine steps) for the "
                      "chaos vignette")
_ap.add_argument("--queue-cap", type=int, default=None,
                 help="bounded admission queue for the chaos vignette")
_ap.add_argument("--trace", default=None, metavar="PATH",
                 help="where the telemetry walkthrough writes its "
                      "Perfetto trace (default: a temp file)")
_ap.add_argument("--co-sort", dest="co_sort", action="store_true",
                 help="also run the heterogeneous co-sort vignette "
                      "(mixed jnp/pallas mesh, 8 fake devices)")
_args = _ap.parse_args()

rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=100_000).astype(np.float32))

# -- Algorithm 3: the foreachindex copy kernel ------------------------------
src = x
dst = ak.foreachindex(lambda i: src[i], src.shape[0])
assert bool((dst == src).all())

# -- the full suite, portable (XLA) path ------------------------------------
print("merge_sort        :", ak.merge_sort(x)[:4])
print("sortperm          :", ak.sortperm(x)[:4])
print("sortperm_lowmem   :", ak.sortperm_lowmem(x)[:4])
print("reduce (+)        :", float(ak.reduce(jnp.add, x, init=0.0)))
print("mapreduce (x²,+)  :",
      float(ak.mapreduce(lambda a: a * a, jnp.add, x, init=0.0)))
print("accumulate (max)  :", ak.accumulate(jnp.maximum, x,
                                           init=-np.inf)[-4:])
hay = ak.merge_sort(x)
print("searchsortedfirst :", ak.searchsortedfirst(hay, x[:4]))
print("searchsortedlast  :", ak.searchsortedlast(hay, x[:4]))
print("any > 4σ          :", bool(ak.any_pred(lambda a: a > 4.0, x)))
print("all finite        :", bool(ak.all_pred(jnp.isfinite, x)))
hist, mn, mx = ak.minmax_histogram(x, 16, -4.0, 4.0)
print("histogram         :", hist)

# -- segmented primitives: CSR (offsets, values) ragged batches -------------
# One dense launch per call, no per-segment kernels (DESIGN.md §10). These
# power the MoE expert dispatch: since the bucketed-dispatch PR, moe_ffn
# gathers tokens expert-contiguously and combines with ONE segmented_reduce
# instead of a zero-padded (E*C, d) capacity buffer.
offsets = jnp.asarray([0, 3, 3, 7, 10], jnp.int32)  # 4 segments, one empty
seg = x[:10]
print("segmented_reduce  :",
      ak.segmented_reduce(jnp.add, seg, offsets, init=0.0))
print("segmented_scan    :",
      ak.segmented_scan(jnp.add, seg, offsets, init=0.0)[:4])
print("segmented_sort    :", ak.segmented_sort(seg, offsets)[:4])

# -- the same call sites, hand-tiled Pallas TPU path ------------------------
# (interpret-mode on CPU; identical results — the paper's dispatch story)
with ak.backend("pallas"):
    s2 = ak.merge_sort(x)
    r2 = ak.reduce(jnp.add, x, init=0.0)
np.testing.assert_array_equal(np.asarray(s2), np.asarray(hay))
np.testing.assert_allclose(float(r2),
                           float(ak.reduce(jnp.add, x, init=0.0)), rtol=1e-4)
print("pallas backend    : identical results ✓")

# -- autotune: measure once, resolve forever --------------------------------
# Search the legal knob space per (primitive, dtype, size-class) and persist
# the verdicts per device (DESIGN.md §7). `model_measure` evaluates the
# benchmarks/cost.py model — deterministic and instant; drop it to time the
# real wall clock on actual hardware. With the cache attached,
# backend="auto" picks pallas-vs-jnp from the MEASURED crossover and runs
# the measured-best block geometry; scoped overrides still win.
import os
import tempfile

from repro import tune

cache = tune.tune_all(
    sizes=(4096, 2**17), dtypes=("float32",),
    primitives=("sort", "mapreduce"), measure=tune.model_measure,
    path=os.path.join(tempfile.mkdtemp(), "autotune.json"),
)
cache.save()                                   # versioned, fingerprinted
cache = tune.TuneCache.load(cache.path)        # what a later run does
with ak.tuning.using_cache(cache):
    big = jnp.asarray(rng.normal(size=2**17).astype(np.float32))
    s3 = ak.merge_sort(big)                    # auto -> measured backend
    entry = cache.lookup("sort", "float32", 17)
np.testing.assert_array_equal(np.asarray(s3), np.sort(np.asarray(big)))
print(f"autotuned sort    : {entry['backend']} {entry['knobs']} "
      f"({entry['speedup']:.1f}x modelled, cache hits={cache.stats.hits})")

# -- telemetry: spans, metrics, and a Perfetto trace ------------------------
# One global flag gates everything: disabled (the default) costs a single
# read per call site; enabled, every registry dispatch opens a span that
# records its backend and size (DESIGN.md §11).
ak.telemetry.enable()
with ak.telemetry.span("quickstart.walkthrough", cat="example"):
    ak.merge_sort(x)
    ak.reduce(jnp.add, x, init=0.0)
    with ak.backend("pallas"):
        ak.merge_sort(x)
ak.telemetry.instant("walkthrough-done", cat="example")
trace_path = _args.trace or os.path.join(tempfile.mkdtemp(), "trace.json")
doc = ak.telemetry.export(trace_path)
ak.telemetry.validate_trace(doc)
ak.telemetry.disable()
snap = ak.metrics.snapshot()["metrics"]
calls = sum(s["value"]
            for s in snap["ak_registry_calls_total"]["samples"])
print(f"telemetry         : {len(doc['traceEvents'])} events -> "
      f"{trace_path} (ui.perfetto.dev); "
      f"{calls:.0f} registry calls in ak.metrics.snapshot()")

# -- optional: the paged KV cache on the serving path -----------------------
# AK primitives AS the allocator: accumulate + searchsortedfirst find free
# pages, bincount measures occupancy, merge_sort_by_key orders the defrag
# permutation (DESIGN.md §8a). Token-identical to the contiguous engine.
if _args.paged:
    import jax

    from repro.configs import load_smoke_config
    from repro.launch.engine import Engine, Request
    from repro.models import model as M

    cfg = load_smoke_config("internlm2_1_8b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    ps = _args.page_size
    plen, max_new, cache_len = 4, 6, -(-10 // ps) * ps
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (4, plen), 0, cfg.vocab))
    reqs = lambda: [Request(rid=i, prompt=prompts[i], max_new=max_new)
                    for i in range(4)]

    def serve(paged):
        eng = Engine(params, cfg, slots=2, cache_len=cache_len,
                     prompt_pad=plen, temperature=0.0, paged=paged,
                     page_size=ps if paged else None)
        res, st = eng.run(reqs())
        return {r: res[r].tokens for r in res}, st

    contig, _ = serve(False)
    paged, st = serve(True)
    assert paged == contig            # bit-for-bit the same tokens
    print(f"paged KV cache    : tokens identical; "
          f"{st.num_pages} pages x {ps}, "
          f"occupancy {st.mean_occupancy:.2f}, "
          f"{st.resident_bytes_per_active_token:.0f} B/active token")

    # -- failure tier: chaos the same batch (DESIGN.md §9) ------------------
    # seeded fault plan + undersized pool: injected allocator/admission/
    # device-step failures get absorbed by supervised retries and
    # preempt-and-recompute; completed requests still match the
    # contiguous reference bit for bit.
    if _args.chaos is not None:
        from repro.launch.engine import COMPLETED
        from repro.runtime import faults
        from repro.runtime.supervisor import Supervisor

        eng = Engine(params, cfg, slots=2, cache_len=cache_len,
                     prompt_pad=plen, temperature=0.0, paged=True,
                     page_size=ps, num_pages=2 * (cache_len // ps),
                     preempt=True, queue_cap=_args.queue_cap,
                     supervisor=Supervisor(None, n_hosts=1, max_retries=3,
                                           sleep=lambda s: None))
        with faults.active(faults.FaultPlan.seeded(_args.chaos)) as plan:
            res, cst = eng.run([
                Request(rid=i, prompt=prompts[i], max_new=max_new,
                        deadline=_args.deadline)
                for i in range(4)
            ])
        done = [r for r in res if res[r].status == COMPLETED]
        assert all(res[r].tokens == contig[r] for r in done)
        print(f"chaos (seed {_args.chaos}) : "
              f"{len(done)}/4 completed token-identical; "
              f"faults={plan.injected} preempt={cst.preemptions} "
              f"retries={cst.step_retries} "
              f"statuses={sorted(res[r].status for r in res)}")

# -- optional: heterogeneous co-sort (DESIGN.md §12) ------------------------
# Mixed-backend co-processing needs a multi-rank mesh, so this vignette
# hands off to the distributed demo in a child process pinned to the CPU on
# purpose: it simulates an 8-rank mesh on host devices (two jnp ranks
# beside six Pallas ranks), and this process may already hold the chip.
if _args.co_sort:
    import subprocess
    import sys

    demo = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "distributed_sort.py")
    print("\nco-sort vignette  : examples/distributed_sort.py --hetero")
    rc = subprocess.call([sys.executable, demo, "--hetero"],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    if rc != 0:
        raise SystemExit(rc)
