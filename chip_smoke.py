#!/usr/bin/env python3
"""Smoke test of the system's main path on a TPU.

    python chip_smoke.py              # one chip: primitives + serving
    python chip_smoke.py --chips 4    # four chips: SIHSort only

One chip, three phases:

  (a) device check: the first device must be a TPU; there is no CPU
      fallback.
  (b) AK primitives at real sizes through the public ``repro.core`` API
      (backend "auto"): each must resolve to the Pallas kernels, its
      compiled program must hold ``tpu_custom_call`` (no kernel ran in
      interpret mode), and it must agree with its jnp oracle on the same
      data — bitwise wherever the library promises it.
  (c) internlm2_1_8b at its published widths through the serve CLI's own
      ``main`` (random weights from ``--seed``): 16 requests on 8 slots,
      each must end COMPLETED with its full token count; then prefill plus
      a few ``decode_step``s are checked against ``forward`` over the same
      tokens.

``--chips 4`` runs only SIHSort: 2^24 f32 keys per chip, key-only and with
an int32 payload, on a mesh of the four devices, checked against a host
``np.sort`` / stable argsort of the same seeded input.

Any failed check raises, so the exit code is non-zero and no result line is
printed. On success the last line of stdout is one JSON object naming the
device. Times printed along the way include compilation and are not
measurements.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Tolerance of the teacher-forced logits check, as max |cached - forward|
#: over max |forward|. Both paths run the same bf16 weights and bf16
#: activations and differ only in how attention is evaluated (cached decode
#: vs one causal pass), i.e. in rounding order; bf16 keeps 8 significant
#: bits (relative spacing 2^-8), so a few spacings of the largest logit
#: after 24 layers stay well under 5e-2. A wrong position, cache slot or
#: RoPE offset moves the logits by their own size (relative error ~1).
LOGITS_RTOL = 5e-2

#: What a compiled program holds when a Pallas kernel was compiled for the
#: chip (in interpret mode the kernel is inlined as ordinary XLA ops).
KERNEL_MARK = "tpu_custom_call"

#: Dense model and serving load of phase (c).
ARCH = "internlm2_1_8b"
SERVE_ARGS = ["--slots", "8", "--requests", "16", "--prompt-len", "512",
              "--max-new", "32", "--top-k", "50", "--top-p", "0.9"]


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def device_check(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"the first device is {d.platform!r}, not a TPU")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    log(f"(a) device: {d.platform} / {d.device_kind} x{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# --------------------------------------------------------------------------
# (b) primitives
# --------------------------------------------------------------------------

def on_chip(name, prims, fn, *args):
    """Compile ``fn`` (AK calls, backend "auto") and run it. Every
    primitive in ``prims`` must have resolved to Pallas, and the compiled
    program must hold a TPU kernel."""
    import jax
    from repro.core import registry

    registry.clear_caches()
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    for p in prims:
        got = registry.get(p).cache_backends()
        check(got == ("pallas",), f"{name}: {p} resolved to {got}")
    check(KERNEL_MARK in compiled.as_text(),
          f"{name}: no {KERNEL_MARK} in the compiled program")
    out = jax.block_until_ready(compiled(*args))
    log(f"(b) {name}: backend pallas ({', '.join(prims)}); "
        f"tpu_custom_call present; {time.perf_counter() - t0:.1f}s "
        f"with compile")
    return out


def oracle(fn, *args):
    import jax
    from repro import core as ak

    with ak.backend("jnp"):
        return jax.block_until_ready(jax.jit(fn)(*args))


def same(name, got, want, how="bitwise") -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        check(g.shape == w.shape and g.dtype == w.dtype,
              f"{name}: {g.shape}/{g.dtype} vs oracle {w.shape}/{w.dtype}")
        bad = int(jnp.sum(g != w))
        check(bad == 0, f"{name}: {bad} of {g.size} elements differ from "
                        f"the jnp oracle")
    log(f"(b) {name}: agrees with the jnp oracle ({how}, "
        f"{sum(int(np.prod(x.shape)) for x in jax.tree.leaves(got))} "
        f"elements)")


def primitives_phase(seed: int, n: int = 2 ** 24,
                     vocab_rows=(8, 94208)) -> None:
    import jax
    import jax.numpy as jnp
    from repro import core as ak

    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (n,), jnp.float32)
    iota = jnp.arange(n, dtype=jnp.int32)
    logits = 4.0 * jax.random.normal(ks[1], vocab_rows, jnp.float32)

    def sort(x):
        return ak.merge_sort(x)
    same("sort", on_chip("sort", ["sort"], sort, x), oracle(sort, x))

    def sort_kv(k, v):
        return ak.merge_sort_by_key(k, v)
    sk, sv = on_chip("sort_kv", ["sort_kv"], sort_kv, x, iota)
    wk, _ = oracle(sort_kv, x, iota)
    same("sort_kv keys", sk, wk)
    # equal keys may carry their payloads in any order: check the pairs
    check(bool(jnp.all(x[sv] == sk)), "sort_kv: payload/key pairs broken")
    check(bool(jnp.all(jnp.sort(sv) == iota)),
          "sort_kv: payload is not a permutation")
    log("(b) sort_kv: every (key, payload) pair intact")

    def topk(lg):
        return ak.topk(lg, 50)
    same("topk", on_chip("topk", ["topk"], topk, logits),
         oracle(topk, logits))

    def nucleus(lg):
        return ak.nucleus_mask(lg, top_p=0.9)
    same("nucleus_mask", on_chip("nucleus_mask", ["nucleus_mask"], nucleus,
                                 logits),
         oracle(nucleus, logits))

    def hist(x):
        return ak.minmax_histogram(x, 256, -4.0, 4.0)
    same("minmax_histogram", on_chip("minmax_histogram",
                                     ["minmax_histogram"], hist, x),
         oracle(hist, x))

    hay = jnp.sort(x)
    q = jnp.concatenate([jax.random.normal(ks[2], (4096 - 64,)),
                         hay[:: n // 64]])

    def search(h, q):
        return ak.searchsortedlast(h, q)
    same("searchsortedlast", on_chip("searchsortedlast", ["searchsorted"],
                                     search, hay, q),
         oracle(search, hay, q))

    def sumsq(x):
        return ak.mapreduce(jnp.square, jnp.add, x, init=0.0)
    got, want = on_chip("mapreduce", ["mapreduce"], sumsq, x), oracle(
        sumsq, x)
    rel = abs(float(got) - float(want)) / abs(float(want))
    # f32 sums of 2^24 terms in two different orders: ~1e-7 per add
    # relative, far below 1e-4
    check(rel < 1e-4, f"mapreduce: relative gap {rel:.3g} to the oracle")
    log(f"(b) mapreduce: agrees with the jnp oracle (f32 sum, relative gap "
        f"{rel:.3g} < 1e-4)")

    ints = jax.random.randint(ks[3], (n,), -8, 9, jnp.int32)

    def scan(v):
        return ak.accumulate(jnp.add, v, init=0)
    same("accumulate", on_chip("accumulate", ["accumulate"], scan, ints),
         oracle(scan, ints), how="bitwise, int32")

    runs = jnp.sort(x.reshape(4, -1), axis=1).reshape(-1)

    def merge(r):
        return ak.merge(r, 4)
    same("merge", on_chip("merge", ["merge"], merge, runs),
         oracle(merge, runs))


# --------------------------------------------------------------------------
# (c) model
# --------------------------------------------------------------------------

def serve_phase(seed: int, arch: str, serve_args):
    from repro.core import registry
    from repro.launch import serve

    registry.clear_caches()
    argv = ["--arch", arch, "--full", "--seed", str(seed)] + list(serve_args)
    t0 = time.perf_counter()
    results, stats = serve.main(argv)
    n_req = int(argv[argv.index("--requests") + 1])
    max_new = int(argv[argv.index("--max-new") + 1])
    check(len(results) == n_req, f"serve: {len(results)} results")
    for rid, r in sorted(results.items()):
        check(r.status == "COMPLETED",
              f"serve: request {rid} ended {r.status}")
        check(len(r.tokens) == max_new,
              f"serve: request {rid} has {len(r.tokens)} tokens")
    check(stats.tokens == n_req * max_new, f"serve: {stats.tokens} tokens")
    for p in ("topk", "nucleus_mask"):
        got = registry.get(p).cache_backends()
        check(got == ("pallas",), f"serve sampler: {p} resolved to {got}")
    log(f"(c) serve: {n_req}/{n_req} requests COMPLETED with {max_new} "
        f"tokens each; sampler topk + nucleus_mask on pallas; "
        f"{time.perf_counter() - t0:.1f}s with compile")


def teacher_forced_phase(seed: int, arch: str, batch: int = 2,
                         prompt: int = 60, steps: int = 4):
    import jax
    import jax.numpy as jnp
    from repro.configs import load_config
    from repro.models import model as M

    cfg = load_config(arch)
    params = jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    total = prompt + steps
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, total), 0, cfg.vocab)
    v = cfg.vocab

    forward = jax.jit(lambda p, t: M.forward(p, cfg, t)[0])
    prefill = jax.jit(lambda p, t: M.prefill(p, cfg, t, cache_len=total))
    decode = jax.jit(lambda p, t, c, i: M.decode_step(p, cfg, t, c, i))

    def gap(got, want):
        got = got[..., :v].astype(jnp.float32)
        want = want[..., :v].astype(jnp.float32)
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    ref = forward(params, tokens)
    logits, caches, _ = prefill(params, tokens[:, :prompt])
    gaps = [gap(logits, ref[:, :prompt])]
    for t in range(prompt, total):
        logits, caches = decode(params, tokens[:, t:t + 1], caches,
                                jnp.int32(t))
        gaps.append(gap(logits[:, 0], ref[:, t]))
    worst = max(gaps)
    check(worst <= LOGITS_RTOL,
          f"teacher-forced: logits gap {worst:.3g} > {LOGITS_RTOL}")
    log(f"(c) teacher-forced {cfg.name} ({cfg.n_layers} layers, d_model "
        f"{cfg.d_model}): prefill {prompt} + {steps} decode steps vs "
        f"forward; max |gap| / max |logit| = {worst:.3g} (prefill "
        f"{gaps[0]:.3g}, decode {max(gaps[1:]):.3g}) <= {LOGITS_RTOL}")


# --------------------------------------------------------------------------
# four chips: SIHSort
# --------------------------------------------------------------------------

def sihsort_phase(seed: int, n_per: int = 2 ** 24, ndev: int = 4):
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import core as ak
    from repro.core import compat

    check(len(jax.devices()) == ndev,
          f"SIHSort wants {ndev} devices, found {len(jax.devices())}")
    mesh = compat.make_mesh((ndev,), ("data",))
    shard = NamedSharding(mesh, P("data"))
    n = ndev * n_per
    keys = jax.jit(lambda k: jax.random.normal(k, (n,), jnp.float32),
                   out_shardings=shard)(jax.random.PRNGKey(seed))
    payload = jax.jit(lambda: jnp.arange(n, dtype=jnp.int32),
                      out_shardings=shard)()
    host = np.asarray(keys)
    want = np.sort(host)
    want_perm = np.argsort(host, kind="stable")

    def collect(res):
        counts = np.asarray(res.count).reshape(-1)
        vals = np.asarray(res.values).reshape(ndev, -1)
        out = np.concatenate([vals[r, :counts[r]] for r in range(ndev)])
        pay = None
        if res.payload is not None:
            p = np.asarray(res.payload).reshape(ndev, -1)
            pay = np.concatenate([p[r, :counts[r]] for r in range(ndev)])
        return out, pay, counts

    for label, args in (("key-only", (keys,)), ("with payload",
                                                (keys, payload))):
        t0 = time.perf_counter()

        def run(*a):
            kw = {} if len(a) == 1 else {"payload": a[1]}
            return ak.sihsort_sharded(a[0], mesh, "data",
                                      capacity_factor=2.0, **kw)
        compiled = jax.jit(run).lower(*args).compile()
        hlo = compiled.as_text()
        a2a = len(re.findall(r"= [^\n]*\ball-to-all(?:-start)?\(", hlo))
        check(a2a == 1, f"SIHSort {label}: {a2a} all-to-all ops compiled")
        check(KERNEL_MARK in hlo,
              f"SIHSort {label}: no {KERNEL_MARK} in the program")
        res = jax.block_until_ready(compiled(*args))
        devices = {s.device for s in res.values.addressable_shards}
        check(len(devices) == ndev,
              f"SIHSort {label}: result on {len(devices)} devices")
        overflow = int(np.asarray(res.overflow).sum())
        check(overflow == 0, f"SIHSort {label}: overflow {overflow}")
        got, pay, counts = collect(res)
        check(np.array_equal(got, want),
              f"SIHSort {label}: keys differ from np.sort")
        if pay is not None:
            # equal keys may arrive in any payload order: order each run of
            # equal keys by payload, then it must be the stable argsort
            check(np.array_equal(host[pay], got),
                  f"SIHSort {label}: (key, payload) pairs broken")
            check(np.array_equal(pay[np.lexsort((pay, got))], want_perm),
                  f"SIHSort {label}: payload differs from the stable "
                  f"argsort")
        log(f"SIHSort {label}: {n_per} f32 keys x {ndev} chips == np.sort"
            f"{' (payload == stable argsort per run of equal keys)' if pay is not None else ''}; "
            f"1 all-to-all; overflow 0; shards on {len(devices)} distinct "
            f"devices; per-rank counts {counts.tolist()}; "
            f"{time.perf_counter() - t0:.1f}s with compile")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: primitives + serving; 4: SIHSort only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit("chip_smoke.py: src/repro not found next to this script; "
                 "run it from a checkout of the repository")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    # libtpu logs under /tmp unless told otherwise; keep to the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.runtime import compile_cache

    log(f"compilation cache: {compile_cache.enable()}")
    device = device_check(args.chips)
    if args.chips == 4:
        sihsort_phase(args.seed)
    else:
        primitives_phase(args.seed)
        serve_phase(args.seed, ARCH, SERVE_ARGS)
        teacher_forced_phase(args.seed, ARCH)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
