"""Serving driver — a thin CLI over the continuous-batching engine.

The sampler is deliberately built from the paper's primitives — this is the
"sorting is the hot path of real applications" claim made executable:

    top-k cut       -> ak.topk                     (sort-derived)
    top-p (nucleus) -> ak.nucleus_mask             (ONE fused registry call:
                       descending sortperm + inclusive prefix sum + top-p
                       cut + keep comparison; kernels/nucleus_kernel.py)

``fused=False`` keeps the historical unfused composition (sortperm_batched
+ vmapped accumulate + vmapped searchsortedfirst + XLA scatter) — the
serving gate (benchmarks/serving.py) counts its launches against the fused
path's every CI run.

The actual serving loop lives in ``launch.engine``: a slot scheduler with
per-slot decode state, EOS/limit retirement, in-place refill from a request
queue under fully static shapes, and EOS-aware token accounting.
``serve_loop`` (the fixed-batch entry point the tests and examples use)
delegates to the engine for the schedulable families and keeps a small
fixed-batch fallback for encdec/vlm (whose per-request encoder/vision
features are not slot-refillable yet).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import core as ak
from repro.core import registry
from repro.kernels.common import NEG_MASK
from repro.launch.engine import ENGINE_FAMILIES, Engine, Request
from repro.models import model as M

# Registry tuning for the decode-step sampler. Per step the sampler touches
# vocab-sized rows (tens of K elements): plenty for the tiled kernels, but
# the bitonic network's n·log²n work only beats XLA's sort once launches
# amortise — so small rows demote to the portable path (AK's switch_below,
# as a declarative table instead of branches). The registry's jit cache does
# the rest: every primitive here traces once for the whole serve loop
# instead of once per decode step.
#
# Registered as the named preset "sampler": the hand-rolled numbers are the
# WEAK layer — an attached autotune cache (repro.tune) overrides them with
# measured per-size-class verdicts, and `repro.tune.tune_all` seeds the
# cache from this preset so un-measured keys keep these values. An explicit
# ``ak_tuning=`` argument still applies as scoped overrides (strongest).
SAMPLER_TUNING = registry.tuning.register_preset("sampler", {
    "argsort_batched": {"switch_below": 4096},
    "topk": {"switch_below": 4096},
    "accumulate": {"switch_below": 4096},
    "searchsorted": {"switch_below": 4096},
    "nucleus_mask": {"switch_below": 4096},
})


def _batched_keys(rng):
    """True when ``rng`` is a batch of per-row keys: (B, 2) raw uint32 keys
    or a (B,) typed key array — the engine's per-request sampling path."""
    if jnp.issubdtype(rng.dtype, jnp.unsignedinteger):
        return rng.ndim == 2
    return rng.ndim == 1      # typed key dtype


def sample_logits(rng, logits, *, temperature=1.0, top_k=0, top_p=1.0,
                  vocab=None, fused=True):
    """logits: (B, V) -> token ids (B,). AK-primitive nucleus sampling.

    ``rng``: one key for the whole batch, or a batch of per-row keys (the
    engine passes per-request keys so a sampled token depends only on the
    request, never the slot/batch it rides in). ``fused=True`` routes the
    top-p mask through the fused ``nucleus_mask`` primitive (1 registry
    dispatch); ``fused=False`` is the historical unfused composition.
    """
    B, V = logits.shape
    lg = logits.astype(jnp.float32)
    if vocab is not None and vocab < V:
        lg = jnp.where(jnp.arange(V)[None, :] < vocab, lg, NEG_MASK)
    if temperature <= 0.0:
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)
    lg = lg / temperature

    if top_k and top_k < V:
        kth = ak.topk(lg, top_k)[0][:, -1]
        lg = jnp.where(lg < kth[:, None], NEG_MASK, lg)

    if top_p < 1.0:
        if fused:
            keep = ak.nucleus_mask(lg, top_p=float(top_p))
        else:
            # the unfused composition the fused primitive replaced:
            # descending order for the WHOLE batch in one batched sortperm,
            # then a vmapped per-row scan + search + an XLA scatter
            order = ak.sortperm_batched(-lg)
            probs = jax.nn.softmax(
                jnp.take_along_axis(lg, order, axis=-1), axis=-1
            )

            def cut_row(crow):
                # host-scalar init keeps one registry cache key (a device
                # scalar would route to the uncached path); first index
                # where cumulative mass exceeds top_p — AK scan + search
                cum = ak.accumulate(jnp.add, crow, init=0.0)
                return ak.searchsortedfirst(cum, jnp.float32(top_p)[None])[0]

            cut = jax.vmap(cut_row)(probs)
            keep_sorted = jnp.arange(V)[None, :] <= cut[:, None]
            keep = jnp.zeros_like(keep_sorted).at[
                jnp.arange(B)[:, None], order
            ].set(keep_sorted)
        lg = jnp.where(keep, lg, NEG_MASK)

    rng = jnp.asarray(rng)
    if _batched_keys(rng):
        return jax.vmap(jax.random.categorical)(rng, lg).astype(jnp.int32)
    return jax.random.categorical(rng, lg).astype(jnp.int32)


@dataclasses.dataclass
class ServeStats:
    prefill_s: float
    decode_s: float
    tokens: int          # EOS-aware when the loop ran with an eos_id
    #: per-rid terminal status (engine path only; None for the fixed-batch
    #: fallback, which predates the status lifecycle)
    statuses: dict | None = None
    #: the engine's full EngineStats (preemptions, step_retries,
    #: faults_injected, ...) when the engine served the batch
    engine_stats: object | None = None

    @property
    def tokens_per_s(self):
        return self.tokens / max(self.decode_s, 1e-9)


def serve_loop(params, cfg, prompts, *, max_new: int = 32, cache_len: int,
               temperature=1.0, top_k=0, top_p=1.0, seed=0, eos_id=None,
               frames=None, patches=None, ak_tuning=None, fused=True,
               paged=False, page_size=None, num_pages=None,
               preempt=False, queue_cap=None, deadline=None, chaos=None):
    """prompts: (B, S_prompt) int32. Returns (generated (B, max_new), stats).

    Engine-schedulable families run through the continuous-batching engine
    (one slot per prompt row; EOS-aware token accounting — a sequence that
    stops early pads its output row with ``eos_id`` and stops counting).
    encdec/vlm take the fixed-batch fallback.

    ``ak_tuning``: per-primitive registry overrides for the sampler's AK
    primitives ({primitive: {tunable: value}}); default: the "sampler"
    preset (which a measured autotune cache, when attached, overrides
    per size class — explicit ak_tuning beats both).

    ``paged``: block-pool KV cache with copy-on-write prefix reuse
    (dense/moe; DESIGN.md §8a). ``page_size`` defaults to the
    ``page_gather`` primitive's TuningTable knob, ``num_pages`` to a
    full-footprint pool (undersize it to see the admission gate defer).

    Failure tier (engine families only; DESIGN.md §9): ``preempt`` turns
    page exhaustion into evict-and-replay instead of a crash; ``deadline``
    (engine steps from submission) retires late requests TIMED_OUT;
    ``queue_cap`` bounds admission (overflow REJECTED); ``chaos`` (a seed)
    runs under ``faults.FaultPlan.seeded`` with a retrying supervisor —
    same seed, same injected failures. Per-rid outcomes land in
    ``ServeStats.statuses``/``engine_stats``.
    """
    if cfg.family in ENGINE_FAMILIES and frames is None and patches is None:
        B, S = prompts.shape
        sup = None
        if chaos is not None:
            from repro.runtime.supervisor import Supervisor
            sup = Supervisor(None, n_hosts=1, max_retries=3,
                             sleep=lambda s: None)
        eng = Engine(
            params, cfg, slots=B, cache_len=cache_len, prompt_pad=S,
            temperature=temperature, top_k=top_k, top_p=top_p, seed=seed,
            eos_id=eos_id, fused_sampler=fused, ak_tuning=ak_tuning,
            paged=paged, page_size=page_size, num_pages=num_pages,
            preempt=preempt or chaos is not None, queue_cap=queue_cap,
            supervisor=sup,
        )
        host = np.asarray(prompts, np.int32)
        from repro.runtime import faults
        # only install a plan when asked — active(None) would mask a plan
        # the CALLER installed around this call
        ctx = (faults.active(faults.FaultPlan.seeded(chaos))
               if chaos is not None else contextlib.nullcontext())
        with ctx:
            results, es = eng.run(
                [Request(rid=i, prompt=host[i], max_new=max_new,
                         deadline=deadline)
                 for i in range(B)]
            )
        pad = eos_id if eos_id is not None else 0
        toks = np.full((B, max_new), pad, np.int32)
        for i in range(B):
            got = results[i].tokens[:max_new]
            toks[i, :len(got)] = got
        return jnp.asarray(toks), ServeStats(
            prefill_s=es.prefill_s, decode_s=es.decode_s, tokens=es.tokens,
            statuses={i: results[i].status for i in sorted(results)},
            engine_stats=es,
        )

    scope = (
        registry.tuning.preset("sampler") if ak_tuning is None
        else registry.tuning.overrides(ak_tuning)
    )
    with scope:
        return _serve_loop_fixed(
            params, cfg, prompts, max_new=max_new, cache_len=cache_len,
            temperature=temperature, top_k=top_k, top_p=top_p, seed=seed,
            frames=frames, patches=patches, fused=fused,
        )


def _serve_loop_fixed(params, cfg, prompts, *, max_new, cache_len,
                      temperature, top_k, top_p, seed, frames, patches,
                      fused):
    """Fixed-batch reference loop (encdec/vlm): shared scalar position, no
    EOS, no refill — the pre-engine behaviour, kept for the families whose
    cross-attention caches are not slot-refillable yet."""
    B, S = prompts.shape
    rng = jax.random.PRNGKey(seed)

    t0 = time.perf_counter()
    logits, caches, pos = M.prefill(
        params, cfg, prompts, cache_len=cache_len, frames=frames,
        patches=patches,
    )
    logits = jax.block_until_ready(logits)
    t1 = time.perf_counter()

    decode = jax.jit(
        lambda p, t, c, i: M.decode_step(p, cfg, t, c, i),
        donate_argnums=(2,),
    )

    out = []
    rng, k = jax.random.split(rng)
    tok = sample_logits(k, logits[:, -1], temperature=temperature,
                        top_k=top_k, top_p=top_p, vocab=cfg.vocab,
                        fused=fused)
    out.append(tok)
    for step in range(max_new - 1):
        logits, caches = decode(params, tok[:, None], caches, pos + step)
        rng, k = jax.random.split(rng)
        tok = sample_logits(k, logits[:, 0], temperature=temperature,
                            top_k=top_k, top_p=top_p, vocab=cfg.vocab,
                            fused=fused)
        out.append(tok)
    toks = jax.block_until_ready(jnp.stack(out, axis=1))
    t2 = time.perf_counter()
    stats = ServeStats(prefill_s=t1 - t0, decode_s=t2 - t1,
                       tokens=B * max_new)
    return toks, stats


def main(argv=None):
    """Serve seeded random prompts with random weights; returns
    ``(results, EngineStats)`` for engine families (``None`` for the
    encdec/vlm fallback)."""
    from repro.configs import load_config, load_smoke_config
    from repro.runtime import compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--full", action="store_true",
                    help="build the architecture's published CONFIG "
                         "(full widths and depth) instead of the 64-wide "
                         "smoke preset")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--top-k", type=int, default=16)
    ap.add_argument("--top-p", type=float, default=0.95)
    ap.add_argument("--eos", type=int, default=None,
                    help="EOS token id (default: none — run to max-new)")
    ap.add_argument("--unfused", action="store_true",
                    help="use the historical unfused top-p composition")
    ap.add_argument("--paged", action="store_true",
                    help="block-pool KV cache with copy-on-write prefix "
                         "reuse (dense/moe)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page (default: the page_gather "
                         "primitive's tuned knob)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool size (default: full footprint — "
                         "slots * cache_len / page_size)")
    ap.add_argument("--defrag-every", type=int, default=0,
                    help="compact the page pool every N retirements "
                         "(0: never)")
    ap.add_argument("--preempt", action="store_true",
                    help="preempt-and-recompute under page exhaustion: "
                         "evict the least-progressed lane and replay it "
                         "later, token-identically (implies --paged "
                         "semantics; no-op for the contiguous cache)")
    ap.add_argument("--deadline", type=int, default=None,
                    help="per-request deadline in engine steps from "
                         "submission; late requests retire TIMED_OUT "
                         "(default: none)")
    ap.add_argument("--queue-cap", type=int, default=None,
                    help="bounded admission queue; arrivals past the cap "
                         "are REJECTED newest-first (default: unbounded)")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="run under a seeded fault plan (runtime/faults.py)"
                         ": injected allocator/admission/device-step "
                         "failures, absorbed by supervised retries and "
                         "preemption; same seed, same faults")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record telemetry spans and export a Perfetto/"
                         "Chrome-trace JSON to PATH at exit (open it at "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write a metrics snapshot to PATH at exit "
                         "(.json: JSON snapshot; else Prometheus text)")
    args = ap.parse_args(argv)

    from repro.runtime import metrics, telemetry
    if args.trace:
        telemetry.enable()

    def export_obs():
        if args.trace:
            doc = telemetry.export(args.trace)
            telemetry.disable()
            print(f"trace: {len(doc['traceEvents'])} events -> "
                  f"{args.trace}")
        if args.metrics:
            metrics.write(args.metrics)
            print(f"metrics: snapshot -> {args.metrics}")

    compile_cache.enable()
    cfg = (load_config if args.full else load_smoke_config)(args.arch)
    rng = jax.random.PRNGKey(args.seed)
    params = jax.jit(M.init_params, static_argnums=1)(rng, cfg)
    prompts = np.asarray(jax.random.randint(
        rng, (args.requests, args.prompt_len), 0, cfg.vocab
    ))

    if cfg.family in ENGINE_FAMILIES:
        cache_len = args.prompt_len + args.max_new
        if args.paged:
            # the paged cache requires cache_len % page_size == 0 (decode
            # attention width must equal the contiguous width bit-for-bit)
            ps = args.page_size or int(
                registry.tuning.lookup("page_gather")["page_size"])
            cache_len = -(-cache_len // ps) * ps
        chaos = args.chaos is not None
        sup = None
        if chaos:
            # chaos runs want retries with no real sleeping in the loop
            from repro.runtime.supervisor import Supervisor
            sup = Supervisor(None, n_hosts=1, max_retries=3,
                             sleep=lambda s: None)
        eng = Engine(
            params, cfg, slots=args.slots, cache_len=cache_len,
            prompt_pad=args.prompt_len, top_k=args.top_k, top_p=args.top_p,
            seed=args.seed, eos_id=args.eos, fused_sampler=not args.unfused,
            paged=args.paged, page_size=args.page_size,
            num_pages=args.num_pages, defrag_every=args.defrag_every,
            preempt=args.preempt or chaos, queue_cap=args.queue_cap,
            supervisor=sup,
        )
        from repro.runtime import faults
        ctx = (faults.active(faults.FaultPlan.seeded(args.chaos))
               if chaos else contextlib.nullcontext())
        with ctx:
            results, stats = eng.run([
                Request(rid=i, prompt=prompts[i], max_new=args.max_new,
                        deadline=args.deadline)
                for i in range(args.requests)
            ])
        done = sum(r.finished_step >= 0 for r in results.values())
        print(
            f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
            f"{M.param_count(params):,} params"
        )
        print(
            f"served {done}/{args.requests} requests on {args.slots} slots; "
            f"{stats.tokens} tokens in {stats.steps} steps; "
            f"prefill {stats.prefill_s:.3f}s; "
            f"decode {stats.tokens_per_s:.1f} tok/s; "
            f"slot util {stats.mean_slot_util:.2f}"
        )
        tt, qw = stats.ttft_s, stats.queue_wait_s
        if tt:
            print(
                f"latency: ttft p50 {tt['p50'] * 1e3:.1f}ms "
                f"p99 {tt['p99'] * 1e3:.1f}ms; "
                f"queue-wait p50 {qw.get('p50', 0.0) * 1e3:.1f}ms; "
                f"mean queue depth {stats.mean_queue_depth:.2f}"
            )
        if args.paged:
            print(
                f"paged: {stats.num_pages} pages x {stats.page_size} tokens; "
                f"occupancy {stats.mean_occupancy:.2f}; "
                f"prefix hits {stats.prefix_hits}/{stats.prefix_lookups}; "
                f"cow forks {stats.cow_forks}; defrags {stats.defrags}; "
                f"{stats.resident_bytes_per_active_token:.0f} "
                f"resident B/active token"
            )
        if chaos or args.preempt or args.deadline is not None \
                or args.queue_cap is not None:
            from collections import Counter
            sts = Counter(r.status for r in results.values())
            print(
                "faults: "
                + " ".join(f"{k}={v}" for k, v in sorted(sts.items()))
                + f"; injected={stats.faults_injected} "
                f"preemptions={stats.preemptions} "
                f"resumes={stats.resumes} retries={stats.step_retries} "
                f"rejections={stats.rejections} timeouts={stats.timeouts}"
            )
        export_obs()
        return results, stats

    # encdec/vlm: fixed-batch fallback
    extras = {}
    if cfg.family == "encdec":
        extras["frames"] = jnp.zeros(
            (args.slots, cfg.enc_seq, cfg.d_model), cfg.dtype)
    if cfg.family == "vlm":
        extras["patches"] = jnp.zeros(
            (args.slots, cfg.vision_seq, cfg.d_model), cfg.dtype)
    toks, stats = serve_loop(
        params, cfg, jnp.asarray(prompts[:args.slots]),
        max_new=args.max_new,
        cache_len=args.prompt_len + args.max_new,
        top_k=args.top_k, top_p=args.top_p, fused=not args.unfused,
        **extras,
    )
    print(f"generated {toks.shape} tokens; prefill {stats.prefill_s:.3f}s; "
          f"decode {stats.tokens_per_s:.1f} tok/s")
    export_obs()


if __name__ == "__main__":
    main()
