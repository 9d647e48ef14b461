"""Offline serving cells: one seeded backlog through ``Engine.run``.

The engine takes arrivals only as step numbers and runs until every
request has finished. The backlog is queued at the start, in the order
the seed draws it, and sized from the engine's own rate during warm-up
so that its queue still holds requests when ``--seconds`` have passed:
the window is the first ``--seconds`` of that ``Engine.run``, with every
slot in use, as a slice of an offline job longer than the window. The
engine serves the rest after the window closes; the timed tokens are
those the host had received by the close, each stamped by the decode step
that produced it (``_Tap``).

After the window the served tokens of a seeded sample of requests (the
longest among them) are read against the plain reference
(``bench/reference.py``) with the engine's own sampling noise.
"""
from __future__ import annotations

import bisect
import gc
import time

import numpy as np

from bench import common, reference, traffic as T, weights


def model_dims(cfg_file: dict) -> dict:
    """The sizes the benchmark's weights, reference and work functions
    read, from a configuration file of published keys."""
    d, H = cfg_file["hidden_size"], cfg_file["num_attention_heads"]
    return {
        "layers": cfg_file["num_hidden_layers"],
        "d_model": d,
        "heads": H,
        "kv_heads": cfg_file["num_key_value_heads"],
        "head_dim": cfg_file.get("head_dim", d // H),
        "d_ff": cfg_file["intermediate_size"],
        "vocab": cfg_file["vocab_size"],
        "padded_vocab": cfg_file["padded_vocab"],
        "rope_theta": float(cfg_file["rope_theta"]),
        "norm_eps": float(cfg_file["rms_norm_eps"]),
        "dtype": cfg_file["torch_dtype"],
    }


def program_config(cfg_file: dict):
    """The program's ModelConfig for the same model."""
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig

    m = model_dims(cfg_file)
    cfg = ModelConfig(
        name=cfg_file["name"], family="dense", n_layers=m["layers"],
        d_model=m["d_model"], n_heads=m["heads"], n_kv_heads=m["kv_heads"],
        d_ff=m["d_ff"], vocab=m["vocab"], head_dim=m["head_dim"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"],
        dtype=jnp.dtype(m["dtype"]),
    )
    if cfg.padded_vocab() != m["padded_vocab"]:
        raise common.SetupError(
            f"the program pads the vocabulary to {cfg.padded_vocab()}, the "
            f"configuration file says {m['padded_vocab']}")
    return cfg


class _Tap:
    """Engine monitor hook, called once per decode step the engine books,
    right after the step's tokens reach their requests: stamps the step on
    the host clock and drives the tracer."""

    def __init__(self, base):
        self.base = base
        self.times = []
        self.tracer = None

    def record(self, host, step_time):
        self.base.record(host, step_time)
        self.times.append(time.perf_counter())
        if self.tracer is not None:
            self.tracer.on_step(len(self.times))


def token_times(tl: dict, tokens: int, steps: list) -> list:
    """Host times at which a request's ``tokens`` tokens arrived: the
    first at its prefill, each later one at a decode step. A live lane
    gets a token at every step, so its decode tokens are those of the
    ``tokens - 1`` steps up to the one that booked its last."""
    if not tokens:
        return []
    if tokens == 1:
        return [tl["first_token_t"]]
    last = bisect.bisect_left(steps, tl["last_token_t"])
    first = last - (tokens - 1) + 1
    if first < 0 or last >= len(steps) or steps[first] < tl["first_token_t"]:
        raise RuntimeError("a request's tokens do not match the steps that "
                           "booked them")
    return [tl["first_token_t"]] + steps[first:last + 1]


def _requests(reqs, budget=None):
    from repro.launch.engine import Request

    return [Request(rid, ids, budget or n) for rid, ids, n in reqs]


def _window(engine, reqs, counter):
    counter.on = True
    t0 = time.perf_counter()
    results, stats = engine.run(reqs)
    t1 = time.perf_counter()
    counter.on = False
    return results, stats, t0, t1


def _tpot(stats, close: float) -> list:
    """(last token - first token) / (tokens - 1) of every request that
    completed by ``close``."""
    return [(tl["last_token_t"] - tl["first_token_t"]) / (tl["tokens"] - 1)
            for tl in stats.timeline.values()
            if tl.get("status") == "COMPLETED" and tl.get("tokens", 0) > 1
            and tl["last_token_t"] <= close]


def serve_window(run, *, engine_hook=None):
    """Set up, warm up and run the window of one serving cell. Returns a
    dict of everything the metrics and the check read. ``engine_hook`` (a
    function of the engine) lets a test break the timed path."""
    import jax
    from repro.launch.engine import COMPLETED, Engine
    from repro.runtime.supervisor import StragglerMonitor

    tr, cfg_file = run.traffic, run.config
    dims = model_dims(cfg_file)
    cfg = program_config(cfg_file)
    s_w, s_traffic, s_engine, s_sample, s_warm = common.seeds(run.seed, 5)
    params = weights.make(dims, s_w, device=run.devices[0])
    jax.block_until_ready(params)
    t_w = time.perf_counter()
    tap = _Tap(StragglerMonitor(1))
    engine = Engine(params, cfg, slots=tr["slots"],
                    cache_len=tr["cache_len"], prompt_pad=tr["prompt_pad"],
                    temperature=tr["temperature"], top_k=tr["top_k"],
                    top_p=tr["top_p"], seed=s_engine, monitor=tap)
    handed = {}
    sample = engine._sample

    def _sample(keys, logits):
        # the dtype of the logits rows the sampler is handed
        handed["logits"] = logits.dtype
        return sample(keys, logits)
    engine._sample = _sample
    if engine_hook is not None:
        engine_hook(engine)

    # warm-up: every program the window runs (prefill at prompt_pad, the
    # decode step at all slots, the sampler at 1 and at all slots), then
    # once more with everything compiled, to time a prefill and a step
    warm = _requests(T.backlog(tr, tr["slots"], s_warm, vocab=dims["vocab"]),
                     budget=tr["warmup_max_new"])
    engine.run(warm)
    _, ws = engine.run(warm)
    per_step = ws.decode_s / max(ws.steps - 1, 1)
    per_prefill = ws.prefill_s / max(ws.prefills - 1, 1)
    n = T.size(tr, run.seconds, s_traffic, per_step, per_prefill)
    backlog = T.backlog(tr, n, s_traffic, vocab=dims["vocab"])
    common.log(f"set-up: weights {t_w - common.T_START:.1f} s after start, "
               f"warm-up {time.perf_counter() - t_w:.1f} s: "
               f"{per_prefill * 1e3:.2f} ms a prefill, {per_step * 1e3:.2f} "
               f"ms a step -> backlog of {n} requests")

    if run.tracer is not None:
        # the slice starts a fifth into the window, with the slots full
        tap.tracer = run.tracer
        run.tracer.start_at = max(2, int(0.2 * run.seconds / per_step))
    tap.times = []
    setup_s = time.perf_counter() - common.T_START
    results, stats, t0, t_end = _window(engine, _requests(backlog),
                                        run.compiles)
    if run.tracer is not None and run.tracer.running:
        run.tracer.stop()
    device = common.device_report(run.devices)
    # the sampler's wrapper ties the engine into a cycle: free its weights
    # and cache now, before the reference needs the room
    del engine, params, sample, _sample
    gc.collect()

    close = t0 + run.seconds
    steps = tap.times
    served = {rid: [t for t in token_times(stats.timeline[rid],
                                           len(r.tokens), steps)
                    if t <= close]
              for rid, r in results.items()}
    budget = {rid: n_out for rid, _, n_out in backlog}
    done = [rid for rid, r in results.items()
            if r.status == COMPLETED and len(r.tokens) == budget[rid]]
    queued = sum(1 for tl in stats.timeline.values()
                 if tl.get("admit_t", close) >= close)
    tpot = _tpot(stats, close)
    in_window = sum(t <= close for t in steps)
    common.log(f"window: {run.seconds:.3f} s of a {t_end - t0:.3f} s run of "
               f"{n} requests: {sum(map(len, served.values()))} of "
               f"{stats.tokens} tokens, {in_window} of "
               f"{stats.steps} steps, {len(tpot)} requests completed; "
               f"{queued} still queued at the close; {run.compiles.count} "
               f"compiles in the run")
    return {
        "dims": dims, "backlog": backlog, "results": results,
        "stats": stats, "window_s": run.seconds, "setup_s": setup_s,
        "device": device, "done": done, "tpot": tpot,
        "served": served, "steps_in_window": in_window,
        "logits_dtype": handed.get("logits"), "close": close,
        "engine_seed": s_engine, "sample_seed": s_sample,
        "weights_seed": s_w, "n": n,
    }


def check_sample(w: dict, tr: dict) -> list:
    """rids to read against the reference: the request with the most
    served tokens and ``check_requests - 1`` more drawn from the seed."""
    done = sorted(w["done"])
    if not done:
        return []
    longest = max(done, key=lambda r: (len(w["results"][r].tokens), -r))
    rest = [r for r in done if r != longest]
    rng = np.random.default_rng(w["sample_seed"])
    k = min(len(rest), tr["check_requests"] - 1)
    return [longest] + sorted(rng.choice(rest, size=k, replace=False)
                              .tolist())


def readings(w: dict, tr: dict, device, *, control: bool = False) -> dict:
    """Worst gap and out-of-set count over the sampled requests' served
    tokens, read against the float32 reference; with ``control`` the
    tokens read are those the fp8 reference would sample instead."""
    import jax
    import jax.numpy as jnp

    dims = w["dims"]
    with jax.default_device(device):
        params = weights.make(dims, w["weights_seed"], device)
        prompts = {rid: ids for rid, ids, _ in w["backlog"]}
        width = tr["output_len"]["max"]
        gaps, outside, tokens = [], 0, 0
        for rid in check_sample(w, tr):
            toks = np.asarray(w["results"][rid].tokens, np.int32)
            p = prompts[rid]
            seq = np.zeros((tr["cache_len"],), np.int32)
            seq[:len(p)] = p
            seq[len(p):len(p) + len(toks) - 1] = toks[:-1]
            seq = jnp.asarray(seq)
            lg = reference.logits(dims, params, seq)
            own = reference.logits(dims, params, seq, fp8=True) \
                if control else None
            g, o = reference.served_gaps(
                lg, len(p) - 1, toks, width=width, seed=w["engine_seed"],
                rid=rid, vocab=dims["vocab"], top_k=tr["top_k"],
                top_p=tr["top_p"], temperature=tr["temperature"],
                margin=tr["check_margin"], own_lg_all=own)
            gaps.append(float(g.max()))
            outside += int(o.sum())
            tokens += len(toks)
    return {"gap": max(gaps) if gaps else float("inf"),
            "outside": outside, "tokens_read": tokens}


def run(run, *, engine_hook=None) -> None:
    w = serve_window(run, engine_hook=engine_hook)
    tr, st = run.traffic, w["stats"]
    tokens = sum(len(t) for t in w["served"].values())
    t0 = time.perf_counter()
    got = readings(w, tr, run.devices[0])
    common.log(f"reference read {got['tokens_read']} served tokens of "
               f"{min(len(w['done']), tr['check_requests'])} requests in "
               f"{time.perf_counter() - t0:.1f} s")
    limits = run.limits
    checks = {
        "gap": {"value": got["gap"], "limit": limits["gap"]},
        "outside": {"value": got["outside"], "limit": limits["outside"]},
        "unfinished": {"value": w["n"] - len(w["done"]), "limit": 0},
    }
    e2e = {
        "serve_tok_s": tokens / w["window_s"],
        "tpot_p95_ms": float(np.percentile(w["tpot"], 95)) * 1e3
        if w["tpot"] else float("inf"),
        "setup_s": w["setup_s"],
    }
    close = w["close"]
    layer = {
        "window_s": w["window_s"], "dims": w["dims"], "slots": tr["slots"],
        "slot_util": st.slot_util[:w["steps_in_window"]],
        "prefill_s": [tl["first_token_t"] - tl["admit_t"]
                      for tl in st.timeline.values()
                      if tl.get("first_token_t", close) <= close],
        "logits_dtype": w["logits_dtype"],
        "requests": [(len(ids), len(w["served"][rid]))
                     for rid, ids, _ in w["backlog"]],
    }
    run.finish(e2e, layer, checks, attempted=w["n"],
               failed=w["n"] - len(w["done"]), device=w["device"])
