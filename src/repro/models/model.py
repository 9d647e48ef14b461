"""Top-level model: init, train/prefill forward, decode step, cache specs.

Everything is family-dispatched off ``cfg.family``. All layer stacks are
scanned (see transformer.py); decode caches are pytrees whose exact
ShapeDtypeStructs ``cache_specs`` reproduces for the dry-run.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import NEG_MASK
from repro.models import layers as L
from repro.models import sharding as SH
from repro.models import ssm as SSM
from repro.models import transformer as T

TP_DEFAULT = 16


def _vocab(cfg):
    return cfg.padded_vocab(TP_DEFAULT)


def _sinusoidal(seq, d):
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _maybe_remat(fn, cfg):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots
        )
    return jax.checkpoint(fn)


def scan_layers(body, carry, xs, cfg):
    """lax.scan over stacked layer params — or a Python unroll when the
    config is in cost-model mode (see ModelConfig.unroll_layers). The
    unroll indexes a numpy leaf of ``xs`` on the host, so a layer index
    given as one reaches the body as a static integer."""
    if not cfg.unroll_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        stacked = None
    return carry, stacked


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(rng, cfg):
    V = _vocab(cfg)
    d = cfg.d_model
    ks = jax.random.split(rng, 8)
    p = {
        "embed": L.embedding_init(ks[0], V, d, cfg.dtype),
        "final_norm": L.rmsnorm_init(d),
        "head": L.lm_head_init(ks[1], d, V, cfg.dtype),
    }
    fam = cfg.family
    if fam == "dense":
        p["layers"] = T._stack_init(
            lambda r: T.dense_layer_init(r, cfg), ks[2], cfg.n_layers
        )
    elif fam == "moe":
        n_moe = cfg.n_layers - int(cfg.first_layer_dense)
        p["layers"] = T._stack_init(
            lambda r: T.moe_layer_init(r, cfg), ks[2], n_moe
        )
        if cfg.first_layer_dense:
            dense_cfg = _dense_ff_view(cfg)
            p["layer0"] = T.dense_layer_init(ks[3], dense_cfg)
    elif fam == "ssm":
        p["layers"] = T._stack_init(
            lambda r: T.ssm_layer_init(r, cfg), ks[2], cfg.n_layers
        )
    elif fam == "hybrid":
        G, gs, tail = _hybrid_shape(cfg)
        flat = T._stack_init(
            lambda r: T.ssm_layer_init(r, cfg), ks[2], G * gs
        )
        p["layers"] = jax.tree.map(
            lambda a: a.reshape((G, gs) + a.shape[1:]), flat
        )
        p["tail"] = T._stack_init(
            lambda r: T.ssm_layer_init(r, cfg), ks[3], tail
        ) if tail else None
        p["shared"] = T.dense_layer_init(ks[4], cfg)  # ONE shared attn block
    elif fam == "encdec":
        p["enc_layers"] = T._stack_init(
            lambda r: T.dense_layer_init(r, cfg), ks[2], cfg.n_enc_layers
        )
        p["layers"] = T._stack_init(
            lambda r: T.encdec_dec_layer_init(r, cfg), ks[3], cfg.n_layers
        )
    elif fam == "vlm":
        G, gs = _vlm_shape(cfg)
        flat = T._stack_init(
            lambda r: T.dense_layer_init(r, cfg), ks[2], G * gs
        )
        p["layers"] = jax.tree.map(
            lambda a: a.reshape((G, gs) + a.shape[1:]), flat
        )
        p["cross"] = T._stack_init(
            lambda r: T.cross_layer_init(r, cfg), ks[3], G
        )
    else:
        raise ValueError(fam)
    return p


def _dense_ff_view(cfg):
    """deepseek-moe layer 0: dense FFN sized like shared+routed activation."""
    import dataclasses

    ff = cfg.d_ff * (cfg.top_k + cfg.n_shared_experts)
    return dataclasses.replace(cfg, d_ff=ff)


def _hybrid_shape(cfg):
    gs = cfg.hybrid_attn_every
    G = cfg.n_layers // gs
    tail = cfg.n_layers - G * gs
    return G, gs, tail


def _vlm_shape(cfg):
    gs = cfg.cross_attn_every - 1  # dense layers per group
    G = cfg.n_layers // cfg.cross_attn_every
    return G, gs


def param_count(params):
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def forward(params, cfg, tokens, *, frames=None, patches=None, mesh=None,
            dp_axes=("data",), use_ep=True, chunk=1024):
    """Logits over the padded vocab. Returns (logits, aux_loss)."""
    B, S = tokens.shape
    x = L.embed(
        {"embed": SH.gather_weight(params["embed"]["embed"], "model", None)},
        tokens,
    )
    positions = jnp.arange(S)
    aux_total = jnp.float32(0.0)
    fam = cfg.family

    if fam == "dense":
        def body(x, p):
            x, _ = T.dense_block(p, cfg, x, positions, chunk=chunk)
            return x, None
        x, _ = scan_layers(_maybe_remat(body, cfg), x, params["layers"], cfg)

    elif fam == "moe":
        if cfg.first_layer_dense:
            x, _ = T.dense_block(params["layer0"], cfg, x, positions,
                                 chunk=chunk)

        def body(carry, p):
            x, aux = carry
            x, a, _ = T.moe_block(p, cfg, x, positions, mesh=mesh,
                                  dp_axes=dp_axes, use_ep=use_ep, chunk=chunk)
            return (x, aux + a), None
        (x, aux_total), _ = scan_layers(
            _maybe_remat(body, cfg), (x, aux_total), params["layers"], cfg
        )

    elif fam == "ssm":
        def body(x, p):
            x, _, _ = T.ssm_block(p, cfg, x)
            return x, None
        x, _ = scan_layers(_maybe_remat(body, cfg), x, params["layers"], cfg)

    elif fam == "hybrid":
        shared = params["shared"]

        def group(x, pg):
            def inner(x, p):
                x, _, _ = T.ssm_block(p, cfg, x)
                return x, None
            x, _ = scan_layers(inner, x, pg, cfg)
            x, _ = T.dense_block(shared, cfg, x, positions, chunk=chunk)
            return x, None
        x, _ = scan_layers(_maybe_remat(group, cfg), x, params["layers"], cfg)
        if params.get("tail") is not None:
            def tail_body(x, p):
                x, _, _ = T.ssm_block(p, cfg, x)
                return x, None
            x, _ = scan_layers(
                _maybe_remat(tail_body, cfg), x, params["tail"], cfg
            )

    elif fam == "encdec":
        enc = frames + _sinusoidal(frames.shape[1], cfg.d_model).astype(
            frames.dtype
        )
        enc_pos = jnp.arange(frames.shape[1])

        def enc_body(h, p):
            h, _ = T.dense_block(p, cfg, h, enc_pos, causal=False,
                                 chunk=chunk)
            return h, None
        enc, _ = scan_layers(
            _maybe_remat(enc_body, cfg), enc, params["enc_layers"], cfg
        )

        def dec_body(x, p):
            x, _ = T.encdec_dec_block(p, cfg, x, positions, enc_out=enc,
                                      chunk=chunk)
            return x, None
        x, _ = scan_layers(_maybe_remat(dec_body, cfg), x, params["layers"], cfg)

    elif fam == "vlm":
        vis = patches

        def group(x, pg):
            pd, pc = pg

            def inner(x, p):
                x, _ = T.dense_block(p, cfg, x, positions, chunk=chunk)
                return x, None
            x, _ = scan_layers(inner, x, pd, cfg)
            x = T.cross_block(pc, cfg, x, vis, positions, chunk=chunk)
            return x, None
        x, _ = scan_layers(
            _maybe_remat(group, cfg), x, (params["layers"], params["cross"]),
            cfg
        )
    else:
        raise ValueError(fam)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.lm_head(
        {"unembed": SH.gather_weight(params["head"]["unembed"], None,
                                     "model")}, x,
    )
    return logits, aux_total


def loss_fn(params, cfg, tokens, labels, *, frames=None, patches=None,
            mesh=None, dp_axes=("data",), use_ep=True, aux_weight=0.01):
    """Next-token CE over the true vocab (padded columns masked).

    Written so every reduction is over the (model-)sharded vocab axis with
    small (B, S) results: the label logit is a masked sum, NOT
    ``take_along_axis`` — gathering along a sharded axis makes GSPMD
    replicate the full global-batch logits (measured 26 GB/step of
    all-reduce on whisper train_4k; EXPERIMENTS.md §Perf iteration 3).
    """
    logits, aux = forward(params, cfg, tokens, frames=frames,
                          patches=patches, mesh=mesh, dp_axes=dp_axes,
                          use_ep=use_ep)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        logits = jax.lax.with_sharding_constraint(
            logits, NamedSharding(mesh, P(dp_axes, None, "model"))
        )
    logits = logits.astype(jnp.float32)
    V = _vocab(cfg)
    iota = jnp.arange(V)
    logits = jnp.where(iota[None, None, :] < cfg.vocab, logits, NEG_MASK)
    m = jnp.max(logits, axis=-1, keepdims=True)          # (B,S,1) reduce
    lse = m[..., 0] + jnp.log(
        jnp.sum(jnp.exp(logits - m), axis=-1)
    )                                                    # (B,S) reduce
    label_logit = jnp.sum(
        jnp.where(iota[None, None, :] == labels[..., None], logits, 0.0),
        axis=-1,
    )                                                    # (B,S) masked sum
    ce = jnp.mean(lse - label_logit)
    return ce + aux_weight * aux, (ce, aux)


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------


def cache_specs(cfg, *, batch, cache_len):
    """ShapeDtypeStructs of the decode cache pytree (dry-run stand-ins)."""
    B, S = batch, cache_len
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    dt = cfg.dtype
    fam = cfg.family

    def kv(n_layers, seq):
        return {
            "k": jax.ShapeDtypeStruct((n_layers, B, seq, KV, hd), dt),
            "v": jax.ShapeDtypeStruct((n_layers, B, seq, KV, hd), dt),
        }

    if fam in ("dense", "moe"):
        return {"kv": kv(cfg.n_layers, S)}
    if fam == "ssm":
        H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
        conv_dim = cfg.d_inner + 2 * N
        return {
            "ssm": jax.ShapeDtypeStruct(
                (cfg.n_layers, B, H, P, N), jnp.float32
            ),
            "conv": jax.ShapeDtypeStruct(
                (cfg.n_layers, B, cfg.ssm_conv - 1, conv_dim), dt
            ),
        }
    if fam == "hybrid":
        G, gs, tail = _hybrid_shape(cfg)
        H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
        conv_dim = cfg.d_inner + 2 * N
        out = {
            "ssm": jax.ShapeDtypeStruct((G, gs, B, H, P, N), jnp.float32),
            "conv": jax.ShapeDtypeStruct(
                (G, gs, B, cfg.ssm_conv - 1, conv_dim), dt
            ),
            "kv": kv(G, S),
        }
        if tail:
            out["ssm_tail"] = jax.ShapeDtypeStruct(
                (tail, B, H, P, N), jnp.float32
            )
            out["conv_tail"] = jax.ShapeDtypeStruct(
                (tail, B, cfg.ssm_conv - 1, conv_dim), dt
            )
        return out
    if fam == "encdec":
        return {
            "kv": kv(cfg.n_layers, S),
            "xkv": kv(cfg.n_layers, cfg.enc_seq),
        }
    if fam == "vlm":
        G, gs = _vlm_shape(cfg)
        return {
            "kv": {
                "k": jax.ShapeDtypeStruct((G, gs, B, S, KV, hd), dt),
                "v": jax.ShapeDtypeStruct((G, gs, B, S, KV, hd), dt),
            },
            "xkv": kv(G, cfg.vision_seq),
        }
    raise ValueError(fam)


def paged_cache_specs(cfg, *, num_pages, page_size):
    """ShapeDtypeStructs of the PAGED decode cache: K/V live in a shared
    pool of ``num_pages`` pages of ``page_size`` tokens instead of per-row
    sequences — the batch axis disappears, and a (B, T) block table maps
    each lane's logical columns onto pool pages at decode time.

    Attention-KV families only (dense/moe): recurrent state is O(1) per
    lane — there is nothing to page."""
    if cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"paged KV cache needs an attention-family cache; family "
            f"{cfg.family!r} has recurrent state (nothing to page)"
        )
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    shape = (cfg.n_layers, num_pages, page_size, KV, hd)
    return {"kv": {
        "k": jax.ShapeDtypeStruct(shape, cfg.dtype),
        "v": jax.ShapeDtypeStruct(shape, cfg.dtype),
    }}


def zero_paged_caches(cfg, *, num_pages, page_size):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        paged_cache_specs(cfg, num_pages=num_pages, page_size=page_size),
    )


def decode_step(params, cfg, tokens, caches, position, *, chunk=1024,
                block_tables=None, page_size=None):
    """One serve step: tokens (B, 1) + caches -> (logits (B, 1, V), caches).

    ``position``: absolute index of the incoming token — a scalar int32
    (every row at the same position: the classic fixed-batch loop) or a
    (B,) int32 vector of PER-SLOT positions (continuous batching: each slot
    is at its own point in its own sequence; RoPE, the cache write column
    and the attention-length mask all follow the vector; positions past the
    cache length park the slot — the write drops and the lane decodes
    garbage nobody reads).

    ``block_tables`` (B, T) int32 + ``page_size``: caches are the paged
    pool from ``paged_cache_specs`` — writes go to (page, offset) through
    the table, reads come back through the ``page_gather`` primitive.
    """
    if block_tables is not None and cfg.family not in ("dense", "moe"):
        raise ValueError(f"paged decode unsupported for {cfg.family!r}")
    return _decode(params, cfg, tokens, caches, position, chunk=chunk,
                   block_tables=block_tables, page_size=page_size)


def _decode(params, cfg, tokens, caches, position, *, chunk=1024,
            block_tables=None, page_size=None, refill_row=None):
    """Cache-stepping forward for any query length: S=1 is the decode step,
    S=prompt_len with zeroed caches and position=0 is the prefill (the KV
    writes land in slots [0, S) and causal masking hides the empty tail).
    ``refill_row`` (dense/moe): batch-1 tokens rewrite that row of the
    shared caches whole, as a prefill into zeroed caches would leave it."""
    B, S = tokens.shape
    x = L.embed(params["embed"], tokens)
    # scalar position -> (S,) shared positions; (B,) vector -> (B, S)
    positions = jnp.asarray(position)[..., None] + jnp.arange(S)
    fam = cfg.family

    if fam in ("dense", "moe"):
        # The stacked (L, ...) K/V ride in the scan's carry and each layer
        # writes only its new columns into its own row: XLA updates the
        # donated cache in place. As scan xs/ys they would be rebuilt in
        # fresh whole-cache buffers and copied back every step.
        def block(p, x, kv, layer, dense):
            kw = dict(cache=kv, cache_index=position, layer=layer,
                      block_table=block_tables, page_size=page_size,
                      refill_row=refill_row, chunk=chunk)
            if dense:
                return T.dense_block(p, cfg, x, positions, **kw)
            x, _, nc = T.moe_block(p, cfg, x, positions, use_ep=False, **kw)
            return x, nc

        def body(carry, inp):
            x, ck, cv = carry
            p, layer = inp
            x, nc = block(p, x, {"k": ck, "v": cv}, layer, fam == "dense")
            return (x, nc["k"], nc["v"]), None

        kv = caches["kv"]
        first = 0
        if fam == "moe" and cfg.first_layer_dense:
            x, kv = block(params["layer0"], x, kv, 0, True)
            first = 1
        layers = np.arange(first, kv["k"].shape[0], dtype=np.int32)
        (x, nk, nv), _ = scan_layers(
            body, (x, kv["k"], kv["v"]), (params["layers"], layers), cfg
        )
        new_caches = {"kv": {"k": nk, "v": nv}}

    elif fam == "ssm":
        def body(x, inp):
            p, st, cv = inp
            x, nst, ncv = T.ssm_block(p, cfg, x, state=st, conv_state=cv)
            return x, (nst, ncv)
        x, (nst, ncv) = scan_layers(
            body, x, (params["layers"], caches["ssm"], caches["conv"]), cfg
        )
        new_caches = {"ssm": nst, "conv": ncv}

    elif fam == "hybrid":
        shared = params["shared"]

        def group(x, inp):
            pg, st_g, cv_g, ck, cv = inp

            def inner(x, inp2):
                p, st, cvs = inp2
                x, nst, ncv = T.ssm_block(p, cfg, x, state=st, conv_state=cvs)
                return x, (nst, ncv)
            x, (nst, ncv) = scan_layers(inner, x, (pg, st_g, cv_g), cfg)
            x, nc = T.dense_block(shared, cfg, x, positions,
                                  cache={"k": ck, "v": cv},
                                  cache_index=position, chunk=chunk)
            return x, (nst, ncv, nc["k"], nc["v"])

        x, (nst, ncv, nk, nv) = scan_layers(
            group, x,
            (params["layers"], caches["ssm"], caches["conv"],
             caches["kv"]["k"], caches["kv"]["v"]), cfg,
        )
        new_caches = {"ssm": nst, "conv": ncv, "kv": {"k": nk, "v": nv}}
        if params.get("tail") is not None:
            def tail_body(x, inp):
                p, st, cvs = inp
                x, nst, ncv = T.ssm_block(p, cfg, x, state=st, conv_state=cvs)
                return x, (nst, ncv)
            x, (tst, tcv) = scan_layers(
                tail_body, x,
                (params["tail"], caches["ssm_tail"], caches["conv_tail"]),
                cfg,
            )
            new_caches["ssm_tail"] = tst
            new_caches["conv_tail"] = tcv

    elif fam == "encdec":
        def body(x, inp):
            p, ck, cv, xk, xv = inp
            x, nc = T.encdec_dec_block(
                p, cfg, x, positions, enc_kv={"k": xk, "v": xv},
                cache={"k": ck, "v": cv}, cache_index=position, chunk=chunk,
            )
            return x, (nc["k"], nc["v"])
        kvs, xkv = caches["kv"], caches["xkv"]
        x, (nk, nv) = scan_layers(
            body, x, (params["layers"], kvs["k"], kvs["v"],
                      xkv["k"], xkv["v"]), cfg
        )
        new_caches = {"kv": {"k": nk, "v": nv}, "xkv": xkv}

    elif fam == "vlm":
        def group(x, inp):
            pg, pc, ck, cv, xk, xv = inp

            def inner(x, inp2):
                p, ck1, cv1 = inp2
                x, nc = T.dense_block(p, cfg, x, positions,
                                      cache={"k": ck1, "v": cv1},
                                      cache_index=position, chunk=chunk)
                return x, (nc["k"], nc["v"])
            x, (nk, nv) = scan_layers(inner, x, (pg, ck, cv), cfg)
            x = T.cross_block_cached(pc, cfg, x, {"k": xk, "v": xv},
                                     positions, chunk=chunk)
            return x, (nk, nv)
        kvs, xkv = caches["kv"], caches["xkv"]
        x, (nk, nv) = scan_layers(
            group, x,
            (params["layers"], params["cross"], kvs["k"], kvs["v"],
             xkv["k"], xkv["v"]), cfg,
        )
        new_caches = {"kv": {"k": nk, "v": nv}, "xkv": xkv}
    else:
        raise ValueError(fam)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.lm_head(params["head"], x)
    return logits, new_caches


def zero_caches(cfg, *, batch, cache_len):
    """Concrete zero-filled caches matching ``cache_specs`` exactly."""
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        cache_specs(cfg, batch=batch, cache_len=cache_len),
    )


def _project_cross_kv(wk, wv, kv_heads, head_dim, src):
    B, Sk, _ = src.shape
    k = (src @ wk).reshape(B, Sk, kv_heads, head_dim)
    v = (src @ wv).reshape(B, Sk, kv_heads, head_dim)
    return k, v


def prefill(params, cfg, tokens, *, cache_len, frames=None, patches=None,
            chunk=1024):
    """Run the prompt, build decode caches.

    Returns (logits (B, S, V), caches, next_position). For encdec/vlm the
    static cross K/V caches are projected once here and reused every decode
    step (they never change).
    """
    B, S = tokens.shape
    caches = zero_caches(cfg, batch=B, cache_len=cache_len)
    fam = cfg.family
    if fam == "encdec":
        enc = frames + _sinusoidal(frames.shape[1], cfg.d_model).astype(
            frames.dtype
        )
        enc_pos = jnp.arange(frames.shape[1])

        def enc_body(h, p):
            h, _ = T.dense_block(p, cfg, h, enc_pos, causal=False,
                                 chunk=chunk)
            return h, None
        enc, _ = scan_layers(enc_body, enc, params["enc_layers"], cfg)

        def xkv_body(_, p):
            k, v = _project_cross_kv(
                p["xattn"]["wk"], p["xattn"]["wv"], cfg.n_kv_heads,
                cfg.head_dim, enc,
            )
            return None, (k, v)
        _, (xk, xv) = scan_layers(xkv_body, None, params["layers"], cfg)
        caches["xkv"] = {"k": xk.astype(cfg.dtype), "v": xv.astype(cfg.dtype)}
    elif fam == "vlm":
        def xkv_body(_, p):
            k, v = _project_cross_kv(
                p["xattn"]["wk"], p["xattn"]["wv"], cfg.n_kv_heads,
                cfg.head_dim, patches,
            )
            return None, (k, v)
        _, (xk, xv) = scan_layers(xkv_body, None, params["cross"], cfg)
        caches["xkv"] = {"k": xk.astype(cfg.dtype), "v": xv.astype(cfg.dtype)}

    logits, caches = _decode(params, cfg, tokens, caches, jnp.int32(0),
                             chunk=chunk)
    return logits, caches, jnp.int32(S)


def cache_batch_axes(cfg):
    """Pytree (same structure as ``cache_specs``) of each cache leaf's
    BATCH axis index.

    The slot scheduler treats one batch row as one serving slot; refilling
    a slot means rewriting exactly that row of every cache leaf. The batch
    axis is NOT uniform across families (hybrid/vlm stack macro-group axes
    in front), so the map is written down explicitly next to
    ``cache_specs`` — the two must agree leaf for leaf."""
    fam = cfg.family
    kv1 = {"k": 1, "v": 1}
    if fam in ("dense", "moe"):
        return {"kv": kv1}
    if fam == "ssm":
        return {"ssm": 1, "conv": 1}
    if fam == "hybrid":
        _, _, tail = _hybrid_shape(cfg)
        out = {"ssm": 2, "conv": 2, "kv": kv1}
        if tail:
            out["ssm_tail"] = 1
            out["conv_tail"] = 1
        return out
    if fam == "encdec":
        return {"kv": kv1, "xkv": kv1}
    if fam == "vlm":
        return {"kv": {"k": 2, "v": 2}, "xkv": kv1}
    raise ValueError(fam)


def slot_prefill(params, cfg, tokens, caches, slot, *, cache_len,
                 frames=None, patches=None, chunk=1024):
    """Prefill ONE request into slot ``slot`` of a shared decode cache.

    tokens: (1, S) int32 prompt (right-pad to a fixed S so the engine jits
    this once); ``slot``: int32 batch row (traced). The result is a batch-1
    prefill into a fresh zero cache written into row ``slot`` of every
    leaf of ``caches`` — live neighbouring slots are untouched bit for
    bit, and the whole slot row is overwritten (the refilled slot needs no
    separate reset). Dense/moe layers write their rows straight into the
    shared cache (``_decode``'s ``refill_row``); the other families run
    the batch-1 prefill and copy it in with a size-1 dynamic-slice update
    along each leaf's batch axis.

    Returns (logits (1, S, V), new shared caches).
    """
    slot = jnp.asarray(slot, jnp.int32)
    if cfg.family in ("dense", "moe"):
        return _decode(params, cfg, tokens, caches, jnp.int32(0),
                       chunk=chunk, refill_row=slot)
    logits, fresh, _ = prefill(
        params, cfg, tokens, cache_len=cache_len, frames=frames,
        patches=patches, chunk=chunk,
    )
    new = jax.tree.map(
        lambda big, small, ax: jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), slot, axis=ax
        ),
        caches, fresh, cache_batch_axes(cfg),
    )
    return logits, new


def paged_prefill(params, cfg, tokens, caches, page_ids, *, cache_len,
                  page_size, chunk=1024):
    """Prefill ONE request and scatter its prompt K/V pages into the shared
    page pool.

    tokens: (1, S) right-padded prompt; ``page_ids``: (ceil(S / page_size),)
    int32 destination pages. Runs the same batch-1 prefill as
    ``slot_prefill`` — at the same internal ``cache_len``, so logits and
    K/V bytes are bit-identical to the contiguous engine's — then cuts the
    first ``len(page_ids)`` pages worth of K/V out of the fresh contiguous
    row and scatters each to its pool page.

    A page id of ``num_pages`` (one past the pool) is the DON'T-WRITE
    sentinel: the scatter drops it. The engine uses it for (a) pages past
    the true prompt length (pure pad — nothing worth storing) and (b)
    prefix pages SHARED via copy-on-write, whose bytes are already in the
    pool; K/V at position p depends only on tokens [0, p] (causal mask +
    absolute RoPE), so an exact token-prefix match at the same positions
    guarantees the resident bytes equal what this prefill just computed —
    rewriting them would be a no-op on content but would clobber a
    co-owner's page if the engine ever mis-shared; dropping is strictly
    safer.

    Returns (logits (1, S, V), new caches)."""
    n_pp = page_ids.shape[0]
    logits, fresh, _ = prefill(params, cfg, tokens, cache_len=cache_len,
                               chunk=chunk)
    new = {}
    for name in ("k", "v"):
        leaf = fresh["kv"][name]              # (L, 1, cache_len, KV, hd)
        L = leaf.shape[0]
        pages = leaf[:, 0, : n_pp * page_size].reshape(
            L, n_pp, page_size, *leaf.shape[3:]
        )
        pool = caches["kv"][name]             # (L, P, page_size, KV, hd)
        new[name] = pool.at[:, page_ids].set(
            pages.astype(pool.dtype), mode="drop"
        )
    return logits, {"kv": new}
