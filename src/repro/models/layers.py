"""Shared transformer layers: RMSNorm, RoPE, GQA attention, SwiGLU MLP.

Pure-JAX (pjit-compatible) implementations. Attention is blockwise
(online-softmax over KV chunks, query-block outer loop) so prefill at 32k
context lowers with O(S·chunk) live memory instead of O(S²) — the XLA-native
equivalent of a flash kernel; see DESIGN.md §5.

Parameter trees are plain nested dicts; initialisers take an ``rng`` and
return the tree. Sharding is applied by `repro.models.sharding` at the pjit
boundary, so nothing here mentions the mesh.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import registry as _registry
from repro.models import sharding as SH

# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------


def rmsnorm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p, x, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return out.astype(x.dtype)


def dense_init(rng, d_in, d_out, dtype):
    scale = 1.0 / math.sqrt(d_in)
    w = jax.random.uniform(rng, (d_in, d_out), jnp.float32, -scale, scale)
    return w.astype(dtype)


def rope_freqs(head_dim, theta):
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    return inv  # (half,)


def apply_rope(x, positions, theta):
    """x: (..., S, H, hd); positions: (S,) or (B, S) absolute positions."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, half)
    # broadcast over head axis: (..., S, 1, half)
    ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attention_init(rng, cfg, *, kv_from_d=None):
    """QKVO projections. ``kv_from_d``: source dim of K/V (cross-attn)."""
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kd = kv_from_d or d
    ks = jax.random.split(rng, 4)
    return {
        "wq": dense_init(ks[0], d, H * hd, cfg.dtype),
        "wk": dense_init(ks[1], kd, KV * hd, cfg.dtype),
        "wv": dense_init(ks[2], kd, KV * hd, cfg.dtype),
        "wo": dense_init(ks[3], H * hd, d, cfg.dtype),
    }


def blockwise_attention(q, k, v, *, causal, q_offset=0, chunk=1024,
                        unroll=False):
    """Online-softmax grouped-query attention.

    q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) with H % KV == 0 — the KV
    planes are NEVER head-repeated: queries reshape to (B, Sq, KV, G, hd)
    and contract against the raw cache layout. (Materialising the repeat
    costs G x cache memory and, under SPMD, forces an involuntary cache
    reshard — measured in EXPERIMENTS.md §Perf iteration 1.)

    Scans KV in chunks with running (max, sum, acc) — flash-style memory.
    ``q_offset``: absolute position of q[0] relative to k[0] for causality —
    a scalar, or a (B,) per-row vector (the serving engine's per-slot
    attention-length mask: each slot attends its own ``[0, pos_b]`` prefix
    of the shared cache, so refilled neighbours and not-yet-written tail
    slots stay invisible).
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = (q * scale).astype(jnp.float32).reshape(B, Sq, KV, G, hd)
    q_off = jnp.asarray(q_offset)
    # q_pos: (Sq,) shared offset, or (B, Sq) per-row offsets
    q_pos = q_off[..., None] + jnp.arange(Sq)

    def _apply_mask(s, mask):
        # s: (B, KV, G, Sq, chunk); mask: (Sq, chunk) or (B, Sq, chunk)
        m = mask[:, None, None] if mask.ndim == 3 else mask[None, None, None]
        return jnp.where(m, s, -jnp.inf), m

    if Sq == 1:
        # decode fast path: one query row — materialising (B,KV,G,1,Sk)
        # scores is cheap and avoids the KV-chunk scan entirely (and its
        # O(chunks) sequential HLO at 500k context).
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32))
        k_pos = jnp.arange(Sk)
        mask = (k_pos <= q_pos[..., None]
                if causal else jnp.ones((Sq, Sk), bool))
        s, _ = _apply_mask(s, mask)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
        return out.reshape(B, Sq, H, hd).astype(q.dtype)
    chunk = min(chunk, Sk)
    n_chunks = -(-Sk // chunk)
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)

    def step(carry, inputs):
        m, l, acc = carry                      # (B,KV,G,Sq) / +(,hd)
        ci, kb, vb = inputs
        k_pos = ci * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kb.astype(jnp.float32))
        mask = k_pos <= q_pos[..., None] if causal else (
            jnp.ones(q_pos.shape + (chunk,), bool)
        )
        valid = k_pos < Sk  # padding chunk guard
        mask = mask & valid
        s, mb = _apply_mask(s, mask)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> nan
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mb, p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
        corr = jnp.where(jnp.isfinite(m), corr, 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, vb.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    if unroll:  # cost-model mode: expose every chunk to cost_analysis
        carry = (m0, l0, a0)
        for ci in range(n_chunks):
            carry, _ = step(carry, (jnp.int32(ci), kc[ci], vc[ci]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(
            step, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc)
        )
    out = acc / jnp.maximum(l, 1e-30)[..., None]   # (B,KV,G,Sq,hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)


def _put(leaf, new, start):
    """``new`` (cast to the leaf's dtype, with leading unit axes added)
    written into ``leaf`` at ``start``."""
    new = new.astype(leaf.dtype)
    return jax.lax.dynamic_update_slice(
        leaf, new.reshape((1,) * (leaf.ndim - new.ndim) + new.shape), start)


def attention_apply(
    p,
    cfg,
    x,
    *,
    positions,
    causal=True,
    kv_src=None,
    kv_positions=None,
    cache=None,
    cache_index=None,
    block_table=None,
    page_size=None,
    layer=None,
    refill_row=None,
    use_rope=True,
    chunk=1024,
    unroll=False,
):
    """Self- or cross-attention with optional KV cache.

    cache: dict(k=(B, S_cache, KV, hd), v=...) — decode appends at
    ``cache_index`` and attends over the full cache. ``cache_index`` is a
    scalar (all rows at the same position — the classic fixed-batch decode)
    or a (B,) vector of per-slot positions (continuous batching: each slot
    writes its own cache column and attends its own valid prefix;
    out-of-range positions drop the write — a parked/finished slot).
    Returns (out, new_cache).

    PAGED cache: with ``block_table`` (B, T) int32 + ``page_size``, the
    cache leaves are a shared page POOL ``(P, page_size, KV, hd)`` instead
    of per-row sequences. Row b's logical column c lives at physical
    ``(block_table[b, c // page_size], c % page_size)``: the incoming K/V
    scatters there (logical columns past ``T * page_size`` — parked lanes —
    and table slots the allocator never backed both resolve out of range
    and DROP), and attention reads the logical view back through the
    ``page_gather`` registry primitive (jnp take / Pallas scalar-prefetch
    gather). Stale bytes in unwritten page tails are hidden by the same
    per-row attention-length mask as the contiguous path, so the math is
    position-for-position identical to the contiguous cache.

    STACKED cache: with ``layer`` (an int32 index), the leaves are the
    whole model's ``(L, ...)`` stacks of either form. This step's K/V
    columns are written into row ``layer`` alone and the layer is read
    back for attention, so a layer scan can carry the stacks and XLA
    updates them in place; ``new_cache`` is the updated stacks.

    REFILL: with ``refill_row`` (batch-1 tokens, scalar ``cache_index``),
    the whole of that batch row is rewritten as a fresh cache row would
    read — zeros, with this chunk's K/V at ``cache_index`` — and attention
    reads that row. A slot's prefill thus lands in the shared cache with
    no batch-1 cache beside it.
    """
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, Sq, _ = x.shape
    src = x if kv_src is None else kv_src
    q = (x @ SH.col_parallel(p["wq"])).reshape(B, Sq, H, hd)
    k = (src @ SH.col_parallel(p["wk"])).reshape(B, src.shape[1], KV, hd)
    v = (src @ SH.col_parallel(p["wv"])).reshape(B, src.shape[1], KV, hd)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        kpos = positions if kv_positions is None else kv_positions
        k = apply_rope(k, kpos, cfg.rope_theta)

    new_cache = None
    new = {"k": k, "v": v}
    at = () if layer is None else (layer,)

    def _read(leaf):
        if layer is None:
            return leaf
        return jax.lax.dynamic_index_in_dim(leaf, layer, keepdims=False)

    if cache is not None and block_table is not None:
        ps = int(page_size)
        P, T = cache["k"].shape[-4], block_table.shape[1]
        ci = jnp.asarray(cache_index)
        ci_v = ci if ci.ndim == 1 else jnp.broadcast_to(ci, (B,))
        cols = ci_v[:, None] + jnp.arange(Sq)[None, :]        # (B, Sq) logical
        slot = jnp.clip(cols // ps, 0, T - 1)
        phys = jnp.take_along_axis(block_table, slot, axis=1)  # (B, Sq)
        # parked lanes (cols >= T*ps) and unbacked table slots (id >= P,
        # the allocator's sentinel) both land out of range -> drop
        phys = jnp.where(cols < T * ps, phys, P)
        offs = cols % ps
        new_cache = {
            n: c.at[at + (phys, offs)].set(new[n].astype(c.dtype),
                                           mode="drop")
            for n, c in cache.items()
        }
        # (B, T*ps, KV, hd) logical view of this layer's pages
        k = _registry.call("page_gather", _read(new_cache["k"]), block_table)
        v = _registry.call("page_gather", _read(new_cache["v"]), block_table)
        q_offset = cache_index
        causal = True
    elif cache is not None:
        ci = jnp.asarray(cache_index)
        if ci.ndim == 1:
            # per-slot scatter: row b writes cache columns ci[b]..ci[b]+Sq-1
            # (out-of-bounds slots DROP — they are parked lanes, and a
            # clamped write would corrupt the last cache column)
            cols = ci[:, None] + jnp.arange(Sq)[None, :]       # (B, Sq)
            rows = jnp.arange(B)[:, None]
            new_cache = {
                n: c.at[at + (rows, cols)].set(new[n].astype(c.dtype),
                                               mode="drop")
                for n, c in cache.items()
            }
        elif refill_row is None:
            new_cache = {n: _put(c, new[n], at + (0, ci, 0, 0))
                         for n, c in cache.items()}
        else:
            fresh = {n: _put(jnp.zeros((1,) + c.shape[-3:], c.dtype), new[n],
                             (0, ci, 0, 0))
                     for n, c in cache.items()}
            new_cache = {n: _put(c, fresh[n], at + (refill_row, 0, 0, 0))
                         for n, c in cache.items()}
        if refill_row is None:
            k, v = _read(new_cache["k"]), _read(new_cache["v"])
        else:
            k, v = fresh["k"], fresh["v"]
        # mask out not-yet-written cache slots via causal offset (per-row
        # when cache_index is the engine's per-slot position vector)
        q_offset = cache_index
        causal = True
    else:
        q_offset = 0

    out = blockwise_attention(
        q, k.astype(q.dtype), v.astype(q.dtype),
        causal=causal, q_offset=q_offset, chunk=chunk, unroll=unroll,
    )
    out = SH.finish_tp(out.reshape(B, Sq, H * hd) @ SH.row_parallel(p["wo"]))
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def swiglu_init(rng, d, d_ff, dtype):
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": dense_init(ks[0], d, d_ff, dtype),
        "w_up": dense_init(ks[1], d, d_ff, dtype),
        "w_down": dense_init(ks[2], d_ff, d, dtype),
    }


def swiglu(p, x):
    gate = jax.nn.silu(x @ SH.col_parallel(p["w_gate"]))
    return SH.finish_tp(
        (gate * (x @ SH.col_parallel(p["w_up"]))) @ SH.row_parallel(
            p["w_down"])
    )


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------


def embedding_init(rng, vocab_padded, d, dtype):
    w = jax.random.normal(rng, (vocab_padded, d), jnp.float32) * 0.02
    return {"embed": w.astype(dtype)}


def embed(p, tokens):
    return jnp.take(p["embed"], tokens, axis=0)


def lm_head_init(rng, d, vocab_padded, dtype):
    return {"unembed": dense_init(rng, d, vocab_padded, dtype)}


def lm_head(p, x):
    return x @ p["unembed"]
