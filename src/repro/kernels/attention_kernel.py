"""Fused flash-attention Pallas TPU kernel (beyond-paper optimization).

The LM substrate's hot spot. The pure-JAX blockwise attention in
models/layers.py keeps memory flat but materialises each (Sq, chunk) score
tile in HBM between ops; this kernel keeps the whole online-softmax state
— score tile, running max/sum, output accumulator — in VMEM across the KV
sweep, the canonical flash schedule mapped to TPU:

  grid = (B*H heads, Sq/BQ query blocks, Sk/BK kv blocks)
  the KV axis is the innermost (sequential) grid dim; (m, l, acc) live in
  VMEM scratch across those steps — the same sequential-grid-carry idiom as
  kernels/scan_kernel.py (TPU grids execute in order, so no cross-block
  synchronisation is needed where CUDA flash needs none either — the
  schedule transfers cleanly).

Forward-only (serving / prefill); training uses the pure-JAX path where XLA
handles the backward. Validated against ref.flash_attention_ref in
interpret mode (tests/test_attention_kernel.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C

BQ = 128   # query rows per block (sublane-aligned x16)
BK = 512   # kv rows per block


def _flash_body(scale, causal, sk_valid, q_ref, k_ref, v_ref, o_ref,
                m_ref, l_ref, acc_ref):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[0].astype(jnp.float32) * scale      # (BQ, hd)
    k = k_ref[0].astype(jnp.float32)              # (BK, hd)
    v = v_ref[0].astype(jnp.float32)              # (BK, hd)
    s = jax.lax.dot_general(                      # (BQ, BK) on the MXU
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    k_pos = ik * BK + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < sk_valid
    if causal:
        q_pos = iq * BQ + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        mask = mask & (k_pos <= q_pos)
    s = jnp.where(mask, s, -jnp.inf)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.where(
        jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0
    )
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc

    @pl.when(ik == nk - 1)
    def _fin():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def flash_attention(q, k, v, *, causal=True):
    """q: (BH, Sq, hd); k, v: (BH, Sk, hd) — already head-flattened (GQA
    callers broadcast K/V across the query-group dim *logically* by passing
    the same slices; no materialised repeat). Returns (BH, Sq, hd)."""
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)

    sq_p = C.round_up(Sq, BQ)
    sk_p = C.round_up(Sk, BK)
    if sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - Sq), (0, 0)))
    if sk_p != Sk:
        k = jnp.pad(k, ((0, 0), (0, sk_p - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_p - Sk), (0, 0)))

    grid = (BH, sq_p // BQ, sk_p // BK)
    out = C.pallas_call(
        functools.partial(_flash_body, scale, causal, Sk),
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BQ, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, BK, hd), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, BK, hd), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, hd), jnp.float32),
        ],
        interpret=C.interpret_mode(),
    )(q, k, v)
    return out[:, :Sq]


def flash_attention_gqa(q, k, v, *, causal=True):
    """Grouped-query wrapper: q (B, Sq, H, hd), k/v (B, Sk, KV, hd).

    K/V heads are *indexed*, not repeated: head h of q reads kv head
    h // (H // KV)."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3)  # (B, KV, Sk, hd)
    kf = jnp.repeat(kf, G, axis=1).reshape(B * H, Sk, hd) if G > 1 else (
        kf.reshape(B * H, Sk, hd)
    )
    vf = v.transpose(0, 2, 1, 3)
    vf = jnp.repeat(vf, G, axis=1).reshape(B * H, Sk, hd) if G > 1 else (
        vf.reshape(B * H, Sk, hd)
    )
    out = flash_attention(qf, kf, vf, causal=causal)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
