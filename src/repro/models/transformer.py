"""Model assembly for all six assigned families.

Layers are STACKED (leading L axis) and driven by ``jax.lax.scan`` so a
95-layer model lowers as one rolled loop — compile time and HLO size stay
flat in depth, which the 40-cell dry-run depends on. Periodic structures
(zamba2's shared attention block, llama-vision's cross-attn interleave)
scan over macro-groups.

Families:
  dense   — [attn, swiglu] × L
  moe     — [attn, moe_ffn] × L (optionally layer 0 dense: deepseek-moe)
  ssm     — [mamba2] × L
  hybrid  — groups of (ssm × k) + ONE shared attn+mlp block (zamba2)
  encdec  — encoder [attn, mlp] × Le on stub frames; decoder adds cross-attn
  vlm     — groups of (dense × k-1) + gated cross-attn layer (llama-vision)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import sharding as SH
from repro.models import ssm as SSM


# ---------------------------------------------------------------------------
# per-layer inits (unstacked); stacked via vmap over layer rngs
# ---------------------------------------------------------------------------


def _stack_init(fn, rng, n):
    return jax.vmap(fn)(jax.random.split(rng, n))


def dense_layer_init(rng, cfg):
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model),
        "attn": L.attention_init(k1, cfg),
        "ln2": L.rmsnorm_init(cfg.d_model),
        "mlp": L.swiglu_init(k2, cfg.d_model, cfg.d_ff, cfg.dtype),
    }


def moe_layer_init(rng, cfg):
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model),
        "attn": L.attention_init(k1, cfg),
        "ln2": L.rmsnorm_init(cfg.d_model),
        "moe": MOE.moe_init(k2, cfg),
    }


def ssm_layer_init(rng, cfg):
    return {"ln": L.rmsnorm_init(cfg.d_model), "ssm": SSM.ssm_init(rng, cfg)}


def cross_layer_init(rng, cfg):
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model),
        "xattn": L.attention_init(k1, cfg),
        "gate_attn": jnp.zeros((), jnp.float32),
        "ln2": L.rmsnorm_init(cfg.d_model),
        "mlp": L.swiglu_init(k2, cfg.d_model, cfg.d_ff, cfg.dtype),
        "gate_mlp": jnp.zeros((), jnp.float32),
    }


# ---------------------------------------------------------------------------
# per-layer applies (single layer; scan drives the stack)
# ---------------------------------------------------------------------------


def dense_block(p, cfg, x, positions, *, cache=None, cache_index=None,
                block_table=None, page_size=None, layer=None,
                refill_row=None, causal=True, chunk=1024):
    h, new_cache = L.attention_apply(
        p["attn"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps),
        positions=positions, causal=causal, cache=cache,
        cache_index=cache_index, block_table=block_table,
        page_size=page_size, layer=layer, refill_row=refill_row,
        chunk=chunk, unroll=cfg.unroll_layers,
    )
    x = x + h
    x = x + L.swiglu(p["mlp"], L.rmsnorm(p["ln2"], x, cfg.norm_eps))
    return x, new_cache


def moe_block(p, cfg, x, positions, *, mesh=None, dp_axes=("data",),
              cache=None, cache_index=None, block_table=None,
              page_size=None, layer=None, refill_row=None, chunk=1024,
              use_ep=True):
    h, new_cache = L.attention_apply(
        p["attn"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps),
        positions=positions, causal=True, cache=cache,
        cache_index=cache_index, block_table=block_table,
        page_size=page_size, layer=layer, refill_row=refill_row,
        chunk=chunk, unroll=cfg.unroll_layers,
    )
    x = x + h
    z = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if use_ep and mesh is not None:
        y, aux = MOE.moe_ffn_ep(p["moe"], cfg, z, mesh=mesh, dp_axes=dp_axes)
    else:
        y, aux = MOE.moe_ffn(p["moe"], cfg, z)
    return x + y, aux, new_cache


def ssm_block(p, cfg, x, *, state=None, conv_state=None):
    h, new_state, new_conv = SSM.ssm_apply(
        p["ssm"], cfg, L.rmsnorm(p["ln"], x, cfg.norm_eps),
        state=state, conv_state=conv_state,
    )
    return x + h, new_state, new_conv


def _gated_add(x, gate, h):
    return x + (jnp.tanh(gate) * h.astype(jnp.float32)).astype(x.dtype)


def cross_block(p, cfg, x, vis, positions, *, chunk=1024):
    """Gated cross-attention layer (llama-3.2-vision style)."""
    h, _ = L.attention_apply(
        p["xattn"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps),
        positions=positions, causal=False, kv_src=vis,
        use_rope=False, chunk=chunk, unroll=cfg.unroll_layers,
    )
    x = _gated_add(x, p["gate_attn"], h)
    h = L.swiglu(p["mlp"], L.rmsnorm(p["ln2"], x, cfg.norm_eps))
    return _gated_add(x, p["gate_mlp"], h)


def _cross_attend(p_attn, cfg, z, enc_kv, chunk):
    """Query ``z`` against precomputed (cached) cross K/V."""
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, Sq, _ = z.shape
    q = (z @ SH.col_parallel(p_attn["wq"])).reshape(B, Sq, H, hd)
    h = L.blockwise_attention(
        q, enc_kv["k"].astype(q.dtype), enc_kv["v"].astype(q.dtype),
        causal=False, chunk=chunk, unroll=cfg.unroll_layers,
    )
    return h.reshape(B, Sq, H * hd) @ SH.row_parallel(p_attn["wo"])


def encdec_dec_block(p, cfg, x, positions, *, enc_out=None, enc_kv=None,
                     cache=None, cache_index=None, chunk=1024):
    """Decoder block: causal self-attn + cross-attn.

    Pass ``enc_out`` (train: project K/V here) or ``enc_kv`` (serve: K/V
    cached at prefill — they never change during decode)."""
    h, new_cache = L.attention_apply(
        p["attn"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps),
        positions=positions, causal=True, cache=cache,
        cache_index=cache_index, chunk=chunk, unroll=cfg.unroll_layers,
    )
    x = x + h
    z = L.rmsnorm(p["lnx"], x, cfg.norm_eps)
    if enc_kv is None:
        B, Se, _ = enc_out.shape
        KV, hd = cfg.n_kv_heads, cfg.head_dim
        enc_kv = {
            "k": (enc_out @ SH.col_parallel(p["xattn"]["wk"])).reshape(
                B, Se, KV, hd),
            "v": (enc_out @ SH.col_parallel(p["xattn"]["wv"])).reshape(
                B, Se, KV, hd),
        }
    x = x + _cross_attend(p["xattn"], cfg, z, enc_kv, chunk)
    x = x + L.swiglu(p["mlp"], L.rmsnorm(p["ln2"], x, cfg.norm_eps))
    return x, new_cache


def cross_block_cached(p, cfg, x, enc_kv, positions, *, chunk=1024):
    """VLM gated cross-attn layer against prefill-cached vision K/V."""
    del positions
    z = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    h = _cross_attend(p["xattn"], cfg, z, enc_kv, chunk)
    x = _gated_add(x, p["gate_attn"], h)
    h = L.swiglu(p["mlp"], L.rmsnorm(p["ln2"], x, cfg.norm_eps))
    return _gated_add(x, p["gate_mlp"], h)


def encdec_dec_layer_init(rng, cfg):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model),
        "attn": L.attention_init(k1, cfg),
        "lnx": L.rmsnorm_init(cfg.d_model),
        "xattn": L.attention_init(k2, cfg),
        "ln2": L.rmsnorm_init(cfg.d_model),
        "mlp": L.swiglu_init(k3, cfg.d_model, cfg.d_ff, cfg.dtype),
    }
