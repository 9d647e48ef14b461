"""Fused nucleus (top-p) keep-mask kernel — the serve sampler's hot path.

The unfused AK composition the serve loop shipped with costs, per decode
step: a batched descending sortperm (the bitonic network), a vmapped
per-row inclusive prefix sum (``accumulate``), a vmapped ``searchsorted``
for the cut index, and an XLA scatter for the keep mask — ~5 registry
dispatches and 2 extra kernel launches after the network. This module fuses
everything after the sort into ONE Pallas launch: softmax over the
descending row, inclusive prefix sum, and the top-p cut, all on the
(rows, vocab) block resident in VMEM. The kernel emits each row's cut as
a (key, index) pair; the keep mask is then one elementwise comparison in
the ORIGINAL column order — no scatter back through the permutation.

Both implementations (the portable oracle and the Pallas path) funnel the
sorted rows through the SAME ``_cut_from_sorted`` expression and the same
``_keep_from_cut`` comparison, so their masks agree bit-for-bit wherever
the two sorts agree — and the sorts agree everywhere because ``-0.0`` is
canonicalised to ``+0.0`` up front (the one place IEEE ``<`` and XLA's
total order rank keys differently; NaN logits are unsupported, as in every
sampler).

Semantics (matching the historical unfused composition exactly): tokens are
ranked by (logit desc, index asc); the mask keeps ranks ``0..cut`` where
``cut`` is the first rank whose inclusive cumulative softmax mass reaches
``top_p``. ``top_p`` small enough keeps exactly the argmax token; ties at
the cut resolve by ascending index (stable). Rank ``<= cut`` is exactly
``(logit, -index) >= (cut key, -cut index)``, which is what
``_keep_from_cut`` evaluates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C
from repro.kernels import sort_kernel as SK

# Scoped-VMEM budget of the fused launch, in (rows, vocab) f32 blocks: two
# operands, double-buffered, and as much again for the prefix-sum
# temporaries — 24 MiB at (8, 94208), inside a v5e core's 128 MiB of VMEM.
_VMEM_BLOCKS = 8
_VMEM_FLOOR = 16 * 2 ** 20
_VMEM_CAP = 100 * 2 ** 20


def _canon(lg):
    """f32 view with -0.0 folded into +0.0 (x + 0.0 is exact elsewhere), so
    the bitonic network's ``<`` and XLA's total-order sort rank identically.
    """
    return lg.astype(jnp.float32) + 0.0


def _prefix_sum(x, roll):
    """Inclusive prefix sum along the last axis: a Hillis–Steele log-step
    ladder of lane rotations. ``roll`` is ``pltpu.roll`` inside the kernel
    and ``jnp.roll`` in the oracle — the same rotation, so the two add the
    same terms in the same order."""
    n = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    shift = 1
    while shift < n:
        x = x + jnp.where(lane >= shift, roll(x, shift, x.ndim - 1), 0.0)
        shift *= 2
    return x


def _cut_from_sorted(s, perm, *, top_p, n_valid, roll):
    """Each row's cut as (key, index), both (R, 1).

    s: (R, Vp) f32, rows sorted descending, padding = -inf;
    perm: (R, Vp) i32 original column of each sorted slot. Shared verbatim
    by the jnp oracle and the Pallas kernel body — the equality guarantee
    lives here.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = lane < n_valid
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(valid, jnp.exp(s - m), 0.0)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    cum = _prefix_sum(probs, roll)
    # first rank whose inclusive mass reaches top_p == count of strictly
    # smaller prefixes (searchsortedfirst over a non-decreasing row); a row
    # whose mass never reaches top_p keeps every valid rank
    below = (valid & (cum < top_p)).astype(jnp.int32)
    cut = jnp.minimum(jnp.sum(below, axis=-1, keepdims=True), n_valid - 1)
    at = lane == cut
    key = jnp.max(jnp.where(at, s, -jnp.inf), axis=-1, keepdims=True)
    idx = jnp.max(jnp.where(at, perm, -1), axis=-1, keepdims=True)
    return key, idx


def _keep_from_cut(lg, key, idx):
    """Keep mask in original column order: rank <= cut, i.e.
    (logit, -column) >= (key, -idx). lg: (R, n) canonical f32."""
    col = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    return (lg > key) | ((lg == key) & (col <= idx))


def _pad_sorted(s, perm, n):
    """Pad (B, n) sorted rows out to a lane multiple: keys -inf (zero mass,
    sorts last), perm n (past every real column)."""
    vp = C.round_up(max(n, C.LANES), C.LANES)
    if vp == n:
        return s, perm, vp
    pad = vp - n
    s = jnp.pad(s, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    perm = jnp.pad(perm, ((0, 0), (0, pad)), constant_values=n)
    return s, perm, vp


def _flatten(lg):
    n = lg.shape[-1]
    lead = lg.shape[:-1]
    return lg.reshape(-1, n), lead, n


def nucleus_mask_ref(lg, *, top_p):
    """Portable oracle: XLA stable argsort + the shared cut expression."""
    flat, lead, n = _flatten(_canon(lg))
    order = jnp.argsort(-flat, axis=-1, stable=True).astype(jnp.int32)
    s = jnp.take_along_axis(flat, order, axis=-1)
    s, order, _ = _pad_sorted(s, order, n)
    key, idx = _cut_from_sorted(s, order, top_p=top_p, n_valid=n,
                                roll=jnp.roll)
    return _keep_from_cut(flat, key, idx).reshape(*lead, n)


def _nucleus_body(top_p, n_valid, s_ref, p_ref, key_ref, idx_ref):
    key, idx = _cut_from_sorted(s_ref[...], p_ref[...], top_p=top_p,
                                n_valid=n_valid, roll=pltpu.roll)
    key_ref[...] = jnp.broadcast_to(key, key_ref.shape)
    idx_ref[...] = jnp.broadcast_to(idx, idx_ref.shape)


def nucleus_mask_blocks(lg, *, top_p):
    """Pallas path: batched bitonic sortperm (descending, stable) + ONE
    fused softmax/prefix-sum/cut launch over the whole batch, then the
    keep comparison in original order."""
    flat, lead, n = _flatten(_canon(lg))

    def one(row):
        # sort ascending on the negated row with an index tie-break:
        # (-lg asc, idx asc) == (lg desc, idx asc) == stable argsort(-lg)
        idx = jnp.arange(n, dtype=jnp.int32)
        sk, perm = SK.bitonic_sort_kv(-row, idx, tie_break=True)
        return -sk, perm

    s, perm = jax.vmap(one)(flat)
    s, perm, vp = _pad_sorted(s, perm, n)

    br = C.block_rows()
    b = s.shape[0]
    bp = C.round_up(max(b, br), br)
    if bp != b:
        s = jnp.pad(s, ((0, bp - b), (0, 0)), constant_values=-jnp.inf)
        perm = jnp.pad(perm, ((0, bp - b), (0, 0)), constant_values=n)

    spec = pl.BlockSpec((br, vp), lambda i: (i, 0))
    cut_spec = pl.BlockSpec((br, C.LANES), lambda i: (i, 0))
    vmem = min(max(_VMEM_BLOCKS * br * vp * 4, _VMEM_FLOOR), _VMEM_CAP)
    key, idx = C.pallas_call(
        functools.partial(_nucleus_body, top_p, n),
        name="nucleus_cut",
        grid=(bp // br,),
        in_specs=[spec, spec],
        out_specs=[cut_spec, cut_spec],
        out_shape=[jax.ShapeDtypeStruct((bp, C.LANES), jnp.float32),
                   jax.ShapeDtypeStruct((bp, C.LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem),
        interpret=C.interpret_mode(),
    )(s, perm)
    keep = _keep_from_cut(flat, key[:b, :1], idx[:b, :1])
    return keep.reshape(*lead, n)
