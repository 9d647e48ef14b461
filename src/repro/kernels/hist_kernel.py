"""Fused min/max + fixed-bin histogram — the SIHSort sampling kernel.

MPISort's splitter estimation needs, per rank: the global value range and an
"interpolated histogram" of the local keys.  The paper's headline MPI trick
is *fusing* payloads ("counters hidden at the end of integer arrays") so the
number of communication rounds is minimal.  We keep the insight at both
levels:

  * on-device: ONE pass over the data produces min, max and the histogram
    together (one kernel, one HBM read) — the one-pass moment-fusion idiom;
  * across devices: `core.distributed` ships min/max/counts in a single
    fused `psum` payload (see there).

Binning is gather-free: each (8, 1024) chunk is transposed so its elements
run down the sublanes, one-hot-ranked against the bin ids along the lanes
with a broadcast compare, and summed over the sublanes — scatter-free
histogramming, the TPU replacement for atomics-based GPU binning. Only the
bins in use (``nbins`` rounded up to a lane multiple) are compared.

Min and max accumulate as (8, 128) vector partials (no scalar is stored to
vector memory); the caller folds them to scalars. The range arrives as two
SMEM scalars: ``lo`` and the bin ``width``, computed once outside the
kernel with the oracle's own expression.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C

_MAX_BINS = 1024  # one lane row of bins


def _bin_width(lo, hi, nbins):
    """The oracle's bin width (kernels/ref.py), bit for bit."""
    return jnp.maximum((hi - lo) / nbins, 1e-30)


def _fold_lanes(op, x):
    """(R, L) -> (R, 128): lane-aligned column slices, combined."""
    out = x[:, :C.LANES]
    for j in range(1, x.shape[1] // C.LANES):
        out = op(out, x[:, j * C.LANES:(j + 1) * C.LANES])
    return out


def _hist_body(nbins, n, x_ref, lo_ref, w_ref, h_ref, mn_ref, mx_ref):
    i = pl.program_id(0)
    lo, width = lo_ref[0, 0], w_ref[0, 0]
    x = x_ref[...]  # (BLOCK_ROWS, BLOCK_COLS)
    R, L = x.shape
    base = i * R * L
    flat = (
        jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) * L
        + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        + base
    )
    valid = flat < n

    @pl.when(i == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)
        mn_ref[...] = jnp.full(mn_ref.shape, C.type_max(mn_ref.dtype))
        mx_ref[...] = jnp.full(mx_ref.shape, C.type_min(mx_ref.dtype))

    xf = x.astype(jnp.float32)
    b = jnp.clip(((xf - lo) / width).astype(jnp.int32), 0, nbins - 1)
    b = jnp.where(valid, b, nbins)  # padding lands in a ghost bin
    # one-hot rank, elements down the sublanes against bin ids along the
    # lanes: (L, 1) == (1, NBINS) -> sum over sublanes
    bt = b.T
    ids = jax.lax.broadcasted_iota(jnp.int32, (1, h_ref.shape[1]), 1)
    counts = h_ref[...]
    for r in range(R):
        onehot = (bt[:, r:r + 1] == ids).astype(jnp.int32)
        counts = counts + jnp.sum(onehot, axis=0, keepdims=True)
    h_ref[...] = counts

    big = C.type_max(x.dtype)
    small = C.type_min(x.dtype)
    mn_ref[...] = jnp.minimum(
        mn_ref[...], _fold_lanes(jnp.minimum, jnp.where(valid, x, big)))
    mx_ref[...] = jnp.maximum(
        mx_ref[...], _fold_lanes(jnp.maximum, jnp.where(valid, x, small)))


def minmax_histogram_blocks(
    x: jax.Array, nbins: int, lo, hi
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-pass (histogram[nbins], min, max) of ``x`` over range [lo, hi).

    Values outside the range clip into the edge bins (SIHSort only needs
    rank densities, so clipping is the correct behaviour).
    """
    if nbins > _MAX_BINS:
        raise ValueError(f"nbins {nbins} > {_MAX_BINS}")
    n = x.size
    view, _ = C.as_blocks(x, fill=jnp.zeros((), x.dtype))
    br, bc = C.block_rows(), C.block_cols()
    grid = (view.shape[0] // br,)
    lo = jnp.asarray(lo, jnp.float32)
    width = _bin_width(lo, jnp.asarray(hi, jnp.float32), nbins)
    nbp = C.round_up(nbins, C.LANES)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    acc = pl.BlockSpec((br, C.LANES), lambda i: (0, 0))

    hist, mn, mx = C.pallas_call(
        functools.partial(_hist_body, nbins, n),
        name="histogram",
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i: (i, 0)), smem, smem],
        out_specs=[pl.BlockSpec((1, nbp), lambda i: (0, 0)), acc, acc],
        out_shape=[
            jax.ShapeDtypeStruct((1, nbp), jnp.int32),
            jax.ShapeDtypeStruct((br, C.LANES), x.dtype),
            jax.ShapeDtypeStruct((br, C.LANES), x.dtype),
        ],
        interpret=C.interpret_mode(),
    )(view, lo.reshape(1, 1), width.reshape(1, 1))
    return hist[0, :nbins], jnp.min(mn), jnp.max(mx)
