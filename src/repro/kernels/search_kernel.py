"""``searchsortedfirst`` / ``searchsortedlast`` — gather-free binary search.

AK.jl runs one binary search per GPU thread.  Binary search is exactly the
kind of data-dependent addressing the TPU vector unit cannot express (no
per-lane gather from VMEM) — so we use the order-statistics identity

    searchsortedfirst(hay, q) = #{ h in hay : h <  q }   (0-based insertion)
    searchsortedlast (hay, q) = #{ h in hay : h <= q }

and compute the counts with a tiled comparison-matrix kernel: the grid walks
(query-tile × haystack-chunk) cells. A query tile is a (128, 1) column —
one query per sublane row — broadcast across the lanes; each cell compares
it against every (1, 128) lane slice of a (8, 1024) haystack block and adds
the hits into a (128, 128) vector accumulator. The sequential grid carries
the accumulator across haystack chunks, and the last chunk folds its lanes
into the tile's counts.
Identical results, zero gathers, MXU-free VPU work.  O(N·Q/8192) vreg ops
instead of O(Q log N) scalar probes — the standard throughput-for-latency
trade this hardware wants (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C

_Q_TILE = 128  # queries per grid row, one sublane each


def _search_body(strict, n_hay, q_ref, h_ref, o_ref, acc_ref):
    hj = pl.program_id(1)
    q = jnp.broadcast_to(q_ref[...], acc_ref.shape)  # (Q_TILE, LANES)
    h = h_ref[...]  # (BLOCK_ROWS, BLOCK_COLS)

    @pl.when(hj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Mask haystack padding (pad = +max sorts after everything, but equal
    # keys at type-max would miscount searchsortedlast; mask by index).
    R, L = h.shape
    base = hj * R * L
    flat = _flat_index(h.shape) + base
    acc = acc_ref[...]
    for r in range(R):
        for c in range(0, L, C.LANES):
            hs = h[r:r + 1, c:c + C.LANES]       # (1, LANES)
            ok = flat[r:r + 1, c:c + C.LANES] < n_hay
            hit = (hs < q) if strict else (hs <= q)
            acc = acc + (hit & ok).astype(jnp.int32)
    acc_ref[...] = acc

    @pl.when(hj == pl.num_programs(1) - 1)
    def _fin():
        o_ref[...] = jnp.sum(acc, axis=1, keepdims=True)


def _flat_index(shape):
    acc = jax.lax.broadcasted_iota(jnp.int32, shape, 0) * shape[1]
    return acc + jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def searchsorted_blocks(
    hay: jax.Array, queries: jax.Array, *, side: str = "left"
) -> jax.Array:
    """0-based insertion indices of ``queries`` into sorted ``hay``.

    side='left'  -> searchsortedfirst (first position keeping order)
    side='right' -> searchsortedlast  (last position keeping order)
    """
    strict = side == "left"
    n_hay = hay.shape[0]
    nq = queries.shape[0]
    if n_hay == 0:
        return jnp.zeros((nq,), jnp.int32)

    hview, _ = C.as_blocks(hay, fill=C.type_max(hay.dtype))
    q_pad = C.pad_to(queries, C.round_up(max(nq, 1), _Q_TILE),
                     C.type_min(queries.dtype))
    qview = q_pad.reshape(-1, 1)

    br, bc = C.block_rows(), C.block_cols()
    grid = (qview.shape[0] // _Q_TILE, hview.shape[0] // br)
    tile = pl.BlockSpec((_Q_TILE, 1), lambda qi, hj: (qi, 0))
    out = C.pallas_call(
        functools.partial(_search_body, strict, n_hay),
        name="search",
        grid=grid,
        in_specs=[tile, pl.BlockSpec((br, bc), lambda qi, hj: (hj, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(qview.shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM((_Q_TILE, C.LANES), jnp.int32)],
        interpret=C.interpret_mode(),
    )(qview, hview)
    return out.reshape(-1)[:nq]
