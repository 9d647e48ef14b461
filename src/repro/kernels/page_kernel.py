"""Paged KV-cache gather — block-table indirection as a Pallas kernel.

The serving engine's paged cache stores K/V in a shared pool of fixed-size
pages ``(P, page_size, ...)``; each request owns a logical sequence described
by a block table row ``(T,)`` of physical page ids. Attention wants the
logical view ``(B, T * page_size, ...)`` — a gather of whole pages.

On TPU the block table is exactly what ``PrefetchScalarGridSpec`` exists
for: the table is a *scalar-prefetch* operand (resident in SMEM before the
grid runs), and the input ``index_map`` reads it to pick which page block
the next grid step DMAs into VMEM. The kernel body is a straight copy —
all the indirection lives in the BlockSpec machinery (the same hyper-block
idiom as kernels/sort_kernel.py: geometry in the grid spec, bodies dumb),
so the DMA pipeline double-buffers page fetches exactly like any dense
kernel.

The jnp oracle is ``pages[block_table]`` — one take along the page axis.
Both implementations live under the ``page_gather`` record in
``repro.core.registry``; the page size itself is a TuningTable knob
(``page_size``) owned by this primitive, which is how the engine and the
autotune sweep agree on legal page geometry.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C


def page_gather_ref(pages, block_table):
    """jnp oracle: pages (P, ps, *tail), block_table (B, T) int32 ->
    logical view (B, T * ps, *tail). Table entries must be in [0, P)."""
    B, T = block_table.shape
    g = jnp.take(pages, block_table, axis=0)        # (B, T, ps, *tail)
    return g.reshape(B, T * pages.shape[1], *pages.shape[2:])


def _gather_body(bt_ref, pages_ref, out_ref):
    # bt_ref is the scalar-prefetch operand; the index_map already consumed
    # it — the body only forwards the page block it was handed.
    del bt_ref
    out_ref[...] = pages_ref[...][None]


def page_gather_blocks(pages, block_table):
    """Pallas page gather: one grid step per (sequence, table slot); the
    input index_map reads the prefetched block table to choose the page."""
    P, ps = pages.shape[0], pages.shape[1]
    tail = pages.shape[2:]
    D = math.prod(tail) if tail else 1
    B, T = block_table.shape
    pages3 = pages.reshape(P, ps, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, ps, D), lambda b, t, bt_ref: (bt_ref[b, t], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, ps, D), lambda b, t, bt_ref: (b, t, 0, 0)
        ),
    )
    out = C.pallas_call(
        _gather_body,
        name="page_gather",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, ps, D), pages.dtype),
        interpret=C.interpret_mode(),
    )(block_table.astype(jnp.int32), pages3)
    return out.reshape(B, T * ps, *tail)
