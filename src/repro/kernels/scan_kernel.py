"""``accumulate`` — prefix scan with the decoupled-lookback insight, TPU-native.

AK.jl implements Merrill & Garland's *single-pass prefix scan with decoupled
look-back*: each GPU workgroup publishes a block aggregate, then spins,
inspecting predecessors' status flags until it can resolve its exclusive
prefix.  The whole mechanism exists because CUDA thread blocks execute in an
UNDEFINED order.

A TPU TensorCore executes its Pallas grid **sequentially and in order** —
the "look-back" therefore degenerates to an exact carry held in VMEM scratch
across grid steps.  Zero flags, zero spinning, still a single pass over HBM:
the paper's insight (one read of the data, no second global pass) survives;
the GPU mechanism evaporates.  This is the canonical hardware adaptation in
this repo (DESIGN.md §2).

Within a block the scan is computed on the 2-D (8, 1024) layout without any
flat reshape: a row-wise scan (length-1024 log-tree of lane rotations) plus
a carry of row totals folded down the sublanes — i.e. the classic
scan-of-scans, laid out for the VPU. The inter-block carry is a (1, 1024)
vector row (every lane holds it), so no scalar ever moves between vector
memory and the scalar unit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C


def _row_scan(op, block):
    """Inclusive scan along the last axis via a Hillis–Steele log-tree.

    (R, L) -> (R, L); L must be a power of two. Shifts are lane rotations;
    lanes whose predecessor would wrap around keep their value.
    """
    r, l = block.shape
    out = block
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, l), 1)
    shift = 1
    while shift < l:
        shifted = pltpu.roll(out, shift, 1)
        out = jnp.where(lane >= shift, op(out, shifted), out)
        shift *= 2
    return out


def _row_carries(op, carry, totals):
    """Exclusive left fold of row totals, seeded by ``carry``.

    carry: (1, L) with every lane equal; totals: (R, L), row r's total in
    every lane. Returns (per-row carries (R, L), carry out (1, L)) — the
    sequential fold ``acc = op(acc, total_r)`` done on whole rows.
    """
    r = totals.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, totals.shape, 0)
    carries = jnp.broadcast_to(carry, totals.shape)
    acc = carry
    for k in range(r):
        carries = jnp.where(row == k, jnp.broadcast_to(acc, totals.shape),
                            carries)
        acc = op(acc, totals[k:k + 1])
    return carries, acc


def _last_lane(x):
    """(R, L) -> (R, L) holding each row's last lane in every lane."""
    return jnp.broadcast_to(x[:, -1:], x.shape)


def _scan_body(op, unit, x_ref, o_ref, carry_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry_ref[...] = jnp.full(carry_ref.shape, unit, carry_ref.dtype)

    x = x_ref[...]  # (BLOCK_ROWS, BLOCK_COLS)
    rows = _row_scan(op, x)  # inclusive per-row
    carries, acc = _row_carries(op, carry_ref[...], _last_lane(rows))
    o_ref[...] = op(rows, carries)
    carry_ref[...] = acc


def scan_blocks(op, x: jax.Array, *, unit, exclusive: bool = False) -> jax.Array:
    """Inclusive (or exclusive) prefix scan of flat ``x`` under ``op``.

    ``unit`` is the identity of ``op`` (pads the tail; seeds the carry).
    """
    shape, n = x.shape, x.size
    view, _ = C.as_blocks(x, fill=jnp.asarray(unit, x.dtype))
    br, bc = C.block_rows(), C.block_cols()
    rows = view.shape[0]
    grid = (rows // br,)
    spec = pl.BlockSpec((br, bc), lambda i: (i, 0))

    out = C.pallas_call(
        functools.partial(_scan_body, op, unit),
        name="scan",
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(view.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((1, bc), x.dtype)],
        interpret=C.interpret_mode(),
    )(view)
    flat = out.reshape(-1)[:n]
    if exclusive:
        flat = jnp.concatenate([jnp.full((1,), unit, x.dtype), flat[:-1]])
    return flat.reshape(shape)
