"""``reduce`` / ``mapreduce`` — tiled two-level reduction.

AK.jl reduces within workgroups (shared memory) and then across workgroup
partials, optionally finishing tiny tails on the host (``switch_below``).
TPU adaptation: the Pallas grid on a TensorCore executes **in order**, so the
cross-workgroup level becomes a running partial held in a VMEM scratch
accumulator — no atomics, no second launch.  The ``switch_below`` insight
(stop paying launch overhead on tiny tails) is preserved structurally:
there is only ever ONE launch here.

The accumulator is (8, 128) vector-shaped rather than scalar: reducing each
(8, 1024) block to a scalar every grid step would serialise on the scalar
unit; folding to a vreg keeps the VPU busy, and the vreg is collapsed once,
in the final grid step, by a halving tree of sublane and lane rotations
that leaves the total in every element. The kernel's output is that
(8, 128) block; the caller reads element [0, 0]. This mirrors the paper's
"no warp shuffles, still fast" design point — partials stay in vector
registers, and no scalar is ever stored to vector memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C

_ACC_ROWS, _ACC_COLS = C.SUBLANES, C.LANES


def _collapse(op, a):
    """Fold an (R, L) block (powers of two) to its op-total in element
    [0, 0]: halving trees over sublanes, then lanes. Rotating by
    ``size - half`` brings element ``i + half`` to ``i``, so element 0
    combines (lower half, upper half) exactly as a halving tree of slices."""
    for axis in (0, 1):
        half = a.shape[axis] // 2
        while half >= 1:
            a = op(a, pltpu.roll(a, a.shape[axis] - half, axis))
            half //= 2
    return a


def _reduce_body(f, op, unit, n_ops, *refs):
    # refs = (*in_refs, out_ref, acc_ref)
    i = pl.program_id(0)
    acc, out = refs[-1], refs[-2]
    ins = [refs[k][...] for k in range(n_ops)]
    mapped = f(*ins)  # (BLOCK_ROWS, BLOCK_COLS)
    # Fold the (8, 1024) block into an (8, 128) vreg-shaped partial:
    # lane-aligned column slices, combined left to right.
    rows, cols = mapped.shape
    part = mapped[:, :_ACC_COLS]
    for j in range(1, cols // _ACC_COLS):
        part = op(part, mapped[:, j * _ACC_COLS:(j + 1) * _ACC_COLS])
    if rows != _ACC_ROWS:
        folded = part[:_ACC_ROWS]
        for r in range(1, rows // _ACC_ROWS):
            folded = op(folded, part[r * _ACC_ROWS:(r + 1) * _ACC_ROWS])
        part = folded

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.full((_ACC_ROWS, _ACC_COLS), unit, mapped.dtype)

    acc[...] = op(acc[...], part)

    @pl.when(i == pl.num_programs(0) - 1)
    def _fin():
        out[...] = _collapse(op, acc[...])


def reduce_blocks(f, op, *arrays: jax.Array, unit, out_dtype=None) -> jax.Array:
    """``mapreduce(f, op, arrays...) -> scalar`` via one sequential-grid kernel.

    ``unit`` must be the identity of ``op``; it pads the tail block and seeds
    the accumulator. Returns a 0-d array of ``out_dtype``.
    """
    x0 = arrays[0]
    out_dtype = jnp.dtype(out_dtype or x0.dtype)
    views = [C.as_blocks(a, fill=jnp.asarray(unit, a.dtype))[0] for a in arrays]
    br, bc = C.block_rows(), C.block_cols()
    rows = views[0].shape[0]
    grid = (rows // br,)
    spec = pl.BlockSpec((br, bc), lambda i: (i, 0))

    out = C.pallas_call(
        functools.partial(_reduce_body, f, op, unit, len(views)),
        name="reduce",
        grid=grid,
        in_specs=[spec] * len(views),
        out_specs=pl.BlockSpec((_ACC_ROWS, _ACC_COLS), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((_ACC_ROWS, _ACC_COLS), out_dtype),
        scratch_shapes=[pltpu.VMEM((_ACC_ROWS, _ACC_COLS), out_dtype)],
        interpret=C.interpret_mode(),
    )(*views)
    return out[0, 0]
