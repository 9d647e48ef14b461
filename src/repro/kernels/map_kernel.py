"""``foreachindex`` — the paper's fundamental parallel-looping block.

AK.jl turns ``for i in eachindex(itr)`` into one GPU thread per iteration.
The TPU-native equivalent is a tiled elementwise kernel: the grid walks
(8, 1024) VMEM blocks and the loop body — an arbitrary traceable Julia-like
closure ``f`` — is applied to whole vector registers instead of scalar
threads.  Closures capture surrounding arrays exactly as AK's ``do`` blocks
do: extra operands are passed as positional block refs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common as C


def _map_body(f, n_operands, *refs):
    # refs = (*in_refs, out_ref)
    ins = [refs[i][...] for i in range(n_operands)]
    refs[-1][...] = f(*ins)


def map_blocks(f, *arrays: jax.Array, out_dtype=None) -> jax.Array:
    """Apply elementwise ``f(*arrays) -> array`` via a tiled Pallas kernel.

    All arrays must share a shape. Returns an array of that shape with
    ``out_dtype`` (defaults to the dtype of the first operand).
    """
    x0 = arrays[0]
    shape, n = x0.shape, x0.size
    out_dtype = jnp.dtype(out_dtype or x0.dtype)
    views = []
    for a in arrays:
        if a.shape != shape:
            raise ValueError(f"operand shape mismatch: {a.shape} vs {shape}")
        v, _ = C.as_blocks(a, fill=jnp.zeros((), a.dtype))
        views.append(v)
    br, bc = C.block_rows(), C.block_cols()
    rows = views[0].shape[0]
    grid = (rows // br,)
    spec = pl.BlockSpec((br, bc), lambda i: (i, 0))

    out = C.pallas_call(
        functools.partial(_map_body, f, len(views)),
        name="map",
        grid=grid,
        in_specs=[spec] * len(views),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(views[0].shape, out_dtype),
        interpret=C.interpret_mode(),
    )(*views)
    return out.reshape(-1)[:n].reshape(shape)
