"""Segmented primitives over CSR-style ``(offsets, values)`` pairs.

AK.jl's primitives (and ours, until this module) operate on dense flat
arrays.  The segmented generalisation — one independent reduce/scan/sort per
CSR row — is the unlock for ragged workloads: sparse assembly, graph ops,
and (the proof case in this repo) MoE expert buckets, where tokens routed to
expert ``e`` occupy ``values[offsets[e]:offsets[e+1]]``.

CSR convention (shared by every entry point here):

* ``offsets`` is int, 1-D, length ``S + 1``, non-decreasing, with
  ``offsets[0] == 0`` and ``offsets[-1] == len(values)``.  Empty segments
  (``offsets[s] == offsets[s+1]``) are legal anywhere.
* ``values`` is 1-D (the Pallas kernels) or ``(n, ...)`` with trailing
  feature axes (portable flagged-scan path only — used by the MoE combine).

The scan/reduce kernel is the flagged-pair formulation of the classic
segmented scan: carry ``(flag, value)`` pairs under the associative combine

    (fa, va) ⊕ (fb, vb) = (fa | fb,  vb if fb else op(va, vb))

which resets accumulation at every segment head.  That drops straight into
``scan_kernel``'s sequential-grid machinery — the Hillis–Steele lane tree,
the per-row carry fold, and the (1, L) vector carry scratch all stay, each
now carrying an int32 flag beside the value.  Segment boundaries cost one extra
int32 flag stream; there is no per-segment launch, so the launch count is
identical to the dense scan: ``rows / block_rows`` for one pass.

``segmented_sort`` is dispatch-as-sort in miniature: sorting the pair
``(segment_id, value)`` lexicographically IS the per-segment sort, so the
kernel is one ``bitonic_sort_kv`` pass over the existing hyper-block
network with segment ids as keys and ``tie_break=True`` ordering equal ids
by value.  Ragged tails are masked with type-max ids/values exactly like
the merge kernel's run tails — padding sorts past every live element and is
sliced off.  The payload variant runs the stable-argsort network twice
(value pass, then segment-id pass over the permuted ids); composing two
stable sorts is the textbook LSD radix argument, so ties break by original
index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as C
from repro.kernels import sort_kernel as SK


# --------------------------------------------------------------------------
# CSR helpers
# --------------------------------------------------------------------------

def segment_ids(offsets: jax.Array, n: int) -> jax.Array:
    """Element -> segment index, int32 of shape (n,).

    ``searchsorted(offsets, i, side='right') - 1`` lands element ``i`` in the
    unique ``s`` with ``offsets[s] <= i < offsets[s+1]`` and skips empty
    segments automatically.
    """
    nseg = offsets.shape[0] - 1
    idx = jnp.arange(n, dtype=offsets.dtype)
    ids = jnp.searchsorted(offsets, idx, side="right") - 1
    return jnp.clip(ids, 0, max(nseg - 1, 0)).astype(jnp.int32)


def head_flags(offsets: jax.Array, n: int) -> jax.Array:
    """int32 (n,) mask: 1 at the first element of each (non-empty) segment."""
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    ids = segment_ids(offsets, n)
    first = jnp.ones((1,), dtype=jnp.bool_)
    return jnp.concatenate([first, ids[1:] != ids[:-1]]).astype(jnp.int32)


def _flag_combine(op, fa, va, fb, vb):
    """The flagged-pair segmented-scan combine; ``b`` is the later element."""
    return fa | fb, jnp.where(fb, vb, op(va, vb))


# --------------------------------------------------------------------------
# Flagged blocked scan — the Pallas kernel
# --------------------------------------------------------------------------

def _flagged_row_scan(op, v, f):
    """Inclusive segmented scan along lanes of an (R, L) block.

    Hillis–Steele with the flagged combine: a lane stops absorbing its
    left neighbourhood once its accumulated window contains a head flag.
    ``f`` is int32 0/1 (flags are rotated and selected as integers).
    """
    r, l = v.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, l), 1)
    shift = 1
    while shift < l:
        pv = pltpu.roll(v, shift, 1)
        pf = pltpu.roll(f, shift, 1)
        has = lane >= shift
        v = jnp.where(has & (f == 0), op(pv, v), v)
        f = jnp.where(has, f | pf, f)
        shift *= 2
    return v, f


def _segscan_block(op, carry, v, f):
    """One (R, L) block of the segmented scan given an inter-block carry.

    ``carry = (cv, cf)`` is the accumulated (value, seen-a-flag) pair for
    everything before this block, each a (1, L) row with every lane equal.
    Returns the block output and new carry.
    """
    cv, cf = carry
    v, f = _flagged_row_scan(op, v, f)
    shape = v.shape
    totals_v = jnp.broadcast_to(v[:, -1:], shape)
    totals_f = jnp.broadcast_to(f[:, -1:], shape)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    row_cv = jnp.broadcast_to(cv, shape)
    for r in range(shape[0]):
        row_cv = jnp.where(row == r, jnp.broadcast_to(cv, shape), row_cv)
        tf = totals_f[r:r + 1]
        cv = jnp.where(tf != 0, totals_v[r:r + 1], op(cv, totals_v[r:r + 1]))
        cf = cf | tf
    # Element i absorbs the row carry only if no head flag precedes it
    # within the row (its accumulated flag is clear); the carry flag never
    # changes an element's value.
    out = jnp.where(f != 0, v, op(row_cv, v))
    return out, (cv, cf)


def _segscan_body(op, unit, v_ref, f_ref, o_ref, cv_ref, cf_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cv_ref[...] = jnp.full(cv_ref.shape, unit, cv_ref.dtype)
        cf_ref[...] = jnp.zeros(cf_ref.shape, cf_ref.dtype)

    out, (cv, cf) = _segscan_block(
        op, (cv_ref[...], cf_ref[...]), v_ref[...], f_ref[...]
    )
    o_ref[...] = out
    cv_ref[...] = cv
    cf_ref[...] = cf


def _exclusive_shift(inclusive, flags, unit):
    """Inclusive -> exclusive within each segment: heads get ``unit``,
    everything else its predecessor's inclusive value."""
    shifted = jnp.concatenate(
        [jnp.full((1,), unit, inclusive.dtype), inclusive[:-1]]
    )
    return jnp.where(flags != 0, jnp.asarray(unit, inclusive.dtype), shifted)


def segmented_scan_blocks(op, values, offsets, *, unit,
                          exclusive=False) -> jax.Array:
    """Per-segment prefix scan of 1-D ``values``, one Pallas pass."""
    n = values.size
    flags = head_flags(offsets, n)
    view_v, _ = C.as_blocks(values, fill=jnp.asarray(unit, values.dtype))
    view_f, _ = C.as_blocks(flags, fill=jnp.asarray(0, jnp.int32))
    br, bc = C.block_rows(), C.block_cols()
    grid = (view_v.shape[0] // br,)
    spec = pl.BlockSpec((br, bc), lambda i: (i, 0))

    out = C.pallas_call(
        functools.partial(_segscan_body, op, unit),
        name="segmented_scan",
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(view_v.shape, values.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, bc), values.dtype),
            pltpu.VMEM((1, bc), jnp.int32),
        ],
        interpret=C.interpret_mode(),
    )(view_v, view_f)
    flat = out.reshape(-1)[:n]
    if exclusive:
        flat = _exclusive_shift(flat, flags, unit)
    return flat


def segmented_scan_launches(n: int) -> int:
    """Closed-form launch count (mirrors ``scan_kernel``: one grid pass)."""
    if n == 0:
        return 0
    return 1


# --------------------------------------------------------------------------
# jnp oracles — independent formulations, NOT the kernel re-spelled
# --------------------------------------------------------------------------

def segmented_scan_ref(op, values, offsets, *, unit,
                       exclusive=False) -> jax.Array:
    """Flagged ``lax.associative_scan`` over (flag, value) pairs.

    A genuinely different evaluation order from the kernel's lane tree +
    carry fold, which is what makes bitwise agreement on exact-arithmetic
    inputs a real test.  Supports trailing feature axes (n, ...) — flags
    broadcast over them.
    """
    n = values.shape[0]
    if n == 0:
        return values
    flags = head_flags(offsets, n) != 0
    f = flags.reshape((n,) + (1,) * (values.ndim - 1))

    def comb(a, b):
        fa, va = a
        fb, vb = b
        nf, nv = _flag_combine(op, fa, va, fb, vb)
        return nf, nv

    _, scanned = jax.lax.associative_scan(comb, (f, values))
    if exclusive:
        unit_row = jnp.full((1,) + values.shape[1:], unit, values.dtype)
        shifted = jnp.concatenate([unit_row, scanned[:-1]])
        scanned = jnp.where(f, jnp.asarray(unit, values.dtype), shifted)
    return scanned


def _segment_ends(scanned, offsets, init):
    """Pick each segment's last inclusive-scan value; empty segments -> init."""
    nseg = offsets.shape[0] - 1
    n = scanned.shape[0]
    fill = jnp.full((nseg,) + scanned.shape[1:], init, scanned.dtype)
    if n == 0:
        return fill
    ends = jnp.clip(offsets[1:] - 1, 0, n - 1)
    nonempty = (offsets[1:] > offsets[:-1]).reshape(
        (nseg,) + (1,) * (scanned.ndim - 1)
    )
    return jnp.where(nonempty, scanned[ends], fill)


def segmented_reduce_ref(op, values, offsets, *, init) -> jax.Array:
    """jnp oracle: ``segment_sum`` for the additive case (the MoE combine),
    flagged associative scan + segment-end gather otherwise."""
    nseg = offsets.shape[0] - 1
    n = values.shape[0]
    if n == 0:
        return jnp.full((nseg,) + values.shape[1:], init, values.dtype)
    if op is jnp.add and init == 0:
        ids = segment_ids(offsets, n)
        return jax.ops.segment_sum(values, ids, num_segments=nseg)
    scanned = segmented_scan_ref(op, values, offsets, unit=init)
    return _segment_ends(scanned, offsets, init)


def segmented_reduce_blocks(op, values, offsets, *, init) -> jax.Array:
    """Pallas path: one flagged-scan pass, then gather segment ends."""
    scanned = segmented_scan_blocks(op, values, offsets, unit=init)
    return _segment_ends(scanned, offsets, init)


# --------------------------------------------------------------------------
# Segmented sort — the hyper-block network with segment ids as major key
# --------------------------------------------------------------------------

def segmented_sort_ref(values, offsets, payload=None):
    """jnp oracle via ``lexsort``: stable (segment, value) order, so ties
    keep their original relative order — the contract the payload variant's
    double stable argsort reproduces exactly."""
    n = values.shape[0]
    if n == 0:
        return values if payload is None else (values, payload)
    ids = segment_ids(offsets, n)
    perm = jnp.lexsort((values, ids)) if payload is None else jnp.lexsort(
        (jnp.arange(n), values, ids)
    )
    if payload is None:
        return values[perm]
    return values[perm], payload[perm]


def segmented_sort_blocks(values, offsets, payload=None):
    """Pallas path over the existing bitonic hyper-block network.

    No payload: one kv pass with ``keys = segment_ids`` and the values as
    payload; ``tie_break=True`` orders equal ids by value, which is exactly
    per-segment sorted order.  With payload: two stable argsort passes
    (sort by value, then stably by segment id) composed LSD-style, then one
    gather each for values and payload.
    """
    n = values.shape[0]
    if n == 0:
        return values if payload is None else (values, payload)
    ids = segment_ids(offsets, n)
    if payload is None:
        _, out = SK.bitonic_sort_kv(ids, values, tie_break=True)
        return out
    iota = jnp.arange(n, dtype=jnp.int32)
    _, p1 = SK.bitonic_sort_kv(values, iota, tie_break=True)
    _, p2 = SK.bitonic_sort_kv(ids[p1], iota, tie_break=True)
    perm = p1[p2]
    return values[perm], payload[perm]


def segmented_sort_launches(n: int, hyper: int | None = None) -> int:
    """Launches = one kv network pass (two for the payload variant's
    double argsort — report the single-pass figure, the common case)."""
    return SK.network_launches(n, hyper)
