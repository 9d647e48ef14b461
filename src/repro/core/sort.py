"""The AK.jl primitive suite, part 2: sorting.

``merge_sort`` / ``merge_sort_by_key`` / ``sortperm`` / ``sortperm_lowmem``
from the paper §II-B.  The TPU specialisation is the blocked bitonic network
(kernels/sort_kernel.py — DESIGN.md §2 records why a literal merge sort is
the wrong shape for this hardware); the portable path is ``jnp.sort`` /
``jnp.argsort`` which XLA lowers to its own sorting network. Both sides are
registered once in ``repro.core.registry``; these wrappers adapt the public
signatures and leave dispatch, jit caching and tuning to the registry.

``topk`` is an extension the LM substrate needs (MoE routing, samplers); it
is sort-derived, as in AK where it would compose from the same blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import registry

_sort = registry.get("sort")
_sort_kv = registry.get("sort_kv")
_merge = registry.get("merge")
_merge_kv = registry.get("merge_kv")
_argsort = registry.get("argsort")
_sort_batched = registry.get("sort_batched")
_argsort_batched = registry.get("argsort_batched")
_topk = registry.get("topk")
_nucleus_mask = registry.get("nucleus_mask")
_segmented_sort = registry.get("segmented_sort")


def merge_sort(x, *, descending: bool = False, backend: str | None = None):
    """Sort a 1-D collection (AK ``merge_sort``; allocating form)."""
    return _sort(x, descending=descending, backend=backend)


def merge_sort_by_key(keys, vals, *, backend: str | None = None):
    """Sort (keys, payload) kept in separate arrays (AK
    ``merge_sort_by_key``). Equal-key payload order is unspecified, exactly
    as in a non-stable parallel sort."""
    return _sort_kv(keys, vals, backend=backend)


def sortperm(x, *, backend: str | None = None):
    """Index permutation that sorts ``x`` (AK ``sortperm``), stable.

    Implemented as a by-key sort of (x, iota) with (key, index) lexicographic
    ties — the faster, +50%-memory variant of the paper.
    """
    return _argsort(x, backend=backend)


def sortperm_lowmem(x, *, backend: str | None = None):
    """AK ``sortperm_lowmem``: trade speed for footprint.

    The payload rides as packed low bits of a widened key (one array instead
    of two): f32/i32 keys widen to i64 = (key-bits << 32) | index, sorted
    key-only, indices unpacked. One n-element temp vs two.

    Needs 64-bit ints; when jax x64 is disabled (the default) this falls
    back to the two-array ``sortperm`` — same results, AK's memory note
    simply doesn't apply.
    """
    n = x.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    if not jax.config.jax_enable_x64 or x.dtype not in (
        jnp.float32, jnp.int32
    ):
        return sortperm(x, backend=backend)
    if x.dtype == jnp.float32:
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        # order-preserving int mapping of IEEE754: flip sign bit, or all bits
        bits = jnp.where(bits < 0, ~bits, bits ^ jnp.int32(-2147483648))
    else:
        bits = x
    wide = (bits.astype(jnp.int64) << 32) | jnp.arange(n, dtype=jnp.int64)
    swide = merge_sort(wide, backend=backend)
    return (swide & (2**32 - 1)).astype(jnp.int32)


def merge_sort_batched(x, *, descending: bool = False,
                       backend: str | None = None):
    """Sort (..., n) along its last axis — the batched AK ``merge_sort``.

    MoE routing and the top-p sampler operate on per-row distributions; this
    entry point runs the whole batch through one vmapped network (one launch
    set, the batch as an extra grid dim) instead of round-tripping each row
    through the 1-D primitive.
    """
    return _sort_batched(x, descending=descending, backend=backend)


def sortperm_batched(x, *, backend: str | None = None):
    """Stable index permutation along the last axis of (..., n)."""
    return _argsort_batched(x, backend=backend)


def merge(x, nruns: int, *, counts=None, backend: str | None = None):
    """Merge ``nruns`` consecutive pre-sorted ascending runs of 1-D ``x``
    into one sorted array of the same length.

    ``counts`` (optional, (nruns,) ints, traced) marks each run's valid
    prefix; slots past it are masked to type-max and sort to the global
    tail, so the merged valid prefix is ``sum(counts)`` long.  The portable
    oracle is a full (concatenate+)sort; the pallas path runs only the
    bitonic network's merge phases — O(n log P) cross launches instead of
    the full O(n log² n) rebuild (kernels/merge_kernel.py, DESIGN.md §2b).
    This is SIHSort's finish stage over the P runs the exchange delivers.
    """
    if counts is None:
        return _merge(x, nruns=nruns, backend=backend)
    return _merge(x, counts, nruns=nruns, backend=backend)


def merge_kv(keys, vals, nruns: int, *, counts=None,
             tie_break: bool = False, backend: str | None = None):
    """Key/value k-way merge of pre-sorted runs; pairs survive intact.

    ``tie_break=True`` additionally requires each run to be
    (key, value)-lexicographically sorted and yields the stable
    lexicographic merge; otherwise equal-key pair order is unspecified,
    as in ``merge_sort_by_key``.
    """
    if counts is None:
        return _merge_kv(keys, vals, nruns=nruns, tie_break=tie_break,
                         backend=backend)
    return _merge_kv(keys, vals, counts, nruns=nruns, tie_break=tie_break,
                     backend=backend)


def nucleus_mask(x, *, top_p: float, backend: str | None = None):
    """Fused nucleus (top-p) keep mask along the last axis of logits.

    Keeps the smallest descending-probability prefix whose inclusive
    softmax mass reaches ``top_p`` (ties at the cut break by ascending
    index). One registry call replacing the historical sampler composition
    (descending ``sortperm_batched`` + vmapped ``accumulate`` + vmapped
    ``searchsortedfirst`` + scatter): the portable path is the XLA oracle,
    the Pallas path re-enters the batched bitonic network and finishes with
    a single fused softmax/prefix-sum/cut launch
    (kernels/nucleus_kernel.py). ``top_p`` is static (host float).
    """
    return _nucleus_mask(x, top_p=float(top_p), backend=backend)


def segmented_sort(values, offsets, *, vals=None,
                   backend: str | None = None):
    """Sort each CSR segment of 1-D ``values`` independently, ascending —
    the ragged ``merge_sort`` (DESIGN.md §10).

    ``offsets`` follows the CSR contract (length ``S + 1``, ``offsets[0] ==
    0``, ``offsets[-1] == len(values)``; empty segments legal). With
    ``vals`` (same-length payload) returns ``(sorted_values, payload)``
    with equal values keeping their original relative order (stable, like
    ``sortperm``); without, returns the sorted values. On TPU this is ONE
    pass of the existing bitonic hyper-block network with segment ids as
    the major key — dispatch-as-sort, no per-segment launches.
    """
    if vals is None:
        return _segmented_sort(values, offsets, backend=backend)
    return _segmented_sort(values, offsets, vals, backend=backend)


def topk(x, k: int, *, backend: str | None = None):
    """Top-k values and indices along the last axis (descending).

    Registered like every other primitive, so ``backend=`` is honoured:
    the portable path is ``lax.top_k``; the pallas path derives it from the
    batched bitonic network (descending stable order, first k), as AK would
    compose it from the same sorting blocks.
    """
    return _topk(x, k=k, backend=backend)
