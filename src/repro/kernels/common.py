"""Shared helpers for the Pallas TPU kernels.

All kernels in this package are written against the TPU lowering rules
(2-D blocks, last dim a multiple of 128, second-to-last a multiple of the
sublane count) and compile through Mosaic for TPU chips
(tests/test_tpu_compile.py compiles each for a described v5e). Off the
TPU they run with ``interpret=True``: the kernel body executes as ordinary
XLA ops on the CPU, which is how the test suite checks them against the
jnp oracles.
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# TPU vector-register geometry (v4/v5): 8 sublanes x 128 lanes.
SUBLANES = 8
LANES = 128
TILE = SUBLANES * LANES  # 1024 elements: the minimum well-shaped f32 tile.

# The ONE vocab-masking constant (loss padded-vocab mask, sampler top-k /
# top-p / vocab cuts). Finite on purpose: ``-inf`` makes an all-masked row
# produce ``inf - inf = nan`` in log-sum-exp/softmax reductions and kills
# gradients through ``where``; ``-1e30`` underflows to exactly 0 probability
# after ``exp(x - max)`` for any realistic max, so the two behave identically
# on live rows while the finite value stays total-order-sortable and
# nan-free. models/model.py (loss) and launch/serve.py (sampler) used to
# disagree (-1e30 vs -inf); both now read this.
NEG_MASK = -1e30

# Default block used by the 1-D streaming kernels (map/reduce/scan/hist):
# (8, 1024) f32 = 32 KiB per operand — small against ~16 MiB VMEM, so
# several operands + double-buffering fit comfortably.  The live values are
# read through ``block_rows()``/``block_cols()`` so the primitive registry's
# tuning table (core/registry.py) can re-tile a kernel without editing it.
BLOCK_ROWS = 8
BLOCK_COLS = 1024
BLOCK_ELEMS = BLOCK_ROWS * BLOCK_COLS

_tuning = threading.local()


def block_rows() -> int:
    return getattr(_tuning, "block_rows", None) or BLOCK_ROWS


def block_cols() -> int:
    return getattr(_tuning, "block_cols", None) or BLOCK_COLS


def block_elems() -> int:
    return block_rows() * block_cols()


def sort_hyper() -> int | None:
    """Hyper-block order ``m`` for the fused bitonic cross-stage kernel
    (sort_kernel.py): each cross launch maps ``2^m`` blocks per grid step and
    runs ``m`` compare-exchange stages in VMEM. ``None`` = the kernel's
    default; ``0`` = the unfused one-launch-per-stage layout (kept as the
    benchmark's counted baseline)."""
    return getattr(_tuning, "sort_hyper", None)


def interpret_mode() -> bool:
    """Pallas kernels run in interpret mode everywhere except real TPUs
    (unless a tuning scope pins it explicitly)."""
    override = getattr(_tuning, "interpret", None)
    if override is not None:
        return bool(override)
    return jax.default_backend() != "tpu"


@contextlib.contextmanager
def tuning_scope(*, interpret=None, block_rows=None, block_cols=None,
                 sort_hyper=None):
    """Scoped kernel-tuning overrides, read at trace time by every kernel in
    this package. ``None`` keeps the current value. The registry wraps each
    kernel trace in this scope so the tuning table's knobs take effect
    without any kernel knowing about the table."""
    prev = (
        getattr(_tuning, "interpret", None),
        getattr(_tuning, "block_rows", None),
        getattr(_tuning, "block_cols", None),
        getattr(_tuning, "sort_hyper", None),
    )
    if interpret is not None:
        _tuning.interpret = interpret
    if block_rows is not None:
        _tuning.block_rows = block_rows
    if block_cols is not None:
        _tuning.block_cols = block_cols
    if sort_hyper is not None:
        _tuning.sort_hyper = sort_hyper
    try:
        yield
    finally:
        (_tuning.interpret, _tuning.block_rows, _tuning.block_cols,
         _tuning.sort_hyper) = prev


# --------------------------------------------------------------------------
# Trace-time launch counter — package-wide, thread-safe, attributed.
#
# Incremented once per ``pl.pallas_call`` ANY kernel in this package issues,
# i.e. once per kernel launch of a single execution of the traced program.
# Benchmarks read it under ``jax.eval_shape`` to *count* (not estimate)
# launches: the sort gate (benchmarks/sort_throughput.py) counts the fused
# network's launches, the serving gate (benchmarks/serving.py) counts
# launches per decode step for the fused vs unfused sampler. Kernels issue
# launches through ``pallas_call`` below; ``sort_kernel`` re-exports the
# counter so existing callers keep working.
#
# Launches are attributed to the label set by the innermost
# ``launch_attribution(label)`` scope — the registry opens one per primitive
# trace, so ``launch_counts()`` breaks the total down per primitive. The
# label scope is thread-local; the tallies live under one lock because jax
# may retrace the same program from several threads.
# --------------------------------------------------------------------------

_launch_lock = threading.Lock()
_launches = 0
_launch_by_label: dict[str, int] = {}
_launch_label = threading.local()


def launch_count() -> int:
    return _launches


def launch_counts() -> dict[str, int]:
    """Per-label launch tallies (label = primitive name from the registry's
    ``launch_attribution`` scope; bare launches land under ``"unattributed"``).
    Values sum to ``launch_count()``."""
    with _launch_lock:
        return dict(_launch_by_label)


def reset_launch_count() -> None:
    global _launches
    with _launch_lock:
        _launches = 0
        _launch_by_label.clear()


@contextlib.contextmanager
def launch_attribution(label: str):
    """Attribute every ``pallas_call`` traced in this (thread-local) scope
    to ``label``. Nestable — the innermost label wins."""
    prev = getattr(_launch_label, "value", None)
    _launch_label.value = label
    try:
        yield
    finally:
        _launch_label.value = prev


def pallas_call(*args, name: str, **kwargs):
    """Counted ``pl.pallas_call`` — every kernel in this package launches
    through here so trace-time launch counting covers the whole suite.
    ``name`` is the kernel's role (``bitonic_inblock``, ``nucleus_cut``):
    the compiled operation takes it as its name, so a profile's device ops
    say which kernel ran."""
    global _launches
    label = getattr(_launch_label, "value", None) or "unattributed"
    with _launch_lock:
        _launches += 1
        _launch_by_label[label] = _launch_by_label.get(label, 0) + 1
    return pl.pallas_call(*args, name=name, **kwargs)


def xor_partner(x: jax.Array, d: int, axis: int) -> jax.Array:
    """``out[i] = x[i ^ d]`` along ``axis`` (``d`` a power of two below the
    axis length): the compare-exchange partner of every slot. On the chip,
    two rotations and a select on the index bit; interpreted, a reversal
    of each (low, high) pair, which compiles to a fraction of the host
    code. Both only move data, so they give the same bits."""
    n = x.shape[axis]
    if interpret_mode():
        pairs = x.shape[:axis] + (n // (2 * d), 2, d) + x.shape[axis + 1:]
        return jnp.flip(x.reshape(pairs), axis + 1).reshape(x.shape)
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    up = pltpu.roll(x, n - d, axis)    # up[i] = x[i + d]
    down = pltpu.roll(x, d, axis)      # down[i] = x[i - d]
    return jnp.where((idx & d) == 0, up, down)


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b


def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def size_class(n: int) -> int:
    """Pow2 size bucket of an element count: the exponent of next_pow2(n)
    (0 for n <= 1). The autotune cache (repro.tune) keys measured knobs per
    (primitive, backend, dtype, size-class); calls bucket the live length
    through the SAME function so a knob tuned at 2^17 serves every length in
    (2^16, 2^17]. Kept here, next to the block geometry it buckets, so
    kernels, the registry and the tuner cannot drift apart."""
    return 0 if n <= 1 else int(n - 1).bit_length()


def pad_to(x: jax.Array, n: int, fill) -> jax.Array:
    """Pad 1-D ``x`` up to length ``n`` with ``fill``."""
    pad = n - x.shape[0]
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,), fill, dtype=x.dtype)])


def type_max(dtype) -> jax.Array:
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def type_min(dtype) -> jax.Array:
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).min, dtype)


def as_blocks(x: jax.Array, fill) -> tuple[jax.Array, int]:
    """Flatten ``x``, pad to a BLOCK_ELEMS multiple and reshape to
    (rows, BLOCK_COLS). Returns the 2-D view and the original length.

    Row-major order preserves the flat element order, which the scan kernel
    relies on.
    """
    n = x.size
    elems, cols = block_elems(), block_cols()
    flat = x.reshape(-1)
    padded = pad_to(flat, max(round_up(n, elems), elems), fill)
    return padded.reshape(-1, cols), n
