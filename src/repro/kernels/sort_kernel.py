"""``merge_sort`` / ``merge_sort_by_key`` / ``sortperm`` — TPU-native sorting.

AK.jl ships a merge sort because its portable layer has no warp shuffles and
radix sort "requires intrinsics for high performance" (paper §I-B).  The TPU
portable layer has the same constraint *plus* a vector memory that hates the
data-dependent branches of a sequential merge path.  The TPU-idiomatic
equivalent is a **bitonic sorting network**: every compare-exchange step is a
branch-free rotate + min/max + select over whole (8·k, 1024) vector
registers, with zero gathers — trading the O(n log n) of merge sort for
O(n log² n) *perfectly vectorised* work.  (DESIGN.md §2 records this as a
hardware adaptation; the AK "merge" view survives inside the network — a
bitonic merge of two sorted runs is exactly `concat(a, reverse(b))` followed
by the final half-cleaner stages.)

Two kernels (DESIGN.md §2a records the fusion design):

  * an **in-block** kernel applying any list of (k, j) compare-exchange
    stages (j < BLOCK elements) to each VMEM-resident block;
  * a **hyper-block** cross kernel: one launch covers a *window* of up to
    ``m`` consecutive cross stages (j ≥ BLOCK).  Each grid step maps the
    ``2^w`` blocks (w = window size) that those stages exchange — expressed
    as ONE BlockSpec over a (Q, 2^w, S, R, L) view of the array, so the
    strided block group arrives as a single ref — and runs the whole
    member-butterfly in VMEM before writing back.  The window that reaches
    block distance 1 additionally absorbs the k-phase's in-block finishing
    stages, so a full k-phase beyond the block size costs
    ``ceil(log2(k/BLOCK) / m)`` launches instead of ``log2(k/BLOCK) + 1``.
    Outputs are written through the same index maps (every block is written
    by exactly one grid step — no recombination pass) and
    ``input_output_aliases`` makes the exchange in-place in HBM.

Key/value variants of both kernels serve ``sortperm`` (values = iota) and
``merge_sort_by_key``; ``bitonic_sort_batched`` / ``bitonic_argsort_batched``
vmap the network over leading axes for last-axis sorts (MoE routing, top-p
sampling) without 1-D round-trips.

Direction bits come from broadcasted iotas over the *global* flat index —
``asc = ((i & k) == 0)`` — so every stage is oblivious (data-independent),
which is also what makes the multi-device SIHSort composition deterministic.
Block geometry (rows/cols) and the hyper-block order ``m`` are tuning-table
knobs, read through ``common.block_rows()/block_cols()/sort_hyper()``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import common as C

# Default block geometry: (8, 1024) = 8192 elements (a power of two, as the
# network requires). f32 keys + i32 values + network temporaries ≈ a few
# hundred KiB of VMEM — comfortable. Overridable per sort-family primitive
# via the registry tuning table (block_rows/block_cols, power-of-two only).
SORT_ROWS = 8
SORT_COLS = 1024
SORT_BLOCK = SORT_ROWS * SORT_COLS

# Default hyper-block order m: each cross launch fuses up to m stages over
# 2^m blocks. m=3 → 8 blocks = 64 Ki f32 elements = 256 KiB keys (+ as much
# again for values) resident per grid step — well inside VMEM with double
# buffering. Tunable via the registry's ``sort_hyper`` knob; 0 selects the
# unfused one-launch-per-stage layout (the benchmark's counted baseline).
HYPER_ORDER = 3

# Trace-time launch counter: incremented once per ``pl.pallas_call``, i.e.
# once per kernel launch of a single execution of the traced program.
# ``benchmarks/sort_throughput.py`` reads it under ``jax.eval_shape`` to
# *count* (not estimate) launches. The counter itself now lives in
# kernels/common.py and is shared by the whole kernel package (the serving
# gate counts sampler launches across sort + nucleus kernels); these
# aliases keep the original read/reset surface.
launch_count = C.launch_count
launch_counts = C.launch_counts
reset_launch_count = C.reset_launch_count


def _geometry() -> tuple[int, int, int]:
    """Live (rows, cols, block) from the tuning scope; the network needs a
    power-of-two block."""
    rows, cols = C.block_rows(), C.block_cols()
    block = rows * cols
    if block & (block - 1):
        raise ValueError(
            f"bitonic sort needs a power-of-two block, got "
            f"{rows}x{cols} = {block}"
        )
    return rows, cols, block


def _hyper_order() -> int:
    m = C.sort_hyper()
    return HYPER_ORDER if m is None else m


def _bit(x, p):
    """Bit ``p`` (a power of two) of int32 ``x`` as 0/1 int32. The network's
    masks stay int32 until the final compare: Mosaic cannot select between
    boolean vectors, so no boolean is ever a select operand."""
    return (x >> (p.bit_length() - 1)) & 1


def _cx(keys, vals, j, k, base, tie_break):
    """One compare-exchange stage at distance ``j`` (< block size) on a
    (R, L) block whose first element has global flat index ``base``.

    Returns the exchanged (keys, vals). ``vals`` may be None (key-only).
    Every slot fetches its partner ``i ^ j`` (``common.xor_partner``: along
    lanes for ``j < L``, along sublanes — ``j // L`` rows — otherwise), so
    no reshape touches the vector layout. Both slots of a pair evaluate the
    same (low, high) comparison, so they agree on the outcome; the pair
    sorts ascending iff bit ``k`` of its index is clear.
    """
    R, L = keys.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (R, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (R, L), 1)
    if j < L:
        axis, d, high = 1, j, _bit(col, j)
    else:
        axis, d, high = 0, j // L, _bit(row, j // L)
    desc = _bit(row * L + col + base, k)
    low = high == 0

    def partner(x):
        return C.xor_partner(x, d, axis)

    pk = partner(keys)
    lo_k, hi_k = jnp.where(low, keys, pk), jnp.where(low, pk, keys)
    if vals is None:
        mn, mx = jnp.minimum(lo_k, hi_k), jnp.maximum(lo_k, hi_k)
        return jnp.where((high ^ desc) == 0, mn, mx), None

    # Key-value: one swap predicate drives both planes, with optional
    # (key, value)-lexicographic tie-break (used by sortperm so ties resolve
    # to ascending index == stable argsort order).
    pv = partner(vals)
    lo_v, hi_v = jnp.where(low, vals, pv), jnp.where(low, pv, vals)
    gt = lo_k > hi_k
    if tie_break:
        gt = gt | ((lo_k == hi_k) & (lo_v > hi_v))
    swap = (gt.astype(jnp.int32) ^ desc) != 0
    return jnp.where(swap, pk, keys), jnp.where(swap, pv, vals)


def _swap_blocks(ka, kb, va, vb, desc, tie_break):
    """Whole-block compare-exchange: every lane of block ``a`` against the
    same lane of block ``b``. ``desc`` is an int32 scalar (1 = descending),
    uniform across the pair because all member-varying index bits sit
    strictly below k."""
    flip = jnp.full(ka.shape, desc, jnp.int32)
    if va is None:
        lo, hi = jnp.minimum(ka, kb), jnp.maximum(ka, kb)
        up = flip == 0
        return (jnp.where(up, lo, hi), jnp.where(up, hi, lo), None, None)
    gt = ka > kb
    if tie_break:
        gt = gt | ((ka == kb) & (va > vb))
    swap = (gt.astype(jnp.int32) ^ flip) != 0
    return (
        jnp.where(swap, kb, ka),
        jnp.where(swap, ka, kb),
        jnp.where(swap, vb, va),
        jnp.where(swap, va, vb),
    )


def _inblock_body(stages, tie_break, has_vals, block, *refs):
    """Apply ``stages`` = [(k, j), ...] (all j < block) to each block."""
    b = pl.program_id(0)
    base = b * block
    if has_vals:
        k_ref, v_ref, ok_ref, ov_ref = refs
        keys, vals = k_ref[...], v_ref[...]
    else:
        k_ref, ok_ref = refs
        keys, vals = k_ref[...], None
    for (k, j) in stages:
        keys, vals = _cx(keys, vals, j, k, base, tie_break)
    ok_ref[...] = keys
    if has_vals:
        ov_ref[...] = vals


def _hyper_body(k, H, S, tail, tie_break, has_vals, block, *refs):
    """Fused cross window: the ``H = 2^w`` member blocks of one exchange
    group arrive as a single (1, H, 1, R, L) ref; run the w-stage member
    butterfly (block distances S·2^(w-1) … S) entirely in VMEM, then the
    optional in-block ``tail`` stages (only when S == 1, i.e. the window
    bottomed out at adjacent blocks), then write every member back.

    Direction is one scalar per grid step: members vary only block-index
    bits [log2 S, log2 S + w), all strictly below bit log2(k/block), so the
    whole group shares its k-bit.
    """
    q, r = pl.program_id(0), pl.program_id(1)
    base_block = q * (H * S) + r
    desc = _bit(base_block * block, k)
    if has_vals:
        k_ref, v_ref, ok_ref, ov_ref = refs
        vals = [v_ref[0, t, 0] for t in range(H)]
    else:
        k_ref, ok_ref = refs
        vals = None
    keys = [k_ref[0, t, 0] for t in range(H)]

    s = H // 2
    while s >= 1:
        for t in range(H):
            if t & s:
                continue
            u = t | s
            ka, kb, va, vb = _swap_blocks(
                keys[t], keys[u],
                None if vals is None else vals[t],
                None if vals is None else vals[u],
                desc, tie_break,
            )
            keys[t], keys[u] = ka, kb
            if vals is not None:
                vals[t], vals[u] = va, vb
        s //= 2

    for (tk, tj) in tail:
        for t in range(H):
            base = (base_block + t * S) * block
            nk, nv = _cx(keys[t], None if vals is None else vals[t],
                         tj, tk, base, tie_break)
            keys[t] = nk
            if vals is not None:
                vals[t] = nv

    for t in range(H):
        ok_ref[0, t, 0] = keys[t]
        if has_vals:
            ov_ref[0, t, 0] = vals[t]


def _stages_upto_block(k, block):
    """All in-block j stages for a given k: j = min(k//2, block//2) .. 1."""
    j = min(k // 2, block // 2)
    out = []
    while j >= 1:
        out.append((k, j))
        j //= 2
    return out


def _run_inblock(stages, keys2d, vals2d, tie_break, n_blocks, rows, cols,
                 role):
    has_vals = vals2d is not None
    spec = pl.BlockSpec((rows, cols), lambda i: (i, 0))
    specs = [spec] * (2 if has_vals else 1)
    outs = (
        [jax.ShapeDtypeStruct(keys2d.shape, keys2d.dtype)]
        + ([jax.ShapeDtypeStruct(vals2d.shape, vals2d.dtype)] if has_vals
           else [])
    )
    res = C.pallas_call(
        functools.partial(_inblock_body, stages, tie_break, has_vals,
                          rows * cols),
        name=f"{role}_inblock",
        grid=(n_blocks,),
        in_specs=specs,
        out_specs=specs if has_vals else specs[0],
        out_shape=outs if has_vals else outs[0],
        input_output_aliases={i: i for i in range(len(specs))},
        interpret=C.interpret_mode(),
    )(*([keys2d, vals2d] if has_vals else [keys2d]))
    return res if has_vals else (res, None)


def _run_hyper(k, window, tail, keys2d, vals2d, tie_break, n_blocks,
               rows, cols, role):
    """One fused cross launch for ``window`` = consecutive halving block
    distances [d, d/2, …, S]. The (n_blocks·rows, cols) arrays are viewed as
    (Q, H, S, rows, cols) — a pure reshape: block g = q·(H·S) + t·S + r maps
    to [q, t, r] — so one BlockSpec hands each grid step (q, r) its whole
    exchange group and writes it back through the same map. Every block is
    written exactly once across the grid; aliasing makes it in-place."""
    H = 1 << len(window)
    S = window[-1]
    assert all(a == 2 * b for a, b in zip(window, window[1:])), window
    Q = n_blocks // (H * S)
    block = rows * cols
    has_vals = vals2d is not None

    def view(a):
        return a.reshape(Q, H, S, rows, cols)

    spec = pl.BlockSpec((1, H, 1, rows, cols), lambda q, r: (q, 0, r, 0, 0))
    ins = [view(keys2d)] + ([view(vals2d)] if has_vals else [])
    outs = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in ins]
    res = C.pallas_call(
        functools.partial(_hyper_body, k, H, S, tail, tie_break, has_vals,
                          block),
        name=f"{role}_cross",
        grid=(Q, S),
        in_specs=[spec] * len(ins),
        out_specs=[spec] * len(ins) if has_vals else spec,
        out_shape=outs if has_vals else outs[0],
        input_output_aliases={i: i for i in range(len(ins))},
        interpret=C.interpret_mode(),
    )(*ins)
    if has_vals:
        k5, v5 = res
        return k5.reshape(keys2d.shape), v5.reshape(vals2d.shape)
    return res.reshape(keys2d.shape), None


def _prepare(keys, vals, pad_key, block, cols):
    n = keys.shape[0]
    total = max(C.next_pow2(n), block)
    keys_p = C.pad_to(keys, total, pad_key)
    view_k = keys_p.reshape(-1, cols)
    view_v = None
    if vals is not None:
        pad_v = C.type_max(vals.dtype)
        view_v = C.pad_to(vals, total, pad_v).reshape(-1, cols)
    return view_k, view_v, total


def bitonic_sort(keys: jax.Array, *, descending: bool = False) -> jax.Array:
    """Full sort of a 1-D array via the blocked bitonic network."""
    n = keys.shape[0]
    if n == 0:
        return keys
    rows, cols, block = _geometry()
    pad = C.type_max(keys.dtype)
    k2d, _, total = _prepare(keys, None, pad, block, cols)
    k2d, _ = _sort_network(k2d, None, total, tie_break=False,
                           rows=rows, cols=cols)
    out = k2d.reshape(-1)[:n]
    return out[::-1] if descending else out


def bitonic_sort_kv(
    keys: jax.Array, vals: jax.Array, *, tie_break: bool = False
) -> tuple[jax.Array, jax.Array]:
    """Sort (keys, vals) pairs by key. ``tie_break=True`` orders equal keys
    by ascending value (making index payloads reproduce a stable argsort)."""
    n = keys.shape[0]
    if n == 0:
        return keys, vals
    rows, cols, block = _geometry()
    pad = C.type_max(keys.dtype)
    k2d, v2d, total = _prepare(keys, vals, pad, block, cols)
    k2d, v2d = _sort_network(k2d, v2d, total, tie_break=tie_break,
                             rows=rows, cols=cols)
    return k2d.reshape(-1)[:n], v2d.reshape(-1)[:n]


def bitonic_sort_batched(
    keys: jax.Array, *, descending: bool = False
) -> jax.Array:
    """Sort along the last axis of (..., n): the 1-D network vmapped over
    the flattened leading axes (the batching rule turns the vmap into an
    extra grid dimension — one launch set for the whole batch, no per-row
    1-D round-trips)."""
    if keys.ndim <= 1:
        return bitonic_sort(keys, descending=descending)
    lead = keys.shape[:-1]
    flat = keys.reshape(-1, keys.shape[-1])
    out = jax.vmap(
        functools.partial(bitonic_sort, descending=descending)
    )(flat)
    return out.reshape(*lead, keys.shape[-1])


def bitonic_argsort_batched(keys: jax.Array) -> jax.Array:
    """Stable argsort along the last axis of (..., n) — the kv network with
    an iota payload and index tie-break, vmapped over leading axes."""
    n = keys.shape[-1]

    def one(row):
        idx = jnp.arange(n, dtype=jnp.int32)
        _, perm = bitonic_sort_kv(row, idx, tie_break=True)
        return perm

    if keys.ndim <= 1:
        return one(keys)
    lead = keys.shape[:-1]
    out = jax.vmap(one)(keys.reshape(-1, n))
    return out.reshape(*lead, n)


def bitonic_topk_batched(
    keys: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Descending top-k (values, indices) along the last axis, with
    ``lax.top_k``'s (value desc, index asc) tie order.

    No key negation (which would wrap INT_MIN): sort ascending with a
    REVERSED-iota payload (n-1-i) and index tie-break, then read the run
    backwards — (key asc, n-1-i asc) reversed is (key desc, i asc).
    """
    n = keys.shape[-1]

    def one(row):
        rev = jnp.arange(n - 1, -1, -1, dtype=jnp.int32)
        _, pay = bitonic_sort_kv(row, rev, tie_break=True)
        return (n - 1) - pay[::-1][:k]

    if keys.ndim <= 1:
        order = one(keys)
    else:
        order = jax.vmap(one)(keys.reshape(-1, n)).reshape(
            *keys.shape[:-1], k
        )
    return jnp.take_along_axis(keys, order, axis=-1), order


def _sort_network(k2d, v2d, total, tie_break, *, rows, cols, first_k=2):
    """Run bitonic phases ``k = first_k, 2·first_k, …, total`` over the 2-D
    block view. ``first_k=2`` is the full sort. ``first_k=2·L`` resumes the
    network on data that is already L-run alternating-sorted — this is the
    k-way merge tail used by ``kernels/merge_kernel.py``: only the merge
    phases run, the log²-depth build phases below ``first_k`` are skipped.
    Its kernels are named for that role: ``bitonic_inblock`` and
    ``bitonic_cross`` in a sort, ``merge_inblock`` and ``merge_cross`` in
    a merge."""
    role = "bitonic" if first_k == 2 else "merge"
    block = rows * cols
    n_blocks = total // block
    hyper = _hyper_order()
    # Phase 1: every stage with k <= block is in-block for all blocks
    # (the block base b*block contributes nothing to (i & k)).
    stages = []
    k = first_k
    while k <= min(total, block):
        stages.extend(_stages_upto_block(k, block))
        k *= 2
    if stages:
        k2d, v2d = _run_inblock(stages, k2d, v2d, tie_break, n_blocks,
                                rows, cols, role)
    # (when first_k > block the loop above never ran and k == first_k: the
    # cross loop starts directly at the first merge phase)
    # Phase 2: k > block — cross stages at block distances k/(2·block) … 1,
    # then the in-block finish. Fused: windows of up to ``hyper`` stages per
    # launch, the last window absorbing the finish. hyper == 0 keeps the
    # one-launch-per-stage + separate-finish layout (counted baseline).
    while k <= total:
        dists = []
        d = k // (2 * block)
        while d >= 1:
            dists.append(d)
            d //= 2
        if hyper <= 0:
            for d in dists:
                k2d, v2d = _run_hyper(k, [d], [], k2d, v2d, tie_break,
                                      n_blocks, rows, cols, role)
            k2d, v2d = _run_inblock(_stages_upto_block(k, block), k2d,
                                    v2d, tie_break, n_blocks, rows, cols,
                                    role)
        else:
            idx = 0
            while idx < len(dists):
                w = min(hyper, len(dists) - idx)
                window = dists[idx:idx + w]
                idx += w
                # for k > block, _stages_upto_block is exactly the
                # j = block/2 .. 1 finishing ladder
                tail = (_stages_upto_block(k, block)
                        if idx == len(dists) else [])
                k2d, v2d = _run_hyper(k, window, tail, k2d, v2d, tie_break,
                                      n_blocks, rows, cols, role)
        k *= 2
    return k2d, v2d


def network_launches(total: int, *, first_k: int = 2, hyper: int,
                     block: int) -> int:
    """Closed-form launch count of ``_sort_network(total, first_k=…)``:
    one in-block launch if any phase fits a block, then per cross phase
    ``⌈i/m⌉`` fused launches (``i+1`` unfused) for ``i = log₂(k/block)``."""
    launches = 0
    k = first_k
    if k <= min(total, block):
        launches += 1
        while k <= min(total, block):
            k *= 2
    while k <= total:
        i = (k // block).bit_length() - 1  # cross stages this phase
        if hyper <= 0:
            launches += i + 1
        else:
            launches += -(-i // hyper)
        k *= 2
    return launches


def cross_launches(n: int, *, hyper: int | None = None,
                   block: int | None = None) -> int:
    """Closed-form launch count of the network for an n-element sort —
    kept next to the network so the benchmark's *counted* numbers can be
    cross-checked against the model (and the DESIGN.md formula)."""
    if block is None:
        _, _, block = _geometry()
    if hyper is None:
        hyper = _hyper_order()
    total = max(C.next_pow2(n), block)
    return network_launches(total, first_k=2, hyper=hyper, block=block)
