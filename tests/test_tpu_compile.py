"""Ahead-of-time compiles for a TPU v5e chip, without the chip.

The TPU compiler is installed next to JAX, so a program can be compiled for
a *described* ``v5e:2x2`` topology: what Mosaic or XLA would refuse on the
chip (layouts interpret mode accepts, scalars stored to vector memory,
primitives without a TPU lowering, programs that do not fit HBM) fails
here, at no chip time. Nothing runs. The topology is described inside a
fixture — never at import — so every pytest worker collects the same tests
and only the worker that runs this file loads the TPU library.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import common as C
from repro.kernels import hist_kernel, map_kernel, merge_kernel
from repro.kernels import nucleus_kernel, page_kernel, reduce_kernel
from repro.kernels import scan_kernel, search_kernel, segment_kernel
from repro.kernels import sort_kernel

N = 2 ** 20
VOCAB_ROWS = (8, 94208)     # internlm2_1_8b decode batch x padded vocab
HBM_BYTES = 16 * 2 ** 30    # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described device is written but can never be read back
    without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    with C.tuning_scope(interpret=False):
        return jax.jit(fn).lower(*args).compile()


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


#: name -> (kernel call, argument shapes given the chip's sharding)
KERNELS = {
    "sort": (sort_kernel.bitonic_sort, lambda s: [_f32((N,), s)]),
    "sort_kv": (
        lambda k, v: sort_kernel.bitonic_sort_kv(k, v, tie_break=True),
        lambda s: [_f32((N,), s), _i32((N,), s)]),
    "kway_merge": (
        lambda x, c: merge_kernel.kway_merge(x, 4, counts=c),
        lambda s: [_f32((N,), s), _i32((4,), s)]),
    "topk": (lambda x: sort_kernel.bitonic_topk_batched(x, 50),
             lambda s: [_f32(VOCAB_ROWS, s)]),
    "nucleus": (lambda x: nucleus_kernel.nucleus_mask_blocks(x, top_p=0.9),
                lambda s: [_f32(VOCAB_ROWS, s)]),
    "reduce": (
        lambda x: reduce_kernel.reduce_blocks(
            jnp.square, jnp.add, x, unit=0.0),
        lambda s: [_f32((N,), s)]),
    "histogram": (
        lambda x: hist_kernel.minmax_histogram_blocks(x, 1024, -4.0, 4.0),
        lambda s: [_f32((N,), s)]),
    "scan": (lambda x: scan_kernel.scan_blocks(jnp.add, x, unit=0),
             lambda s: [_i32((N,), s)]),
    "segmented_scan": (
        lambda v, o: segment_kernel.segmented_scan_blocks(
            jnp.add, v, o, unit=0.0),
        lambda s: [_f32((N,), s), _i32((65,), s)]),
    "searchsorted": (
        lambda h, q: search_kernel.searchsorted_blocks(h, q, side="right"),
        lambda s: [_f32((N,), s), _f32((4096,), s)]),
    "map": (lambda x: map_kernel.map_blocks(lambda a: a * 2.0 + 1.0, x),
            lambda s: [_f32((N,), s)]),
    "page_gather": (
        page_kernel.page_gather_blocks,
        lambda s: [jax.ShapeDtypeStruct((512, 16, 8, 128), jnp.bfloat16,
                                        sharding=s),
                   _i32((8, 32), s)]),
}


#: name -> the roles its Pallas kernels are named for in the compiled
#: program (the operation names a profile shows)
ROLES = {
    "sort": ("bitonic_inblock", "bitonic_cross"),
    "sort_kv": ("bitonic_inblock", "bitonic_cross"),
    "kway_merge": ("merge_cross",),
    "topk": ("bitonic_inblock", "bitonic_cross"),
    "nucleus": ("nucleus_cut",),
    "reduce": ("reduce",),
    "histogram": ("histogram",),
    "scan": ("scan",),
    "segmented_scan": ("segmented_scan",),
    "searchsorted": ("search",),
    "map": ("map",),
    "page_gather": ("page_gather",),
}


def _kernel_names(text: str) -> set:
    """Names of the Pallas kernels' operations in a compiled program."""
    return {m.group(1) for m in re.finditer(
        r"^\s*%([\w.\-]+) = .*custom_call_target=\"tpu_custom_call\"",
        text, re.M)}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, *shapes(one_chip))
    text = compiled.as_text()
    assert "tpu_custom_call" in text, name
    names = _kernel_names(text)
    for role in ROLES[name]:
        assert any(role in n for n in names), (role, sorted(names))


def _internlm2_on_chip(one_chip):
    """internlm2_1_8b's config, its parameters' shapes on the described
    chip, and the function that puts a shape there."""
    from repro.configs import load_config
    from repro.models import model as M

    cfg = load_config("internlm2_1_8b")
    on_chip = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,  # noqa: E731
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda k: M.init_params(k, cfg), jax.random.PRNGKey(0)))
    return cfg, params, on_chip


def test_internlm2_decode_step_fits_v5e(one_chip):
    """The engine's decode step at published widths (24 layers, d=2048,
    16/8 heads, vocab padded to 94208), 8 slots x 4096 cache, compiles
    for one chip and fits its HBM."""
    from repro.launch import engine
    from repro.models import model as M

    cfg, params, on_chip = _internlm2_on_chip(one_chip)
    caches = jax.tree.map(on_chip, M.cache_specs(cfg, batch=8,
                                                 cache_len=4096))
    compiled = engine._decode_jit.lower(
        params, _i32((8, 1), one_chip), caches, _i32((8,), one_chip),
        cfg=cfg,
    ).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2 ** 30:.2f} GiB"


#: case -> (slots, cache_len, page_size or None for the contiguous cache):
#: the serving cells' shapes, and the decode cell's bytes as a page pool
IN_PLACE = {
    "decode_backlog": (32, 1024, None),
    "prefill_backlog": (16, 2112, None),
    "paged": (32, 1024, 16),
}


@pytest.mark.parametrize("case", sorted(IN_PLACE))
def test_decode_step_updates_cache_in_place(one_chip, case):
    """The engine's decode step writes the donated cache where it lies: no
    ``copy`` in the compiled program has a cache leaf's shape, and its
    scratch memory is smaller than one leaf (a scan that rebuilt the cache
    as its outputs would hold a second whole cache and copy it back)."""
    from repro.launch import engine
    from repro.models import model as M

    slots, cache_len, page_size = IN_PLACE[case]
    cfg, params, on_chip = _internlm2_on_chip(one_chip)
    tok, pos = _i32((slots, 1), one_chip), _i32((slots,), one_chip)
    with C.tuning_scope(interpret=False):
        if page_size is None:
            caches = jax.tree.map(on_chip, M.cache_specs(
                cfg, batch=slots, cache_len=cache_len))
            lowered = engine._decode_jit.lower(params, tok, caches, pos,
                                               cfg=cfg)
        else:
            table_len = cache_len // page_size
            caches = jax.tree.map(on_chip, M.paged_cache_specs(
                cfg, num_pages=slots * table_len, page_size=page_size))
            lowered = engine._decode_paged_jit.lower(
                params, tok, caches, pos,
                _i32((slots, table_len), one_chip), cfg=cfg,
                page_size=page_size)
        compiled = lowered.compile()
    _assert_in_place(compiled, caches)


def test_slot_prefill_updates_cache_in_place(one_chip):
    """The engine's prefill at the prefill cell's shape (16 slots x 2,112,
    a 2,048-token prompt) writes the slot's row into the donated cache:
    no whole-cache copy, and no batch-1 cache beside it."""
    from repro.launch import engine
    from repro.models import model as M

    cfg, params, on_chip = _internlm2_on_chip(one_chip)
    caches = jax.tree.map(on_chip, M.cache_specs(cfg, batch=16,
                                                 cache_len=2112))
    with C.tuning_scope(interpret=False):
        compiled = engine._prefill_jit.lower(
            params, _i32((1, 2048), one_chip), caches, _i32((), one_chip),
            cfg=cfg, cache_len=2112).compile()
    _assert_in_place(compiled, caches, scratch_leaves=1 / 16)


def _assert_in_place(compiled, caches, scratch_leaves=1.0):
    """No ``copy`` in ``compiled`` has a cache leaf's shape, and its
    scratch memory is below ``scratch_leaves`` of one leaf's bytes."""
    text = compiled.as_text()
    leaves = jax.tree.leaves(caches)
    for leaf in leaves:
        shape = "bf16[" + ",".join(map(str, leaf.shape)) + "]"
        copies = re.findall(
            r"= " + re.escape(shape) + r"\{[^}]*\} copy\(", text)
        assert not copies, (shape, len(copies))
    leaf_bytes = leaves[0].size * leaves[0].dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < scratch_leaves * leaf_bytes, (temp / 2 ** 30,
                                                leaf_bytes / 2 ** 30)

