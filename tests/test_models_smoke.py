"""Per-arch smoke tests (reduced same-family configs, 1 CPU device).

For each of the 10 assigned architectures: one forward + one train step,
asserting output shapes and no NaNs — plus the serve-path consistency
invariant: token-by-token decode reproduces the teacher-forced forward
logits (within f32 tolerance), which exercises KV caches, SSM states and
cross-attention caches end to end.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, load_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.launch.train import jitted_train_step, init_sharded
from repro.models import model as M


def _extras(cfg, B, rng):
    out = {}
    if cfg.family == "encdec":
        out["frames"] = jax.random.normal(
            rng, (B, cfg.enc_seq, cfg.d_model), cfg.dtype)
    if cfg.family == "vlm":
        out["patches"] = jax.random.normal(
            rng, (B, cfg.vision_seq, cfg.d_model), cfg.dtype)
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = load_smoke_config(arch)
    rng = jax.random.PRNGKey(0)
    p = M.init_params(rng, cfg)
    B, S = 2, 16
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab)
    logits, aux = M.forward(p, cfg, tokens, use_ep=False,
                            **_extras(cfg, B, rng))
    assert logits.shape == (B, S, cfg.padded_vocab(16))
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_runs_and_finite(arch):
    cfg = load_smoke_config(arch)
    mesh = make_host_mesh()
    params, opt = init_sharded(cfg, mesh)
    step = jitted_train_step(cfg, mesh, use_ep=False, lr=1e-3)
    B, S = 2, 16
    rng = jax.random.PRNGKey(0)
    batch = {
        "tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab),
        **_extras(cfg, B, rng),
    }
    params, opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["gnorm"]))
    assert int(opt.step) == 1
    assert all(
        np.isfinite(np.asarray(x, np.float32)).all()
        for x in jax.tree.leaves(params)
    )


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch):
    """prefill+decode token-by-token == teacher-forced forward (f32)."""
    cfg = dataclasses.replace(load_smoke_config(arch), dtype=jnp.float32)
    rng = jax.random.PRNGKey(0)
    p = M.init_params(rng, cfg)
    B, S = 2, 12
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab)
    ex = _extras(cfg, B, rng)
    want, _ = M.forward(p, cfg, tokens, use_ep=False, **ex)

    cache_len = 16
    prefix = 4
    logits_p, caches, pos = M.prefill(
        p, cfg, tokens[:, :prefix], cache_len=cache_len, **ex
    )
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(want[:, :prefix]),
        rtol=2e-3, atol=2e-3,
    )
    for t in range(prefix, S):
        logits_t, caches = M.decode_step(
            p, cfg, tokens[:, t : t + 1], caches, jnp.int32(t)
        )
        np.testing.assert_allclose(
            np.asarray(logits_t[:, 0]), np.asarray(want[:, t]),
            rtol=2e-3, atol=2e-3, err_msg=f"{arch} step {t}",
        )


def test_cache_specs_match_zero_caches():
    for arch in ARCH_IDS:
        cfg = load_smoke_config(arch)
        specs = M.cache_specs(cfg, batch=2, cache_len=8)
        zeros = M.zero_caches(cfg, batch=2, cache_len=8)
        s_flat, s_def = jax.tree.flatten(specs)
        z_flat, z_def = jax.tree.flatten(zeros)
        assert s_def == z_def, arch
        for s, z in zip(s_flat, z_flat):
            assert s.shape == z.shape and s.dtype == z.dtype, arch


def _random_caches(specs, seed):
    """Caches of ``specs``' shapes filled with normal noise, so that every
    byte a step leaves alone can be told from one it writes."""
    leaves, tree = jax.tree.flatten(specs)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
        for s, k in zip(leaves, keys)])


#: case -> (arch, page size or None for the contiguous cache)
WRITE_CASES = {
    "dense": ("internlm2_1_8b", None),
    "dense_paged": ("internlm2_1_8b", 4),
    "moe_first_layer_dense": ("deepseek_moe_16b", None),
}


@pytest.mark.parametrize("case", sorted(WRITE_CASES))
def test_decode_step_writes_only_its_columns(case):
    """One decode step with per-slot positions changes each cache leaf at
    [layer, row, pos[row]] for every layer and live row (at the (page,
    offset) the block table names, when paged) and leaves every other
    byte as it was: the parked row, past the cache, is untouched."""
    arch, page_size = WRITE_CASES[case]
    cfg = load_smoke_config(arch)
    assert cfg.family == "dense" or cfg.first_layer_dense
    p = M.init_params(jax.random.PRNGKey(0), cfg)
    B, cache_len = 4, 16
    pos = np.array([3, 9, cache_len + 2, 15], np.int32)    # row 2 parked
    live = [b for b in range(B) if pos[b] < cache_len]
    kw = {}
    if page_size is None:
        specs = M.cache_specs(cfg, batch=B, cache_len=cache_len)
        where = [(b, pos[b]) for b in live]
    else:
        T = cache_len // page_size
        num_pages = B * T + 3
        table = np.random.RandomState(1).permutation(num_pages)[: B * T]
        table = table.reshape(B, T).astype(np.int32)
        kw = dict(block_tables=jnp.asarray(table), page_size=page_size)
        specs = M.paged_cache_specs(cfg, num_pages=num_pages,
                                    page_size=page_size)
        where = [(table[b, pos[b] // page_size], pos[b] % page_size)
                 for b in live]
    caches = _random_caches(specs, 2)
    before = [np.asarray(a) for a in jax.tree.leaves(caches)]
    tokens = jnp.arange(B, dtype=jnp.int32)[:, None] + 1
    logits, new = M.decode_step(p, cfg, tokens, caches, jnp.asarray(pos),
                                **kw)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    after = [np.asarray(a) for a in jax.tree.leaves(new)]
    for old, got in zip(before, after):
        assert got.shape == old.shape and got.dtype == old.dtype
        written = np.zeros(old.shape[:3], bool)
        for row, col in where:
            written[:, row, col] = True
        same = (got == old).reshape(*old.shape[:3], -1).all(-1)
        np.testing.assert_array_equal(same, ~written, err_msg=case)


@pytest.mark.parametrize("arch", ["deepseek_moe_16b", "granite_moe_1b"])
def test_slot_prefill_row_equals_fresh_prefill(arch):
    """A slot prefill writes the prompt's layers straight into the shared
    cache: the refilled row, every other row and the logits are bitwise
    what a batch-1 prefill into zeroed caches, copied into the row, gives
    (the MoE layer stacks, with and without a dense first layer;
    tests/test_engine.py checks the dense one)."""
    cfg = load_smoke_config(arch)
    p = M.init_params(jax.random.PRNGKey(0), cfg)
    B, S, cache_len, slot = 3, 6, 16, 2
    caches = _random_caches(
        M.cache_specs(cfg, batch=B, cache_len=cache_len), 4)
    prompt = jax.random.randint(jax.random.PRNGKey(5), (1, S), 0, cfg.vocab)
    logits, new = M.slot_prefill(p, cfg, prompt, caches, slot,
                                 cache_len=cache_len)
    want_logits, fresh, _ = M.prefill(p, cfg, prompt, cache_len=cache_len)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    for old, got, row in zip(jax.tree.leaves(caches), jax.tree.leaves(new),
                             jax.tree.leaves(fresh)):
        want = np.asarray(old).copy()
        want[:, slot] = np.asarray(row)[:, 0]
        np.testing.assert_array_equal(np.asarray(got), want)
