"""Seeded weights of a dense decoder, made on the device in one program.

The tree has the layout the serving engine reads (stacked layers under
``layers``; ``embed``/``final_norm``/``head`` at the top). The benchmark,
not the program, makes the weights, so the plain reference can make the
same ones again from the same seed without taking anything from the
program.

Matrices are N(0, 1/fan_in), the embedding N(0, 1) and norm scales 1, so
the logits have a standard deviation near 1: the top-50 / top-0.9 cut of
the sampler then keeps a few tens of tokens, as it does for a trained
model's flatter steps.
"""
from __future__ import annotations

import functools


def shapes(m: dict) -> dict:
    """name -> (shape, kind) for the model described by ``m`` (the keys of
    ``serve.model_dims``)."""
    L, d, ff, V = m["layers"], m["d_model"], m["d_ff"], m["padded_vocab"]
    H, KV, hd = m["heads"], m["kv_heads"], m["head_dim"]
    return {
        "embed/embed": ((V, d), "embed"),
        "final_norm/scale": ((d,), "norm"),
        "head/unembed": ((d, V), "matrix"),
        "layers/ln1/scale": ((L, d), "norm"),
        "layers/ln2/scale": ((L, d), "norm"),
        "layers/attn/wq": ((L, d, H * hd), "matrix"),
        "layers/attn/wk": ((L, d, KV * hd), "matrix"),
        "layers/attn/wv": ((L, d, KV * hd), "matrix"),
        "layers/attn/wo": ((L, H * hd, d), "matrix"),
        "layers/mlp/w_gate": ((L, d, ff), "matrix"),
        "layers/mlp/w_up": ((L, d, ff), "matrix"),
        "layers/mlp/w_down": ((L, ff, d), "matrix"),
    }


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, leaf = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[leaf] = v
    return out


@functools.lru_cache(maxsize=None)
def _maker(dims: tuple):
    import jax
    import jax.numpy as jnp

    m = dict(dims)
    table = shapes(m)
    dtype = jnp.dtype(m["dtype"])

    def make(seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), len(table))
        flat = {}
        for key, (path, (shape, kind)) in zip(keys, sorted(table.items())):
            if kind == "norm":
                flat[path] = jnp.ones(shape, jnp.float32)
                continue
            w = jax.random.normal(key, shape, dtype)
            if kind == "matrix":
                w = w * jnp.asarray(shape[-2] ** -0.5, dtype)
            flat[path] = w
        return _nest(flat)

    return jax.jit(make)


def make(dims: dict, seed: int, device):
    """The weights for ``dims`` from ``seed`` (a 31-bit int), on
    ``device``."""
    import jax

    with jax.default_device(device):
        return _maker(tuple(sorted(dims.items())))(seed)
