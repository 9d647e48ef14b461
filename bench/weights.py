"""Seeded weights of a served model, made on the device in one program.

The tree is the family module's (``weight_shapes`` in
``bench/models/<arch>.py``), nested at its ``/``s into the layout the
serving engine reads. The benchmark, not the program, makes the weights,
so the plain reference can make the same ones again from the same seed
without taking anything from the program.

Matrices are N(0, 1/fan_in), the embedding N(0, 1) and norm scales 1, so
the logits have a standard deviation near 1: the top-50 / top-0.9 cut of
the sampler then keeps a few tens of tokens, as it does for a trained
model's flatter steps. Leaves are drawn in the sorted order of their
paths, one key each from the seed.
"""
from __future__ import annotations

import functools

from bench import common


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
    table = common.model_module(m["arch"]).weight_shapes(m)
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
