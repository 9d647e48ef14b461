"""The one generator of the benchmark's traffic: request lengths, prompt
ids and sort keys, all from a seed.

Lengths are drawn in stratified blocks: the stream of a backlog is a run
of blocks of ``BLOCK`` requests, each block the distribution's quantiles
at ``(i + 0.5) / BLOCK`` in an order the seed draws afresh. Every seed
then asks for the same lengths in every whole block, in another order, so
seeds differ by what the system does with the order, not by how much
there is to do; and a longer backlog only appends to a shorter one.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

_STD_NORMAL = NormalDist()

#: Requests to a stratified block: enough that a block reaches the
#: lognormal tails' clipped ends, few enough that a window holds several.
BLOCK = 64


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths (int) at stratified quantiles of ``dist``:
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}`` (both ends included)."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        z = np.array([_STD_NORMAL.inv_cdf(float(p)) for p in q])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif dist["dist"] == "uniform":
        x = lo + q * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def lengths(dist: dict, n: int, rng) -> np.ndarray:
    """The first ``n`` lengths of a stream of stratified blocks."""
    q = quantiles(dist, BLOCK)
    blocks = [rng.permutation(q) for _ in range(-(-n // BLOCK))]
    return np.concatenate(blocks)[:n] if blocks else q[:0]


def output_lengths(traffic: dict, n: int, seed: int) -> np.ndarray:
    """The output budgets of a backlog of ``n``, in submission order."""
    return lengths(traffic["output_len"], n, np.random.default_rng(seed))


def backlog(traffic: dict, n: int, seed: int, *, vocab: int):
    """``n`` requests as (rid, prompt ids, output budget)."""
    out = output_lengths(traffic, n, seed)
    plen = lengths(traffic["prompt_len"], n, np.random.default_rng([seed, 1]))
    return [(rid, np.random.default_rng([seed, 2, rid]).integers(
                0, vocab, size=int(plen[rid]), dtype=np.int32), int(out[rid]))
            for rid in range(n)]


def schedule(outs, slots: int, per_step: float, per_prefill: float):
    """A slot scheduler serving budgets ``outs`` in order: free slots
    refill from the queue before each step, one prefill at a time (each
    yields a request's first token), then one decode step for all slots;
    a lane is held one step past its last token, as the engine sees a
    finished lane one step late. Returns each request's admission time
    and the time the last one finishes, in seconds."""
    queue = list(outs)[::-1]
    lanes = [0] * slots
    admitted = []
    t = 0.0
    while queue or any(lanes):
        for i in range(slots):
            if lanes[i] == 0 and queue:
                lanes[i] = queue.pop()
                t += per_prefill
                admitted.append(t)
        t += per_step
        lanes = [max(x - 1, 0) for x in lanes]
    return admitted, t


#: The backlog outlasts the window by this share of it, by the scheduler
#: model, so every slot stays busy until the window closes.
OUTLAST = 1.15


def size(traffic: dict, seconds: float, seed: int, per_step: float,
         per_prefill: float) -> int:
    """The backlog (at least one request per slot) of which the scheduler
    of ``schedule`` has admitted every request by ``OUTLAST * seconds``:
    its queue holds requests until after the window closes."""
    slots = traffic["slots"]
    until = OUTLAST * seconds
    m = 2 * slots
    while True:
        admitted, _ = schedule(output_lengths(traffic, m, seed), slots,
                               per_step, per_prefill)
        n = sum(1 for t in admitted if t <= until)
        if n < m:
            return max(n, slots)
        m *= 2


def sort_keys(key, n: int, dist: str, shape_dtype, sharding):
    """``n`` keys made from a JAX key in one program, placed by
    ``sharding``."""
    import jax
    import jax.numpy as jnp

    if dist != "normal":
        raise ValueError(f"unknown key distribution {dist!r}")
    dtype = jnp.dtype(shape_dtype)
    fn = jax.jit(lambda k: jax.random.normal(k, (n,), dtype),
                 out_shardings=sharding)
    return fn(key)
