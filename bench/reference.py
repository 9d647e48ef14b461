"""Plain references the benchmark compares the timed path against.

* ``logits``: a dense decoder (InternLM2 as run: RMSNorm, half-split RoPE,
  grouped-query causal attention, SwiGLU, untied head) in float32 at
  ``highest`` matmul precision, one layer at a time over one whole
  sequence: no cache, no kernels, no batching. With ``fp8=True`` every
  linear layer's operands are rounded to float8 e4m3 (per-tensor scale for
  weights, per-row for activations): the control, one precision below the
  bfloat16 the model is served in.
* ``served_gaps``: reads served tokens against reference logits. The
  engine samples ``argmax(logits / T + g)`` over its top-k / top-p set,
  with Gumbel noise ``g`` from the request's key
  ``fold_in(fold_in(PRNGKey(seed), rid), index)``; the same noise makes a
  sampled token as checkable as a greedy one.
* ``sort_*``: XLA's own sort as the oracle of the sort cells.

Nothing here imports the program.
"""
from __future__ import annotations

import functools

import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _q8(x, axis):
    import jax.numpy as jnp

    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x, w, fp8):
    """x (S, i) f32 @ w (i, o) at the highest precision; with ``fp8``
    both operands are rounded to e4m3 first."""
    import jax
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, None)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    import jax

    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, heads, hd), half-split rotation at positions 0..S-1."""
    import jax.numpy as jnp

    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


@functools.lru_cache(maxsize=None)
def _forward(dims: tuple, fp8: bool):
    import jax
    import jax.numpy as jnp

    m = dict(dims)
    H, KV, hd, eps = m["heads"], m["kv_heads"], m["head_dim"], m["norm_eps"]
    G = H // KV

    def layer(x, p):
        S = x.shape[0]
        h = _rmsnorm(x, p["ln1"]["scale"], eps)
        q = _linear(h, p["attn"]["wq"], fp8).reshape(S, H, hd)
        k = _linear(h, p["attn"]["wk"], fp8).reshape(S, KV, hd)
        v = _linear(h, p["attn"]["wv"], fp8).reshape(S, KV, hd)
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
        k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k,
                       precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", a, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + _linear(o.reshape(S, H * hd), p["attn"]["wo"], fp8)
        h = _rmsnorm(x, p["ln2"]["scale"], eps)
        gate = jax.nn.silu(_linear(h, p["mlp"]["w_gate"], fp8))
        up = _linear(h, p["mlp"]["w_up"], fp8)
        return x + _linear(gate * up, p["mlp"]["w_down"], fp8), None

    def forward(params, tokens):
        x = params["embed"]["embed"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _rmsnorm(x, params["final_norm"]["scale"], eps)
        return _linear(x, params["head"]["unembed"], fp8)

    return jax.jit(forward)


def logits(dims: dict, params, tokens, *, fp8: bool = False):
    """(S, padded vocab) f32 logits of one sequence ``tokens`` (S,)."""
    return _forward(tuple(sorted(dims.items())), fp8)(params, tokens)


# --------------------------------------------------------------------------
# reading served tokens
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _reader(vocab: int, top_k: int, top_p: float, temperature: float,
            margin: float):
    import jax
    import jax.numpy as jnp

    def cut(lt):
        """Logit of the last token the top-k / top-p set keeps."""
        kth = jax.lax.top_k(lt, top_k)[0][-1]
        lk = jnp.where(lt >= kth, lt, -jnp.inf)
        s = jnp.sort(lk)[::-1]
        cum = jnp.cumsum(jax.nn.softmax(s))
        c = jnp.minimum(jnp.sum(cum < top_p), top_k - 1)
        return s[c]

    def one(lg, tok, key, own_choice):
        """Gap and out-of-set flag of the served token ``tok`` or, with
        ``own_choice``, of the token these logits would sample."""
        width = lg.shape[0]
        lt = jnp.where(jnp.arange(width) < vocab, lg / temperature,
                       -jnp.inf)
        g = jax.random.gumbel(key, (width,), jnp.float32)
        thr = cut(lt)
        z = lt + g
        own = jnp.argmax(jnp.where(lt >= thr, z, -jnp.inf))
        tok = jnp.where(own_choice, own, tok)
        gap = jnp.max(jnp.where(lt >= thr + margin, z, -jnp.inf)) - z[tok]
        return jnp.maximum(gap, 0.0), lt[tok] < thr - margin, tok

    def read(lg_all, start, toks, n, seed, rid, own_lg_all):
        """Rows ``start .. start + n - 1`` of ``lg_all`` sampled ``toks``
        (padded to a fixed width, ``n`` of them real)."""
        width = toks.shape[0]
        idx = jnp.arange(width, dtype=jnp.int32)
        rows = jnp.clip(start + idx, 0, lg_all.shape[0] - 1)
        base = jax.random.fold_in(jax.random.PRNGKey(seed), rid)
        keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(idx)
        valid = idx < n
        if own_lg_all is not None:
            _, _, toks = jax.vmap(one, (0, 0, 0, None))(
                own_lg_all[rows], toks, keys, True)
        gap, out, _ = jax.vmap(one, (0, 0, 0, None))(
            lg_all[rows], toks, keys, False)
        return jnp.where(valid, gap, 0.0), valid & out

    return jax.jit(read)


def served_gaps(lg_all, start, toks, *, width, seed, rid, vocab, top_k,
                top_p, temperature, margin, own_lg_all=None):
    """Read the ``len(toks)`` served tokens of one request against the
    reference logits ``lg_all`` (S, V) of its prompt and tokens; token i
    was sampled at row ``start + i``. Returns, per token, ``gap``: how far
    (in logit units, noise included) it lies below the best token of the
    reference's sure set (the tokens at least ``margin`` above the cut of
    the top-k / top-p set), and ``outside``: whether it lies more than
    ``margin`` below that cut. With ``own_lg_all`` (the control) the tokens
    read are those ``own_lg_all`` would sample, not ``toks``. ``width``
    pads the token axis so one program serves every request."""
    import jax.numpy as jnp

    read = _reader(int(vocab), int(top_k), float(top_p), float(temperature),
                   float(margin))
    n = len(toks)
    padded = np.zeros((width,), np.int32)
    padded[:n] = toks
    gap, out = read(lg_all, jnp.int32(start), jnp.asarray(padded),
                    jnp.int32(n), jnp.int32(seed), jnp.int32(rid),
                    own_lg_all)
    return np.asarray(gap)[:n], np.asarray(out)[:n]


# --------------------------------------------------------------------------
# sort oracle
# --------------------------------------------------------------------------

def sorted_keys(keys):
    """XLA's sort of f32 ``keys``, keys alone."""
    import jax

    return jax.lax.sort(keys)


def bf16_sort(keys):
    """The control: XLA's sort of f32 ``keys`` in the order of the keys
    rounded to bfloat16, ties kept in index order, with the original index
    as payload."""
    import jax
    import jax.numpy as jnp

    iota = jnp.arange(keys.shape[0], dtype=jnp.int32)
    _, k, v = jax.lax.sort((keys.astype(jnp.bfloat16), keys, iota),
                           num_keys=1, is_stable=True)
    return k, v
