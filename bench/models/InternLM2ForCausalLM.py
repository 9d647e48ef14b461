"""InternLM2ForCausalLM: a dense decoder (RMSNorm, half-split RoPE,
grouped-query causal attention, SwiGLU MLP, untied head).

Everything the serving harness knows of this family, found by
``bench.common.model_module`` under the name a configuration file gives
in ``architectures[0]``:

* ``dims``: the sizes, as hashable scalars;
* ``program_config``: the program's ``ModelConfig``;
* ``weight_shapes``: the flat weight tree, path -> (shape, kind) with
  kind ``embed``, ``norm`` or ``matrix``, in the layout the serving
  engine reads (stacked layers under ``layers``);
* ``forward``: the plain float32 reference at ``highest`` precision, one
  layer at a time over one whole sequence, or with ``fp8`` the control;
* ``flops_per_token``, ``attention_flops``, ``prefill_attention_flops``:
  the work, from shapes;
* ``TINY``: the configuration keys that shrink it to CPU size for tests.
"""
from __future__ import annotations

from bench.reference import linear, rmsnorm, rope

#: CPU-size overrides of the configuration file's keys.
TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=128,
            vocab_size=250, padded_vocab=2048)


def dims(cfg_file: dict) -> dict:
    """The sizes the weights, reference and work functions read, from a
    configuration file of published keys."""
    d, H = cfg_file["hidden_size"], cfg_file["num_attention_heads"]
    return {
        "layers": cfg_file["num_hidden_layers"],
        "d_model": d,
        "heads": H,
        "kv_heads": cfg_file["num_key_value_heads"],
        "head_dim": cfg_file.get("head_dim", d // H),
        "d_ff": cfg_file["intermediate_size"],
        "vocab": cfg_file["vocab_size"],
        "padded_vocab": cfg_file["padded_vocab"],
        "rope_theta": float(cfg_file["rope_theta"]),
        "norm_eps": float(cfg_file["rms_norm_eps"]),
        "dtype": cfg_file["torch_dtype"],
    }


def program_config(cfg_file: dict, m: dict):
    """The program's ModelConfig for the same model."""
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=cfg_file["name"], family="dense", n_layers=m["layers"],
        d_model=m["d_model"], n_heads=m["heads"], n_kv_heads=m["kv_heads"],
        d_ff=m["d_ff"], vocab=m["vocab"], head_dim=m["head_dim"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"],
        dtype=jnp.dtype(m["dtype"]),
    )


def weight_shapes(m: dict) -> dict:
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


def forward(m: dict, fp8: bool):
    """(params, tokens (S,)) -> (S, padded vocab) f32 logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    H, KV, hd, eps = m["heads"], m["kv_heads"], m["head_dim"], m["norm_eps"]
    G = H // KV

    def layer(x, p):
        S = x.shape[0]
        h = rmsnorm(x, p["ln1"]["scale"], eps)
        q = linear(h, p["attn"]["wq"], fp8).reshape(S, H, hd)
        k = linear(h, p["attn"]["wk"], fp8).reshape(S, KV, hd)
        v = linear(h, p["attn"]["wv"], fp8).reshape(S, KV, hd)
        q, k = rope(q, m["rope_theta"]), rope(k, m["rope_theta"])
        k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k,
                       precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", a, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + linear(o.reshape(S, H * hd), p["attn"]["wo"], fp8)
        h = rmsnorm(x, p["ln2"]["scale"], eps)
        gate = jax.nn.silu(linear(h, p["mlp"]["w_gate"], fp8))
        up = linear(h, p["mlp"]["w_up"], fp8)
        return x + linear(gate * up, p["mlp"]["w_down"], fp8), None

    def run(params, tokens):
        x = params["embed"]["embed"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = rmsnorm(x, params["final_norm"]["scale"], eps)
        return linear(x, params["head"]["unembed"], fp8)

    return run


def flops_per_token(m: dict) -> int:
    """2 x the weights a token passes through: every layer's projections
    and MLP, and the head over the true vocabulary (the embedding is a
    lookup)."""
    d, H, KV, hd, ff = (m["d_model"], m["heads"], m["kv_heads"],
                        m["head_dim"], m["d_ff"])
    per_layer = d * (H + 2 * KV) * hd + H * hd * d + 3 * d * ff
    return 2 * (m["layers"] * per_layer + d * m["vocab"])


def attention_flops(m: dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys, in
    every layer: 2 x 2 x heads x head_dim x context."""
    return m["layers"] * 4 * m["heads"] * m["head_dim"] * context


def prefill_attention_flops(m: dict, length: int) -> int:
    """``attention_flops`` of a causal pass over ``length`` positions,
    position i attending over i + 1 keys, in closed form."""
    return attention_flops(m, length * (length + 1) // 2)
