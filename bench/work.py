"""The work a problem needs, from its shapes: operations and bytes.

Counted as the problem requires, not as the program happens to do it:
padding, recomputation and masked-out columns are waste, not work.
"""
from __future__ import annotations


def dense_matmul_flops_per_token(m: dict) -> int:
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


def prefill_flops(m: dict, length: int) -> int:
    """One causal pass over a prompt of its true ``length``; the head
    only at the last position, whose logits are sampled."""
    head = 2 * m["d_model"] * m["vocab"]
    body = dense_matmul_flops_per_token(m) - head
    attn = m["layers"] * 4 * m["heads"] * m["head_dim"] \
        * length * (length + 1) // 2
    return length * body + attn + head


def request_flops(m: dict, prompt: int, tokens: int) -> int:
    """A request of ``prompt`` tokens that was served ``tokens`` tokens:
    the prefill yields the first, each decode step one more, attending
    over everything before it and itself."""
    total = prefill_flops(m, prompt)
    per_tok = dense_matmul_flops_per_token(m)
    for i in range(1, tokens):
        total += per_tok + attention_flops(m, prompt + i)
    return total


def sort_bytes(n: int, key_bytes: int = 4, payload_bytes: int = 4) -> int:
    """A key-value sort of ``n`` pairs must read every pair once and write
    it once."""
    return 2 * n * (key_bytes + payload_bytes)


def sampler_bytes(rows: int, padded_vocab: int, logit_bytes: int) -> int:
    """A sampler call over ``rows`` logits rows must read each row of the
    padded vocabulary once, ``logit_bytes`` a logit as it is handed over,
    and write one int32 token per row."""
    return rows * padded_vocab * logit_bytes + rows * 4
