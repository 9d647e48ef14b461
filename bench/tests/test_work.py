"""The work functions' arithmetic, against sums written out by hand."""
from __future__ import annotations

from bench import serve, work
from bench.common import config_file, spec

DIMS = serve.model_dims(config_file(spec(), "internlm2_1_8b"))


def test_internlm2_dims():
    assert (DIMS["layers"], DIMS["d_model"], DIMS["heads"], DIMS["kv_heads"],
            DIMS["head_dim"], DIMS["d_ff"], DIMS["vocab"],
            DIMS["padded_vocab"]) == (24, 2048, 16, 8, 128, 8192, 92544,
                                      94208)


def test_flops_per_token():
    # q 2048x2048, k and v 2048x1024 each, o 2048x2048, gate/up/down
    # 2048x8192 each: 62,914,560 weights a layer; head 2048 x 92,544
    per_layer = 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 8192
    assert per_layer == 62_914_560
    want = 2 * (24 * per_layer + 2048 * 92_544)
    assert work.dense_matmul_flops_per_token(DIMS) == want == 3_398_959_104


def test_attention_and_prefill():
    assert work.attention_flops(DIMS, 10) == 24 * 4 * 16 * 128 * 10
    tiny = dict(DIMS, layers=1, vocab=4)
    head = 2 * 2048 * 4
    body = work.dense_matmul_flops_per_token(tiny) - head
    # three positions attend over 1, 2 and 3 keys
    want = 3 * body + 4 * 16 * 128 * (1 + 2 + 3) + head
    assert work.prefill_flops(tiny, 3) == want


def test_request_flops():
    tiny = dict(DIMS, layers=1, vocab=4)
    tok = work.dense_matmul_flops_per_token(tiny)
    want = (work.prefill_flops(tiny, 5) + 2 * tok
            + work.attention_flops(tiny, 6) + work.attention_flops(tiny, 7))
    assert work.request_flops(tiny, 5, 3) == want
    assert work.request_flops(tiny, 5, 1) == work.prefill_flops(tiny, 5)


def test_bytes():
    assert work.sort_bytes(2 ** 26) == 2 ** 30
    assert work.sampler_bytes(64, 94208, 2) == 64 * 94208 * 2 + 64 * 4
    assert work.sampler_bytes(64, 94208, 4) == 64 * 94208 * 4 + 64 * 4
