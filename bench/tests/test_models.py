"""The serving harness's family seam against goldens made before the
InternLM2 code moved into ``bench/models/``: at the module's ``TINY``
size the seeded weights, the float32 reference's and the fp8 control's
logits of one sequence, the program's ``ModelConfig`` and the work counts
are bitwise what they were. Any change to them moves what the benchmark
measures or compares.

``readings`` uses only names the harness had before the move, so the
goldens were made by running it on that checkout."""
from __future__ import annotations

import hashlib
import json

import numpy as np
import pytest

from bench import common, reference, serve, weights, work

GOLDEN = common.BENCH / "tests" / "golden_internlm2.json"
CONFIG = "internlm2_1_8b"
SEED = 1234567891
#: (prompt, tokens) pairs whose work is pinned, at TINY and full size.
REQUESTS = [(1, 1), (5, 3), (64, 64), (512, 128), (2048, 64)]


def _sha(a) -> str:
    a = np.asarray(a)
    digest = hashlib.sha256(a.tobytes()).hexdigest()
    return f"{a.dtype}{list(a.shape)}:{digest}"


def _work(dims: dict) -> dict:
    return {
        "per_token": work.dense_matmul_flops_per_token(dims),
        "attention_100": work.attention_flops(dims, 100),
        "prefill": {str(p): work.prefill_flops(dims, p)
                    for p, _ in REQUESTS},
        "request": {f"{p},{t}": work.request_flops(dims, p, t)
                    for p, t in REQUESTS},
    }


def readings(tiny: dict) -> dict:
    """Checksums and counts of the configuration shrunk by ``tiny``."""
    import jax
    import jax.numpy as jnp

    full = common.config_file(common.spec(), CONFIG)
    cfg = dict(full, **tiny)
    dims = serve.model_dims(cfg)
    params = weights.make(dims, SEED, jax.devices()[0])
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    seq = jnp.asarray(np.random.default_rng(SEED).integers(
        0, dims["vocab"], 24, dtype=np.int32))
    return {
        "tiny": dict(tiny),
        "weights": {"/".join(k.key for k in path): _sha(leaf)
                    for path, leaf in leaves},
        "logits": _sha(reference.logits(dims, params, seq)),
        "logits_fp8": _sha(reference.logits(dims, params, seq, fp8=True)),
        "program_config": [repr(serve.program_config(cfg)),
                           repr(serve.program_config(full))],
        "work": [_work(dims), _work(serve.model_dims(full))],
    }


@pytest.fixture(scope="module")
def got():
    return readings(common.model_module("InternLM2ForCausalLM").TINY)


@pytest.mark.parametrize("part", ["tiny", "weights", "logits", "logits_fp8",
                                  "program_config", "work"])
def test_matches_golden(got, part):
    assert got[part] == json.loads(GOLDEN.read_text())[part]
