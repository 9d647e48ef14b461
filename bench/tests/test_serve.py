"""The serving runner end to end at a tiny size on the CPU: a sound run is
correct, and a run with its timed path broken underneath is not."""
from __future__ import annotations

import pytest

from bench import serve
from bench.tests.conftest import tiny_run

CELL = "internlm2_1_8b.decode_backlog"


def test_sound_run_is_correct():
    run = tiny_run(CELL)
    serve.run(run)
    out = run.result
    assert out["correct"] and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_tok_s", "tpot_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"


def test_traced_run_reads_the_engine_counters():
    run = tiny_run(CELL, trace=True)
    serve.run(run)
    out = run.result
    assert out["correct"]
    # no TPU plane in a CPU trace: the device readers stay silent
    assert set(out["metrics"]) == {"engine.slot_util", "engine.prefill_ms",
                                   "serve_mfu"}
    assert 0 < out["metrics"]["engine.slot_util"]["value"] <= 100
    assert out["device"]["window_s"] > 0


def _altered_token(engine):
    """A token altered where it is produced: the sampler's choice + 1."""
    sample = engine._sample

    def wrong(keys, logits):
        return (sample(keys, logits) + 1) % engine.cfg.vocab
    engine._sample = wrong


def _state_unchanged(engine):
    """A decode step that hands back its cache as it got it."""
    decode = engine._decode

    def stale(params, tok, caches, pos):
        import jax
        import jax.numpy as jnp

        logits, _ = decode(params, tok, jax.tree.map(jnp.copy, caches), pos)
        return logits, caches
    engine._decode = stale


def _half_batch(engine):
    """Half of the slots left out of the decode step: their lanes get the
    logits of the other half."""
    decode = engine._decode

    def half(params, tok, caches, pos):
        logits, new = decode(params, tok, caches, pos)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:h]), new
    engine._decode = half


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_batch])
def test_broken_path_is_not_correct(fault):
    run = tiny_run(CELL)
    # every request is read: at four slots a fault on half the lanes can
    # miss a sample of three
    run.traffic["check_requests"] = 10 ** 6
    serve.run(run, engine_hook=fault)
    assert run.result["correct"] is False, run.result["checks"]


def test_control_reads_above_the_program():
    """The fp8 reference in the program's place reads wider gaps than the
    bf16 program itself on the same tokens (all of them: at this size
    few tokens sit close enough to a tie for fp8 to flip them)."""
    run = tiny_run(CELL)
    run.traffic["check_requests"] = 10 ** 6
    w = serve.serve_window(run)
    prog = serve.readings(w, run.traffic, run.devices[0])
    ctrl = serve.readings(w, run.traffic, run.devices[0], control=True)
    assert ctrl["gap"] > prog["gap"]
