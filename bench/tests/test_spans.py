"""The readers of the program's own spans (``bench/spans.py``): on made-up
events, and on a small trace recorded on a TPU v5e (a smoke-size
``internlm2_1_8b`` engine serving six requests on four slots).

Recorded on the chip with ``python3 -m bench.tests.test_spans <out>``.
"""
from __future__ import annotations

import pytest

from bench import spans
from bench.common import BENCH
from bench.run import Context, reader
from bench.trace import NAMES, Trace

RECORDED = BENCH / "testdata" / "tiny_serve.xplane.pb"
NEW = ("engine.prefill_wait_ms", "device_idle.admit", "device_idle.retire")
PREFILL = NAMES["prefill_module"]


def _made_up():
    """Two decode steps and one prefill, whose device clock reads 0.1 s
    behind the host's (the fastest launch, the prefill's, starts 0.1 s
    "before" its host call), and the host's spans around them; a 4 s
    slice."""
    mods = {0: [(0.0, 1.0, "jit__decode_jit(1)"),
                (1.5, 1.9, "jit__prefill_jit(3)"),
                (2.0, 3.0, "jit__decode_jit(1)")]}
    ops = {0: [(0.0, 1.2, "fusion.1"), (1.5, 2.0, "fusion.2"),
               (2.0, 3.3, "bitonic_inblock.3")]}
    # on the host's clock the device is busy over [0.1, 1.3] and
    # [1.6, 3.4]
    host = [(0.1, 0.11, spans.LAUNCH),
            (1.25, 1.35, spans.RETIRE),    # 0.05 s idle
            (1.35, 1.7, spans.ADMIT),      # 0.25 s idle, then busy
            (1.45, 1.62, spans.PREFILL),   # its prefill starts at 1.6
            (1.6, 1.61, spans.LAUNCH),
            (1.95, 1.96, spans.LAUNCH),
            (3.35, 3.6, spans.RETIRE),     # busy, then past the last op
            (0.0, 4.0, "PjitFunction(_decode_jit)")]
    return Trace(ops, mods, host)


def _read(trace, window_s=4.0):
    ctx = Context(trace, window_s, {}, None)
    return {name: reader(name)(ctx) for name in NEW + ("device_idle.serve",)}


def test_readers_on_made_up_events():
    got = _read(_made_up())
    assert spans.joined(_made_up(), PREFILL, 0)[1] == pytest.approx(-0.1)
    assert got["engine.prefill_wait_ms"] == pytest.approx(150.0)
    assert got["device_idle.admit"] == pytest.approx(100 * 0.25 / 4)
    assert got["device_idle.retire"] == pytest.approx(100 * 0.05 / 4)
    assert got["device_idle.serve"] == pytest.approx(100 * 1.0 / 4)
    assert (got["device_idle.admit"] + got["device_idle.retire"]
            <= got["device_idle.serve"])


def test_a_program_without_the_names_reads_nothing():
    """A checkout that puts no spans into the profile (the parent of this
    benchmark's readers) leaves the metrics out."""
    t = _made_up()
    bare = Trace(t.ops, t.modules,
                 [h for h in t.host if not h[2].startswith("engine.")])
    got = _read(bare)
    assert all(got[name] is None for name in NEW)
    assert all(v is None for v in _read(Trace({}, {}, [])).values())


@pytest.mark.parametrize("fault", ["extra span", "no launch", "no run"])
def test_spans_that_do_not_pair_read_nothing(fault):
    """Prefill spans and executions that do not pair one to one, or a span
    that launched nothing, leave the metrics out instead of joining a span
    to another admission's prefill."""
    t = _made_up()
    if fault == "extra span":
        t.host.append((3.5, 3.6, spans.PREFILL))
    elif fault == "no launch":
        t.host.remove((1.6, 1.61, spans.LAUNCH))
    else:
        t.modules[0].pop(1)
    assert all(v is None for k, v in _read(t).items() if k in NEW)


def test_idle_under_counts_overlapping_spans_once():
    t = _made_up()
    t.host += [(1.26, 1.34, spans.RETIRE)]
    assert spans.idle_under(t, spans.RETIRE, 0, -0.1) == pytest.approx(0.05)
    assert spans.idle_under(t, "engine.defrag", 0, -0.1) is None


def test_recorded_tpu_trace():
    """On the chip's trace: the spans are there; the device's clock reads
    behind the host's by about a millisecond, as launches show; every
    prefill starts after its span opened and before the admission that
    holds it fetched the first token."""
    t = Trace.load(RECORDED)
    assert t.devices == [0]
    names = {n for _, _, n in t.host}
    assert {spans.ADMIT, spans.PREFILL, spans.RETIRE,
            "engine.decode", "engine.sample"} <= names
    assert t.modules_matching(NAMES["decode_module"], 0)
    pairs, offset = spans.joined(t, PREFILL, 0)
    assert len(pairs) == 6
    assert -5e-3 < offset < 0
    admits = spans.host_spans(t, spans.ADMIT)
    for (s, r), w in zip(pairs, spans.waits(t, PREFILL, 0)):
        (a0, a1), = [(a0, a1) for a0, a1 in admits if a0 <= s <= a1]
        assert 0 <= w < a1 - s
    got = _read(t, window_s=_extent(t))
    assert all(got[name] is not None and got[name] >= 0 for name in NEW)
    assert (got["device_idle.admit"] + got["device_idle.retire"]
            <= got["device_idle.serve"] + 1e-9)


def _extent(trace) -> float:
    ops = trace.ops[0]
    return max(e for _, e, _ in ops) - min(s for s, _, _ in ops)


def record(out: str) -> None:
    """Record ``RECORDED`` on a chip: a smoke-size engine, compiled by one
    run, then the same six requests on four slots while the profiler
    records, as ``bench/run.py``'s tracer does."""
    import pathlib
    import shutil
    import tempfile

    import jax

    from repro.configs import load_smoke_config
    from repro.launch.engine import Engine, Request
    from repro.models import model as M

    cfg = load_smoke_config("internlm2_1_8b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (6, 12), 0,
                                 cfg.vocab)
    eng = Engine(params, cfg, slots=4, cache_len=64, prompt_pad=16,
                 top_k=8, top_p=0.9, seed=3)

    def reqs():
        return [Request(rid=i, prompt=prompts[i, :4 + i], max_new=6 + i)
                for i in range(6)]

    eng.run(reqs())
    where = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(where, profiler_options=opts)
    eng.run(reqs())
    jax.profiler.stop_trace()
    (found,) = pathlib.Path(where).rglob("*.xplane.pb")
    shutil.copy(found, out)
    shutil.rmtree(where)


if __name__ == "__main__":
    import sys

    record(sys.argv[1])
