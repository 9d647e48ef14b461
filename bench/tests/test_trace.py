"""The trace reducer, on made-up events and on a small trace recorded on a
TPU v5e (three back-to-back ``sort_kv`` calls of 2^16 pairs)."""
from __future__ import annotations

import pytest

from bench import trace as TR
from bench.common import BENCH

RECORDED = BENCH / "testdata" / "tiny_sort.xplane.pb"


def _made_up():
    ops = {0: [(0.0, 1.0, "fusion.1"), (0.5, 2.0, "_inblock_body"),
               (3.0, 4.0, "all-to-all.2"), (3.5, 3.6, "fusion.2"),
               (6.0, 7.0, "_hyper_body")],
           1: [(0.0, 0.5, "all-to-all.2")]}
    mods = {0: [(0.0, 4.0, "jit_sort_kv(1)"), (6.0, 7.0, "jit_sort_kv(1)")],
            1: [(0.0, 0.5, "jit_sort_kv(1)")]}
    host = [(1.9, 3.2, "PjitFunction(sort_kv)"), (0.0, 100.0, "session")]
    return TR.Trace(ops, mods, host)


def test_busy_is_the_union_of_operations():
    t = _made_up()
    assert t.busy_s(0) == pytest.approx(2.0 + 1.0 + 1.0)
    assert t.busy_s(1) == pytest.approx(0.5)
    assert t.mean_busy_s() == pytest.approx(2.25)


def test_time_by_pattern_and_program():
    t = _made_up()
    assert t.op_time_in(r"_inblock_body|_hyper_body", 0, ".") == \
        pytest.approx(2.5)
    assert len(t.modules_matching(r"jit_sort_kv\b", 0)) == 2
    assert t.op_time_in("fusion", 0, "jit_sort_kv") == pytest.approx(1.1)
    assert t.op_time_in("_hyper_body", 0, "jit_other") == 0


def test_exposed_collective():
    t = _made_up()
    total, exposed = t.exposed_s("all-to-all", 0)
    assert total == pytest.approx(1.0)
    assert exposed == pytest.approx(0.9)
    assert t.exposed_s("all-to-all", 1) == pytest.approx((0.5, 0.5))


def test_breakdown():
    t = _made_up()
    top = t.top_ops(2)
    # averaged over the two devices
    assert top == [["_inblock_body", pytest.approx(0.75)],
                   ["all-to-all.2", pytest.approx(0.75)]]
    gaps = t.idle_gaps(2)
    assert gaps[0] == ["no host event", pytest.approx(2.0)]
    assert gaps[1] == ["PjitFunction(sort_kv)", pytest.approx(1.0)]


def test_recorded_tpu_trace():
    t = TR.Trace.load(RECORDED)
    assert t.devices == [0]
    runs = t.modules_matching(TR.NAMES["sort_module"], 0)
    assert len(runs) == 3
    busy = t.busy_s(0)
    kernels = t.op_time_in(TR.NAMES["sort_kernels"], 0,
                           TR.NAMES["sort_module"])
    span = max(e for _, e, _ in t.ops[0]) - min(s for s, _, _ in t.ops[0])
    assert 0 < kernels <= busy <= span
    # the network is most of a sort's device time
    assert kernels > 0.5 * sum(e - s for s, e in runs)
    assert TR.short_name(t.top_ops(1)[0][0]).endswith(
        "custom-call f32[64,1024]")
