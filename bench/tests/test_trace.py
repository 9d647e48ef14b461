"""The trace reducer, on made-up events and on a small trace recorded on a
TPU v5e (three back-to-back ``sort_kv`` calls of 2^16 pairs)."""
from __future__ import annotations

import re

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


def test_busy_is_cut_at_the_slice():
    """Operations recorded before the host's first event (a program in
    flight when the profile started) do not count as busy."""
    ops = {0: [(0.0, 2.0, "fusion.1"), (3.0, 4.0, "fusion.2"),
               (4.5, 6.0, "fusion.3")]}
    t = TR.Trace(ops, {}, [(1.0, 4.0, "PjitFunction(sort_kv)"),
                           (4.5, 5.0, "PjitFunction(sort_kv)")])
    lo, hi = 1.0 - TR.SKEW_S, 5.0 - TR.SKEW_S
    assert t.window() == pytest.approx((lo, hi))
    assert t.busy_s(0) == pytest.approx((2.0 - lo) + 1.0 + (hi - 4.5))
    assert t.busy_s(0) <= hi - lo


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


@pytest.mark.parametrize("name, hit", [
    # a v5e:2x2 SIHSort's exchange, as the trace prints it (its name keeps
    # JAX's underscores, its opcode is XLA's)
    ("%all_to_all.9 = s32[4,1,65537]{2,1,0:T(1,128)S(1)} all-to-all("
     "%copy.11), channel_id=1, replica_groups={{0,1,2,3}}", True),
    ("%all-to-all-start = (s32[4,8]{1,0}) all-to-all-start(%copy.2)", True),
    # an operation that only reads the exchange's result
    ("%fusion.3 = s32[8]{0} fusion(s32[4,1,2]{2,1,0} %all_to_all.9), "
     "kind=kLoop", False),
])
def test_all_to_all_pattern_matches_the_opcode(name, hit):
    assert bool(re.search(TR.NAMES["all_to_all"], name)) is hit


def _three_sorts():
    """Three SIHSort executions on one chip, the first and last cut by the
    slice's edges; each holds one exchange."""
    a2a = "%all_to_all.9 = s32[4,1,8]{2,1,0} all-to-all(%copy.1)"
    net = ('%bitonic_inblock.1 = f32[8]{0} custom-call(%p), '
           'custom_call_target="tpu_custom_call"')
    ops = {0: [(0.0, 1.0, "%fusion.1 = f32[8]{0} fusion(%p)"),
               (1.0, 1.5, a2a),
               (1.9, 2.0, net),
               (2.0, 3.0, "%fusion.1 = f32[8]{0} fusion(%p)"),
               (3.0, 3.2, a2a),
               (3.8, 4.0, net),
               (4.0, 5.0, "%fusion.1 = f32[8]{0} fusion(%p)")]}
    mods = {0: [(0.0, 1.5, "jit_sihsort(7)"), (1.9, 3.2, "jit_sihsort(7)"),
                (3.8, 5.0, "jit_sihsort(7)")]}
    return TR.Trace(ops, mods, [])


def test_whole_modules_leave_out_the_cut_ones():
    t = _three_sorts()
    whole = t.whole_modules(TR.NAMES["sort_module"], 0)
    assert whole == [(1.9, 3.2)]
    assert t.op_time_within(TR.NAMES["all_to_all"], 0, whole) == \
        pytest.approx(0.2)


def test_a2a_ms_reads_whole_sorts_only():
    from bench import run as R

    ctx = R.Context(_three_sorts(), 5.0, {}, {})
    assert R.reader("a2a_ms")(ctx) == pytest.approx(200.0)
    # the share reads every exchange in the slice: nothing runs beside it
    assert R.reader("a2a_exposed_share")(ctx) == pytest.approx(100.0)


def test_sort_readers_read_whole_sorts_only():
    """The kernels' roofline and SIHSort's XLA time count the one sort
    that lies wholly inside the slice."""
    from bench import run as R, work

    ctx = R.Context(_three_sorts(), 5.0, {"n_per_chip": 8},
                    {"hbm_bytes_per_s": 1e3})
    need = work.sort_bytes(8) / 1e3
    assert R.reader("sort_roofline")(ctx) == pytest.approx(
        100 * need / 0.1)
    # the fusion; neither the network nor the exchange
    assert R.reader("sihsort_xla_ms")(ctx) == pytest.approx(1000.0)
