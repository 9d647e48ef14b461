"""Unified telemetry tier: spans, metrics registry, exporters.

Covers the PR-9 acceptance criteria (DESIGN.md §11):
  * span nesting/ordering: parent complete-events contain their children
    in time, exit order is recorded innermost-first, and every span keeps
    its own args — property-tested over random span trees when hypothesis
    is available, with a deterministic fallback tree either way;
  * the profiler's clock: a span opened while ``jax.profiler`` records
    lands by name, with its args, on the profile's host plane inside the
    profile's window, with the ring buffer on or off;
  * golden Perfetto/Chrome-trace schema: exported docs carry the
    displayTimeUnit + process/thread metadata the viewer needs, every
    event passes ``validate_trace``, and structurally broken docs are
    rejected with ``ValueError``;
  * disabled-mode no-op contract: with the ring buffer off and no profile
    recording, ``span()`` returns ONE shared no-op singleton, nothing is
    buffered, ``instant`` is free;
  * Prometheus round-trip: ``parse_prometheus(prometheus_text())``
    reproduces every counter/gauge/histogram sample the snapshot holds,
    including labels, escapes, and the cumulative bucket form;
  * legacy-counter absorption: the kernels launch counter (thread-safe,
    per-label) and the supervisor's retry/straggler instrumentation
    surface in ``ak.telemetry.snapshot()`` without breaking the legacy
    accessors.
"""
from __future__ import annotations

import json
import threading
import time

import pytest

from repro.runtime import metrics, telemetry


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts disabled with an empty ring buffer."""
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


# --------------------------------------------------------------------------
# Disabled mode: the no-op contract
# --------------------------------------------------------------------------

def test_disabled_span_is_shared_singleton():
    assert not telemetry.enabled()
    assert not telemetry.active()
    s1 = telemetry.span("a", cat="x", foo=1)
    s2 = telemetry.span("b")
    assert s1 is s2  # no allocation per call on the disabled path
    assert s1 is telemetry._NOOP
    with s1:
        with telemetry.span("nested", step=3):
            pass
        telemetry.instant("boom")
        telemetry.async_begin("req", 7)
        telemetry.async_end("req", 7)
    assert telemetry.events() == []
    assert telemetry.dropped() == 0


def test_disabled_records_nothing_into_metrics_registry():
    before = json.dumps(metrics.snapshot(), sort_keys=True)
    with telemetry.span("a", step=5):
        telemetry.instant("tick")
    assert json.dumps(metrics.snapshot(), sort_keys=True) == before


def test_disable_mid_span_drops_the_event():
    telemetry.enable()
    with telemetry.span("outer", step=1):
        telemetry.disable()
    assert all(e["name"] != "outer" for e in telemetry.events())
    assert telemetry.span("after") is telemetry._NOOP


# --------------------------------------------------------------------------
# The profiler's clock
# --------------------------------------------------------------------------

def _profile(tmp_path, body):
    """Run ``body`` while ``jax.profiler`` records; returns the profile's
    host events as (start_ns, duration_ns, name, stats), start_ns from the
    profile's start, and the longest the profile can have lasted."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    t0 = time.perf_counter_ns()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    longest = time.perf_counter_ns() - t0
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = [(e.start_ns, e.duration_ns, e.name, dict(e.stats))
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    return events, longest


@pytest.mark.parametrize("ring_buffer", [False, True])
def test_span_lands_on_the_profile_host_plane(tmp_path, ring_buffer):
    """A span opened while the profiler records appears by name on the
    profile's host plane, inside the profile's window, with its args as
    metadata — with the ring buffer off (the benchmark's traced runs) and
    on (then it is in both)."""
    if ring_buffer:
        telemetry.enable()

    def body():
        assert telemetry.active()
        with telemetry.span("engine.admit", cat="engine", step=3, rid=7,
                            resume=False):
            time.sleep(0.005)

    events, longest = _profile(tmp_path, body)
    (start, dur, _, args), = [e for e in events if e[2] == "engine.admit"]
    assert 0 <= start and start + dur <= longest
    assert dur >= 5e6
    assert args == {"step": 3, "rid": 7, "resume": 0}
    kept = [e["name"] for e in telemetry.events() if e["ph"] == "X"]
    assert kept == (["engine.admit"] if ring_buffer else [])
    if not ring_buffer:   # the profile stopped: the shared no-op again
        assert telemetry.span("engine.admit") is telemetry._NOOP


# --------------------------------------------------------------------------
# Span nesting / ordering
# --------------------------------------------------------------------------

def _run_tree(tree, prefix="s"):
    """Execute a nested span tree (a list of subtrees); returns the names
    depth-first (parent before child) that were opened."""
    names = []
    for i, sub in enumerate(tree):
        name = f"{prefix}.{i}"
        names.append(name)
        with telemetry.span(name, cat="test", depth=name.count(".")):
            names.extend(_run_tree(sub, prefix=name))
    return names


def _check_tree_invariants(opened):
    evs = [e for e in telemetry.events() if e["ph"] == "X"]
    by_name = {e["name"]: e for e in evs}
    # every opened span recorded exactly once
    assert sorted(by_name) == sorted(opened)
    assert len(evs) == len(opened)
    for name, e in by_name.items():
        # parent intervals contain child intervals...
        parent = name.rsplit(".", 1)[0]
        if parent in by_name:
            p = by_name[parent]
            assert p["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= p["ts"] + p["dur"]
        # ...and each keeps its own args, none of its children's
        assert e["args"] == {"depth": name.count(".")}
    # complete events are recorded at EXIT: children before parents
    order = [e["name"] for e in evs]
    for name in order:
        parent = name.rsplit(".", 1)[0]
        if parent in by_name:
            assert order.index(name) < order.index(parent)


def test_span_nesting_deterministic_tree():
    telemetry.enable()
    opened = _run_tree([[[], [[]]], [], [[], []]])
    telemetry.disable()
    _check_tree_invariants(opened)


def test_current_span_tracks_the_stack():
    telemetry.enable()
    assert telemetry.current_span() is None
    with telemetry.span("outer"):
        assert telemetry.current_span() == "outer"
        with telemetry.span("inner"):
            assert telemetry.current_span() == "inner"
        assert telemetry.current_span() == "outer"
    assert telemetry.current_span() is None


def test_span_nesting_property_random_trees():
    hypothesis = pytest.importorskip(
        "hypothesis", reason="optional test dep (pip install .[test])"
    )
    from hypothesis import given, settings, strategies as st

    trees = st.recursive(
        st.lists(st.none(), max_size=3).map(lambda l: [[] for _ in l]),
        lambda sub: st.lists(sub, max_size=3),
        max_leaves=12,
    )

    @settings(max_examples=40, deadline=None)
    @given(tree=trees)
    def check(tree):
        telemetry.enable()
        opened = _run_tree(tree)
        telemetry.disable()
        _check_tree_invariants(opened)

    check()


def test_ring_buffer_bounds_and_counts_drops():
    telemetry.enable(capacity=8)
    for i in range(20):
        telemetry.instant(f"e{i}")
    assert len(telemetry.events()) == 8
    assert telemetry.dropped() == 12
    # oldest evicted, newest kept
    assert [e["name"] for e in telemetry.events()] == [
        f"e{i}" for i in range(12, 20)
    ]
    assert telemetry.export_doc()["otherData"]["dropped_events"] == 12


def test_spans_from_threads_get_distinct_tids():
    telemetry.enable()
    # all three threads must be alive at once: OS thread idents are
    # reused by sequential threads, which would legitimately share a tid
    barrier = threading.Barrier(3)

    def work(tag):
        with telemetry.span(tag, rid=int(tag[1:])):
            barrier.wait(timeout=30)

    ts = [threading.Thread(target=work, args=(f"t{i}",)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    evs = {e["name"]: e for e in telemetry.events()}
    assert len(evs) == 3
    assert len({e["tid"] for e in evs.values()}) == 3
    # the span stack is thread-local: each span kept exactly its own args
    assert all(e["args"] == {"rid": int(n[1:])} for n, e in evs.items())


# --------------------------------------------------------------------------
# Golden Perfetto schema
# --------------------------------------------------------------------------

def test_exported_doc_matches_golden_schema(tmp_path):
    telemetry.enable()
    telemetry.async_begin("req", 3, rid=3)
    with telemetry.span("phase", cat="engine", step=0):
        with telemetry.span("ak.sort", cat="primitive", backend="pallas",
                            n=4096):
            pass
        telemetry.instant("fault-injected", cat="fault", site="pool.alloc")
    telemetry.async_end("req", 3, status="COMPLETED")
    telemetry.disable()

    path = tmp_path / "trace.json"
    doc = telemetry.export(str(path))
    # the validator accepts what we wrote, from memory and from disk
    assert telemetry.validate_trace(doc) is doc
    on_disk = telemetry.validate_trace_file(str(path))
    assert on_disk == json.loads(json.dumps(doc))

    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    # golden structure: process metadata first, one thread_name per tid
    assert evs[0] == {"name": "process_name", "ph": "M", "pid": 0,
                      "ts": 0, "args": {"name": "repro"}}
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)
    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
    assert {"M", "X", "i", "b", "e"} <= set(by_ph)
    for e in by_ph["X"]:
        assert set(e) >= {"name", "cat", "ph", "ts", "dur", "pid", "tid"}
    (inst,) = by_ph["i"]
    assert inst["s"] == "t" and inst["args"]["site"] == "pool.alloc"
    assert by_ph["b"][0]["id"] == "3" and by_ph["e"][0]["id"] == "3"
    sort_span = next(e for e in by_ph["X"] if e["name"] == "ak.sort")
    assert sort_span["args"] == {"backend": "pallas", "n": 4096}


@pytest.mark.parametrize("breakage", [
    {"ph": "Z"},                     # unknown phase
    {"name": 7},                     # non-string name
    {"ts": -1},                      # negative timestamp
    {"dur": None},                   # complete event without duration
    {"s": "x"},                      # bad instant scope
    {"args": [1, 2]},                # args not an object
])
def test_validate_trace_rejects_broken_events(breakage):
    telemetry.enable()
    with telemetry.span("ok"):
        pass
    telemetry.instant("tick")
    telemetry.disable()
    doc = telemetry.export_doc()
    target = "ok" if set(breakage) & {"dur"} else \
        "tick" if set(breakage) & {"s"} else None
    for ev in doc["traceEvents"]:
        if ev["ph"] == "M" and target is None and "ts" in breakage:
            continue  # metadata events legitimately skip the ts checks
        if target is None or ev["name"] == target:
            ev.update(breakage)
            break
    with pytest.raises(ValueError):
        telemetry.validate_trace(doc)


def test_validate_trace_rejects_async_without_string_id():
    telemetry.enable()
    telemetry.async_begin("req", 1)
    telemetry.disable()
    doc = telemetry.export_doc()
    ev = next(e for e in doc["traceEvents"] if e["ph"] == "b")
    ev["id"] = 1
    with pytest.raises(ValueError):
        telemetry.validate_trace(doc)


# --------------------------------------------------------------------------
# Metrics registry + Prometheus round-trip
# --------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = metrics.MetricsRegistry()
    c = reg.counter("ak_test_events_total", "events")
    c.inc()
    c.inc(2, site="a")
    assert c.value() == 1 and c.value(site="a") == 2
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("ak_test_depth")
    g.set(5)
    g.dec(2)
    assert g.value() == 3
    h = reg.histogram("ak_test_wait_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    ((labels, agg),) = h.samples()
    assert labels == {}
    assert agg["buckets"] == {"0.1": 1, "1.0": 2, "+Inf": 3}
    assert agg["count"] == 3 and agg["sum"] == pytest.approx(2.55)
    # kind mismatch on an existing name is an error, same kind is get-or-create
    assert reg.counter("ak_test_events_total") is c
    with pytest.raises(ValueError):
        reg.gauge("ak_test_events_total")


def test_prometheus_text_round_trip():
    reg = metrics.MetricsRegistry()
    c = reg.counter("ak_rt_events_total", 'help with "quotes"')
    c.inc(3, site="pool.alloc")
    c.inc(1, site='we"ird\\label')
    reg.gauge("ak_rt_level", "level").set(2.5, host="h0")
    h = reg.histogram("ak_rt_lat_seconds", "latency", buckets=(0.5, 1.0))
    h.observe(0.2, phase="decode")
    h.observe(4.0, phase="decode")

    text = reg.prometheus_text()
    parsed = metrics.parse_prometheus(text)

    assert (dict([("site", "pool.alloc")]), 3.0) in parsed["ak_rt_events_total"]
    assert ({"site": 'we"ird\\label'}, 1.0) in parsed["ak_rt_events_total"]
    assert parsed["ak_rt_level"] == [({"host": "h0"}, 2.5)]
    buckets = {l["le"]: v for l, v in parsed["ak_rt_lat_seconds_bucket"]}
    assert buckets == {"0.5": 1.0, "1.0": 1.0, "+Inf": 2.0}
    assert parsed["ak_rt_lat_seconds_sum"] == [({"phase": "decode"}, 4.2)]
    assert parsed["ak_rt_lat_seconds_count"] == [({"phase": "decode"}, 2.0)]

    # every non-histogram snapshot sample survives the round trip verbatim
    snap = reg.snapshot()["metrics"]
    for name, fam in snap.items():
        if fam["type"] == "histogram":
            continue
        got = {tuple(sorted(l.items())): v for l, v in parsed[name]}
        for s in fam["samples"]:
            assert got[tuple(sorted(s["labels"].items()))] == s["value"]


def test_collector_pull_model_and_dedup():
    reg = metrics.MetricsRegistry()
    legacy = {"calls": 0}

    def collect(r):
        r.counter("ak_legacy_calls_total").set_total(
            legacy["calls"], primitive="sort")

    reg.register_collector(collect)
    reg.register_collector(collect)  # idempotent
    legacy["calls"] = 7
    snap = reg.snapshot()["metrics"]["ak_legacy_calls_total"]["samples"]
    assert snap == [{"labels": {"primitive": "sort"}, "value": 7.0}]
    legacy["calls"] = 9  # pull model: the next snapshot re-syncs
    snap = reg.snapshot()["metrics"]["ak_legacy_calls_total"]["samples"]
    assert snap == [{"labels": {"primitive": "sort"}, "value": 9.0}]


def test_snapshot_is_json_and_collector_may_read_registry():
    reg = metrics.MetricsRegistry()
    reg.register_collector(lambda r: r.snapshot())  # must not recurse
    reg.counter("ak_x_total").inc()
    json.dumps(reg.snapshot())  # JSON-able end to end
    text = reg.prometheus_text()
    assert "# TYPE ak_x_total counter" in text


# --------------------------------------------------------------------------
# Legacy counters surface in the snapshot (satellite integrations)
# --------------------------------------------------------------------------

def test_launch_counter_is_thread_safe_and_per_label():
    import jax
    import jax.numpy as jnp

    import repro.core.registry  # noqa: F401 — registers the launch collector
    from repro.kernels import common as KC

    KC.reset_launch_count()
    kernel = lambda ref, out: None
    shape = jax.ShapeDtypeStruct((8, 128), jnp.float32)

    def work(label, n):
        with KC.launch_attribution(label):
            for _ in range(n):
                KC.pallas_call(kernel, name="noop", out_shape=shape,
                               interpret=True)

    ts = [threading.Thread(target=work, args=(f"prim{i % 2}", 50))
          for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    KC.pallas_call(kernel, name="noop", out_shape=shape,
                   interpret=True)  # bare launch
    counts = KC.launch_counts()
    assert counts["prim0"] == counts["prim1"] == 100
    assert counts["unattributed"] == 1
    assert sum(counts.values()) == KC.launch_count() == 201

    # the registry collector mirrors exactly these tallies
    snap = telemetry.snapshot()["metrics"]["ak_pallas_launches_total"]
    got = {s["labels"]["primitive"]: s["value"] for s in snap["samples"]}
    assert got["prim0"] == 100 and got["unattributed"] == 1
    KC.reset_launch_count()


def test_registry_dispatch_spans_carry_attribution():
    """The registry's dispatch span carries the backend it resolved and
    the size it dispatched on."""
    import jax.numpy as jnp
    import numpy as np

    from repro import core as ak
    from repro.core import registry

    registry.clear_caches()
    x = jnp.asarray(np.random.default_rng(0).normal(size=2048), jnp.float32)
    with telemetry.enabled_scope():
        with ak.backend("pallas"):
            ak.merge_sort(x)
    spans = [e for e in telemetry.events()
             if e["ph"] == "X" and e["name"] == "ak.sort"]
    assert spans, "registry dispatch recorded no primitive span"
    assert spans[0]["args"] == {"backend": "pallas", "n": 2048}
    # and the snapshot's registry counters agree with the legacy accessor
    snap = telemetry.snapshot()["metrics"]
    calls = {s["labels"]["primitive"]: s["value"]
             for s in snap["ak_registry_calls_total"]["samples"]}
    assert calls["sort"] == registry.stats("sort")["calls"]


def test_supervisor_retries_publish_metrics_and_events():
    from repro.runtime.supervisor import Supervisor

    sup = Supervisor(None, n_hosts=1, max_retries=3, sleep=lambda s: None)
    before = metrics.counter("ak_supervisor_retries_total").value(host="0")
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise RuntimeError("transient")
        return "ok"

    with telemetry.enabled_scope():
        assert sup.run_step(step_fn=flaky, host=0) == "ok"
    after = metrics.counter("ak_supervisor_retries_total").value(host="0")
    assert after - before == 2
    retries = [e for e in telemetry.events()
               if e["ph"] == "X" and e["name"] == "supervisor.retry"]
    assert [e["args"]["attempt"] for e in retries] == [1, 2]
    failures = [e for e in telemetry.events()
                if e["ph"] == "i" and e["name"] == "supervisor.step-failure"]
    assert len(failures) == 2
    assert all(e["args"]["severity"] == "warning" for e in failures)
