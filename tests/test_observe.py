"""Telemetry layer unit tests: span tracing (nesting, thread attribution,
disabled fast path), MetricsRegistry aggregation, StageTimes queue-occupancy
sampling, ProgressTracker finish behavior, heartbeat gauges, log setup."""

import json
import logging
import threading

import pytest

from fgumi_tpu.observe import heartbeat as hb
from fgumi_tpu.observe import trace
from fgumi_tpu.observe.metrics import METRICS, MetricsRegistry, record_stage_times
from fgumi_tpu.pipeline import StageTimes


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.stop_trace()
    yield
    trace.stop_trace()


# ---------------------------------------------------------------------------
# span tracing


def test_span_disabled_is_shared_noop():
    assert not trace.tracing_enabled()
    s = trace.span("anything", key="value")
    assert s is trace.NULL_SPAN
    assert trace.span("other") is s  # one shared object, no allocation
    with s:
        s.set(extra=1)  # API parity with the live span
    trace.instant("marker")  # no-op, no error


def test_span_records_complete_events_with_nesting():
    t = trace.start_trace()
    with trace.span("outer", batch=3):
        with trace.span("inner"):
            pass
    events = [e for e in t.snapshot() if e["ph"] == "X"]
    by_name = {e["name"]: e for e in events}
    assert set(by_name) == {"outer", "inner"}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["args"] == {"batch": 3}
    # nesting: the inner complete event lies within the outer's interval
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 0.1
    assert outer["tid"] == inner["tid"]


def test_span_thread_attribution():
    t = trace.start_trace()

    def work():
        with trace.span("in-thread"):
            pass

    th = threading.Thread(target=work, name="obs-test-thread")
    with trace.span("on-main"):
        pass
    th.start()
    th.join()
    events = t.snapshot()
    spans = {e["name"]: e for e in events if e["ph"] == "X"}
    metas = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    assert spans["on-main"]["tid"] != spans["in-thread"]["tid"]
    # each thread named itself exactly once via thread_name metadata
    assert metas[spans["in-thread"]["tid"]] == "obs-test-thread"
    assert metas[spans["on-main"]["tid"]] == threading.current_thread().name


def test_span_records_error_type_and_propagates():
    t = trace.start_trace()
    with pytest.raises(ValueError):
        with trace.span("failing"):
            raise ValueError("boom")
    (ev,) = [e for e in t.snapshot() if e["ph"] == "X"]
    assert ev["args"]["error"] == "ValueError"


def test_span_set_attaches_mid_span_attrs():
    t = trace.start_trace()
    with trace.span("fetch") as sp:
        sp.set(bytes=480)
    (ev,) = [e for e in t.snapshot() if e["ph"] == "X"]
    assert ev["args"] == {"bytes": 480}


def test_trace_event_cap_drops_not_grows():
    t = trace.start_trace(max_events=3)
    for i in range(10):
        with trace.span(f"s{i}"):
            pass
    assert len(t.snapshot()) <= 3
    assert t.dropped >= 7
    assert t.to_json_obj()["otherData"]["dropped_events"] == t.dropped


def test_write_trace_is_valid_chrome_json(tmp_path):
    t = trace.start_trace()
    with trace.span("a"):
        pass
    out = tmp_path / "trace.json"
    trace.write_trace(str(out), t)
    obj = json.loads(out.read_text())
    assert isinstance(obj["traceEvents"], list)
    assert any(e["ph"] == "X" and e["name"] == "a"
               for e in obj["traceEvents"])
    for ev in obj["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(ev)


# ---------------------------------------------------------------------------
# metrics registry


def test_metrics_inc_set_max_and_snapshot_sorted():
    m = MetricsRegistry()
    m.inc("b.count")
    m.inc("b.count", 4)
    m.set("a.gauge", 7)
    m.max("c.peak", 10)
    m.max("c.peak", 3)  # lower value does not regress the high-water mark
    m.max("c.peak", 12)
    snap = m.snapshot()
    assert snap == {"a.gauge": 7, "b.count": 5, "c.peak": 12}
    assert list(snap) == ["a.gauge", "b.count", "c.peak"]


def test_metrics_update_accumulates_numbers_under_prefix():
    m = MetricsRegistry()
    m.update({"dispatches": 2, "mode": "wire"}, prefix="device")
    m.update({"dispatches": 3}, prefix="device")
    snap = m.snapshot()
    assert snap["device.dispatches"] == 5  # numeric values sum
    assert snap["device.mode"] == "wire"   # non-numeric overwrite
    m.reset()
    assert m.snapshot() == {}


def test_record_stage_times_folds_into_global_registry():
    METRICS.reset()
    st = StageTimes()
    st.add_busy("read", 1.5)
    st.add_busy("read", 0.5)
    st.add_blocked("write", 0.25)
    st.sample_queues(2, 4)
    st.sample_queues(4, 0)
    record_stage_times(st)
    snap = METRICS.snapshot()
    assert snap["pipeline.stage.read.busy_s"] == 2.0
    assert snap["pipeline.stage.write.blocked_s"] == 0.25
    assert snap["pipeline.queue.samples"] == 2
    assert snap["pipeline.queue.in.sum"] == 6
    assert snap["pipeline.queue.in.max"] == 4
    assert snap["pipeline.queue.out.max"] == 4
    METRICS.reset()


# ---------------------------------------------------------------------------
# StageTimes queue-occupancy sampling (previously untested)


def test_stage_times_queue_sampling_mean_and_max():
    st = StageTimes()
    for q_in, q_out in ((0, 1), (2, 3), (4, 2)):
        st.sample_queues(q_in, q_out)
    assert st.q_samples == 3
    assert st.q_in_sum == 6 and st.q_in_max == 4
    assert st.q_out_sum == 6 and st.q_out_max == 3
    table = st.format_table()
    assert "in avg 2.0 max 4" in table
    assert "out avg 2.0 max 3" in table
    assert "(3 samples)" in table


def test_stage_times_no_samples_no_queue_line():
    st = StageTimes()
    st.add_busy("read", 0.1)
    assert "queues" not in st.format_table()


# ---------------------------------------------------------------------------
# ProgressTracker.finish


def test_progress_finish_short_run_emits_debug_done_line(caplog):
    from fgumi_tpu.utils.progress import ProgressTracker

    METRICS.reset()
    p = ProgressTracker("shortcmd", every=1000)
    p.add(5)
    with caplog.at_level(logging.DEBUG, logger="fgumi_tpu"):
        p.finish()
    done = [r for r in caplog.records if "done, 5 records" in r.message]
    assert done and done[0].levelno == logging.DEBUG
    assert METRICS.get("records.shortcmd") == 5
    METRICS.reset()


def test_progress_finish_long_run_stays_info(caplog):
    from fgumi_tpu.utils.progress import ProgressTracker

    METRICS.reset()
    p = ProgressTracker("longcmd", every=10)
    with caplog.at_level(logging.INFO, logger="fgumi_tpu"):
        p.add(25)
        p.finish()
    done = [r for r in caplog.records if "done, 25 records" in r.message]
    assert done and done[0].levelno == logging.INFO
    METRICS.reset()


def test_progress_finish_zero_records_silent(caplog):
    from fgumi_tpu.utils.progress import ProgressTracker

    p = ProgressTracker("emptycmd", every=10)
    with caplog.at_level(logging.DEBUG, logger="fgumi_tpu"):
        p.finish()
    assert not [r for r in caplog.records if "emptycmd" in r.message]


# ---------------------------------------------------------------------------
# heartbeat


def test_heartbeat_beat_includes_registered_gauges(caplog):
    token = hb.register_gauge(lambda: {"read": 7, "q_in": "2/4"})
    try:
        beat = hb.Heartbeat(0)  # interval 0: no thread; beat manually
        with caplog.at_level(logging.INFO, logger="fgumi_tpu"):
            beat.beat()
        line = [r.message for r in caplog.records
                if r.message.startswith("heartbeat:")][0]
        assert "read=7" in line and "q_in=2/4" in line
    finally:
        hb.unregister_gauge(token)
    beat.stop()


def test_heartbeat_gauge_errors_do_not_kill_the_beat(caplog):
    def bad():
        raise RuntimeError("gauge broke")

    token = hb.register_gauge(bad)
    try:
        beat = hb.Heartbeat(0)
        with caplog.at_level(logging.INFO, logger="fgumi_tpu"):
            beat.beat()
        assert any(r.message.startswith("heartbeat:")
                   for r in caplog.records)
    finally:
        hb.unregister_gauge(token)


def test_heartbeat_thread_stops_and_joins():
    before = {t.name for t in threading.enumerate()}
    beat = hb.Heartbeat(60)
    assert any(t.name == "fgumi-heartbeat" for t in threading.enumerate())
    beat.stop()
    alive = {t.name for t in threading.enumerate()
             if t.name == "fgumi-heartbeat"}
    assert not alive or "fgumi-heartbeat" in before


# ---------------------------------------------------------------------------
# pipeline span integration


def test_run_stages_emits_stage_spans_when_tracing():
    from fgumi_tpu.pipeline import run_stages

    t = trace.start_trace()
    sunk = []
    run_stages(iter([1, 2, 3]), lambda x: [x * 2], sunk.append,
               threads=0, resolve_fn=lambda x: x + 1)
    assert sunk == [3, 5, 7]
    names = {e["name"] for e in t.snapshot() if e["ph"] == "X"}
    assert {"pipeline.read", "pipeline.process", "pipeline.resolve",
            "pipeline.sink"} <= names


def test_run_stages_no_spans_when_disabled():
    from fgumi_tpu.pipeline import run_stages

    assert not trace.tracing_enabled()
    sunk = []
    run_stages(iter([1, 2]), lambda x: [x], sunk.append, threads=0)
    assert sunk == [1, 2]


def test_run_stages_threaded_spans_attribute_to_stage_threads():
    from fgumi_tpu.pipeline import run_stages

    t = trace.start_trace()
    sunk = []
    run_stages(iter(range(8)), lambda x: [x], sunk.append, threads=2)
    assert sorted(sunk) == list(range(8))
    events = t.snapshot()
    metas = {e["tid"]: e["args"]["name"] for e in events if e["ph"] == "M"}
    read_tids = {e["tid"] for e in events
                 if e["ph"] == "X" and e["name"] == "pipeline.read"}
    sink_tids = {e["tid"] for e in events
                 if e["ph"] == "X" and e["name"] == "pipeline.sink"}
    assert {metas[tid] for tid in read_tids} == {"fgumi-reader"}
    assert {metas[tid] for tid in sink_tids} == {"fgumi-writer"}


# ---------------------------------------------------------------------------
# latency histograms (ISSUE 9)


def test_histogram_bucket_determinism():
    from fgumi_tpu.observe.metrics import HIST_EDGES, Histogram

    # the same value lands in the same bucket, every time, and boundaries
    # are exact: a value equal to an edge belongs to that edge's bucket
    for v in (1e-7, 1e-6, 0.00123, 0.5, 3.25, 1e7):
        assert Histogram.bucket_index(v) == Histogram.bucket_index(v)
    edge = HIST_EDGES[40]
    assert Histogram.bucket_index(edge) == 40
    assert Histogram.bucket_index(edge * 1.0001) == 41
    # beyond either end clamps instead of raising
    assert Histogram.bucket_index(0.0) == 0
    assert Histogram.bucket_index(1e12) == len(HIST_EDGES) - 1


def test_histogram_quantile_ordering_and_summary():
    from fgumi_tpu.observe.metrics import Histogram

    h = Histogram()
    for v in (0.001, 0.002, 0.004, 0.008, 0.5):
        for _ in range(5):
            h.observe(v)
    s = h.summary()
    assert s["count"] == 25
    assert s["p50"] <= s["p90"] <= s["p99"] <= s["max"]
    assert s["max"] == 0.5
    # a quantile is never below the true value's bucket lower edge nor
    # above the observed max
    assert 0.0005 < s["p50"] < 0.01
    # negative and NaN observations are rejected, not binned
    h.observe(-1.0)
    h.observe(float("nan"))
    assert h.count == 25


def test_histogram_merge_sums_counts_and_keeps_max():
    from fgumi_tpu.observe.metrics import Histogram

    a, b = Histogram(), Histogram()
    for v in (0.01, 0.02):
        a.observe(v)
    for v in (0.04, 8.0):
        b.observe(v)
    a.merge(b)
    assert a.count == 4
    assert a.max == 8.0
    assert abs(a.total - 8.07) < 1e-9
    assert a.buckets()[-1][1] == 4  # cumulative series ends at count


def test_registry_observe_and_summaries():
    m = MetricsRegistry()
    m.observe("x.wait_s", 0.1)
    m.observe("x.wait_s", 0.2)
    m.observe("y.wait_s", 1.0)
    summ = m.summaries()
    assert list(summ) == ["x.wait_s", "y.wait_s"]  # name-sorted
    assert summ["x.wait_s"]["count"] == 2
    m.reset()
    assert m.summaries() == {}


def test_histogram_per_scope_isolation():
    from fgumi_tpu.observe.scope import scoped_telemetry

    with scoped_telemetry("job-a") as a:
        METRICS.observe("iso.wait_s", 0.5)
        with_inner = METRICS.summaries()
    with scoped_telemetry("job-b"):
        assert METRICS.histogram("iso.wait_s") is None
    assert a.metrics.histogram("iso.wait_s").count == 1
    assert "iso.wait_s" in with_inner


def test_histogram_merge_on_scope_exit():
    """publish_to_global MERGES scope histograms into the process-global
    registry (cumulative daemon-lifetime view) while counters replace."""
    from fgumi_tpu.observe import metrics as metrics_mod
    from fgumi_tpu.observe.scope import publish_to_global, scoped_telemetry

    metrics_mod._GLOBAL_REGISTRY.reset()
    try:
        for _ in range(2):
            with scoped_telemetry("job") as scope:
                METRICS.observe("merge.wait_s", 0.25)
            publish_to_global(scope)
        g = metrics_mod._GLOBAL_REGISTRY.histogram("merge.wait_s")
        assert g is not None and g.count == 2  # merged, not replaced
    finally:
        metrics_mod._GLOBAL_REGISTRY.reset()


def test_latency_section_in_report_and_validator():
    from fgumi_tpu.observe.report import build_report, validate_report

    METRICS.reset()
    METRICS.observe("device.dispatch.wall_s", 0.125)
    report = build_report("simplex", ["simplex"], 0.0, 1.0, 0)
    try:
        assert "latency" in report
        entry = report["latency"]["device.dispatch.wall_s"]
        assert entry["count"] == 1
        assert validate_report(report) == []
        # the validator rejects disordered quantiles
        bad = dict(report)
        bad["latency"] = {"x": {"count": 1, "sum": 1, "p50": 2.0,
                                "p90": 1.0, "p99": 3.0, "max": 3.0}}
        assert any("not ordered" in e for e in validate_report(bad))
        bad["latency"] = {"x": {"count": 1}}
        assert any("missing numeric" in e for e in validate_report(bad))
    finally:
        METRICS.reset()


def test_trace_truncation_marker_and_metric(tmp_path):
    """Satellite: overflow writes an explicit truncation marker into the
    exported trace and counts trace.dropped_events in METRICS."""
    METRICS.reset()
    t = trace.start_trace(max_events=2)
    for i in range(6):
        with trace.span(f"s{i}"):
            pass
    out = tmp_path / "trunc.json"
    trace.write_trace(str(out), t)
    try:
        obj = json.loads(out.read_text())
        markers = [e for e in obj["traceEvents"]
                   if e["name"] == "trace.truncated"]
        assert len(markers) == 1
        assert markers[0]["args"]["dropped_events"] == t.dropped > 0
        assert METRICS.get("trace.dropped_events") == t.dropped
    finally:
        METRICS.reset()


def test_heartbeat_rate_ewma_and_eta(caplog):
    counter = {"n": 0}
    token = hb.register_gauge(lambda: {"written": counter["n"]})
    assert hb.set_goal(1000, "t-ewma")
    try:
        beat = hb.Heartbeat(0)
        beat.beat()            # first beat: records baseline, no rate yet
        counter["n"] = 500
        import time as _time

        _time.sleep(0.02)
        with caplog.at_level(logging.INFO, logger="fgumi_tpu"):
            beat.beat()
        line = [r.message for r in caplog.records
                if r.message.startswith("heartbeat:")][-1]
        assert "rate=" in line and "eta=" in line
        assert beat.rate_ewma > 0
        assert beat.last_eta_s is not None
        METRICS.reset()
        beat.stop()
        assert METRICS.get("heartbeat.records_per_s") > 0
        assert METRICS.get("heartbeat.last_eta_s") is not None
    finally:
        hb.clear_goal("t-ewma")
        hb.unregister_gauge(token)
        METRICS.reset()


def test_progress_tracker_total_arms_heartbeat_goal():
    from fgumi_tpu.observe import heartbeat as hb_mod
    from fgumi_tpu.utils.progress import ProgressTracker

    p = ProgressTracker("goalcmd", every=10, total=100)
    try:
        assert hb_mod._goal_total() == 100
        p.add(10)
        states = hb_mod._gauge_states()
        assert any(s.get("records") == 10 for _t, s in states)
    finally:
        p.finish()
    assert hb_mod._goal_total() is None
    METRICS.reset()


def test_concurrent_goal_holders_do_not_clobber():
    """Two live ProgressTrackers with totals (serve daemon workers): the
    first claims the heartbeat goal, the second silently gets no ETA, and
    the loser's finish() cannot clear the winner's goal."""
    from fgumi_tpu.observe import heartbeat as hb_mod
    from fgumi_tpu.utils.progress import ProgressTracker

    a = ProgressTracker("job-a", total=100)
    b = ProgressTracker("job-b", total=999)  # loses the race: no gauge/goal
    try:
        assert hb_mod._goal_total() == 100
        assert b._hb_token is None
        b.finish()  # non-holder clear is a no-op
        assert hb_mod._goal_total() == 100
    finally:
        a.finish()
    assert hb_mod._goal_total() is None
    METRICS.reset()


# ---------------------------------------------------------------------------
# one span system, three sinks (ISSUE 25)


def _spans():
    return trace.current_aggregate().snapshot()["by_name"]


def test_span_unarmed_reads_no_clock_no_rusage_no_jax(monkeypatch):
    """With neither --trace nor --run-report a span is the shared no-op:
    nothing is read, nothing allocated, jax is not looked for."""
    def boom(*a, **k):
        raise AssertionError("an unarmed span touched a sink")

    monkeypatch.setattr(trace._resource, "getrusage", boom)
    monkeypatch.setattr(trace, "_annotation_cls", boom)
    monkeypatch.setattr(trace._Span, "__init__", boom)
    monkeypatch.setattr(trace.time, "monotonic", boom)
    assert trace.span("engine.pack", rusage=True, batch=1) is trace.NULL_SPAN
    assert trace.span("chain.get", wait=True) is trace.NULL_SPAN
    trace.record_interval("feeder.queue_wait", 0.0, 1.0)
    trace.count("engine.pack", "staging_allocs")
    assert list(trace.spanned_iter("sort.merge", [1, 2])) == [1, 2]
    assert trace.spanned("group.assign")(lambda x: x + 1)(1) == 2
    assert trace.current_aggregate() is None


def test_arm_spans_without_chrome_tracer():
    """--run-report alone arms the aggregate, not the Chrome trace."""
    agg = trace.arm_spans()
    assert trace.tracing_enabled() and trace.arm_spans() is agg
    assert trace._current_tracer() is None
    with trace.span("a"):
        pass
    assert _spans()["a"]["count"] == 1
    trace.instant("marker")  # needs the Chrome tracer: a no-op here
    assert trace.stop_trace() is None
    assert not trace.tracing_enabled()


def test_span_parent_and_self_time_nested_and_siblings_two_threads():
    import time as _time

    t = trace.start_trace()

    def work():
        with trace.span("outer"):
            with trace.span("child"):
                _time.sleep(0.02)
            with trace.span("child"):
                _time.sleep(0.02)
            _time.sleep(0.01)

    th = threading.Thread(target=work, name="obs-other")
    with trace.span("main-top"):
        th.start()
        th.join()  # the other thread's spans are no children of this one
    work()
    by = _spans()
    outer, child, top = by["outer"], by["child"], by["main-top"]
    assert outer["count"] == 2 and child["count"] == 4
    by_thread = trace.current_aggregate().snapshot()["threads"]
    assert {t for t, rec in by_thread.items() if "outer" in rec["self_s"]} \
        == {"obs-other", threading.current_thread().name}
    # a root is a root of its own thread: main-top is none of obs-other's
    assert set(by_thread["obs-other"]["roots"]) == {"outer"}
    assert set(by_thread[threading.current_thread().name]["roots"]) \
        == {"outer", "main-top"}
    # self time = duration minus the children's cover, per thread
    assert child["self_s"] == pytest.approx(child["wall_s"])
    assert outer["self_s"] == pytest.approx(
        outer["wall_s"] - child["wall_s"], abs=1e-4)
    assert 0.015 <= outer["self_s"] <= outer["wall_s"] - 0.07
    assert top["self_s"] == pytest.approx(top["wall_s"])
    assert child["p50_s"] <= child["max_s"]
    # the Chrome events name their parent
    parents = {(e["name"], (e.get("args") or {}).get("parent"))
               for e in t.snapshot() if e["ph"] == "X"}
    assert parents == {("outer", None), ("child", "outer"),
                       ("main-top", None)}


def test_span_wait_cover_propagates_to_every_ancestor():
    import time as _time

    trace.arm_spans()
    with trace.span("stage"):
        with trace.span("pull"):
            with trace.span("q.get", wait=True):
                _time.sleep(0.02)
        _time.sleep(0.005)
    by = _spans()
    assert by["q.get"]["wait_s"] == pytest.approx(by["q.get"]["wall_s"])
    assert by["pull"]["wait_s"] == pytest.approx(by["q.get"]["wall_s"])
    assert by["stage"]["wait_s"] == pytest.approx(by["q.get"]["wall_s"])
    own = by["stage"]["wall_s"] - by["stage"]["wait_s"]
    assert 0.004 <= own < by["stage"]["wall_s"]


def test_span_rusage_fields_only_when_asked():
    trace.arm_spans()
    with trace.span("plain"):
        pass
    import mmap

    with trace.span("layer", rusage=True):
        # a fresh anonymous mapping faults on first touch whatever state the
        # heap is in (a bytearray may come from resident pages and fault 0)
        with mmap.mmap(-1, 4 << 20) as m:
            m.write(b"x" * (4 << 20))
        sum(range(200000))
    by = _spans()
    assert "minflt" not in by["plain"]
    rec = by["layer"]
    for key in ("utime_s", "stime_s", "minflt", "majflt", "nvcsw", "nivcsw"):
        assert key in rec and rec[key] >= 0
    assert isinstance(rec["minflt"], int) and rec["minflt"] >= 1024
    assert rec["utime_s"] + rec["stime_s"] > 0


def test_span_counters_and_record_interval():
    import time as _time

    t = trace.start_trace()
    with trace.span("engine.pack"):
        with trace.span("engine.pack.wire"):
            trace.count("engine.pack", "staging_reuses")
            trace.count("engine.pack", "staging_reuses")
            trace.count("no.such.span", "x")
    t0 = _time.monotonic()
    trace.record_interval("feeder.queue_wait", t0 - 0.5, t0, slot=3)
    by = _spans()
    assert by["engine.pack"]["staging_reuses"] == 2
    assert "staging_reuses" not in by["engine.pack.wire"]
    assert by["feeder.queue_wait"]["wall_s"] == pytest.approx(0.5)
    (ev,) = [e for e in t.snapshot() if e["name"] == "feeder.queue_wait"]
    assert ev["dur"] == pytest.approx(0.5e6, rel=1e-3)
    assert ev["args"] == {"slot": 3}


def test_spanned_iter_spans_each_pull_not_the_consumer():
    import time as _time

    trace.arm_spans()

    def gen():
        for i in range(3):
            _time.sleep(0.01)
            yield i

    consumer_s = 0.0
    t0 = _time.perf_counter()
    for _ in trace.spanned_iter("merge", gen()):
        c0 = _time.perf_counter()
        _time.sleep(0.02)  # the consumer's time is nobody's pull
        consumer_s += _time.perf_counter() - c0
    elapsed_s = _time.perf_counter() - t0
    rec = _spans()["merge"]
    assert rec["count"] == 4  # three items and the pull that ends it
    # measured, not assumed: a loaded machine oversleeps on both sides
    assert consumer_s >= 0.06
    assert 0.03 <= rec["wall_s"] <= elapsed_s - consumer_s


def test_run_stages_generator_stage_yields_item_by_item_when_tracing():
    """The `materialize` repair: tracing must not change when outputs reach
    the next stage. A generator stage's second output is produced only
    after the first was sunk, exactly as with tracing off."""
    from fgumi_tpu.pipeline import run_stages

    def run(tracing):
        order = []

        def process(item):
            for k in range(3):
                order.append(("made", item, k))
                yield (item, k)

        if tracing:
            trace.start_trace()
        run_stages(iter([0, 1]), process,
                   lambda out: order.append(("sunk",) + out), threads=0)
        trace.stop_trace()
        return order

    plain, traced = run(False), run(True)
    assert traced == plain
    assert traced[:4] == [("made", 0, 0), ("sunk", 0, 0),
                          ("made", 0, 1), ("sunk", 0, 1)]


def test_run_stages_spans_list_stage_once_and_waits_when_threaded():
    from fgumi_tpu.pipeline import run_stages

    trace.arm_spans()
    sunk = []
    run_stages(iter(range(4)), lambda x: [x, x], sunk.append, threads=2)
    by = _spans()
    assert by["pipeline.process"]["count"] == 4  # per item, not per output
    assert by["pipeline.wait_in"]["count"] == 5  # four items and the end
    assert by["pipeline.wait_out"]["count"] == 8
    assert by["pipeline.wait_in"]["wait_s"] == \
        pytest.approx(by["pipeline.wait_in"]["wall_s"])


def test_flight_ring_takes_no_per_block_span_and_none_without_trace():
    from fgumi_tpu.observe.flight import FLIGHT

    def ring_spans():
        return [e.get("name") for e in FLIGHT.events()
                if e.get("kind") == "span"]

    FLIGHT.reset()
    trace.arm_spans()  # --run-report alone: the ring hears nothing
    with trace.span("device.dispatch"):
        pass
    assert ring_spans() == []
    trace.stop_trace()
    trace.start_trace()  # --trace: layer spans, never per-block I/O
    for name in ("bgzf.compress", "bgzf.decompress", "io.prefetch.read",
                 "device.dispatch"):
        with trace.span(name):
            pass
    assert ring_spans() == ["device.dispatch"]
    FLIGHT.reset()


def test_span_mirrors_onto_profiler_clock(tmp_path):
    """Sink B: with a profiler session open, a spanned dispatch lands in the
    xplane's host plane under its own name, on the named thread, through
    the benchmark's own trace loader."""
    import os
    import sys

    import jax
    import jax.numpy as jnp

    from fgumi_tpu.observe.scope import spawn_thread

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        import tracered
    finally:
        sys.path.remove(bench)

    trace.arm_spans()

    def dispatch():
        with trace.span("engine.pack", rusage=True, batch=7):
            with trace.span("device.dispatch"):
                jnp.arange(8).sum().block_until_ready()

    jax.profiler.start_trace(str(tmp_path))
    try:
        th = spawn_thread(dispatch, name="fgumi-process")
        th.start()
        th.join()
    finally:
        jax.profiler.stop_trace()
    _device, host = tracered.load(tracered.find_xplane(str(tmp_path)))
    names = {name for name, _s, _d, _m in host.get("fgumi-process", [])}
    assert {"engine.pack", "device.dispatch"} <= names
    pack = [(s, d) for n, s, d, _m in host["fgumi-process"]
            if n == "engine.pack"]
    inner = [(s, d) for n, s, d, _m in host["fgumi-process"]
             if n == "device.dispatch"]
    assert pack[0][0] <= inner[0][0]
    assert inner[0][0] + inner[0][1] <= pack[0][0] + pack[0][1] + 1e-6


def test_name_os_thread_sets_comm():
    from fgumi_tpu.observe.scope import name_os_thread, spawn_thread

    seen = {}

    def comm():
        with open("/proc/thread-self/comm") as f:
            return f.read().strip()

    def work():
        seen["before"] = comm()
        name_os_thread("fgumi-device-feeder")
        seen["after"] = comm()

    th = spawn_thread(work, name="fgumi-worker-0")
    th.start()
    th.join()
    assert seen["before"] == "fgumi-worker-0"
    assert seen["after"] == "device-feeder"  # 15 bytes: the prefix goes


def test_compilewatch_keeps_cache_load_seconds_and_process_records():
    from fgumi_tpu.observe import compilewatch, process
    from fgumi_tpu.ops.datapath import SHAPE_REGISTRY

    METRICS.reset()
    before = len(process.snapshot()["compiles"])
    new = SHAPE_REGISTRY.observe("obs-test", 7, 9)
    with SHAPE_REGISTRY.attribute_compiles(new):
        compilewatch._on_event(compilewatch._CACHE_HIT_EVENT)
        compilewatch._on_duration(compilewatch._BACKEND_COMPILE_EVENT, 0.25,
                                  fun_name="jit(fn)")
        compilewatch._on_duration(compilewatch._BACKEND_COMPILE_EVENT, 1.5,
                                  fun_name="jit(fn)")
    try:
        assert METRICS.get("device.compile_cache_hits") == 1
        assert METRICS.get("device.compile_cache_load_s") == 0.25
        assert METRICS.get("device.backend_compiles") == 1
        assert METRICS.get("device.backend_compile_s") == 1.5
        assert METRICS.get("device.shape_bucket.recompiles") == 1
        recs = process.snapshot()["compiles"][before:]
        if recs:  # the bounded list may be full in a long test process
            assert [r["kind"] for r in recs] == ["cache_load", "compile"]
            assert recs[0]["shape"] == "obs-test:7x9"
            assert recs[0]["s"] == 0.25 and recs[0]["fun"] == "jit(fn)"
            assert recs[0]["at_s"] <= recs[1]["at_s"]
    finally:
        METRICS.reset()


def test_process_record_bounded_oldest_kept(monkeypatch):
    from fgumi_tpu.observe import process

    monkeypatch.setattr(process, "_compiles", [])
    monkeypatch.setattr(process, "_compiles_dropped", 0)
    for i in range(process.MAX_COMPILE_RECORDS + 5):
        process.note_compile("compile", float(i))
    snap = process.snapshot()
    assert len(snap["compiles"]) == process.MAX_COMPILE_RECORDS
    assert snap["compiles"][0]["s"] == 0.0  # the oldest stay
    assert snap["compiles_dropped"] == 5


def test_startup_span_records_first_occurrence_only(monkeypatch):
    from fgumi_tpu.observe import process

    monkeypatch.setattr(process, "_spans", {})
    trace.arm_spans()
    with process.startup_span("startup.test"):
        pass
    first = process.snapshot()["spans"]["startup.test"]
    with process.startup_span("startup.test"):
        pass
    assert process.snapshot()["spans"]["startup.test"] == first
    assert first["s"] >= 0 and first["at_s"] > 0
    assert _spans()["startup.test"]["count"] == 2


def test_span_aggregate_loses_no_update_under_thread_contention():
    """More threads than cores, a shortened switch interval: every span of
    every thread is in the aggregate, and each thread's parents saw exactly
    their own children."""
    import sys

    trace.arm_spans()
    n_threads, n_spans = 12, 300
    errors = []

    def work():
        try:
            for _ in range(n_spans):
                with trace.span("outer") as outer:
                    with trace.span("inner", wait=True):
                        pass
                    trace.count("outer", "hits")
                assert outer._parent is None
        except BaseException as e:  # noqa: BLE001 - relayed to the assert
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert errors == []
    by = _spans()
    total = n_threads * n_spans
    assert by["outer"]["count"] == by["inner"]["count"] == total
    assert by["outer"]["hits"] == total
    assert by["outer"]["wait_s"] == pytest.approx(by["inner"]["wall_s"])
    assert by["outer"]["self_s"] <= by["outer"]["wall_s"] + 1e-6
