"""Run-report tests: schema validation, golden-file shape, CLI end-to-end
emission (--run-report / --trace), and per-command DeviceStats/metrics reset
so back-to-back in-process invocations don't cross-contaminate."""

import json
import os

import pytest

from fgumi_tpu.cli import main as cli_main
from fgumi_tpu.observe.metrics import METRICS, record_stage_times
from fgumi_tpu.observe.report import (SCHEMA_VERSION, build_report,
                                      validate_report, write_report)
from fgumi_tpu.ops.kernel import DEVICE_STATS
from fgumi_tpu.pipeline import StageTimes

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "run_report_golden.json")


@pytest.fixture
def clean_registries():
    METRICS.reset()
    DEVICE_STATS.reset()
    yield
    METRICS.reset()


# ---------------------------------------------------------------------------
# schema


def test_validate_report_accepts_minimal_valid():
    report = {"schema_version": SCHEMA_VERSION, "tool": "fgumi-tpu",
              "command": "sort", "argv": ["sort"], "started_unix": 1.0,
              "wall_s": 0.5, "exit_status": 0, "pid": 1, "metrics": {}}
    assert validate_report(report) == []


def test_validate_report_flags_problems():
    assert validate_report([]) == ["report is not a JSON object"]
    errs = validate_report({"schema_version": "1"})
    assert any("missing required field" in e for e in errs)
    assert any("'schema_version' has type str" in e for e in errs)
    report = {"schema_version": SCHEMA_VERSION, "tool": "fgumi-tpu",
              "command": "sort", "argv": ["sort"], "started_unix": 1.0,
              "wall_s": 0.5, "exit_status": 0, "pid": 1, "metrics": {},
              "bogus_field": 1}
    assert any("unknown fields" in e for e in validate_report(report))
    report.pop("bogus_field")
    # a report of another schema is refused, older or newer
    for other in (SCHEMA_VERSION + 1, 9):
        report["schema_version"] = other
        assert any(f"schema_version {other}" in e
                   for e in validate_report(report))


# ---------------------------------------------------------------------------
# golden file


def test_report_matches_golden_shape(clean_registries):
    st = StageTimes()
    st.add_busy("read", 0.5)
    st.add_blocked("read", 0.125)
    st.add_busy("process", 0.75)
    st.sample_queues(1, 0)
    st.sample_queues(3, 2)
    record_stage_times(st)
    METRICS.inc("io.bytes_read", 2048)
    METRICS.inc("io.bytes_written", 1024)
    METRICS.inc("records.dedup", 42)
    # the span aggregate, fed exact values: a layer span with rusage and a
    # counter, and a wait below it
    from fgumi_tpu.observe import trace

    trace.stop_trace()
    agg = trace.arm_spans()
    agg.record("engine.pack", 0.25, 0.125, 0.0625,
               rusage=[0.125, 0.0625, 4096],
               counts={"staging_reuses": 1}, thread="fgumi-process")
    agg.record("engine.pack", 0.75, 0.5, 0.0, thread="fgumi-process")
    agg.record("chain.get", 0.0625, 0.0625, 0.0625, thread="fgumi-process")
    # the thread's root spans: work with its clock's deltas, and a wait
    agg.record("pipeline.process", 1.5, 0.4375, 0.125,
               thread="fgumi-process", root=True,
               root_rusage=[0.75, 0.25, 8192])
    agg.record("pipeline.wait_in", 0.5, 0.5, 0.5, thread="fgumi-process",
               root=True)
    agg.record("pipeline.resolve", 0.25, 0.25, 0.0, thread="fgumi-worker-0",
               root=True, root_rusage=[0.0, 0.125, 0])
    try:
        report = build_report(
            "dedup", ["dedup", "-i", "in.bam", "-o", "out.bam"],
            started_unix=1700000000.0, wall_s=1.5, exit_status=0)
    finally:
        trace.stop_trace()
    assert validate_report(report) == []
    # normalize host-specific fields before the golden compare
    report["pid"] = 0
    report.pop("hostname", None)
    # the process-level record is this test process's history: validated
    # above, its shape pinned here
    proc = report["process"]
    assert proc["start_unix"] > 0 and isinstance(proc["compiles"], list)
    assert isinstance(proc["jobs"], list)
    report["process"] = {"start_unix": 0.0, "spans": {}, "compiles": [],
                         "jobs": []}
    assert "alloc" not in report  # only an invocation with --run-report
    # this process imported the kernel module, so the report names a
    # platform — which one depends on whether an earlier test started jax
    assert report.pop("device")["platform"] == "cpu"
    golden = json.load(open(GOLDEN))
    assert report == golden


def test_write_report_is_atomic_and_json(tmp_path, clean_registries):
    out = tmp_path / "report.json"
    report = build_report("sort", ["sort"], 0.0, 0.1, 0)
    write_report(str(out), report)
    loaded = json.loads(out.read_text())
    assert loaded == json.loads(json.dumps(report))
    # no temp residue from the atomic commit
    assert [p for p in os.listdir(tmp_path)] == ["report.json"]


# ---------------------------------------------------------------------------
# CLI end-to-end


@pytest.fixture(scope="module")
def grouped_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("obs") / "grouped.bam")
    assert cli_main(["simulate", "grouped-reads", "-o", path,
                     "--num-families", "20", "--family-size", "3",
                     "--seed", "5"]) == 0
    return path


def _run_simplex(grouped_bam, tmp_path, tag, extra_global=()):
    out = str(tmp_path / f"out_{tag}.bam")
    rpt = str(tmp_path / f"report_{tag}.json")
    rc = cli_main([*extra_global, "--run-report", rpt, "simplex",
                   "-i", grouped_bam, "-o", out, "--min-reads", "1",
                   "--devices", "1"])
    assert rc == 0
    return json.load(open(rpt))


def test_cli_emits_schema_valid_report(grouped_bam, tmp_path):
    trace_path = str(tmp_path / "trace.json")
    report = _run_simplex(grouped_bam, tmp_path, "a",
                          extra_global=("--trace", trace_path))
    assert validate_report(report) == []
    assert report["command"] == "simplex"
    assert report["exit_status"] == 0
    assert report["wall_s"] > 0
    assert report["metrics"]["io.bytes_read"] > 0
    assert report["metrics"]["io.bytes_written"] > 0
    # 20 families x 3 read pairs = 120 input records counted
    assert report["records"]["simplex"] == 120
    assert report["stages"]  # run_stages timings folded in
    assert report["trace_path"] == trace_path
    # the trace on disk is well-formed Chrome trace-event JSON
    obj = json.load(open(trace_path))
    names = {e["name"] for e in obj["traceEvents"] if e["ph"] == "X"}
    assert "pipeline.process" in names
    assert "bgzf.decompress" in names or "bgzf.compress" in names


def test_back_to_back_commands_do_not_cross_contaminate(grouped_bam,
                                                        tmp_path):
    first = _run_simplex(grouped_bam, tmp_path, "b1")
    second = _run_simplex(grouped_bam, tmp_path, "b2")
    # identical work -> identical counters; without the per-command reset
    # the second report would carry doubled records/bytes/dispatch tallies
    assert first["records"] == second["records"]
    assert first["io"]["bytes_read"] == second["io"]["bytes_read"]
    assert first.get("device", {}).get("dispatches") \
        == second.get("device", {}).get("dispatches")


def test_failed_command_still_reports_nonzero_exit(tmp_path):
    rpt = str(tmp_path / "fail.json")
    rc = cli_main(["--run-report", rpt, "simplex", "-i",
                   str(tmp_path / "missing.bam"), "-o",
                   str(tmp_path / "o.bam"), "--min-reads", "0"])
    assert rc == 2
    report = json.load(open(rpt))
    assert validate_report(report) == []
    assert report["exit_status"] == 2


def test_report_env_var_equivalent(grouped_bam, tmp_path, monkeypatch):
    rpt = str(tmp_path / "env.json")
    monkeypatch.setenv("FGUMI_TPU_RUN_REPORT", rpt)
    out = str(tmp_path / "env_out.bam")
    assert cli_main(["simplex", "-i", grouped_bam, "-o", out,
                     "--min-reads", "1", "--devices", "1"]) == 0
    assert validate_report(json.load(open(rpt))) == []


# ---------------------------------------------------------------------------
# schema 9: the spans and process sections (ISSUE 25)


def _minimal(**extra):
    return {"schema_version": SCHEMA_VERSION, "tool": "fgumi-tpu",
            "command": "sort", "argv": ["sort"], "started_unix": 1.0,
            "wall_s": 0.5, "exit_status": 0, "pid": 1, "metrics": {},
            **extra}


def _span_rec(**over):
    rec = {"count": 1, "wall_s": 1.0, "self_s": 0.5, "wait_s": 0.25,
           "p50_s": 1.0, "max_s": 1.0}
    rec.update(over)
    return rec


@pytest.mark.parametrize("spans, problem", [
    ({"job": 1, "by_name": {"a": _span_rec()}}, None),
    ({"job": "j-3", "by_name": {}}, None),
    ({"by_name": {}}, "spans.job"),
    ({"job": 1}, "spans.by_name"),
    ({"job": 1, "by_name": {"a": 3}}, "not an object"),
    ({"job": 1, "by_name": {"a": _span_rec(self_s=2.0)}}, "exceeds wall_s"),
    ({"job": 1, "by_name": {"a": _span_rec(wait_s=1.5)}}, "exceeds wall_s"),
    ({"job": 1, "by_name": {"a": _span_rec(p50_s="x")}}, "missing numeric"),
    # a schema-9 record's list of thread names is a counter like any other
    ({"job": 1, "by_name": {"a": _span_rec(threads=["t"])}}, None),
])
def test_validate_spans_section(spans, problem):
    errs = validate_report(_minimal(spans=spans))
    if problem is None:
        assert errs == []
    else:
        assert any(problem in e for e in errs), errs


@pytest.mark.parametrize("process, problem", [
    ({"start_unix": 1.0, "spans": {}, "compiles": [], "jobs": []}, None),
    ({"start_unix": 1.0, "first_main_s": 0.5,
      "spans": {"startup.jax_import": {"s": 2.0, "at_s": 0.25}},
      "compiles": [{"kind": "cache_load", "s": 0.5, "at_s": 9.0,
                    "shape": "segwfp:1x2", "fun": "jit(fn)"}],
      "compiles_dropped": 2, "jobs": []}, None),
    ({"spans": {}, "compiles": []}, "start_unix"),
    ({"start_unix": 1.0, "first_main_s": "x", "spans": {}, "compiles": []},
     "first_main_s"),
    ({"start_unix": 1.0, "spans": {"a": 1}, "compiles": []},
     "process.spans"),
    ({"start_unix": 1.0, "spans": {}, "compiles": {}}, "process.compiles"),
    ({"start_unix": 1.0, "spans": {},
      "compiles": [{"kind": "jit", "s": 1, "at_s": 1}]}, "entry"),
])
def test_validate_process_section(process, problem):
    errs = validate_report(_minimal(process=process))
    if problem is None:
        assert errs == []
    else:
        assert any(problem in e for e in errs), errs


def test_run_report_alone_arms_spans_and_carries_process(grouped_bam,
                                                         tmp_path):
    """--run-report without --trace: the report has the span aggregate of
    the processing thread's layers and the process record, and no trace."""
    report = _run_simplex(grouped_bam, tmp_path, "spans")
    assert validate_report(report) == []
    assert "trace_path" not in report
    by = report["spans"]["by_name"]
    assert isinstance(report["spans"]["job"], int)
    for name in ("process.decode", "process.group", "process.prep",
                 "pipeline.process", "reader.decode", "sink.write",
                 "resolve.serialize"):
        assert by[name]["count"] >= 1, name
        assert by[name]["self_s"] <= by[name]["wall_s"] + 1e-6
    assert "minflt" in by["process.prep"]
    # the layer spans are children of the wrapper's pulls: what no span
    # covers is the wrapper's self time
    covered = sum(by[n]["wall_s"] for n in by if n.startswith("process."))
    assert covered <= by["pipeline.process"]["wall_s"] + 1e-6
    proc = report["process"]
    assert proc["first_main_s"] >= 0
    assert "startup.native_load" in proc["spans"]


def test_no_flag_run_creates_no_live_span(grouped_bam, tmp_path,
                                          monkeypatch):
    """Neither --trace nor --run-report: every span site, old and new,
    gets the shared no-op (a live span would raise here)."""
    from fgumi_tpu.observe import trace

    def boom(*a, **k):
        raise AssertionError("a live span in an unarmed run")

    monkeypatch.setattr(trace._Span, "__init__", boom)
    monkeypatch.setattr(trace, "arm_spans", boom)
    monkeypatch.delenv("FGUMI_TPU_RUN_REPORT", raising=False)
    monkeypatch.delenv("FGUMI_TPU_TRACE", raising=False)
    out = str(tmp_path / "plain.bam")
    assert cli_main(["simplex", "-i", grouped_bam, "-o", out,
                     "--min-reads", "1", "--devices", "1",
                     "--threads", "4"]) == 0
    assert not trace.tracing_enabled()


def test_device_path_report_has_engine_and_feeder_spans(grouped_bam,
                                                        tmp_path,
                                                        monkeypatch):
    """The XLA device path on the CPU: engine.pack agrees with the
    timeline's pack_s, and the feeder's spans ride the submitter's scope."""
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "device")
    report = _run_simplex(grouped_bam, tmp_path, "dev")
    assert validate_report(report) == []
    by = report["spans"]["by_name"]
    n = report["device"]["dispatches"]
    assert n >= 1
    for name in ("engine.pack", "engine.pack.gather", "engine.pack.wire",
                 "router.decide", "feeder.queue_wait", "feeder.upload",
                 "device.dispatch", "device.fetch", "resolve.wait",
                 "resolve.unpack"):
        assert by[name]["count"] == n, name
    pack = by["engine.pack"]
    assert pack["staging_allocs"] + pack.get("staging_reuses", 0) == n
    inside = pack["wall_s"]
    outside = report["latency"]["device.dispatch.pack_s"]["sum"]
    # the span opens before the stamp's first instant and ends after the
    # stamp is taken (the hand-off to the feeder lies between): on a tiny
    # input under a loaded test host only that order is exact; the chip
    # runs hold the two within 0.2% (PERF.md)
    assert outside - 1e-3 <= inside <= outside + 0.05
    assert by["engine.pack.wire"]["wall_s"] <= inside
    assert by["resolve.wait"]["wait_s"] == \
        pytest.approx(by["resolve.wait"]["wall_s"])
