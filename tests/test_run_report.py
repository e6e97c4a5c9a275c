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
    report["schema_version"] = SCHEMA_VERSION + 1
    assert any("schema_version" in e for e in validate_report(report))


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
    report = build_report("dedup", ["dedup", "-i", "in.bam", "-o", "out.bam"],
                          started_unix=1700000000.0, wall_s=1.5,
                          exit_status=0)
    assert validate_report(report) == []
    # normalize host-specific fields before the golden compare
    report["pid"] = 0
    report.pop("hostname", None)
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
