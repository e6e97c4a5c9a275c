"""A job's phases (run report schema 11): the ``job.open`` / ``job.fill`` /
``job.drain`` / ``job.close`` spans of the command thread and the always-on
job record of the process record, the resolve worker's ``queue.worker_get``
wait, and the phases in the latency decomposition."""

import json
import os
import threading
import time

import pytest

from fgumi_tpu.cli import main as cli_main
from fgumi_tpu.observe import process, trace
from fgumi_tpu.observe.report import SCHEMA_VERSION, validate_report
from fgumi_tpu.pipeline import run_stages

PHASE_FIELDS = ("before_arm_s", "open_s", "fill_s", "drain_s", "close_s",
                "report_s")


@pytest.fixture(autouse=True)
def spans_off_after():
    yield
    trace.stop_trace()


@pytest.fixture(scope="module")
def grouped_bams(tmp_path_factory):
    d = tmp_path_factory.mktemp("jobs")
    paths = {}
    for n in (1500, 6000):
        paths[n] = str(d / f"grouped{n}.bam")
        assert cli_main(["simulate", "grouped-reads", "-o", paths[n],
                         "--num-families", str(n), "--family-size", "4",
                         "--seed", "11"]) == 0
    return paths


def _simplex_argv(bam, out, threads):
    return ["simplex", "-i", bam, "-o", out, "--min-reads", "1",
            "--threads", str(threads), "--devices", "1",
            "--batch-bytes", str(1 << 18)]


def _reported_job(bam, tmp_path, threads):
    """Run one simplex job with a run report: its report and its entry of
    the process record."""
    report = str(tmp_path / f"report{threads}.json")
    argv = _simplex_argv(bam, str(tmp_path / f"out{threads}.bam"), threads)
    assert cli_main(["--run-report", report] + argv) == 0
    entry = process.snapshot()["jobs"][-1]
    with open(report) as f:
        return json.load(f), entry


def _command_thread(report):
    (name,) = [t for t, rec in report["threads"].items()
               if "job.open" in rec["roots"]]
    return name


# ---------------------------------------------------------------------------
# the phases tile the job


@pytest.mark.parametrize("threads", [4, 2, 0])
def test_phases_and_pipeline_roots_tile_the_job(grouped_bams, tmp_path,
                                                threads):
    report, entry = _reported_job(grouped_bams[6000], tmp_path, threads)
    assert report["schema_version"] == SCHEMA_VERSION == 11
    assert validate_report(report) == []
    assert set(entry) == {"at_s", "wall_s", *PHASE_FIELDS}
    assert sum(entry[f] for f in PHASE_FIELDS) <= entry["wall_s"] + 1e-5
    # the job's own report was made before its entry was complete: the
    # entry began before the report's start and ended after it
    start = report["process"]["start_unix"]
    assert start + entry["at_s"] <= report["started_unix"] + 1e-3
    assert start + entry["at_s"] + entry["wall_s"] >= report["started_unix"]
    cmd = _command_thread(report)
    assert cmd == threading.current_thread().name
    rec = report["threads"][cmd]
    assert "pipeline.process" in rec["roots"]
    by = report["spans"]["by_name"]
    for phase in ("open", "close"):
        assert by[f"job.{phase}"]["count"] == 1
        assert "utime_s" in by[f"job.{phase}"]  # work, with its clock
        assert by[f"job.{phase}"]["wall_s"] == pytest.approx(
            entry[f"{phase}_s"], abs=2e-3)
    if threads >= 2:
        # the command thread waits through the fill and the drain
        for phase in ("fill", "drain"):
            assert by[f"job.{phase}"]["count"] == 1
            assert by[f"job.{phase}"]["wait_s"] == pytest.approx(
                by[f"job.{phase}"]["wall_s"])
            assert rec["roots"][f"job.{phase}"] == 0.0
            assert "utime_s" not in by[f"job.{phase}"]
            assert entry[f"{phase}_s"] > 0
    else:
        # inline, this thread makes every item itself: nothing to wait for
        assert "job.fill" not in by and "job.drain" not in by
        assert entry["fill_s"] == entry["drain_s"] == 0.0
    # job.unnamed_share's arithmetic: what of the wall, less the report's
    # making, neither before_arm nor the command thread's roots cover
    wall = entry["wall_s"] - entry["report_s"]
    unnamed = wall - entry["before_arm_s"] - rec["root_wall_s"]
    assert -1e-3 <= unnamed <= 0.03 * wall, (unnamed, wall)
    # no other thread has a job phase
    assert not [t for t, r in report["threads"].items() if t != cmd
                and any(n.startswith("job.") for n in r["self_s"])]


def test_phases_in_the_latency_decomposition(grouped_bams, tmp_path):
    report, entry = _reported_job(grouped_bams[6000], tmp_path, 4)
    dec = report["latency_decomposition"]
    for phase in ("open", "fill", "drain"):
        assert dec[f"{phase}_s"] == pytest.approx(entry[f"{phase}_s"],
                                                  abs=1e-5)
    # the close net of the commit it holds
    commit = report["latency"]["io.commit_s"]["sum"]
    assert dec["commit_s"] == pytest.approx(commit, abs=1e-5)
    assert dec["close_s"] == pytest.approx(entry["close_s"] - commit,
                                           abs=1e-4)
    comp = sum(v for k, v in dec.items() if k != "total_s")
    assert comp <= dec["total_s"] + 0.005
    # the residual is the pipeline's run, less the device's share of it
    assert dec["host_complete_s"] <= entry["wall_s"] - entry["open_s"] \
        - entry["fill_s"] - entry["drain_s"]


def test_the_first_report_carries_the_jobs_before_it(grouped_bams,
                                                     tmp_path):
    """The jobs a process runs without a report leave their entries, which
    the next report carries."""
    before = len(process.snapshot()["jobs"])
    for k in range(2):
        assert cli_main(_simplex_argv(
            grouped_bams[1500], str(tmp_path / f"warm{k}.bam"), 4)) == 0
    report, entry = _reported_job(grouped_bams[1500], tmp_path, 4)
    jobs = report["process"]["jobs"]
    assert len(jobs) == before + 2
    for warm in jobs[-2:]:
        assert warm["fill_s"] > 0 and warm["drain_s"] > 0
        assert warm["report_s"] < 0.05  # no report to make
    assert process.snapshot()["jobs"][-1] == entry
    assert entry["report_s"] > 0


# ---------------------------------------------------------------------------
# spans off: the record alone, at a fixed cost a job


def test_spans_off_no_span_and_a_fixed_number_of_clock_reads(
        grouped_bams, tmp_path, monkeypatch):
    made = []
    monkeypatch.setattr(trace._Span, "__init__",
                        lambda *a, **k: made.append(1))
    reads = []
    real = process._clock

    def counted():
        reads.append(1)
        return real()

    monkeypatch.setattr(process, "_clock", counted)
    per_job = []
    for n, bam in sorted(grouped_bams.items()):
        del reads[:]
        assert cli_main(_simplex_argv(bam, str(tmp_path / f"o{n}.bam"),
                                      4)) == 0
        per_job.append(len(reads))
        entry = process.snapshot()["jobs"][-1]
        assert entry["fill_s"] > 0 and entry["drain_s"] > 0
    assert made == []
    # entry, open, fill, run, drain, close, report, end: whatever the
    # number of batches (the larger input has four times as many)
    assert per_job == [8, 8]
    assert trace.span("job.open") is trace.NULL_SPAN


def test_job_record_cap_and_dropped_count(monkeypatch):
    monkeypatch.setattr(process, "_jobs", [])
    monkeypatch.setattr(process, "_jobs_dropped", 0)
    monkeypatch.setattr(process, "MAX_JOB_RECORDS", 3)
    for _ in range(5):
        with process.job_record() as job:
            assert process.current_job() is job
        assert process.current_job() is None
    snap = process.snapshot()
    assert len(snap["jobs"]) == 3 and snap["jobs_dropped"] == 2
    ends = [process.job_end(e) for e in snap["jobs"]]
    assert ends == sorted(ends)
    assert process.MAX_JOB_RECORDS == 3  # the patched cap was the one read


def test_default_cap_is_the_compile_records():
    assert process.MAX_JOB_RECORDS == process.MAX_COMPILE_RECORDS == 128


def test_entries_stay_in_the_order_the_jobs_ended(monkeypatch):
    """A daemon's jobs overlap: the one that began first may end last."""
    monkeypatch.setattr(process, "_jobs", [])
    first = process.JobClock()
    time.sleep(0.01)
    with process.job_record():
        time.sleep(0.01)
    with process._lock:
        process._jobs.append(first.entry())
        process._jobs.sort(key=process.job_end)
    jobs = process.snapshot()["jobs"]
    assert jobs[0]["at_s"] > jobs[1]["at_s"]
    assert validate_report(_minimal(process=_proc(jobs))) == []


def test_job_clock_phases_sum_to_no_more_than_its_wall():
    job = process.JobClock()
    for phase in ("open", "fill", None, "drain", "close", "report"):
        job.begin(phase)
        time.sleep(0.002)
    entry = job.entry()
    assert sum(entry[f] for f in PHASE_FIELDS) <= entry["wall_s"]
    assert entry["fill_s"] >= 0.002 and entry["report_s"] >= 0.002


@pytest.mark.parametrize("phase, owner", [
    ("open", "here"), ("fill", None), ("open", "thread")])
def test_job_here_only_on_its_thread_while_opening(phase, owner):
    with process.job_record() as job:
        job.begin(phase)
        if owner == "here":
            assert process.job_here() is job
        elif owner is None:
            assert process.job_here() is None
        else:
            seen = []
            th = threading.Thread(target=lambda: seen.append(
                process.job_here()))
            th.start()
            th.join(timeout=10)
            assert not th.is_alive() and seen == [None]
    with process.job_record(nested=True) as nested:
        assert nested is None and process.job_here() is None


# ---------------------------------------------------------------------------
# the resolve worker's wait


def test_worker_get_holds_the_workers_idle_time():
    agg = trace.arm_spans()

    def slow_source():
        for i in range(6):
            time.sleep(0.04)
            yield i

    def resolve(x):
        t0 = time.thread_time()
        while time.thread_time() - t0 < 0.005:
            pass
        return x

    out = []
    run_stages(slow_source(), lambda x: [x], out.append, threads=4,
               resolve_fn=resolve, resolve_workers=1)
    assert out == list(range(6))
    (worker,) = [rec for rec in agg.snapshot()["threads"].values()
                 if "pipeline.resolve" in rec["roots"]]
    # starved by the source: most of its life in the pool is the wait
    got = worker["self_s"]["queue.worker_get"]
    assert got >= 0.15
    assert worker["roots"]["queue.worker_get"] == 0.0
    assert worker["wait_s"] >= got - 1e-6
    # its work is what it was: the resolves, no part of the wait
    assert worker["work_s"] == pytest.approx(
        worker["roots"]["pipeline.resolve"], abs=1e-5)
    assert worker["work_s"] < 0.15
    assert worker["root_wall_s"] == pytest.approx(
        worker["work_s"] + worker["wait_s"], abs=2e-6)


# ---------------------------------------------------------------------------
# a chained command's stages open no phase


@pytest.fixture(scope="module")
def fastq_inputs(tmp_path_factory):
    d = tmp_path_factory.mktemp("jobs_fq")
    r1, r2 = str(d / "r1.fq.gz"), str(d / "r2.fq.gz")
    assert cli_main(["simulate", "fastq-reads", "-1", r1, "-2", r2,
                     "--num-families", "50", "--family-size", "4",
                     "--read-length", "80", "--error-rate", "0.005",
                     "--seed", "23"]) == 0
    return r1, r2


@pytest.mark.parametrize("fuse", [True, False])
def test_nested_pipeline_stage_opens_no_job_span(fastq_inputs, tmp_path,
                                                 monkeypatch, fuse):
    from fgumi_tpu.native import batch as nb

    if fuse and not nb.available():
        pytest.skip("fused chain requires the native lib")
    flags = os.environ.get("XLA_FLAGS", "")
    monkeypatch.setenv("XLA_FLAGS", " ".join(
        f for f in flags.split() if "host_platform_device_count" not in f))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    r1, r2 = fastq_inputs
    report = str(tmp_path / "chain.json")
    argv = ["--run-report", report, "pipeline", "-i", r1, r2, "-r", "8M+T",
            "+T", "--sample", "s", "--library", "l", "-o",
            str(tmp_path / "chain.bam"), "--filter-min-reads", "2",
            "--threads", "4"]
    if not fuse:
        argv.append("--no-fuse")
    assert cli_main(argv) == 0
    with open(report) as f:
        rep = json.load(f)
    assert validate_report(rep) == []
    by = rep["spans"]["by_name"]
    # the outer invocation's phases only: no stage has a fill or a drain of
    # its own, whichever thread it runs on; the staged driver's stages run
    # on its own thread, all inside its open
    assert by["job.open"]["count"] == 1
    assert by.get("job.close", {"count": 0})["count"] == int(fuse)
    assert "job.fill" not in by and "job.drain" not in by
    cmd = _command_thread(rep)
    assert cmd == threading.current_thread().name
    assert not [t for t, r in rep["threads"].items() if t != cmd
                and any(n.startswith("job.") for n in r["self_s"])]
    entry = process.snapshot()["jobs"][-1]
    assert entry["fill_s"] == entry["drain_s"] == 0.0
    if fuse:
        # open ends as the stage threads start, close begins at their join
        assert entry["open_s"] + entry["close_s"] < 0.9 * entry["wall_s"]
    else:
        assert entry["close_s"] == 0.0
        assert entry["open_s"] > 0.5 * entry["wall_s"]


# ---------------------------------------------------------------------------
# schema 11


def _minimal(**extra):
    return {"schema_version": SCHEMA_VERSION, "tool": "fgumi-tpu",
            "command": "sort", "argv": ["sort"], "started_unix": 1.0,
            "wall_s": 0.5, "exit_status": 0, "pid": 1, "metrics": {},
            **extra}


def _entry(**over):
    rec = {"at_s": 1.0, "wall_s": 2.0, "before_arm_s": 0.25, "open_s": 0.25,
           "fill_s": 0.125, "drain_s": 0.125, "close_s": 0.25,
           "report_s": 0.0}
    rec.update(over)
    return {k: v for k, v in rec.items() if v is not None}


def _proc(jobs, **extra):
    return {"start_unix": 1.0, "spans": {}, "compiles": [], "jobs": jobs,
            **extra}


@pytest.mark.parametrize("proc, problem", [
    (_proc([]), None),
    (_proc([_entry(), _entry(at_s=2.5)]), None),
    (_proc([_entry()], jobs_dropped=4), None),
    (_proc([_entry(wall_s=0.75)]), "past its wall_s"),
    (_proc([_entry(at_s=2.5), _entry()]), "ended before"),
    (_proc([_entry(close_s=None)]), "is not"),
    (_proc([_entry(extra_s=0.5)]), "is not"),
    (_proc([_entry(open_s="x")]), "is not"),
    (_proc([_entry(open_s=-0.5)]), "is not"),
    (_proc([3]), "is not"),
    (_proc({}), "process.jobs is not a list"),
    ({"start_unix": 1.0, "spans": {}, "compiles": []},
     "process.jobs is not a list"),
    (_proc([], jobs_dropped=0), "jobs_dropped"),
    (_proc([], jobs_dropped="x"), "jobs_dropped"),
])
def test_validate_process_jobs(proc, problem):
    errs = validate_report(_minimal(process=proc))
    if problem is None:
        assert errs == []
    else:
        assert any(problem in e for e in errs), errs


def test_schema_10_report_is_refused():
    errs = validate_report(_minimal(schema_version=10))
    assert errs == ["schema_version 10 != 11"]
