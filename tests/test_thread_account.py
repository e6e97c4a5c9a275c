"""The thread account of the run report (schema 10, ISSUE 35): the
``threads`` section built from each thread's root spans, ``threads_pacing``,
the ``alloc`` record, the chain's thread names, and that none of it exists
while spans are off."""

import json
import os
import threading
import time

import pytest

from fgumi_tpu.cli import main as cli_main
from fgumi_tpu.observe import alloc, trace
from fgumi_tpu.observe.report import SCHEMA_VERSION, validate_report
from fgumi_tpu.observe.scope import os_thread_name, set_thread_prefix, \
    spawn_thread

#: the run_stages wrapper each stage thread's root spans are
WRAPPERS = ("pipeline.read", "pipeline.process", "pipeline.resolve",
            "pipeline.sink")


@pytest.fixture(autouse=True)
def spans_off_after():
    yield
    trace.stop_trace()


@pytest.fixture(scope="module")
def grouped_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("threads") / "grouped.bam")
    assert cli_main(["simulate", "grouped-reads", "-o", path,
                     "--num-families", "3000", "--family-size", "4",
                     "--seed", "11"]) == 0
    return path


def _simplex(grouped_bam, tmp_path, threads, *flags):
    out = str(tmp_path / f"out{threads}.bam")
    report = str(tmp_path / f"report{threads}.json")
    argv = ["simplex", "-i", grouped_bam, "-o", out, "--min-reads", "1",
            "--threads", str(threads), "--devices", "1",
            "--batch-bytes", str(1 << 20)]
    assert cli_main(["--run-report", report, *flags] + argv) == 0
    with open(report) as f:
        return json.load(f)


def _threads_with(report, root):
    return sorted(t for t, rec in report["threads"].items()
                  if root in rec["roots"])


# ---------------------------------------------------------------------------
# a threaded run_stages job


@pytest.mark.parametrize("threads, stage_threads", [
    (4, {"pipeline.read": ["fgumi-reader"],
         "pipeline.process": ["MainThread"],
         "pipeline.resolve": ["fgumi-worker-0"],
         "pipeline.sink": ["fgumi-writer"]}),
    (2, {"pipeline.read": ["fgumi-reader"],
         "pipeline.process": ["MainThread"],
         "pipeline.resolve": ["fgumi-writer"],
         "pipeline.sink": ["fgumi-writer"]}),
    (0, {name: ["MainThread"] for name in WRAPPERS}),
])
def test_each_stage_thread_has_its_wrapper_as_root(grouped_bam, tmp_path,
                                                   threads, stage_threads):
    report = _simplex(grouped_bam, tmp_path, threads)
    assert report["schema_version"] == SCHEMA_VERSION == 11
    assert validate_report(report) == []
    for wrapper in WRAPPERS:
        assert _threads_with(report, wrapper) == stage_threads[wrapper]
    # one entry a stage thread, and no stage thread without one
    stage = {t for names in stage_threads.values() for t in names}
    assert stage <= set(report["threads"])
    assert not [t for t in report["threads"]
                if t.startswith("fgumi-") and t not in stage
                and t != "fgumi-prefetch"]


@pytest.mark.parametrize("threads", [4, 0])
def test_thread_records_add_up(grouped_bam, tmp_path, threads):
    report = _simplex(grouped_bam, tmp_path, threads)
    by_name = report["spans"]["by_name"]
    for name, rec in report["threads"].items():
        assert rec["work_s"] + rec["wait_s"] == pytest.approx(
            rec["root_wall_s"], abs=2e-6), name
        assert sum(rec["roots"].values()) == pytest.approx(
            rec["work_s"], abs=1e-5), name
        # a root span's own time is part of what the thread did
        assert set(rec["roots"]) <= set(rec["self_s"]), name
    # what the spans section says by name, this one says by thread
    for name, rec in by_name.items():
        by_thread = sum(t["self_s"].get(name, 0.0)
                        for t in report["threads"].values())
        assert by_thread == pytest.approx(rec["self_s"], abs=1e-5), name
    # a record by name no longer lists threads: the section above does
    assert not [n for n, rec in by_name.items() if "threads" in rec]


def test_processing_thread_waits_are_roots_not_work(grouped_bam, tmp_path):
    report = _simplex(grouped_bam, tmp_path, 4)
    main = report["threads"]["MainThread"]
    assert main["roots"]["pipeline.wait_in"] == 0.0
    assert main["wait_s"] >= main["self_s"]["pipeline.wait_in"] - 1e-6
    # the stage figure holds the same pulls, measured round the same calls
    assert main["roots"]["pipeline.process"] == pytest.approx(
        report["stages"]["process"]["busy_s"], rel=0.2, abs=0.05)
    # the reader and the workers read their clock over their roots
    for name in ("fgumi-reader", "fgumi-worker-0", "MainThread"):
        rec = report["threads"][name]
        assert rec["offcpu_s"] <= rec["work_s"] + 1e-6
        assert rec["utime_s"] >= 0 and rec["stime_s"] >= 0


def test_pacing_thread_is_the_one_with_most_work(grouped_bam, tmp_path):
    report = _simplex(grouped_bam, tmp_path, 4)
    pacing = report["threads_pacing"]
    rec = report["threads"][pacing["thread"]]
    assert pacing["work_s"] == rec["work_s"] \
        == max(t["work_s"] for t in report["threads"].values())
    assert pacing["role"] == max(rec["roots"], key=rec["roots"].get)
    assert pacing["role"] in WRAPPERS


def test_alloc_record_brackets_the_job(grouped_bam, tmp_path):
    report = _simplex(grouped_bam, tmp_path, 4)
    start, end = report["alloc"]["start"], report["alloc"]["end"]
    assert 0 < start["maxrss_kb"] <= end["maxrss_kb"]
    assert set(start) == set(end)
    if "arenas" in end:  # glibc
        assert 1 <= start["arenas"] <= end["arenas"]
    if "arena_bytes" in end:  # glibc 2.33+
        assert end["in_use_bytes"] + end["arena_free_bytes"] \
            <= end["arena_bytes"]
        assert end["mmap_chunks"] >= 0 and end["mmap_bytes"] >= 0


def test_trace_alone_reads_no_allocator(grouped_bam, tmp_path, monkeypatch):
    """--trace arms the spans (and so the threads section, were a report
    built) but asks for no report: the allocator is not read."""
    calls = []
    monkeypatch.setattr(alloc, "read", lambda: calls.append(1) or {})
    out = str(tmp_path / "t.bam")
    assert cli_main(["--trace", str(tmp_path / "t.json"), "simplex", "-i",
                     grouped_bam, "-o", out, "--min-reads", "1",
                     "--threads", "4", "--devices", "1"]) == 0
    assert calls == []


# ---------------------------------------------------------------------------
# spans off: nothing new runs


def test_job_without_flags_reads_no_thread_clock_and_no_allocator(
        grouped_bam, tmp_path, monkeypatch):
    calls = {"getrusage": 0, "alloc": 0, "span": 0}

    def counting(key, result=None):
        def call(*a, **k):
            calls[key] += 1
            return result
        return call

    monkeypatch.setattr(trace._resource, "getrusage", counting("getrusage"))
    monkeypatch.setattr(alloc, "read", counting("alloc", {}))
    monkeypatch.setattr(alloc, "_bind", counting("alloc", (None, None)))
    monkeypatch.setattr(trace._Span, "__init__", counting("span"))
    out = str(tmp_path / "off.bam")
    assert cli_main(["simplex", "-i", grouped_bam, "-o", out, "--min-reads",
                     "1", "--threads", "4", "--devices", "1"]) == 0
    assert calls == {"getrusage": 0, "alloc": 0, "span": 0}
    assert trace.span("pipeline.process") is trace.NULL_SPAN
    assert trace.current_aggregate() is None


# ---------------------------------------------------------------------------
# the root-span rule


def _count_getrusage(monkeypatch):
    real = trace._resource.getrusage
    calls = []

    def counted(who):
        calls.append(who)
        return real(who)

    monkeypatch.setattr(trace._resource, "getrusage", counted)
    return calls


@pytest.mark.parametrize("name, kw, reads", [
    ("pipeline.resolve", {}, 2),            # a root: the thread's clock
    ("engine.pack", {"rusage": True}, 2),   # the call site's, once
    ("pipeline.wait_in", {"wait": True}, 0),  # a wait is no work to clock
    ("bgzf.decompress", {}, 0),             # per-block I/O
    ("io.prefetch.read", {}, 0),
])
def test_root_span_reads_the_thread_clock(monkeypatch, name, kw, reads):
    calls = _count_getrusage(monkeypatch)
    agg = trace.arm_spans()
    with trace.span(name, **kw):
        with trace.span("child"):  # not a root: reads nothing
            pass
    assert len(calls) == reads
    snap = agg.snapshot()
    rec = snap["threads"][threading.current_thread().name]
    assert list(rec["roots"]) == [name]
    assert ("utime_s" in rec) == bool(reads) == ("offcpu_s" in rec)
    # the name's own record has the deltas only where the call site asked
    assert ("utime_s" in snap["by_name"][name]) == bool(kw.get("rusage"))
    assert "utime_s" not in snap["by_name"]["child"]


def test_child_with_rusage_is_no_root(monkeypatch):
    calls = _count_getrusage(monkeypatch)
    agg = trace.arm_spans()
    with trace.span("pipeline.process"):
        with trace.span("process.prep", rusage=True):
            pass
    assert len(calls) == 4
    rec = agg.snapshot()["threads"][threading.current_thread().name]
    assert list(rec["roots"]) == ["pipeline.process"]
    assert set(rec["self_s"]) == {"pipeline.process", "process.prep"}


def test_wait_inside_a_root_is_wait_not_work():
    agg = trace.arm_spans()
    with trace.span("pipeline.resolve"):
        with trace.span("resolve.unpack"):
            with trace.span("resolve.wait", wait=True):
                time.sleep(0.05)
    with trace.span("pipeline.wait_out", wait=True):
        time.sleep(0.02)
    rec = agg.snapshot()["threads"][threading.current_thread().name]
    assert rec["wait_s"] >= 0.07 - 1e-3
    assert rec["work_s"] == pytest.approx(rec["root_wall_s"] - rec["wait_s"],
                                          abs=2e-6)
    assert rec["work_s"] < 0.03
    assert rec["roots"]["pipeline.wait_out"] == 0.0


def test_interval_from_another_thread_is_no_root():
    """``feeder.queue_wait`` began where the batch was submitted: it is in
    the ending thread's ``self_s`` (so the two sections agree) and in no
    thread's wall."""
    agg = trace.arm_spans()
    t0 = time.monotonic()
    trace.record_interval("feeder.queue_wait", t0 - 0.5, t0)
    rec = agg.snapshot()["threads"][threading.current_thread().name]
    assert rec["self_s"] == {"feeder.queue_wait": 0.5}
    assert rec["roots"] == {} and rec["root_wall_s"] == 0.0
    assert "utime_s" not in rec


def _in_thread(fn, name):
    th = threading.Thread(target=fn, name=name)
    th.start()
    th.join(timeout=60)
    assert not th.is_alive()


def test_sleeping_thread_is_off_cpu_spinning_thread_is_on():
    """The tolerances are for a clock that ticks in 10 ms steps (the chip's
    host) and a sandbox whose cores are shared: the spinner spins until its
    own CPU clock has 0.2 s, so its share is not the scheduler's to take."""
    agg = trace.arm_spans()

    def sleeps():
        with trace.span("acct.sleeps"):
            time.sleep(0.3)

    def spins():
        with trace.span("acct.spins"):
            t0 = time.thread_time()
            while time.thread_time() - t0 < 0.2:
                pass

    _in_thread(sleeps, "acct-sleeper")
    _in_thread(spins, "acct-spinner")
    threads = agg.snapshot()["threads"]
    sleeper, spinner = threads["acct-sleeper"], threads["acct-spinner"]
    assert sleeper["work_s"] >= 0.3 and sleeper["wait_s"] == 0.0
    assert sleeper["utime_s"] + sleeper["stime_s"] <= 0.05
    assert sleeper["offcpu_s"] >= sleeper["work_s"] - 0.05
    assert spinner["utime_s"] + spinner["stime_s"] >= 0.17
    assert spinner["offcpu_s"] <= spinner["work_s"] - 0.17
    assert spinner["offcpu_s"] == pytest.approx(max(
        spinner["work_s"] - spinner["utime_s"] - spinner["stime_s"], 0.0),
        abs=2e-6)


def test_fetch_runner_wait_is_a_declared_wait():
    """A resolver's wait for its deadline-bounded helper thread is a wait
    span, so a worker's ``work_s`` holds none of the fetch."""
    from fgumi_tpu.ops.kernel import _DeadlineRunner

    agg = trace.arm_spans()
    runner = _DeadlineRunner("acct-helper", "device.fetch_wait")

    def fetch():
        with trace.span("device.fetch"):
            time.sleep(0.05)
        return 7

    with trace.span("pipeline.resolve"):
        assert runner.run(fetch, 30.0, "test fetch") == 7
        assert runner.run(lambda: 8, None, "inline: no helper") == 8
    snap = agg.snapshot()
    me = snap["threads"][threading.current_thread().name]
    assert snap["by_name"]["device.fetch_wait"]["count"] == 1
    assert me["wait_s"] >= 0.05 - 1e-3
    assert me["work_s"] <= me["root_wall_s"] - 0.05 + 1e-3
    assert snap["threads"]["acct-helper-1"]["roots"]["device.fetch"] >= 0.05


# ---------------------------------------------------------------------------
# names


@pytest.mark.parametrize("name, os_name", [
    ("fgumi-reader", "fgumi-reader"),
    ("fgumi-device-feeder", "device-feeder"),
    ("fgumi-device-fetch-1", "device-fetch-1"),
    ("chain-simplex-reader", "simplex-reader"),
    ("chain-simplex-worker-0", "simplexworker-0"),
    ("chain-simplex-worker-1", "simplexworker-1"),
    ("chain-group-writer", "group-writer"),
])
def test_os_thread_name_fits_and_keeps_workers_apart(name, os_name):
    assert os_thread_name(name) == os_name
    assert len(os_name.encode()) <= 15


def test_thread_prefix_renames_helpers_of_its_context_only():
    seen = []

    def stage():
        set_thread_prefix("chain-simplex")
        th = spawn_thread(lambda: seen.append(
            threading.current_thread().name), name="fgumi-reader")
        th.start()
        th.join(timeout=10)
        assert spawn_thread(lambda: None, name="other").name == "other"

    outer = spawn_thread(stage, name="fgumi-chain-simplex")
    outer.start()
    outer.join(timeout=10)
    assert seen == ["chain-simplex-reader"]
    # the prefix lived in the stage thread's context copy
    assert spawn_thread(lambda: None, name="fgumi-reader").name \
        == "fgumi-reader"


@pytest.fixture(scope="module")
def fastq_inputs(tmp_path_factory):
    d = tmp_path_factory.mktemp("threads_fq")
    r1, r2 = str(d / "r1.fq.gz"), str(d / "r2.fq.gz")
    assert cli_main(["simulate", "fastq-reads", "-1", r1, "-2", r2,
                     "--num-families", "50", "--family-size", "4",
                     "--read-length", "80", "--error-rate", "0.005",
                     "--seed", "23"]) == 0
    return r1, r2


def test_chain_threads_carry_their_stage(fastq_inputs, tmp_path,
                                         monkeypatch):
    from fgumi_tpu.native import batch as nb

    if not nb.available():
        pytest.skip("fused chain requires the native lib")
    flags = os.environ.get("XLA_FLAGS", "")
    monkeypatch.setenv("XLA_FLAGS", " ".join(
        f for f in flags.split() if "host_platform_device_count" not in f))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    r1, r2 = fastq_inputs
    report = str(tmp_path / "chain.json")
    assert cli_main(["--run-report", report, "pipeline", "-i", r1, r2, "-r",
                     "8M+T", "+T", "--sample", "s", "--library", "l", "-o",
                     str(tmp_path / "chain.bam"), "--filter-min-reads", "2",
                     "--threads", "4"]) == 0
    with open(report) as f:
        rep = json.load(f)
    assert validate_report(rep) == []
    threads = rep["threads"]
    # every stage thread's life is one root span named after the stage
    for stage in ("extract", "sort", "group", "simplex", "filter"):
        assert list(threads[f"fgumi-chain-{stage}"]["roots"]) \
            == [f"chain.{stage}"]
    # and the threads run_stages starts inside a stage are that stage's
    assert _threads_with(rep, "pipeline.resolve") \
        == ["chain-simplex-worker-0"]
    assert "chain-simplex-reader" in _threads_with(rep, "pipeline.read")
    assert "chain-simplex-writer" in _threads_with(rep, "pipeline.sink")
    assert not [t for t in threads
                if t in ("fgumi-reader", "fgumi-writer", "fgumi-worker-0")]
    # under a stage's life span the processing pulls are no roots
    assert _threads_with(rep, "pipeline.process") == []
    assert rep["threads_pacing"]["role"].startswith(("chain.", "pipeline."))


# ---------------------------------------------------------------------------
# the allocator record


def test_alloc_read_names_what_glibc_gives():
    rec = alloc.read()
    assert rec["maxrss_kb"] > 0
    assert all(isinstance(v, int) for v in rec.values())
    import platform

    libc, version = platform.libc_ver()
    if libc == "glibc" and tuple(map(int, version.split("."))) >= (2, 33):
        assert rec["arenas"] >= 1
        assert rec["arena_bytes"] >= rec["in_use_bytes"] > 0


def test_alloc_read_leaves_out_what_the_c_library_lacks(monkeypatch):
    """Absent, not zero: a reader must not take a missing counter for an
    empty allocator."""
    monkeypatch.setattr(alloc, "_libc", (None, None))
    assert set(alloc.read()) == {"maxrss_kb"}


def test_alloc_arena_count_follows_threads_that_allocate():
    if "arenas" not in alloc.read():
        pytest.skip("no malloc_info in this C library")
    before = alloc.read()["arenas"]
    go = threading.Event()

    def hold():
        block = [bytearray(4096) for _ in range(64)]
        go.wait(10)
        del block

    ths = [threading.Thread(target=hold) for _ in range(3)]
    for th in ths:
        th.start()
    time.sleep(0.05)
    during = alloc.read()["arenas"]
    go.set()
    for th in ths:
        th.join(timeout=10)
    assert during >= before  # an arena, once made, stays
    assert alloc.read()["arenas"] >= during


# ---------------------------------------------------------------------------
# schema 10


def _minimal(**extra):
    return {"schema_version": SCHEMA_VERSION, "tool": "fgumi-tpu",
            "command": "sort", "argv": ["sort"], "started_unix": 1.0,
            "wall_s": 0.5, "exit_status": 0, "pid": 1, "metrics": {},
            **extra}


def _thread(**over):
    rec = {"root_wall_s": 1.0, "wait_s": 0.25, "work_s": 0.75,
           "utime_s": 0.5, "stime_s": 0.125, "offcpu_s": 0.125, "roots": {"pipeline.process": 0.75},
           "self_s": {"pipeline.process": 0.75}}
    rec.update(over)
    return {k: v for k, v in rec.items() if v is not None}


UNCLOCKED = dict(utime_s=None, stime_s=None, offcpu_s=None)


@pytest.mark.parametrize("extra, problem", [
    ({"threads": {"t": _thread()}}, None),
    ({"threads": {"t": _thread(**UNCLOCKED)}}, None),
    ({"threads": {"t": _thread()},
      "threads_pacing": {"thread": "t", "role": "pipeline.process",
                         "work_s": 0.75}}, None),
    ({"threads": {"t": 3}}, "not an object"),
    ({"threads": {"t": _thread(work_s="x")}}, "missing numeric"),
    ({"threads": {"t": _thread(work_s=0.5)}}, "is not root_wall_s"),
    ({"threads": {"t": _thread(offcpu_s=None)}}, "and not all"),
    ({"threads": {"t": _thread(roots=["pipeline.process"])}},
     "roots or self_s"),
    ({"threads": {"t": _thread(self_s={"a": "x"})}}, "roots or self_s"),
    ({"threads": {"t": _thread()},
      "threads_pacing": {"thread": "u", "role": "r", "work_s": 1.0}},
     "threads_pacing"),
    ({"threads_pacing": {"thread": "t", "role": "r", "work_s": 1.0}},
     "without a threads section"),
    ({"threads": {"t": _thread()},
      "spans": {"job": 0, "by_name": {"pipeline.process": {
          "count": 1, "wall_s": 1.0, "self_s": 0.5, "wait_s": 0.25,
          "p50_s": 1.0, "max_s": 1.0}}}}, "sums to"),
    ({"alloc": {"start": {"maxrss_kb": 1}, "end": {"maxrss_kb": 2,
                                                   "arenas": 3}}}, None),
    ({"alloc": {"start": {"maxrss_kb": 1}}}, "alloc.end"),
    ({"alloc": {"start": {"arenas": 1}, "end": {"maxrss_kb": 2}}},
     "alloc.start"),
    ({"alloc": {"start": {"maxrss_kb": 1}, "end": {"maxrss_kb": "x"}}},
     "alloc.end"),
    ({"alloc": {"start": {"maxrss_kb": 1}, "end": {"maxrss_kb": 2},
                "middle": {}}}, "alloc unknown"),
])
def test_validate_threads_and_alloc_sections(extra, problem):
    errs = validate_report(_minimal(**extra))
    if problem is None:
        assert errs == []
    else:
        assert any(problem in e for e in errs), errs


def test_schema_9_report_is_refused():
    errs = validate_report(_minimal(schema_version=9))
    assert errs == ["schema_version 9 != 11"]
