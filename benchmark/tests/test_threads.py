"""The thread and allocator readers (``benchmark/threads.py`` and the eight
metric files on it): each on a hand-made ``run`` record with schema-10
reports, each ``None`` case, and a rehearsal against the program itself.
Runs on the CPU."""

import importlib.util
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.dirname(ROOT)]

import pytest  # noqa: E402

import threads  # noqa: E402

READERS = ("thread.process_work_s_per_mread", "thread.worker_work_s_per_mread",
           "thread.reader_work_s_per_mread", "thread.process_offcpu_share",
           "thread.worker_offcpu_share", "thread.worker_sys_share",
           "alloc.arena_free_growth_gb_per_job", "alloc.arenas")
SHARES = tuple(n for n in READERS if n.endswith("_share"))


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(ROOT, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def thread(root, work, wait=0.0, utime=None, stime=0.0, extra_roots=()):
    """A ``threads`` record: ``work`` seconds under ``root``; the clock
    fields only where ``utime`` is given."""
    rec = {"root_wall_s": work + wait, "wait_s": wait, "work_s": work,
           "roots": {root: work, **{r: 0.0 for r in extra_roots}},
           "self_s": {root: work}}
    if utime is not None:
        rec.update(utime_s=utime, stime_s=stime,
                   offcpu_s=max(work - utime - stime, 0.0))
    return rec


def job(process=1.0, workers=(0.5, 0.75), reader=0.25, arenas=9,
        free=250_000_000, **over):
    """One job's report: a processing thread, resolve workers, a reader, a
    writer, the feeder; half of every thread's work on the CPU, a tenth of
    the workers' in the kernel."""
    ths = {
        "MainThread": thread("pipeline.process", process, wait=0.5,
                             utime=process / 2,
                             extra_roots=("pipeline.wait_in",)),
        "fgumi-reader": thread("pipeline.read", reader, utime=reader / 2),
        "fgumi-writer": thread("pipeline.sink", 0.125, utime=0.0625),
        "fgumi-device-feeder": thread("device.dispatch", 0.0625, utime=0.0)}
    for i, w in enumerate(workers):
        ths[f"fgumi-worker-{i}"] = thread(
            "pipeline.resolve", w, wait=0.25, utime=0.4 * w, stime=0.1 * w)
    ths.update(over)
    return {"threads": ths,
            "alloc": {"start": {"maxrss_kb": 1000},
                      "end": {"maxrss_kb": 2000, "arenas": arenas,
                              "arena_free_bytes": free}}}


def a_run(reports, traced=3):
    return {"reports": reports, "traced_jobs": traced,
            "reads_per_job": 500_000}


SLOW = dict(process=9.0, workers=(9.0, 9.0), reader=9.0, arenas=99, free=0)


def full_run():
    """Three traced jobs and the one that holds the profiler's stop (all
    slow: no reader may read them), then two jobs on their own."""
    return a_run([job(**SLOW)] * 4
                 + [job(), job(process=1.5, workers=(1.25, 0.25),
                               reader=0.5, arenas=11, free=750_000_000)])


PRESENT = {
    # mean over the two jobs read, per half a million reads
    "thread.process_work_s_per_mread": (1.0 + 1.5) / 2 / 0.5,
    "thread.worker_work_s_per_mread": (0.75 + 1.25) / 2 / 0.5,
    "thread.reader_work_s_per_mread": (0.25 + 0.5) / 2 / 0.5,
    "thread.process_offcpu_share": 50.0,
    "thread.worker_offcpu_share": 50.0,
    "thread.worker_sys_share": 10.0,
    # the growth from the end of the first job read to the last's, a job
    "alloc.arena_free_growth_gb_per_job": 0.5,
    # the last job read
    "alloc.arenas": 11,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_present(name):
    assert metric(name)(full_run()) == pytest.approx(PRESENT[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_without_the_sections_is_none(name):
    """Reports of a program from before the thread account, and a window
    that held no job: nothing is read and nothing raises."""
    old = a_run([{"stages": {"process": {"busy_s": 1.0}}}] * 6)
    assert metric(name)(old) is None
    assert metric(name)(a_run([], traced=0)) is None


@pytest.mark.parametrize("name", [n for n in READERS
                                  if n.startswith("thread.")])
def test_reader_role_absent_is_none(name):
    """The chain's case: every ``pipeline.process`` pull lies under a
    stage's life span, so no thread has it as a root; and a job whose
    resolve ran inline has no worker."""
    role = {"process": "MainThread", "worker": "fgumi-worker-",
            "reader": "fgumi-reader"}[name.split(".")[1].split("_")[0]]
    reports = []
    for _ in range(6):
        rep = job()
        rep["threads"] = {t: rec for t, rec in rep["threads"].items()
                          if not t.startswith(role)}
        reports.append(rep)
    assert metric(name)(a_run(reports)) is None
    # and missing in one job read is missing
    assert metric(name)(a_run([job()] * 5 + reports[:1])) is None


@pytest.mark.parametrize("name", SHARES)
def test_share_on_a_clock_that_does_not_tick_is_none(name):
    """A thread that worked over 0.1 s and shows no CPU second: the host's
    thread clock stands still, and a share of it would be 100% of nothing."""
    still = {"MainThread": thread("pipeline.process", 1.0, utime=0.0),
             "fgumi-worker-0": thread("pipeline.resolve", 0.5, utime=0.0),
             "fgumi-worker-1": thread("pipeline.resolve", 0.75, utime=0.4)}
    run = a_run([job()] * 4 + [job(), job(**still)])
    assert metric(name)(run) is None
    # a thread under the floor may well show none on a 10 ms tick
    short = {"MainThread": thread("pipeline.process", 0.05, utime=0.0),
             "fgumi-worker-0": thread("pipeline.resolve", 0.05, utime=0.0)}
    run = a_run([job()] * 4 + [job(), job(workers=(0.75,), **short)])
    assert metric(name)(run) is not None


@pytest.mark.parametrize("name", SHARES)
def test_share_without_clock_fields_is_none(name):
    """Roots that read no ``getrusage`` (per-block I/O): the record has no
    clock fields, and no share."""
    bare = {"MainThread": thread("pipeline.process", 1.0),
            "fgumi-worker-0": thread("pipeline.resolve", 0.5)}
    run = a_run([job()] * 4 + [job(**bare), job()])
    assert metric(name)(run) is None


def test_work_readers_need_no_clock():
    bare = {"MainThread": thread("pipeline.process", 1.0),
            "fgumi-worker-0": thread("pipeline.resolve", 0.5),
            "fgumi-worker-1": thread("pipeline.resolve", 0.75)}
    run = a_run([job()] * 4 + [job(**bare), job(**bare)])
    assert metric("thread.process_work_s_per_mread")(run) == 2.0
    assert metric("thread.worker_work_s_per_mread")(run) == 1.5


@pytest.mark.parametrize("name", ["alloc.arena_free_growth_gb_per_job", "alloc.arenas"])
def test_alloc_key_the_c_library_lacks_is_none(name):
    rep = job()
    rep["alloc"] = {"start": {"maxrss_kb": 1}, "end": {"maxrss_kb": 2}}
    assert metric(name)(a_run([job()] * 5 + [rep])) is None
    rep = job()
    del rep["alloc"]
    assert metric(name)(a_run([job()] * 5 + [rep])) is None


def test_jobs_read_are_those_after_the_profilers_stop():
    run = full_run()
    assert threads.reports_read(run) == run["reports"][4:]


@pytest.mark.parametrize("name", READERS)
def test_reader_with_under_two_jobs_after_the_stop_is_none(name):
    """The traced jobs are no stand-in: the profiler slows them and can
    change who paces, so a short window reads nothing under these names."""
    short = a_run([job(**SLOW)] * 4 + [job()])
    assert threads.reports_read(short) == []
    assert metric(name)(short) is None
    # a window of two jobs, both traced
    assert metric(name)(a_run([job(), job()], traced=2)) is None


def test_arena_free_growth_is_a_job_not_a_level():
    """A faster program fits more jobs into a window: the level at the
    window's end rises with them, the growth a job does not."""
    def window(n):
        return a_run([job(**SLOW)] * 4 + [
            job(free=250_000_000 * (k + 1)) for k in range(n)])
    read = metric("alloc.arena_free_growth_gb_per_job")
    assert read(window(3)) == read(window(9)) == pytest.approx(0.25)
    # an allocator that gives memory back reads below 0, and is not clipped
    back = a_run([job()] * 4 + [job(free=750_000_000), job(free=250_000_000)])
    assert read(back) == pytest.approx(-0.5)


def test_reader_of_a_chain_is_the_busiest_reader():
    """``pairs5``: a reader a stage, found by the root span all the same."""
    rep = job(**{"chain-group-reader": thread("pipeline.read", 0.75,
                                              utime=0.3)})
    assert metric("thread.reader_work_s_per_mread")(
        a_run([job()] * 4 + [rep, rep])) == 1.5


def test_every_reader_has_its_entry_and_says_which_jobs_it_reads():
    with open(os.path.join(os.path.dirname(ROOT), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    for name in READERS:
        entry = entries[name]
        assert entry["moves"] == "reads_per_s" and entry["better"] == "lower"
        assert set(entry["workloads"]) <= cells
        # the chain has no processing thread of its own to read
        assert ("chain-c5.pairs5" in entry["workloads"]) \
            == ("process" not in name)
        with open(os.path.join(ROOT, "metrics", name + ".py")) as f:
            assert "profiler" in f.read().split('"""')[1]


def test_traced_rehearsal_reads_the_program(capsys):
    """The program's own reports on the CPU: every role is found by its
    root span and the sections add up (no number here is a device's)."""
    import run as harness

    rc = harness.main(["--workload", "simplex-c1.lognormal5", "--seed",
                       "2147483693", "--seconds", "3", "--trace", "1",
                       "--rehearse"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["rehearsal"] and result["correct"]
    got = result["metrics"]
    # (jobs of 0.1-0.2 s: the window holds two and more after the stop)
    assert result["attempted"] >= 6
    for name in ("thread.process_work_s_per_mread",
                 "thread.worker_work_s_per_mread",
                 "thread.reader_work_s_per_mread", "alloc.arenas"):
        assert got[name]["value"] > 0, name
    assert abs(got["alloc.arena_free_growth_gb_per_job"]["value"]) < 1
    for name in SHARES:  # None only where this host's clock stands still
        if name in got:
            assert 0 <= got[name]["value"] <= 100, name
