"""The job readers (``benchmark/jobs.py`` and the seven metric files on it):
each on a hand-made ``run`` record with schema-11 reports, ``None`` on the
reports of a program from before the job record, and a rehearsal against
the program itself. Runs on the CPU."""

import importlib.util
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.dirname(ROOT)]

import pytest  # noqa: E402

import jobs  # noqa: E402

READERS = ("job.open_s_per_mread", "job.fill_drain_s_per_mread",
           "job.close_s_per_mread", "job.unnamed_share",
           "thread.worker_starved_share", "device.idle_job_edges_share",
           "startup.first_job_open_s")
#: what each reads from the record's entries of ``full_run`` (0.5 Mread a
#: job): open 0.25 + 0.5, fill 0.125 + drain 0.25, close 0.375; the first
#: entry's 1.0 + 2.0
EXPECTED = {"job.open_s_per_mread": 1.5, "job.fill_drain_s_per_mread": 0.75,
            "job.close_s_per_mread": 0.75,
            "startup.first_job_open_s": 3.0}


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(ROOT, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def entry(at_s, wall=4.0, before_arm=0.25, open_=0.5, fill=0.125,
          drain=0.25, close=0.375, report=0.25):
    return {"at_s": at_s, "wall_s": wall, "before_arm_s": before_arm,
            "open_s": open_, "fill_s": fill, "drain_s": drain,
            "close_s": close, "report_s": report}


def thread(roots, wait=0.0, self_s=None):
    work = sum(roots.values())
    return {"root_wall_s": work + wait, "wait_s": wait, "work_s": work,
            "roots": roots, "self_s": self_s or dict(roots)}


def report(k, jobs_before, cmd_wall=3.375, starved=0.5):
    """Job ``k``'s report: it started 0.05 s into its entry (the parser and
    the set-up before), its process record holds the entries of the jobs
    before it, its command thread's roots cover ``cmd_wall`` seconds."""
    cmd = thread({"job.open": 0.5, "pipeline.process": cmd_wall - 1.75,
                  "job.close": 0.375, "pipeline.wait_in": 0.0,
                  "job.fill": 0.0, "job.drain": 0.0}, wait=0.875)
    worker = thread({"pipeline.resolve": 1.0, "queue.worker_get": 0.0},
                    wait=starved,
                    self_s={"pipeline.resolve": 1.0,
                            "queue.worker_get": starved})
    return {"started_unix": 100.0 + 5.0 * k + 0.05,
            "threads": {"MainThread": cmd, "fgumi-worker-0": worker,
                        "fgumi-worker-1": worker},
            "process": {"start_unix": 100.0, "spans": {}, "compiles": [],
                        "jobs": list(jobs_before)}}


def full_run(n=8):
    """Two warm jobs, then ``n`` of the window (three traced, one that holds
    the profiler's stop): the warm ones start at -10 and -5 s, job k at
    5 k s, each 4 s long; a report holds every job that ended before it."""
    entries = [entry(-10.0, open_=2.0, before_arm=1.0), entry(-5.0)] \
        + [entry(5.0 * k) for k in range(n)]
    reports = [report(k, entries[:k + 2]) for k in range(n)]
    return {"reports": reports, "traced_jobs": 3, "reads_per_job": 500_000,
            "device": {"platform": "tpu"}}


def without_record(run):
    """The same reports as a program from before the job record writes
    them: no ``process.jobs``, no phase spans, no worker wait."""
    old = []
    for rep in run["reports"]:
        rep = json.loads(json.dumps(rep))
        rep["process"].pop("jobs")
        for rec in rep["threads"].values():
            for part in ("roots", "self_s"):
                rec[part] = {n: v for n, v in rec[part].items()
                             if not n.startswith(("job.", "queue."))}
        old.append(rep)
    return dict(run, reports=old)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_present(name):
    assert metric(name)(full_run()) == pytest.approx(EXPECTED[name])


def test_jobs_read_are_those_after_the_stop_with_an_entry():
    run = full_run()
    pairs = jobs.jobs_read(run)
    # jobs 4..6: job 7 is the last, whose entry no report holds
    assert [e["at_s"] for _r, e in pairs] == [20.0, 25.0, 30.0]
    for rep, e in pairs:
        assert 0 < rep["started_unix"] - 100.0 - e["at_s"] < e["wall_s"]


def test_unnamed_share_arithmetic():
    # wall 4 - report 0.25 = 3.75; before_arm 0.25, roots 3.375: 0.125
    assert metric("job.unnamed_share")(full_run()) == pytest.approx(
        100 * 0.125 / 3.75)
    run = full_run()
    for k, rep in enumerate(run["reports"]):
        run["reports"][k] = report(k, rep["process"]["jobs"], cmd_wall=3.5)
    assert metric("job.unnamed_share")(run) == pytest.approx(0.0)


def test_worker_starved_share():
    # per worker 0.5 of 1.5
    assert metric("thread.worker_starved_share")(full_run()) \
        == pytest.approx(100 / 3)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_parents_program_is_none(name, monkeypatch):
    import tracered

    monkeypatch.setattr(jobs, "_edges_cache", {})
    monkeypatch.setattr(tracered, "find_xplane", lambda d: "parent.pb")
    monkeypatch.setattr(tracered, "load", lambda path: (
        {"/device:TPU:0": {"ops": [("fn.1", 2.0, 1.0)], "modules": []}},
        {"python3": [("engine.pack", 1.0, 1.0, None)]}))
    run = without_record(full_run())
    run["reports"][0]["argv"] = ["-o", "/w/out/job0.bam"]
    assert metric(name)(run) is None


@pytest.mark.parametrize("name", [n for n in READERS if n not in (
    "startup.first_job_open_s", "device.idle_job_edges_share")])
def test_reader_with_under_two_jobs_after_the_stop_is_none(name):
    run = full_run(n=5)
    assert metric(name)(run) is None


def test_first_job_open_reads_the_first_traced_report():
    run = full_run()
    # the process's first entry, as the first traced job's report has it
    later = run["reports"][1]["process"]["jobs"]
    later[0] = dict(later[0], open_s=99.0)
    assert metric("startup.first_job_open_s")(run) == 3.0


def test_idle_job_edges_on_a_synthetic_trace(monkeypatch, tmp_path):
    import tracered

    monkeypatch.setattr(jobs, "_edges_cache", {})
    device = {"/device:TPU:0": {
        "ops": [("fn.1", 2.0, 1.0), ("fn.1", 8.0, 1.0)], "modules": []}}
    host = {"python3": [("job.open", 0.0, 1.5, None),
                        ("pipeline.process", 1.5, 6.0, None),
                        ("job.drain", 7.5, 2.0, None),
                        ("job.close", 9.5, 0.5, None)]}
    (tmp_path / "trace").mkdir()
    monkeypatch.setattr(tracered, "find_xplane", lambda d: "x.pb")
    monkeypatch.setattr(tracered, "load", lambda path: (device, host))
    run = full_run()
    run["reports"][0]["argv"] = ["-o", str(tmp_path / "out" / "job0.bam")]
    # window 0-10, busy 2-3 and 8-9: idle 0-2, 3-8, 9-10 = 8 s; the
    # phases hold 0-1.5, 7.5-8 and 9-10 of it = 3 s
    assert jobs.idle_job_edges(run) == {"idle_s": 8.0, "in_jobs_s": 3.0}
    assert metric("device.idle_job_edges_share")(run) == 37.5
    # a CPU rehearsal has no device to be idle
    assert metric("device.idle_job_edges_share")(
        dict(run, device={"platform": "cpu"})) is None


def test_every_reader_has_its_entry():
    with open(os.path.join(os.path.dirname(ROOT), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in READERS:
        entry_ = entries[name]
        assert entry_["moves"] == ("setup_s" if name.startswith("startup.")
                                   else "reads_per_s")
        assert set(entry_["workloads"]) <= set(cells)
    # the chain's command thread runs no pipeline; group has no workers
    everywhere = {n for n in READERS if entries[n]["workloads"] == cells}
    assert set(READERS) - everywhere == {
        "job.fill_drain_s_per_mread", "job.unnamed_share",
        "thread.worker_starved_share"}
    assert "chain-c5.pairs5" not in entries["job.unnamed_share"]["workloads"]
    assert "group-adj.amplicon16k" \
        not in entries["thread.worker_starved_share"]["workloads"]


def test_traced_rehearsal_reads_the_program(capsys):
    """The program's own reports on the CPU: the phases tile the jobs (no
    number here is a device's)."""
    import run as harness

    rc = harness.main(["--workload", "simplex-c1.lognormal5", "--seed",
                       "2147483711", "--seconds", "4", "--trace", "1",
                       "--rehearse"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["rehearsal"] and result["correct"]
    got = result["metrics"]
    # (a job after the stop whose entry a later report holds)
    assert result["attempted"] >= 6
    for name in ("job.open_s_per_mread", "job.fill_drain_s_per_mread",
                 "job.close_s_per_mread", "startup.first_job_open_s"):
        assert got[name]["value"] > 0, name
    assert 0 <= got["job.unnamed_share"]["value"] <= 3
    assert 0 < got["thread.worker_starved_share"]["value"] < 100
    assert "device.idle_job_edges_share" not in got
