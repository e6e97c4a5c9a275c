"""The span readers (``benchmark/spans.py`` and the metric files that use
it): each on a hand-made ``run`` record, the idle intersection on synthetic
intervals, and a rehearsal that prints every new metric with a CPU meaning.
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

import spans  # noqa: E402


def metric(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(ROOT, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def rec(count=1, wall=1.0, own=None, wait=0.0, p50=None, **more):
    return {"count": count, "wall_s": wall,
            "self_s": wall if own is None else own, "wait_s": wait,
            "p50_s": wall / count if p50 is None else p50, "max_s": wall,
            "threads": ["fgumi-process"], **more}


def report(started, by_name=None, compiles=None, busy=None, job=0):
    out = {"started_unix": started,
           "argv": ["simplex", "-o", f"/w/out/job{job}.bam"]}
    if by_name is not None:
        out["spans"] = {"job": job + 3, "by_name": by_name}
    if compiles is not None:
        out["process"] = {
            "start_unix": 1000.0, "first_main_s": 12.0,
            "spans": {"startup.jax_import": {"s": 2.5, "at_s": 1.0},
                      "startup.native_load": {"s": 0.01, "at_s": 0.5}},
            "compiles": compiles}
    if busy is not None:
        out["stages"] = {"process": {"busy_s": busy}}
    return out


def a_run(reports, traced=2, platform="tpu"):
    return {"reports": reports, "traced_jobs": traced,
            "reads_per_job": 500_000, "device": {"platform": platform}}


COMPILES = [
    {"kind": "compile", "s": 4.0, "at_s": 15.0},        # warm job 0
    {"kind": "cache_load", "s": 0.5, "at_s": 18.0},     # warm job 1
    {"kind": "cache_load", "s": 0.25, "at_s": 31.0},    # traced job 1
    {"kind": "compile", "s": 2.0, "at_s": 42.0}]        # untraced job 2

JOB = {
    "process.decode": rec(14, 0.10), "process.group": rec(14, 0.05),
    "process.overlap": rec(14, 0.15), "process.prep": rec(28, 0.30, own=0.2),
    "pipeline.process": rec(14, 3.0, own=0.06),
    "pipeline.wait_out": rec(14, 0.04, wait=0.04),
    "engine.pack": rec(14, 2.4, own=0.1, p50=0.17, utime_s=1.2, stime_s=0.6,
                       minflt=70_000, majflt=0, nvcsw=3, nivcsw=9,
                       staging_reuses=14),
    "resolve.unpack": rec(14, 0.2), "resolve.serialize": rec(14, 0.3),
    "device.fetch": rec(14, 0.25),
    "feeder.queue_wait": rec(14, 0.014, p50=0.0008),
    "feeder.upload": rec(14, 0.07, p50=0.004),
    "chain.extract": rec(1, 1.0, own=0.2, wait=0.25),
    "chain.sort": rec(1, 1.5, wait=1.0),
    "chain.group": rec(1, 3.0, wait=0.5),
    "chain.simplex": rec(1, 3.25, wait=2.0),
    "chain.filter": rec(1, 3.3, wait=3.2)}


def full_run():
    # process start at 1000; first traced job starts at 1030, the second at
    # 1034, an untraced third at 1040
    return a_run([
        report(1030.0, JOB, COMPILES[:2], busy=3.2, job=0),
        report(1034.0, JOB, COMPILES[:3], busy=3.0, job=1),
        report(1040.0, JOB, COMPILES, busy=9.9, job=2)])


PRESENT = {
    "startup.jax_import_s": 2.5,
    "startup.executable_load_s": 4.5,
    "compile.in_window_s": 2.25,
    "host.prep_s_per_mread": 2 * (0.10 + 0.05 + 0.15 + 0.2) / 1.0,
    "host.process_unattributed_share":
        100 * (1 - 2 * (3.0 - 0.06 + 0.04) / 6.2),
    "host.resolve_busy_s_per_mread": 2 * 0.75 / 1.0,
    "engine.pack_minflt_per_batch": 5000.0,
    "engine.pack_sys_share": 25.0,
    "feeder.queue_wait_ms_p50": 0.8,
    "feeder.upload_ms_p50": 4.0,
    "chain.extract_busy_s": 0.75, "chain.sort_busy_s": 0.5,
    "chain.group_busy_s": 2.5, "chain.simplex_busy_s": 1.25,
    "chain.filter_busy_s": pytest.approx(0.1),
}


@pytest.mark.parametrize("name", sorted(PRESENT))
def test_reader_present(name):
    assert metric(name)(full_run()) == pytest.approx(PRESENT[name])


@pytest.mark.parametrize("name", sorted(PRESENT))
def test_reader_absent_section_is_none(name):
    """Reports of a program from before the spans: no ``spans`` and no
    ``process`` section, and no reader raises."""
    old = a_run([report(1030.0, busy=3.2), report(1034.0, busy=3.0)])
    assert metric(name)(old) is None
    assert metric(name)(a_run([], traced=0)) is None


def test_sections_present_but_span_never_ran():
    """A job that sent nothing to the device: sums read 0, ratios and
    medians have nothing to read."""
    run = a_run([report(1030.0, {"process.decode": rec(1, 0.5)}, [],
                        busy=1.0)], traced=1)
    assert metric("host.prep_s_per_mread")(run) == pytest.approx(1.0)
    assert metric("host.resolve_busy_s_per_mread")(run) == 0.0
    assert metric("compile.in_window_s")(run) == 0.0
    assert metric("startup.executable_load_s")(run) == 0.0
    for name in ("engine.pack_minflt_per_batch", "engine.pack_sys_share",
                 "feeder.upload_ms_p50", "chain.group_busy_s"):
        assert metric(name)(run) is None
    # every busy second outside the pulls is unattributed
    assert metric("host.process_unattributed_share")(run) == 100.0


def test_traced_jobs_only_and_every_job_for_compiles():
    run = full_run()
    assert len(spans.traced_reports(run)) == 2
    assert spans.span_sum(run, ("engine.pack",), "count") == 28
    # the in-window reader takes the last job's record, with the compile
    # that only the untraced job paid
    assert spans.compile_seconds(run, in_window=True) == 2.25
    assert spans.compile_seconds(run, in_window=False) == 4.5


# ------------------------------------------------------------- intervals


def test_interval_algebra():
    a = [[0, 4], [6, 10]]
    b = [[1, 2], [3, 7], [9, 12]]
    assert spans.intersect(a, b) == [[1, 2], [3, 4], [6, 7], [9, 10]]
    assert spans.subtract(a, b) == [[0, 1], [2, 3], [7, 9]]
    assert spans.subtract(a, []) == a and spans.intersect(a, []) == []
    assert spans.idle_intervals([[2, 3], [5, 6]], (0, 10)) == \
        [[0, 2], [3, 5], [6, 10]]
    assert spans._length(spans._merge([(0, 2), (1, 3), (5, 6)])) == 4


HOST = {
    # the processing thread: a pull with a pack and a wait on the queue
    "fgumi-process": [
        ("pipeline.process", 0.0, 6.0, None),     # wrapper: not evidence
        ("process.prep", 0.0, 1.0, None),
        ("engine.pack", 1.0, 3.0, None),
        ("engine.pack.wire", 3.0, 1.0, None),
        ("pipeline.wait_out", 5.0, 1.0, None),
        ("$threading.py:355 wait", 5.0, 1.0, None)],  # the python tracer's
    # a chain stage thread: its life, a reader pull that mostly waits
    "chain-group": [
        ("chain.group", 0.0, 10.0, None),
        ("reader.decode", 6.0, 3.0, None),
        ("chain.get", 6.5, 2.0, None)],
    "python3": [("queue.get", 0.0, 10.0, None)],
}


def test_work_cover_drops_waits_wrappers_and_foreign_events():
    assert spans.work_cover(HOST) == [[0.0, 4.0], [6.0, 6.5], [8.5, 9.0]]
    assert spans.work_cover(HOST, ("engine.pack",)) == [[1.0, 4.0]]


def test_idle_attribution_on_a_synthetic_trace(monkeypatch, tmp_path):
    import tracered

    device = {"/device:TPU:0": {
        "ops": [("fn.1", 2.0, 0.5), ("copy", 2.5, 0.5), ("fn.1", 8.0, 1.0)],
        "modules": []}}
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    monkeypatch.setattr(tracered, "find_xplane", lambda d: str(d) + "/x.pb")
    monkeypatch.setattr(tracered, "load", lambda path: (device, HOST))
    run = a_run([{"argv": ["simplex", "-i", "in.bam", "-o",
                           str(tmp_path / "out" / "job0.bam")],
                  "started_unix": 1.0}], traced=1)
    assert spans.xplane_path(run) == str(trace_dir) + "/x.pb"
    cut = spans.idle_attribution(run)
    # window 0-10, busy 2-3 and 8-9: idle 8 s; pack covers 1-2 and 3-4;
    # work covers 0-2, 3-4, 6-6.5 (8.5-9 is under the device's own work)
    assert cut == {"idle_s": 8.0, "under_pack_s": 2.0, "under_work_s": 3.5}
    assert metric("device.idle_under_pack_share")(run) == 25.0
    assert metric("device.idle_unattributed_share")(run) == \
        pytest.approx(100 * (1 - 3.5 / 8))
    # a CPU rehearsal has no device plane to be idle
    cpu = dict(run, device={"platform": "cpu"})
    assert metric("device.idle_under_pack_share")(cpu) is None


def test_idle_attribution_without_program_annotations(monkeypatch, tmp_path):
    """The parent's trace: device operations and the python tracer's events,
    no program span. The readers say nothing and do not raise."""
    import tracered

    device = {"/device:TPU:0": {"ops": [("fn.1", 2.0, 1.0)], "modules": []}}
    (tmp_path / "trace").mkdir()
    monkeypatch.setattr(tracered, "find_xplane", lambda d: "parent.pb")
    monkeypatch.setattr(tracered, "load", lambda path: (
        device, {"python3": HOST["python3"]}))
    run = a_run([{"argv": ["-o", str(tmp_path / "out" / "job0.bam")],
                  "started_unix": 1.0}], traced=1)
    assert spans.idle_attribution(run) is None
    assert metric("device.idle_unattributed_share")(run) is None
    no_trace = a_run([{"argv": ["-o", "/nowhere/out/job0.bam"],
                       "started_unix": 1.0}], traced=1)
    assert spans.idle_attribution(no_trace) is None


# -------------------------------------------------------------- rehearsal

CPU_MEANING = {
    "simplex-c1.lognormal5": {
        "startup.jax_import_s", "startup.executable_load_s",
        "compile.in_window_s", "host.prep_s_per_mread",
        "host.process_unattributed_share", "host.resolve_busy_s_per_mread",
        "engine.pack_minflt_per_batch", "engine.pack_sys_share",
        "feeder.queue_wait_ms_p50", "feeder.upload_ms_p50"},
    "chain-c5.pairs5": {
        "startup.jax_import_s", "startup.executable_load_s",
        "compile.in_window_s", "host.prep_s_per_mread",
        "host.resolve_busy_s_per_mread", "engine.pack_minflt_per_batch",
        "engine.pack_sys_share", "feeder.queue_wait_ms_p50",
        "feeder.upload_ms_p50", "chain.extract_busy_s", "chain.sort_busy_s",
        "chain.group_busy_s", "chain.simplex_busy_s",
        "chain.filter_busy_s"},
}


@pytest.mark.parametrize("workload", sorted(CPU_MEANING))
def test_traced_rehearsal_prints_every_new_metric(workload, monkeypatch,
                                                  capsys):
    """The XLA device path on the CPU (the program's own switches; the
    harness sets nothing), so that pack, feeder and resolve spans exist."""
    import run as harness

    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "device")
    rc = harness.main(["--workload", workload, "--seed", "2147483659",
                       "--seconds", "0.2", "--trace", "1", "--rehearse"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["rehearsal"] and result["correct"]
    got = result["metrics"]
    assert CPU_MEANING[workload] <= set(got), \
        sorted(CPU_MEANING[workload] - set(got))
    # the device's idle time has no CPU meaning
    assert not {n for n in got if n.startswith("device.idle_")}
    other = "chain-c5.pairs5" if workload.startswith("simplex") \
        else "simplex-c1.lognormal5"
    assert not (CPU_MEANING[other] - CPU_MEANING[workload]) & set(got)
    assert 0 <= got["engine.pack_sys_share"]["value"] <= 100
    assert got["startup.jax_import_s"]["value"] > 0
