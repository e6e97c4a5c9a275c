"""The duplex cell's per-layer readers on hand-made run records (CPU): each
reads what it says from the traced jobs' reports, and returns nothing, without
raising, where a program has no such span or counter."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

import roofline_duplex  # noqa: E402
import run as harness  # noqa: E402


def _reader(name):
    return harness.load_module(
        os.path.join(harness.ROOT, "metrics", name + ".py"), "m_" + name)


def _report(spans=None, metrics=None):
    report = {"metrics": metrics or {}}
    if spans is not None:
        report["spans"] = {"by_name": {
            name: {"count": 1, "wall_s": s, "self_s": s, "wait_s": 0.0,
                   "p50_s": s} for name, s in spans.items()}}
    return report


def _run(reports, traced=2, platform="cpu"):
    return {"reports": reports, "traced_jobs": traced, "reads_per_job": 500000,
            "device": {"platform": platform, "kind": "TPU v5 lite"},
            "params": {"read_length": 100}, "consensus_reads_per_row": 0.25}


def test_stage2_is_the_self_time_of_its_four_spans_per_mread():
    spans = {"engine.duplex.classify": 1.0, "engine.duplex.combine": 0.5,
             "engine.duplex.rx": 0.1, "resolve.serialize": 0.2,
             "process.prep": 9.0}
    run = _run([_report(spans), _report(spans), _report({"process.prep": 1})])
    assert _reader("duplex.stage2_s_per_mread").read(run) \
        == pytest.approx(2 * 1.8 / 1.0)


@pytest.mark.parametrize("reports", [
    [_report(), _report()],  # a program from before the spans
    [_report({"process.prep": 1.0, "resolve.serialize": 0.3})] * 2])  # simplex
def test_stage2_reads_nothing_without_duplex_spans(reports):
    assert _reader("duplex.stage2_s_per_mread").read(_run(reports)) is None


def test_counter_shares_cover_every_job_of_the_window():
    jobs = [_report(metrics={"duplex.molecules": 1000,
                             "duplex.slow_molecules": k,
                             "duplex.combine_rows_device": 100 * k,
                             "duplex.combine_rows_host": 1500 - 100 * k})
            for k in (0, 2, 4)]
    run = _run(jobs)
    assert _reader("duplex.slow_molecule_share").read(run) \
        == pytest.approx(100 * 6 / 3000)
    assert _reader("duplex.device_combine_share").read(run) \
        == pytest.approx(100 * 600 / 4500)


@pytest.mark.parametrize("name", ["duplex.slow_molecule_share",
                                  "duplex.device_combine_share"])
def test_counter_shares_read_nothing_without_the_counters(name):
    run = _run([_report(metrics={"device.dispatches": 13})] * 3)
    assert _reader(name).read(run) is None


def test_combine_work_counts_columns_and_observations():
    operations, moved = roofline_duplex.combine_work(1000, 100, 800000)
    assert operations == 20 * 100000 + 2 * 800000
    assert moved == 8 * 100000 + 800000
    # bytes over 819 GB/s bound it on a v5e, not operations over 197 TFLOP/s
    assert roofline_duplex.least_seconds("TPU v5 lite", 1000, 100, 800000) \
        == pytest.approx(1600000 / 819e9)
    with pytest.raises(KeyError):
        roofline_duplex.least_seconds("TPU v9", 1, 1, 1)


@pytest.mark.parametrize("name", ["kernel.duplex_combine_ms_p50",
                                  "kernel.duplex_combine_roofline"])
def test_kernel_readers_read_nothing_off_the_chip(name):
    run = _run([_report(metrics={"duplex.combine_rows_device": 5})] * 3)
    assert _reader(name).read(run) is None
