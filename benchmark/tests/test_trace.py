"""The trace reduction on a small trace recorded on a TPU v5e: three jobs of
the rehearsal-size ``simplex-c1.lognormal5`` (two consensus dispatches on the
device, one batch on the host engine). Runs on the CPU."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

import tracered  # noqa: E402

TRACE = os.path.join(HERE, "data", "small.xplane.pb")


def _config():
    with open(os.path.join(ROOT, "configs", "simplex-c1.json")) as f:
        return json.load(f)


def test_union_merges_overlaps_and_sums_once():
    merged, total = tracered._union([(5, 6), (0, 2), (1, 3), (3, 4)])
    assert merged == [[0, 4], [5, 6]] and total == 5


def test_reduction_of_the_recorded_trace():
    out = tracered.reduce_file(TRACE, _config())
    assert out["planes"] == ["/device:TPU:0"]
    assert out["module_names"] == ["jit_fn"]
    # two executions of the consensus executable, about 1.05 ms each
    assert len(out["kernel_runs_s"]) == 2
    assert all(1.0e-3 < s < 1.1e-3 for s in out["kernel_runs_s"])
    # the device was busy 2.1 ms of a 0.9 s traced window
    assert abs(out["busy_s"] - 2.102482e-3) < 1e-8
    assert 0.85 < out["window_s"] < 0.95
    assert sum(out["kernel_runs_s"]) <= out["busy_s"] * 1.01
    ops = out["breakdown"]["device_ops"]
    assert ops[0][0] == "fn.1_s32_3440_128" and len(ops) <= 10
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    gaps = out["breakdown"]["idle_gaps"]
    assert 1 <= len(gaps) <= 10 and all(sec > 0 for _n, sec in gaps)
    # the gaps cover the window but for the busy time
    assert sum(sec for _n, sec in gaps) <= out["window_s"] - out["busy_s"] + 1e-6


def test_a_pattern_that_matches_no_module_yields_no_kernel_runs():
    out = tracered.reduce_file(TRACE, {"kernel_modules": "^jit_no_such"})
    assert out["kernel_runs_s"] == [] and out["busy_s"] > 0
