"""The mesh cell's own pieces (CPU): a rehearsal of ``simplex-c1-dp4.lognormal5``
on four virtual devices, which has to come out ``correct`` with the three
``mesh.*`` metrics in its line; the float32 control; and the three readers on
a recorded run report and on reports that lack the mesh's spans and counters."""

import json
import os
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.dirname(ROOT)]

import pytest  # noqa: E402

import control  # noqa: E402
import run as harness  # noqa: E402

CELL = "simplex-c1-dp4.lognormal5"
READERS = ("mesh.pack_copy_s_per_mread", "mesh.pad_row_share",
           "mesh.shard_row_imbalance")


def test_the_cell_rehearses_on_four_virtual_devices():
    """A process of its own: the device count is fixed when jax starts, and
    a CPU run takes the device route only where the environment says so."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "0.5", "--trace", "1",
         "--rehearse"], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "FGUMI_TPU_HOST_ENGINE": "0", "FGUMI_TPU_ROUTE": "device"})
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["rehearsal"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert all(v["value"] == 0 for v in result["compared"].values())
    assert result["device"]["count"] == 4
    assert [n for n in READERS if n not in result["metrics"]] == []
    assert result["metrics"]["router.device_batch_share"]["value"] == 100.0


def test_the_cell_is_simplex_c1_but_for_the_named_mesh():
    _bench, cell, config, _reference, params = harness.load_cell(CELL)
    assert cell["chips"] == 4 and config["reduced"] == []
    assert config["command"][:2] == ["--mesh", "dp4xsp1"]
    one = harness.load_cell("simplex-c1.lognormal5")
    assert config["command"][2:] == one[2]["command"] and params == one[4]
    assert config["assumed"]["consensus"] == one[2]["assumed"]["consensus"]


@pytest.mark.parametrize("seed", [7, 2147483659])
def test_control_in_float32_is_not_correct(seed, tmp_path):
    verdict = control.control(CELL, seed, families=1500, work=str(tmp_path))
    assert verdict["correct"] is False
    assert verdict["compared"]["records_differing"]["value"] > 1500


# ------------------------------------------------------------------ readers

def _reader(name):
    return harness.load_module(
        os.path.join(harness.ROOT, "metrics", name + ".py"), "m_" + name)


def _recorded():
    with open(os.path.join(ROOT, "tests", "data", "mesh_report.json")) as f:
        return json.load(f)


def _run(reports, traced=2):
    return {"reports": reports, "traced_jobs": traced,
            "reads_per_job": 163304,
            "device": {"platform": "cpu", "kind": "cpu"}}


def test_readers_on_a_recorded_run_report():
    report = _recorded()
    # two traced jobs, and a third the window held after them
    run = _run([report, report, report])
    by_name = report["spans"]["by_name"]
    pack = by_name["engine.pack"]
    copies = by_name["engine.pack.gather"]["self_s"] \
        + by_name["engine.pack.mesh_layout"]["self_s"]
    assert by_name["engine.pack.mesh_layout"]["self_s"] > 0
    assert _reader("mesh.pack_copy_s_per_mread").read(run) \
        == pytest.approx(2 * copies / (2 * 163304 / 1e6))
    # the recorded job's counters add up with its device section
    assert pack["mesh.rows"] == report["device"]["pad_rows_real"] == 161372
    assert pack["mesh.rows_padded"] == report["device"]["pad_rows_device"] \
        == 169408
    assert pack["mesh.dispatches"] == pack["entry_dense"] \
        == report["device"]["route_device"] == 4
    assert pack["mesh.psums"] == 0
    assert _reader("mesh.pad_row_share").read(run) \
        == pytest.approx(100 * (1 - 161372 / 169408))
    assert _reader("mesh.shard_row_imbalance").read(run) \
        == pytest.approx(100 * (4 * 40890 / 161372 - 1))


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("reports", [
    [{"metrics": {"device.dispatches": 13}}] * 3,  # before the spans
    [{"device": {"mesh": {"dp": 4, "sp": 1, "devices": 4}},  # the parent
      "spans": {"by_name": {
          "engine.pack": {"count": 14, "wall_s": 2.0, "self_s": 0.1,
                          "wait_s": 0.0, "p50_s": 0.1, "entry_dense": 14},
          "engine.pack.gather": {"count": 14, "wall_s": 1.5, "self_s": 1.5,
                                 "wait_s": 0.0, "p50_s": 0.1}}}}] * 3,
    [{"device": {}, "spans": {"by_name": {  # one device
        "engine.pack": {"count": 14, "wall_s": 0.2, "self_s": 0.1,
                        "wait_s": 0.0, "p50_s": 0.01, "entry_ragged": 14},
        "engine.pack.gather": {"count": 14, "wall_s": 0.01, "self_s": 0.01,
                               "wait_s": 0.0, "p50_s": 0.001}}}}] * 3])
def test_readers_read_nothing_without_the_meshs_spans_and_counters(
        name, reports):
    assert _reader(name).read(_run(reports)) is None
