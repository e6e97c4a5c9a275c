"""The four-chip host deployment (benchmark configuration ``simplex-c1-dp4``)
on the CPU: the configuration's command on four virtual devices against the
benchmark's plain reference, byte for byte, on inputs of the cell's own
shape; the spans and counters the mesh pack carries in the run report; and
what ties the command's named shape to the default (four visible devices and
no ``--mesh`` build the same mesh; fewer devices exit 2).

Each (seed, mesh) is one CLI run in a process of its own (the device count
and the route are chosen by the environment a process starts with), made
once and shared by the tests below through ``_run``.
"""

import functools
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
sys.path.insert(0, BENCH)
try:
    import bamio
    import run as harness
    import traffic
finally:
    sys.path.remove(BENCH)

from fgumi_tpu.native import batch as nb  # noqa: E402

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library required")

SEEDS = [11, 2147483659, 3000000019]
FAMILIES = 1500  # about 16,000 reads: one batch a job
#: dp2xsp2 is the psum pair; off is one device, the bytes' other witness
MESHES = ("dp4xsp1", "dp2xsp2", "off")
SHARDED = MESHES[:2]
#: the device route forced, as tests/test_duplex_cell.py forces it
DEVICE_ROUTE = {"FGUMI_TPU_HOST_ENGINE": "0", "FGUMI_TPU_ROUTE": "device"}
_WORK = tempfile.TemporaryDirectory(prefix="mesh_cell_")

MESH_SPANS = ("router.decide", "engine.pack", "engine.pack.gather",
              "engine.pack.mesh_layout", "engine.pack.wire", "feeder.upload",
              "device.dispatch", "device.fetch", "resolve.wait",
              "resolve.unpack", "resolve.mesh_gather", "resolve.serialize")
MESH_COUNTERS = ("mesh.dispatches", "mesh.rows", "mesh.rows_padded",
                 "mesh.shard_rows_max", "mesh.families", "mesh.psums")


@functools.lru_cache(maxsize=None)
def _cell(seed, families=FAMILIES):
    """(configuration, reference, input arrays, input path) of one seed."""
    _bench, _cell, config, reference, params = harness.load_cell(
        "simplex-c1-dp4.lognormal5")
    params["num_families"] = families
    data = traffic.generate(params, seed)
    prefix = os.path.join(_WORK.name, f"in{seed}_{families}")
    (path,) = traffic.write_inputs(data, prefix)
    return config, reference, data, path


@functools.lru_cache(maxsize=None)
def _expected(seed, dtype):
    config, reference, data, _path = _cell(seed)
    exp = reference.expected(data, config, np.dtype(dtype).type)
    return np.ascontiguousarray(exp["records"]).tobytes(), exp["n_records"]


def _cli(argv, devices, report=None):
    """The CLI in a process that sees ``devices`` virtual CPU devices."""
    head = ["--run-report", report] if report else []
    return subprocess.run(
        [sys.executable, "-m", "fgumi_tpu"] + head + argv, cwd=_WORK.name,
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS":
                 f"--xla_force_host_platform_device_count={devices}",
             **DEVICE_ROUTE})


def _command(seed, tag, mesh, families=FAMILIES, extra=()):
    """The configuration's command with its ``--mesh`` value replaced
    (``None``: left out, so that the visible devices decide)."""
    config, _reference, _data, path = _cell(seed, families)
    out = os.path.join(_WORK.name, f"{tag}{seed}.bam")
    argv = [a.format(in0=path, out=out) for a in config["command"]]
    assert argv[:2] == ["--mesh", "dp4xsp1"]
    return (["--mesh", mesh] if mesh else []) + argv[2:] + list(extra), out


def _finished(argv, out, devices=4):
    """(record bytes, run report) of a run that has to exit 0."""
    report = out + ".report.json"
    done = _cli(argv, devices, report)
    assert done.returncode == 0, done.stderr[-2000:]
    payload = bamio.read_bgzf(out)
    _text, start = bamio.split_bam(payload)
    with open(report) as f:
        return payload[start:], json.load(f)


def _threads_of(report, name):
    """The threads a span ran on, from the report's ``threads`` section."""
    return sorted(t for t, rec in report["threads"].items()
                  if name in rec["self_s"])


@functools.lru_cache(maxsize=None)
def _run(seed, mesh):
    return _finished(*_command(seed, mesh, mesh))


@functools.lru_cache(maxsize=None)
def _run_in_small_batches():
    """One job of several same-shape dispatches: ten times the families, cut
    into batches a fifth of the input each (the router's EWMAs take no
    sample from a shape's first dispatch, which compiles)."""
    argv, out = _command(SEEDS[0], "batches", "dp4xsp1", 10 * FAMILIES,
                         ("--batch-bytes", str(9 << 20)))
    return _finished(argv, out)


@pytest.mark.parametrize("mesh", MESHES)
@pytest.mark.parametrize("seed", SEEDS)
def test_cli_writes_the_reference_records(seed, mesh):
    got, _report = _run(seed, mesh)
    want, n_records = _expected(seed, "float64")
    assert len(bamio.record_offsets(got, 0)) - 1 == n_records
    assert got == want


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_reference_differs(seed):
    low, n_low = _expected(seed, "float32")
    want, n_records = _expected(seed, "float64")
    assert n_low == n_records and low != want


def _assert_counters_add_up(report, dp, sp):
    m, pack = report["metrics"], report["spans"]["by_name"]["engine.pack"]
    assert pack["mesh.rows"] == m["device.pad_rows_real"]
    assert pack["mesh.rows_padded"] == m["device.pad_rows_device"]
    assert pack["mesh.dispatches"] == pack["entry_dense"] \
        == m["device.route_device"] == m["device.dispatches"] > 0
    assert "entry_ragged" not in pack and m["device.kernel_pallas"] == 0
    assert pack["mesh.psums"] == (2 if sp > 1 else 0) * pack["mesh.dispatches"]
    # the fullest dp shard holds at least an even share, at most everything
    assert pack["mesh.rows"] <= dp * pack["mesh.shard_rows_max"] \
        <= dp * pack["mesh.rows"]
    assert report["device"]["mesh"] == {"dp": dp, "sp": sp, "devices": 4,
                                        "platform": "cpu"}


@pytest.mark.parametrize("mesh", SHARDED)
@pytest.mark.parametrize("seed", SEEDS)
def test_mesh_counters_add_up(seed, mesh):
    _got, report = _run(seed, mesh)
    dp, sp = (4, 1) if mesh == "dp4xsp1" else (2, 2)
    _assert_counters_add_up(report, dp, sp)
    _config, _reference, data, _path = _cell(seed)
    pack = report["spans"]["by_name"]["engine.pack"]
    # every family of two reads or more is a segment of the one dispatch
    assert pack["mesh.dispatches"] == 1
    assert 0 < pack["mesh.families"] <= 2 * len(data["sizes"])
    assert pack["mesh.rows"] <= data["n_reads"]


@pytest.mark.parametrize("mesh", SHARDED)
@pytest.mark.parametrize("seed", SEEDS)
def test_run_report_names_what_the_mesh_does(seed, mesh):
    _got, report = _run(seed, mesh)
    by_name = report["spans"]["by_name"]
    assert [n for n in MESH_SPANS if n not in by_name] == []
    assert [c for c in MESH_COUNTERS if c not in by_name["engine.pack"]] == []
    # the layout is a child of the gather: the parent's self time is what
    # is left of it, the caller's dense row copies
    gather, layout = by_name["engine.pack.gather"], \
        by_name["engine.pack.mesh_layout"]
    assert gather["count"] == layout["count"] \
        == by_name["engine.pack"]["mesh.dispatches"]
    assert abs(gather["wall_s"] - gather["self_s"] - layout["wall_s"]) < 1e-4
    # the pack on the processing thread, the family-order gather where the
    # batch resolves: a resolve worker at --threads 4
    assert _threads_of(report, "engine.pack.mesh_layout") == ["MainThread"]
    assert all(t.startswith("fgumi-worker-")
               for t in _threads_of(report, "resolve.mesh_gather"))


@pytest.mark.parametrize("seed", SEEDS)
def test_one_device_runs_none_of_it(seed):
    _got, report = _run(seed, "off")
    by_name = report["spans"]["by_name"]
    assert "engine.pack.mesh_layout" not in by_name
    assert "resolve.mesh_gather" not in by_name
    pack = by_name["engine.pack"]
    assert [c for c in MESH_COUNTERS if c in pack] == []
    assert pack["entry_ragged"] == pack["count"] and "entry_dense" not in pack
    assert "mesh" not in report["device"]


def test_four_visible_devices_build_the_cells_mesh_by_default():
    argv, out = _command(SEEDS[0], "default", None)
    assert "--mesh" not in argv
    got, report = _finished(argv, out)
    assert report["device"]["mesh"] == _run(SEEDS[0], "dp4xsp1")[1][
        "device"]["mesh"] == {"dp": 4, "sp": 1, "devices": 4,
                              "platform": "cpu"}
    assert got == _expected(SEEDS[0], "float64")[0]


def test_the_cells_command_exits_2_on_fewer_devices():
    argv, out = _command(SEEDS[0], "two", "dp4xsp1")
    done = _cli(argv, devices=2)
    assert done.returncode == 2, done.stderr[-2000:]
    assert "dp4xsp1" in done.stderr and not os.path.exists(out)


def test_counters_add_up_over_several_dispatches():
    _got, report = _run_in_small_batches()
    _assert_counters_add_up(report, 4, 1)
    assert report["spans"]["by_name"]["engine.pack"]["mesh.dispatches"] >= 3


def test_report_carries_the_four_device_ewmas():
    """What tools/mesh_smoke.py checks on eight devices: the router keeps a
    price set per mesh size, fed by every dispatch but a shape's first."""
    _got, report = _run_in_small_batches()
    routing = report["device"]["routing"]
    assert list(routing["mesh"]) == ["4"]
    ewmas = routing["mesh"]["4"]
    assert ewmas["link_samples"] >= 1 and ewmas["dispatch_wall_s"] > 0
    assert routing["link_samples"] == 0  # nothing ran on one device
