"""The duplex deployment (benchmark configuration ``duplex-c3``) on the CPU:
the ``duplex`` CLI against the benchmark's plain reference, byte for byte, on
inputs of the cell's own shape, and the spans and counters its run report
carries.

Each (seed, route) is one CLI run in a process of its own (the routes are
chosen by the environment a process starts with), made once and shared by
the tests below through ``_run``.
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
FAMILIES = 900  # strand families: about 500 molecules, 7,700 reads
#: route -> (environment, extra arguments); one device, as the chip has
ROUTES = {
    "fast-host": ({"FGUMI_TPU_ROUTE": "host"}, []),
    "fast-device": ({"FGUMI_TPU_HOST_ENGINE": "0",
                     "FGUMI_TPU_ROUTE": "device"}, ["--devices", "1"]),
    "classic": ({}, ["--classic"]),
}
_WORK = tempfile.TemporaryDirectory(prefix="duplex_cell_")

SPANS_BOTH = (
    "process.decode", "process.group", "process.overlap", "process.prep",
    "engine.duplex.single", "engine.duplex.classify", "engine.duplex.combine",
    "engine.duplex.rx", "engine.duplex.slow_molecule", "resolve.unpack",
    "resolve.serialize", "reader.decode", "sink.write")
SPANS = {
    "fast-host": SPANS_BOTH + ("engine.host_gather", "resolve.host_engine"),
    "fast-device": SPANS_BOTH + (
        "router.decide", "engine.pack", "engine.pack.gather",
        "engine.pack.wire", "feeder.upload", "device.dispatch",
        "device.fetch", "resolve.wait"),
}
COUNTERS = (
    "duplex.molecules", "duplex.full", "duplex.ab_only", "duplex.ba_only",
    "duplex.slow_molecules", "duplex.single_segments",
    "duplex.multi_segments", "duplex.combine_rows_device",
    "duplex.combine_rows_host", "duplex.stage2_batches",
    "duplex.stage2_off_thread")


@functools.lru_cache(maxsize=None)
def _cell(seed):
    """(configuration, input arrays, input path) of one seed."""
    _bench, _cell, config, reference, params = harness.load_cell(
        "duplex-c3.panel")
    params["num_families"] = FAMILIES
    data = traffic.generate(params, seed)
    prefix = os.path.join(_WORK.name, f"in{seed}")
    (path,) = traffic.write_inputs(data, prefix)
    return config, reference, data, path


@functools.lru_cache(maxsize=None)
def _expected(seed, dtype):
    config, reference, data, _path = _cell(seed)
    exp = reference.expected(data, config, np.dtype(dtype).type)
    return np.ascontiguousarray(exp["records"]).tobytes(), exp["n_records"]


def _threads_of(report, name):
    """The threads a span ran on, from the report's ``threads`` section."""
    return sorted(t for t, rec in report["threads"].items()
                  if name in rec["self_s"])


@functools.lru_cache(maxsize=None)
def _run(seed, route):
    """(record bytes, run report) of the configuration's command."""
    config, _reference, _data, path = _cell(seed)
    env, extra = ROUTES[route]
    out = os.path.join(_WORK.name, f"{route}{seed}.bam")
    report = out + ".report.json"
    argv = [a.format(in0=path, out=out) for a in config["command"]]
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", "--run-report", report] + argv
        + extra, check=True, cwd=_WORK.name,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "", **env})
    payload = bamio.read_bgzf(out)
    _text, start = bamio.split_bam(payload)
    with open(report) as f:
        return payload[start:], json.load(f)


@pytest.mark.parametrize("route", list(ROUTES))
@pytest.mark.parametrize("seed", SEEDS)
def test_cli_writes_the_reference_records(seed, route):
    got, _report = _run(seed, route)
    want, n_records = _expected(seed, "float64")
    assert len(bamio.record_offsets(got, 0)) - 1 == n_records
    assert got == want


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_reference_differs(seed):
    low, n_low = _expected(seed, "float32")
    want, n_records = _expected(seed, "float64")
    assert n_low == n_records and low != want


def test_the_input_has_the_cells_shape():
    _config, _reference, data, _path = _cell(SEEDS[0])
    kinds = np.bincount(data["mol_kind"], minlength=3)
    assert 0.74 < kinds[0] / kinds.sum() < 0.76
    assert abs(kinds[1] - kinds[2]) <= 1
    assert data["n_reads"] == 2 * data["sizes"].sum() and len(kinds) == 3


@pytest.mark.parametrize("route", list(SPANS))
@pytest.mark.parametrize("seed", SEEDS)
def test_molecule_counters_add_up(seed, route):
    _got, report = _run(seed, route)
    m = report["metrics"]
    _config, _reference, data, _path = _cell(seed)
    assert m["duplex.molecules"] == len(data["mol_kind"])
    assert m["duplex.full"] + m["duplex.ab_only"] + m["duplex.ba_only"] \
        + m.get("duplex.rejected", 0) + m["duplex.slow_molecules"] \
        == m["duplex.molecules"]
    # one molecule, the stream's last, is left to the flush and the slow
    # caller; every other strand family is two single-strand segments
    assert m["duplex.slow_molecules"] == 1
    last_strands = 2 if data["mol_kind"][-1] == 0 else 1
    assert m["duplex.single_segments"] + m["duplex.multi_segments"] \
        == 2 * (len(data["sizes"]) - last_strands)
    combined = m["duplex.combine_rows_device"] + m["duplex.combine_rows_host"]
    assert combined == 2 * m["duplex.full"]


@pytest.mark.parametrize("route", list(SPANS))
def test_run_report_names_what_duplex_does(route):
    _got, report = _run(SEEDS[0], route)
    by_name = report["spans"]["by_name"]
    assert [n for n in SPANS[route] if n not in by_name] == []
    assert [n for n in COUNTERS if n not in report["metrics"]] == []
    side = "device" if route == "fast-device" else "host"
    assert report["metrics"]["duplex.combine_rows_" + side] > 0
    # the configuration runs --threads 4: stage 2 and the completion of
    # stage 1 belong to the resolve workers, every span of them
    for name in ("resolve.unpack", "engine.duplex.classify",
                 "engine.duplex.combine", "engine.duplex.rx",
                 "resolve.serialize"):
        assert all(t.startswith("fgumi-worker-")
                   for t in _threads_of(report, name)), name
    m = report["metrics"]
    assert m["duplex.stage2_off_thread"] == m["duplex.stage2_batches"] > 0
    # and the per-molecule caller to the processing thread
    assert _threads_of(report, "engine.duplex.slow_molecule") \
        == ["MainThread"]


def test_wire_counters_match_the_dispatches():
    _got, report = _run(SEEDS[0], "fast-device")
    pack = report["spans"]["by_name"]["engine.pack"]
    wires = pack.get("wire_native", 0) + pack.get("wire_numpy", 0)
    assert wires == pack["count"] == report["metrics"]["device.kernel_xla"]
    assert report["metrics"]["device.resident_bytes_peak"] > 0
    assert report["metrics"]["device.resident_bytes"] == 0
    # the strand combine is a dispatch too, and packs nothing
    assert report["metrics"]["device.dispatches"] \
        == wires + report["metrics"]["device.route.duplex_combine.device"]
