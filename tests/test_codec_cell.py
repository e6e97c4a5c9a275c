"""The CODEC deployment (benchmark configuration ``codec-c4``) on the CPU:
the ``codec`` CLI against the benchmark's plain reference, byte for byte, on
inputs of the cell's own shape, and the spans and counters its run report
carries.

Each (seed, route, options) is one CLI run in a process of its own (the
routes are chosen by the environment a process starts with), made once and
shared by the tests below through ``_run``.
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
    import reference_codec
    import run as harness
    import traffic
finally:
    sys.path.remove(BENCH)

from fgumi_tpu.native import batch as nb  # noqa: E402

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library required")

SEEDS = [11, 2147483659, 3000000019]
FAMILIES = 500  # molecules: about 3,000 reads
#: route -> (environment, extra arguments); one device, as the chip has
ROUTES = {
    "fast-host": ({"FGUMI_TPU_ROUTE": "host"}, []),
    "fast-device": ({"FGUMI_TPU_HOST_ENGINE": "0",
                     "FGUMI_TPU_ROUTE": "device"}, ["--devices", "1"]),
    "classic": ({}, ["--classic"]),
}
#: the options of the strict case, as the CLI and as the reference take them
STRICT = {"max_duplex_disagreement_rate": 0.02, "min_duplex_length": 60,
          "outer_bases_qual": 5, "single_strand_qual": 10}
STRICT_ARGV = ["--max-duplex-disagreement-rate", "0.02",
               "--min-duplex-length", "60", "--outer-bases-qual", "5",
               "--single-strand-qual", "10"]
_WORK = tempfile.TemporaryDirectory(prefix="codec_cell_")

SPANS_BOTH = (
    "process.decode", "process.group", "process.prep", "engine.codec.single",
    "engine.codec.slow_molecule", "engine.codec.gather", "engine.codec.place",
    "engine.codec.combine", "engine.codec.gates", "resolve.unpack",
    "resolve.serialize", "reader.decode", "sink.write")
SPANS = {
    "fast-host": SPANS_BOTH + ("engine.host_gather", "resolve.host_engine"),
    "fast-device": SPANS_BOTH + (
        "router.decide", "engine.pack", "engine.pack.gather",
        "engine.pack.wire", "feeder.upload", "device.dispatch",
        "device.fetch", "resolve.wait"),
}
COUNTERS = (
    "codec.molecules", "codec.emitted", "codec.slow_molecules",
    "codec.strands", "codec.single_strands", "codec.duplex_bases",
    "codec.disagreements", "codec.stage2_batches", "codec.stage2_off_thread")


@functools.lru_cache(maxsize=None)
def _cell(seed):
    """(configuration, reference module, input arrays, input path)."""
    _bench, _cell, config, reference, params = harness.load_cell(
        "codec-c4.linked")
    params["num_families"] = FAMILIES
    data = traffic.generate(params, seed)
    prefix = os.path.join(_WORK.name, f"in{seed}")
    (path,) = traffic.write_inputs(data, prefix)
    return config, reference, data, path


@functools.lru_cache(maxsize=None)
def _expected(seed, dtype, strict=False):
    """(record bytes, records, what the reference counted)."""
    config, _reference, data, _path = _cell(seed)
    opts = {**config["assumed"]["consensus"], **(STRICT if strict else {})}
    flat, n_records, _reads, counted = reference_codec.codec(
        data, opts, np.dtype(dtype).type)
    return np.ascontiguousarray(flat).tobytes(), n_records, counted


def _threads_of(report, name):
    """The threads a span ran on, from the report's ``threads`` section."""
    return sorted(t for t, rec in report["threads"].items()
                  if name in rec["self_s"])


@functools.lru_cache(maxsize=None)
def _run(seed, route, strict=False):
    """(record bytes, run report) of the configuration's command."""
    config, _reference, _data, path = _cell(seed)
    env, extra = ROUTES[route]
    out = os.path.join(_WORK.name, f"{route}{seed}{int(strict)}.bam")
    report = out + ".report.json"
    argv = [a.format(in0=path, out=out) for a in config["command"]]
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", "--run-report", report] + argv
        + extra + (STRICT_ARGV if strict else []), check=True,
        cwd=_WORK.name,
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
    want, n_records, _counted = _expected(seed, "float64")
    assert len(bamio.record_offsets(got, 0)) - 1 == n_records == FAMILIES
    assert got == want


def test_the_configurations_reference_is_the_same_records():
    config, reference, data, _path = _cell(SEEDS[0])
    exp = reference.expected(data, config, np.float64)
    want, n_records, _counted = _expected(SEEDS[0], "float64")
    assert np.ascontiguousarray(exp["records"]).tobytes() == want
    assert exp["n_records"] == n_records
    assert exp["header"] == ["@HD\tVN:1.6\tSO:unsorted\tGO:query",
                             "@RG\tID:A\tSM:sample"]


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_reference_differs(seed):
    low, n_low, _counted = _expected(seed, "float32")
    want, n_records, _counted = _expected(seed, "float64")
    assert n_low == n_records and low != want


@pytest.mark.parametrize("route", list(ROUTES))
def test_gates_and_masks_with_rejects_present(route):
    seed = SEEDS[2]
    got, report = _run(seed, route, strict=True)
    want, n_records, counted = _expected(seed, "float64", strict=True)
    assert counted["InsufficientOverlap"] > 50
    assert counted["HighDuplexDisagreement"] > 20
    assert n_records == FAMILIES - counted["InsufficientOverlap"] \
        - counted["HighDuplexDisagreement"]
    assert got == want
    if route == "classic":
        return
    m = report["metrics"]
    assert m["codec.emitted"] == n_records
    for reason in ("InsufficientOverlap", "HighDuplexDisagreement"):
        assert m["codec.rejected." + reason] == counted[reason]
    assert m["codec.duplex_bases"] == counted["duplex_bases"]
    assert m["codec.disagreements"] == counted["disagreements"]


def test_the_input_has_the_cells_shape():
    _config, _reference, data, _path = _cell(SEEDS[0])
    sizes, insert = data["sizes"], data["insert"]
    assert len(sizes) == FAMILIES and sizes.min() == 1
    assert 0.2 < (sizes == 1).mean() < 0.3 and 2.8 < sizes.mean() < 3.4
    assert insert.min() >= 160 and insert.max() <= 280
    assert insert.min() < 170 and insert.max() > 270
    assert data["r1_reverse"].sum() == FAMILIES // 2
    assert data["codes1"].shape[1] == 150
    assert data["n_reads"] == 2 * sizes.sum()
    other = traffic.generate({**traffic.load("linked", BENCH),
                              "num_families": FAMILIES}, SEEDS[1])
    assert np.array_equal(np.sort(other["sizes"]), np.sort(sizes))
    assert not np.array_equal(other["r1_reverse"], data["r1_reverse"])


@pytest.mark.parametrize("route", list(SPANS))
@pytest.mark.parametrize("seed,strict", [(seed, False) for seed in SEEDS]
                         + [(SEEDS[2], True)])
def test_molecule_counters_add_up(seed, strict, route):
    _got, report = _run(seed, route, strict)
    m = report["metrics"]
    assert m["codec.molecules"] == FAMILIES
    assert m["codec.emitted"] + m.get("codec.rejected", 0) == FAMILIES
    by_reason = {k: v for k, v in m.items()
                 if k.startswith("codec.rejected.")}
    assert sum(by_reason.values()) == m.get("codec.rejected", 0)
    assert bool(by_reason) == strict
    # stage 2 sees every molecule that no pairing or geometry gate took
    reached = m["codec.emitted"] \
        + m.get("codec.rejected.ClipOverlapFailed", 0) \
        + m.get("codec.rejected.HighDuplexDisagreement", 0)
    assert m["codec.strands"] == 2 * reached
    # one molecule, the stream's last, is left to the flush and the slow path
    assert m["codec.slow_molecules"] == 1
    _config, _reference, data, _path = _cell(seed)
    if not strict:
        assert m["codec.single_strands"] == 2 * (data["sizes"] == 1).sum()
    cells = m.get("codec.combine_cells_device", 0) \
        + m.get("codec.combine_cells_host", 0)
    assert cells > 0
    if not strict:
        assert cells == data["insert"].sum()


@pytest.mark.parametrize("route", list(SPANS))
def test_run_report_names_what_codec_does(route):
    _got, report = _run(SEEDS[0], route)
    by_name = report["spans"]["by_name"]
    assert [n for n in SPANS[route] if n not in by_name] == []
    assert [n for n in COUNTERS if n not in report["metrics"]] == []
    side = "device" if route == "fast-device" else "host"
    assert report["metrics"]["codec.combine_cells_" + side] > 0
    # what is a function of the stream stays on the processing thread
    # (MainThread here) with the pack and the dispatch; the fetch, the
    # thresholds and stage 2 run where the chunk resolves: the resolve
    # workers at the command's --threads 4, and MainThread for the flush's
    # chunk of the last molecule alone
    for name in ("process.prep", "engine.codec.single",
                 "engine.codec.gather"):
        assert _threads_of(report, name) == ["MainThread"], name
    for name in ("resolve.unpack", "engine.codec.place",
                 "engine.codec.combine", "engine.codec.gates",
                 "resolve.serialize"):
        workers = [t for t in _threads_of(report, name) if t != "MainThread"]
        assert workers and all(t.startswith("fgumi-worker-")
                               for t in workers), name
    m = report["metrics"]
    assert m["codec.stage2_off_thread"] == m["codec.stage2_batches"] - 1 >= 1
    busy = [v["busy_s"] for k, v in report["stages"].items()
            if k.startswith("resolve[")]
    assert busy and max(busy) > 0
    if route == "fast-device":
        pack = by_name["engine.pack"]
        assert pack["entry_dense"] == pack["count"] >= 1
        assert "entry_ragged" not in pack
