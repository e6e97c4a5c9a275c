"""The ultra-deep amplicon deployment (benchmark configuration ``group-adj``)
on the CPU: the ``group`` CLI against the benchmark's plain reference, byte
for byte, on inputs of the cell's own layout at a size that still takes all
three neighbour-graph routes at the shipped thresholds, and the spans and
counters its run report carries.

Each (seed, route) is one CLI run in a process of its own, made once and
shared by the tests below through ``_run``.
"""

import functools
import inspect
import json
import os
import re
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
    import reference_group
    import roofline_hamming
    import run as harness
    import traffic
finally:
    sys.path.remove(BENCH)

from fgumi_tpu.native import batch as nb  # noqa: E402
from fgumi_tpu.observe import trace  # noqa: E402
from fgumi_tpu.umi import assigners  # noqa: E402

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library required")

CELL = "group-adj.amplicon16k"
SEEDS = [11, 2147483659, 3000000019]
#: loci: one of 1,300 molecules (its larger orientation sub-group holds
#: 1,100+ unique UMIs: the device route on CPU jax), two of 300 (the dense
#: host route) and three of 50 (the Python BFS under 512 uniques)
GROUPS = [[1, 1300], [2, 300], [3, 50]]
MOLECULES = 2050
#: times the loci of ``GROUPS`` in the input whose run report names the
#: thread that paces the job
PACING_LOCI = 4
#: route -> extra arguments. ``indexed`` sends every graph of 600 uniques or
#: more to the native pigeonhole pass through the existing flag
ROUTES = {"default": [], "indexed": ["--index-threshold", "600"],
          "one-thread": ["--threads", "1"], "classic": ["--classic"],
          "no-native": []}
#: route -> environment. ``no-native`` runs without the native library, so
#: the device route unpacks its bits into the boolean matrix
ENVS = {"no-native": {"FGUMI_TPU_NO_NATIVE": "1"}}
_WORK = tempfile.TemporaryDirectory(prefix="group_cell_")

NEW_SPANS = ("group.assign.umis", "group.assign.graph",
             "group.assign.threshold", "group.assign.bfs", "group.assign.ids")
DEVICE_SPANS = ("group.hamming.upload", "group.hamming.dispatch",
                "device.fetch")
SPANS = ("reader.decode", "pipeline.process", "group.keys", "group.defer",
         "group.assign", "group.rewrite", "sink.write") + NEW_SPANS
COUNTERS = ("group.position_groups", "group.subgroups", "group.templates",
            "group.unique_umis", "group.molecules", "group.neighbor_pairs")
HAMMING = ("group.hamming.dispatches", "group.hamming.rows",
           "group.hamming.cells", "group.hamming.cells_padded",
           "group.hamming.bytes_fetched")


@functools.lru_cache(maxsize=None)
def _cell(seed, loci=1):
    """(configuration, reference module, input arrays, input path). A
    quarter of the templates are F2R1, so a locus has two orientation
    sub-groups; the last seed's input has a few UMIs with an ``N``.
    ``loci`` multiplies the loci of every size in ``GROUPS``."""
    _bench, _cell, config, reference, params = harness.load_cell(CELL)
    params.update(groups=[[n * loci, size] for n, size in GROUPS],
                  num_families=MOLECULES * loci, r1_reverse_share=0.25)
    data = traffic.generate(params, seed)
    if seed == SEEDS[2]:
        data["umi_t"][::997, 3] = traffic.N_CODE
    prefix = os.path.join(_WORK.name, f"in{seed}x{loci}")
    (path,) = traffic.write_inputs(data, prefix)
    return config, reference, data, path


@functools.lru_cache(maxsize=None)
def _expected(seed, edits=1):
    """(record bytes, records, what the reference counted)."""
    _config, _reference, data, _path = _cell(seed)
    flat, n_records, counted = reference_group.group(data, edits)
    return np.ascontiguousarray(flat).tobytes(), n_records, counted


@functools.lru_cache(maxsize=None)
def _run(seed, route, loci=1):
    """(record bytes, header lines, run report) of the configuration's
    command."""
    config, _reference, _data, path = _cell(seed, loci)
    out = os.path.join(_WORK.name, f"{route}{seed}x{loci}.bam")
    report = out + ".report.json"
    argv = [a.format(in0=path, out=out) for a in config["command"]]
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", "--run-report", report] + argv
        + ROUTES[route], check=True, cwd=_WORK.name,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "", **ENVS.get(route, {})})
    payload = bamio.read_bgzf(out)
    text, start = bamio.split_bam(payload)
    lines = [ln for ln in text.splitlines() if not ln.startswith("@PG")]
    with open(report) as f:
        return payload[start:], lines, json.load(f)


RUNS = [(seed, route) for route in ("default", "indexed") for seed in SEEDS] \
    + [(SEEDS[0], "one-thread"), (SEEDS[2], "classic"),
       (SEEDS[1], "no-native")]


@pytest.mark.parametrize("seed,route", RUNS)
def test_cli_writes_the_reference_records(seed, route):
    got, header, _report = _run(seed, route)
    want, n_records, counted = _expected(seed)
    assert len(bamio.record_offsets(got, 0)) - 1 == n_records \
        == 2 * counted["templates"]
    assert got == want
    _config, reference, _data, _path = _cell(seed)
    assert header == reference.HEADER


def test_the_configurations_reference_is_the_same_records():
    config, reference, data, _path = _cell(SEEDS[0])
    exp = reference.expected(data, config, np.float64)
    want, n_records, _counted = _expected(SEEDS[0])
    assert np.ascontiguousarray(exp["records"]).tobytes() == want
    assert exp["n_records"] == n_records
    assert exp["header"][0] == ("@HD\tVN:1.6\tSO:unsorted\tGO:query"
                                "\tSS:unsorted:template-coordinate")


@pytest.mark.parametrize("seed", SEEDS)
def test_the_control_at_zero_mismatches_differs(seed):
    config, reference, data, _path = _cell(seed)
    low = reference.expected(data, config, np.float32)
    want, n_records, counted = _expected(seed)
    zero, n_zero, counted_zero = _expected(seed, edits=0)
    assert np.ascontiguousarray(low["records"]).tobytes() == zero
    assert n_zero == n_records and zero != want
    assert counted_zero["molecules"] == counted["unique_umis"] \
        > counted["molecules"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_input_has_the_cells_layout(seed):
    _config, _reference, data, _path = _cell(seed)
    per_locus = np.bincount(data["locus"])
    assert sorted(per_locus) == sorted(
        m for n, m in GROUPS for _ in range(n))
    assert len(data["sizes"]) == MOLECULES and data["sizes"].min() == 1
    assert 3.8 < data["sizes"].mean() < 4.8
    assert data["insert"].min() >= 120 and data["insert"].max() <= 180
    assert data["codes1"].shape[1] == 100 and data["umi"].shape[1] == 8
    assert data["n_reads"] == 2 * data["sizes"].sum()
    assert 0.2 < data["r1_reverse"].mean() < 0.3
    # one key a locus, and the loci in stream order
    keys, orient = reference_group.template_keys(data)
    loc = data["locus"][data["fam"]]
    assert (np.diff(loc) >= 0).all() and (np.diff(keys[:, 0]) >= 0).all()
    assert len(np.unique(keys, axis=0)) == len(per_locus)
    assert set(orient) == {1, 2}
    # a template's UMI is its molecule's but for the sequencer's errors
    wrong = (data["umi_t"] != data["umi"][data["fam"]]).mean()
    assert 0.004 < wrong < 0.018


def test_seeds_deal_one_multiset_of_loci():
    a, b = _cell(SEEDS[0])[2], _cell(SEEDS[1])[2]
    assert np.array_equal(np.sort(a["sizes"]), np.sort(b["sizes"]))
    assert not np.array_equal(a["umi"], b["umi"])
    assert sorted(np.bincount(a["locus"])) == sorted(np.bincount(b["locus"]))


@pytest.mark.parametrize("seed,route", RUNS[:6])
def test_counters_add_up(seed, route):
    _got, _header, report = _run(seed, route)
    _want, n_records, counted = _expected(seed)
    m = report["metrics"]
    assert [n for n in COUNTERS if n not in m] == []
    assert m["group.position_groups"] == counted["position_groups"] == 6
    assert m["group.subgroups"] == counted["subgroups"] == 12
    assert m["group.templates"] == counted["templates"] == n_records // 2
    assert m["group.unique_umis"] == counted["unique_umis"]
    assert m["group.molecules"] == counted["molecules"]
    by_route = {r: m.get("group.graph." + r, 0)
                for r in ("dense_host", "device", "sparse_native")}
    assert sum(by_route.values()) == counted["graphs"] == 12
    assert by_route["dense_host"] >= 9
    if route == "default":  # the shipped thresholds: no graph of 8,192
        assert by_route["device"] >= 1 and by_route["sparse_native"] == 0
        assert m["group.hamming.dispatches"] == by_route["device"]
        assert m["group.hamming.bytes_fetched"] \
            == m["group.hamming.cells_padded"] // 8  # a bit a padded pair
        assert m["group.hamming.cells_padded"] \
            == 2048 * 2048 * by_route["device"]
        assert 1024 ** 2 * by_route["device"] <= m["group.hamming.cells"] \
            < m["group.hamming.cells_padded"]
        assert m["group.hamming.rows"] ** 2 >= 4 * m["group.hamming.cells"]
        assert report["device"]["bytes_fetched"] \
            == m["group.hamming.bytes_fetched"]
        # every device graph's lists come from the native pass over its bits
        assert m["group.graph.bits_lists"] == by_route["device"]
    else:
        assert by_route["sparse_native"] >= 2 and by_route["device"] == 0
        assert [n for n in HAMMING if n in m] == []
        assert "group.graph.bits_lists" not in m
    assert m["group.neighbor_pairs"] > 0


def test_every_route_finds_the_same_neighbours():
    pairs = {route: _run(SEEDS[0], route)[2]["metrics"]
             ["group.neighbor_pairs"] for route in ("default", "indexed")}
    assert pairs["default"] == pairs["indexed"]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_bits_lists_find_the_native_routes_pairs(seed):
    """The lists the native pass makes from the device's bits hold the
    pairs the native pigeonhole route finds, on every seed."""
    default = _run(seed, "default")[2]["metrics"]
    indexed = _run(seed, "indexed")[2]["metrics"]
    assert default["group.graph.bits_lists"] \
        == default["group.graph.device"] >= 1
    assert default["group.neighbor_pairs"] == indexed["group.neighbor_pairs"]


def test_without_the_native_library_the_device_route_unpacks_the_bits():
    """The fallback takes the same routes and dispatches, builds no list
    from the bits natively, and finds the same pairs (its records are held
    to the reference by ``test_cli_writes_the_reference_records``)."""
    fallback = _run(SEEDS[1], "no-native")[2]["metrics"]
    default = _run(SEEDS[1], "default")[2]["metrics"]
    assert "group.graph.bits_lists" not in fallback
    for name in ("group.graph.device", "group.graph.dense_host",
                 "group.hamming.dispatches", "group.hamming.cells",
                 "group.neighbor_pairs"):
        assert fallback[name] == default[name], name
    assert fallback["group.graph.device"] >= 1


@pytest.mark.parametrize("route", ["default", "indexed"])
def test_run_report_names_what_group_does(route):
    _got, _header, report = _run(SEEDS[0], route)
    by_name = report["spans"]["by_name"]
    want = SPANS + (DEVICE_SPANS if route == "default" else ())
    assert [n for n in want if n not in by_name] == []
    # the assigner's spans are children of group.assign on the processing
    # thread, and cover it but for the sub-group bookkeeping
    threads = {t for t, rec in report["threads"].items()
               if "group.assign" in rec["self_s"]}
    for name in NEW_SPANS:
        assert {t for t, rec in report["threads"].items()
                if name in rec["self_s"]} == threads, name
    assign = by_name["group.assign"]
    below = sum(by_name[n]["wall_s"] for n in NEW_SPANS
                if n != "group.assign.threshold")  # some of it under .bfs
    assert assign["count"] == 6
    assert 0.5 * assign["wall_s"] < below <= assign["wall_s"] + 1e-3
    # sub-groups under assigners._SPAN_MIN_UMIS uniques open no child span
    assert 6 <= by_name["group.assign.graph"]["count"] <= 12
    assert "utime_s" in by_name["group.assign.threshold"]
    if route == "default":
        fetch = by_name["device.fetch"]
        assert fetch["count"] == by_name["group.hamming.dispatch"]["count"] \
            == report["metrics"]["group.hamming.dispatches"]
        graph = by_name["group.assign.graph"]
        assert graph["wall_s"] - graph["self_s"] >= fetch["wall_s"] - 1e-6
    # group's work paces the job once it outweighs the job's fixed opening
    # (job.open), as it does with four times the loci
    report = _run(SEEDS[0], route, loci=PACING_LOCI)[2]
    assert report["threads_pacing"]["role"] in ("group.assign",
                                                "pipeline.process")


def test_the_shipped_thresholds_and_the_configuration_file():
    assert (assigners.DEVICE_THRESHOLD, assigners.SPARSE_THRESHOLD) \
        == (1024, 8192)
    config, _reference, _data, _path = _cell(SEEDS[0])
    for key in ("source", "deployment", "guarantees", "precision", "reduced",
                "assumed", "kernel_modules"):
        assert key in config, key
    assert len(config["source"]) <= 200
    assert config["precision"] == "exact (integers)"
    assert config["command"][:1] == ["group"]
    assert "--index-threshold" not in config["command"]
    # the name the device plane gives the one jitted Hamming executable
    source = inspect.getsource(assigners)
    assert source.count("@jax.jit") == source.count("def dist(") == 1
    assert re.search(config["kernel_modules"], "jit_dist(1234)")
    assert roofline_hamming.HAMMING_MODULES.pattern \
        == config["kernel_modules"]


def test_spans_cost_nothing_when_not_armed():
    assert not trace.tracing_enabled()
    assert trace.span("group.assign.graph", route="device") is trace.NULL_SPAN
    mat = np.frombuffer(b"ACGTACGAACGTTTTT", dtype=np.uint8).reshape(4, 4)
    graph = assigners.build_neighbor_graph(mat, 1)
    assert [list(graph.neighbors(i)) for i in range(4)] \
        == [[1, 2], [0, 2], [0, 1], []]
