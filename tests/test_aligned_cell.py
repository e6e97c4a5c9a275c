"""The deployment ``simplex`` on a BAM as an aligner writes it (benchmark
configuration ``simplex-c2``) on the CPU: the ``simplex`` CLI against the
benchmark's plain reference with the most-common-alignment filter, byte for
byte, on inputs of the cell's own layout (soft clips and indels inside the
families) at the cell's rates, at five times them and at none; the filter's
native pass over whole segments against the per-group scan it replaced; and
the spans and counters of both in the run report.

Each (seed, rates, engine) is one CLI run in a process of its own, made once
and shared by the tests below through ``_run``.
"""

import functools
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
    import reference
    import reference_aligned as ra
    import run as harness
    import traffic
finally:
    sys.path.remove(BENCH)

from cigar_segments import cigar_buffer  # noqa: E402
from fgumi_tpu.consensus import fast  # noqa: E402
from fgumi_tpu.consensus.vanilla import (VanillaConsensusCaller,  # noqa: E402
                                         VanillaOptions)
from fgumi_tpu.core import cigar as program_cigar  # noqa: E402
from fgumi_tpu.io.bam import BamHeader, BamWriter  # noqa: E402
from fgumi_tpu.io.batch_reader import BamBatchReader  # noqa: E402
from fgumi_tpu.native import batch as nb  # noqa: E402
from fgumi_tpu.observe import trace  # noqa: E402
from fgumi_tpu.simulate import _build_mapped_record  # noqa: E402

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library required")

CELL = "simplex-c2.alnpanel"
SEEDS = [11, 2147483659, 3000000019]
FAMILIES = 400
RATE_KEYS = ("softclip_read_rate", "indel_read_rate", "indel_molecule_rate")
#: the cell's rates times this
RATES = {"cell": 1, "five": 5, "none": 0}
ENGINES = {"default": [], "one-thread": ["--threads", "1"],
           "classic": ["--classic"]}
#: a run the reference does not follow: groups of over eight records are
#: downsampled, which is what the per-group scan is left with
DOWNSAMPLED = {"max-reads": ["--max-reads", "8"]}
_WORK = tempfile.TemporaryDirectory(prefix="aligned_cell_")
aligned = traffic.kind_module("aligned_bam")

NEW_SPANS = ("process.prep.legacy", "process.prep.align_filter")
COUNTERS = ("simplex.groups", "simplex.reads", "simplex.input_reads",
            "simplex.consensus_reads")
FILTER = ("simplex.groups.filtered", "simplex.reads.filtered",
          "simplex.filter.segments", "simplex.filter.segments_kept_all",
          "simplex.filter.reads_in", "simplex.filter.reads_rejected")
LEGACY = ("simplex.groups.legacy", "simplex.groups.legacy.downsample",
          "simplex.reads.legacy")
READERS = ("simplex.legacy_group_share", "simplex.legacy_read_share",
           "simplex.legacy_prep_s_per_mread", "simplex.filter_keep_all_share")


@functools.lru_cache(maxsize=None)
def _cell(seed, rates):
    """(configuration, reference module, input arrays, input path)."""
    _bench, _cell, config, module, params = harness.load_cell(CELL)
    params["num_families"] = FAMILIES
    for key in RATE_KEYS:
        params[key] *= RATES[rates]
    data = traffic.generate(params, seed)
    (path,) = traffic.write_inputs(
        data, os.path.join(_WORK.name, f"in{rates}{seed}"))
    return config, module, data, path


@functools.lru_cache(maxsize=None)
def _expected(seed, rates, dtype=np.float64, alignment_filter=True,
              prove_plain=True):
    """(record bytes, records, the reference's tallies)."""
    config, _module, data, _path = _cell(seed, rates)
    flat, n_records, tallies = ra.simplex(
        data, config["assumed"]["consensus"], dtype, alignment_filter,
        prove_plain)
    return np.ascontiguousarray(flat).tobytes(), n_records, tallies


@functools.lru_cache(maxsize=None)
def _run(seed, rates, engine):
    """(record bytes, header lines, run report) of the configuration's
    command."""
    config, _module, _data, path = _cell(seed, rates)
    out = os.path.join(_WORK.name, f"{engine}{rates}{seed}.bam")
    report = out + ".report.json"
    argv = [a.format(in0=path, out=out) for a in config["command"]]
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", "--run-report", report] + argv
        + {**ENGINES, **DOWNSAMPLED}[engine], check=True, cwd=_WORK.name,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": ""})
    payload = bamio.read_bgzf(out)
    text, start = bamio.split_bam(payload)
    lines = [ln for ln in text.splitlines() if not ln.startswith("@PG")]
    with open(report) as f:
        return payload[start:], lines, json.load(f)


def _records(seed, rates):
    """The input BAM's records, decoded, R1 then R2 of every pair."""
    payload = bamio.read_bgzf(_cell(seed, rates)[3])
    _text, start = bamio.split_bam(payload)
    return [bamio.decode_record(payload, int(off))
            for off in bamio.record_offsets(payload, start)[:-1]]


def _cigar(rec):
    return [(ra.OPS[w & 15], w >> 4) for w in rec["cigar"]]


RUNS = [(seed, rates, engine) for engine in ENGINES for rates in RATES
        for seed in SEEDS]
WITH_CIGARS = [(seed, rates) for rates in ("cell", "five") for seed in SEEDS]


@pytest.mark.parametrize("seed,rates,engine", RUNS)
def test_cli_writes_the_reference_records(seed, rates, engine):
    got, header, _report = _run(seed, rates, engine)
    want, n_records, _tallies = _expected(seed, rates)
    assert len(bamio.record_offsets(got, 0)) - 1 == n_records == 2 * FAMILIES
    assert got == want
    assert header == _cell(seed, rates)[1].HEADER


@pytest.mark.parametrize("seed", SEEDS)
def test_the_configurations_reference_is_the_same_records(seed):
    config, module, data, _path = _cell(seed, "cell")
    exp = module.expected(data, config, np.float64)
    want, n_records, _tallies = _expected(seed, "cell")
    assert np.ascontiguousarray(exp["records"]).tobytes() == want
    assert exp["n_records"] == n_records
    low = module.expected(data, config, np.float32)
    assert np.ascontiguousarray(low["records"]).tobytes() \
        == _expected(seed, "cell", np.float32)[0]


@pytest.mark.parametrize("seed", SEEDS)
def test_without_cigars_it_is_reference_simplex(seed):
    config, _module, data, _path = _cell(seed, "none")
    assert (data["ncig1"] == 1).all() and (data["ncig2"] == 1).all()
    flat, n_records, reads = reference.simplex(
        data, config["assumed"]["consensus"])
    want, n_aligned, tallies = _expected(seed, "none")
    assert np.ascontiguousarray(flat).tobytes() == want
    assert n_records == n_aligned
    assert tallies == {"ConsensusReads": reads}
    # and the generator's arrays are grouped_bam's, draw for draw
    params = traffic.load("longtail", BENCH)
    params["num_families"] = FAMILIES
    plain = traffic.generate(params, seed)
    for key in ("sizes", "insert", "start", "len1", "len2", "codes1",
                "codes2", "quals1", "quals2"):
        assert np.array_equal(plain[key], data[key]), key


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_the_float32_control_differs(seed, rates):
    want, n_records, _tallies = _expected(seed, rates)
    low, n_low, _t = _expected(seed, rates, np.float32)
    assert n_low == n_records and low != want


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_the_reference_without_its_filter_differs(seed, rates):
    """Leaving the filter out cannot pass: a family's minority reads change
    its consensus."""
    want, n_records, tallies = _expected(seed, rates)
    loose, n_loose, loose_tallies = _expected(seed, rates,
                                              alignment_filter=False)
    assert tallies["MinorityAlignment"] > 0
    assert "MinorityAlignment" not in loose_tallies
    assert n_loose == n_records and loose != want


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_walking_the_plain_families_changes_nothing(seed, rates):
    assert _expected(seed, rates, prove_plain=False) == _expected(seed, rates)


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_the_input_has_the_cells_layout(seed, rates):
    _config, _module, d, _path = _cell(seed, rates)
    recs = _records(seed, rates)
    assert len(recs) == d["n_reads"] == 2 * d["sizes"].sum()
    fam = d["fam"]
    shapes = {1: set(), 2: set()}
    wrong = aligned_bases = 0
    for i in range(len(fam)):
        r1, r2 = recs[2 * i], recs[2 * i + 1]
        assert (r1["flag"], r2["flag"]) == (97, 145)
        f = fam[i]
        kind, n_mol, at = d["mol_indel"][f], d["mol_n"][f], d["mol_at"][f]
        for mate, rec, other in ((1, r1, r2), (2, r2, r1)):
            cigar = _cigar(rec)
            text = "".join(f"{n}{op}" for op, n in cigar)
            assert other["tags"]["MC"] == text
            assert other["next_pos"] == rec["pos"] == d[f"pos{mate}"][i]
            # l_seq is the query length
            assert len(rec["seq"]) == d[f"len{mate}"][i] \
                == sum(n for op, n in cigar if op in "MIS")
            shapes[mate].add("".join(op for op, _n in cigar))
            # pos is the first aligned base: every M base is the
            # molecule's base at that reference position, but for the
            # sequencer's substitutions
            ref, query = rec["pos"] - d["start"][f], 0
            for op, n in cigar:
                if op == "M":
                    for k in range(n):
                        j = ref + k
                        if kind == aligned.OP_I and j >= at:
                            j += n_mol
                        elif kind == aligned.OP_D:
                            assert not at <= j < at + n_mol
                            j -= n_mol if j >= at else 0
                        wrong += "ACGT"[d["truth"][f, j]] \
                            != rec["seq"][query + k]
                    aligned_bases += n
                if op in "MD":
                    ref += n
                if op in "MIS":
                    query += n
            if mate == 2:
                assert r1["tlen"] == -r2["tlen"] == ref + d["start"][f] \
                    - r1["pos"]
                if cigar[-1][0] == "M":  # R2 ends where the molecule does
                    last = d["insert"][f] - 1
                    if kind == aligned.OP_I and last >= at + n_mol:
                        last -= n_mol
                    elif kind == aligned.OP_D and last >= at:
                        last += n_mol
                    assert ref - 1 == last
    assert 0.005 < wrong / aligned_bases < 0.015
    # R1's clip on the right, R2's on the left; indels inside M runs
    for mate, clipped in ((1, "MS"), (2, "SM")):
        assert {"M", clipped} <= shapes[mate] \
            <= {"M", "MS", "SM", "MIM", "MDM"}
    for mate, clipped in ((1, "MS"), (2, "SM")):
        ev = d[f"event{mate}"]
        rows = np.flatnonzero(ev == aligned.CLIP3)
        assert len(rows)
        for i in rows:
            assert "".join(op for op, _n in _cigar(recs[2 * i + mate - 1])) \
                == clipped
        share = RATES[rates] * np.array([0.03, 0.005])
        assert 0.5 * share[0] < (ev == aligned.CLIP3).mean() < 1.6 * share[0]
        own = np.isin(ev, (aligned.READ_INS, aligned.READ_DEL)).mean()
        assert 0.2 * share[1] < own < 2.5 * share[1]
    assert 0 < (d["mol_indel"] != 0).mean() < 3 * 0.01 * RATES[rates]


def test_seeds_deal_longtails_multiset_of_sizes():
    a, b = _cell(SEEDS[0], "cell")[2], _cell(SEEDS[1], "cell")[2]
    assert np.array_equal(np.sort(a["sizes"]), np.sort(b["sizes"]))
    assert not np.array_equal(a["sizes"], b["sizes"])
    # at the cell's size: longtail's multiset, so the two cells are twins
    cell = traffic.load("alnpanel", BENCH)
    twin = traffic.load("longtail", BENCH)
    for key in ("num_families", "family_size", "family_size_distribution",
                "read_length", "read_length_jitter", "qual_slope",
                "error_rate"):
        assert cell[key] == twin[key], key
    sizes = traffic.family_sizes(np.random.default_rng(1), cell)
    assert 2 * sizes.sum() == 998960 and sizes.max() == 50


@pytest.mark.parametrize("seed,rates", WITH_CIGARS + [(SEEDS[0], "none")])
def test_counters_add_up(seed, rates):
    _got, _header, report = _run(seed, rates, "default")
    _want, n_records, tallies = _expected(seed, rates)
    d = _cell(seed, rates)[2]
    m = report["metrics"]
    assert [n for n in COUNTERS if n not in m] == []
    assert m["simplex.groups"] == FAMILIES
    assert m["simplex.reads"] == m["simplex.input_reads"] == d["n_reads"]
    assert m["simplex.consensus_reads"] == n_records
    if rates == "none":
        assert [n for n in m if n.startswith(("simplex.filter",
                                              "simplex.groups.legacy",
                                              "simplex.rejected"))] == []
        return
    # the filter is a native pass over whole segments: no group leaves the
    # whole-array preparation for it
    assert [n for n in m if n.startswith("simplex.groups.legacy")
            or n == "simplex.reads.legacy"] == []
    assert [n for n in FILTER if n not in m] == []
    assert 0 < m["simplex.reads.filtered"] <= m["simplex.reads"]
    assert 0 < m["simplex.groups.filtered"] < m["simplex.groups"]
    assert m["simplex.filter.reads_rejected"] == tallies["MinorityAlignment"] \
        == m["simplex.rejected.MinorityAlignment"]
    assert 0 < m["simplex.filter.segments_kept_all"] \
        <= m["simplex.filter.segments"]
    assert m["simplex.groups.filtered"] <= m["simplex.filter.segments"] \
        <= 2 * m["simplex.groups.filtered"]
    assert m["simplex.filter.reads_rejected"] < m["simplex.filter.reads_in"] \
        <= m["simplex.reads.filtered"]
    # every input read accounted for
    assert sum(tallies.values()) == d["n_reads"]
    assert tallies["ConsensusReads"] + sum(
        v for k, v in m.items() if k.startswith("simplex.rejected.")) \
        == m["simplex.input_reads"]


def _on_the_prep_thread(report, name):
    """The threads whose account holds ``name`` are ``process.prep``'s."""
    holders = {t for t, rec in report["threads"].items()
               if name in rec["self_s"]}
    return holders and holders == {
        t for t, rec in report["threads"].items()
        if "process.prep" in rec["self_s"]}


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_run_report_names_the_filters_native_pass(seed, rates):
    _got, _header, report = _run(seed, rates, "default")
    by_name = report["spans"]["by_name"]
    assert "process.prep.legacy" not in by_name
    prep, filt = by_name["process.prep"], by_name["process.prep.align_filter"]
    # one span a batch round the one native call, not one a segment; a
    # child of process.prep, whose self time therefore does not hold it
    assert filt["count"] == 1 < report["metrics"]["simplex.filter.segments"]
    assert "utime_s" in filt
    assert 0 < filt["wall_s"] <= prep["wall_s"] - prep["self_s"] + 1e-5
    assert _on_the_prep_thread(report, "process.prep.align_filter")


def test_a_downsample_opens_the_per_group_preparation():
    """``--max-reads``: the groups over it take the per-group scan, under
    one ``process.prep.legacy`` span a batch, and the scan's filter is the
    same native function, a segment a call."""
    _got, _header, report = _run(SEEDS[0], "five", "max-reads")
    m, by_name = report["metrics"], report["spans"]["by_name"]
    assert [n for n in NEW_SPANS if n not in by_name] == []
    assert [n for n in LEGACY + FILTER if n not in m] == []
    prep, legacy = by_name["process.prep"], by_name["process.prep.legacy"]
    assert legacy["count"] == 1 < m["simplex.groups.legacy"] \
        == m["simplex.groups.legacy.downsample"] < m["simplex.groups"]
    assert 0 < m["simplex.reads.legacy"] < m["simplex.reads"]
    assert "utime_s" in legacy
    assert prep["wall_s"] - prep["self_s"] >= legacy["wall_s"] \
        + by_name["process.prep.align_filter"]["wall_s"] - 1e-5
    assert _on_the_prep_thread(report, "process.prep.legacy")
    # the filter's counters hold both routes' segments
    assert m["simplex.filter.reads_rejected"] \
        == m["simplex.rejected.MinorityAlignment"] > 0
    assert m["simplex.filter.reads_in"] <= m["simplex.reads.filtered"]
    assert m["simplex.input_reads"] == m["simplex.reads"] \
        == _cell(SEEDS[0], "five")[2]["n_reads"]


def test_no_cigars_no_new_span():
    """What ``host.prep_s_per_mread`` reads in the accepted cells does not
    move: a job with no group on the per-group path opens neither span."""
    by_name = _run(SEEDS[0], "none", "default")[2]["spans"]["by_name"]
    assert "process.prep" in by_name
    assert [n for n in NEW_SPANS if n in by_name] == []


def test_spans_cost_nothing_when_not_armed():
    assert not trace.tracing_enabled()
    for name in NEW_SPANS:
        assert trace.span(name, rusage=True, segments=3) is trace.NULL_SPAN
    caller = fast.FastSimplexCaller.__new__(fast.FastSimplexCaller)
    caller._filter_tally = fast._FilterTally()
    caller._fold_filter_tally()  # no filter ran: nothing is recorded
    assert caller._filter_tally is None


def _reader(name):
    sys.path.insert(0, BENCH)  # a reader imports the benchmark's ``spans``
    try:
        return harness.load_module(
            os.path.join(BENCH, "metrics", name + ".py"),
            "metric_" + name.replace(".", "_"))
    finally:
        sys.path.remove(BENCH)


@pytest.mark.parametrize("name", READERS)
def test_the_readers(name):
    # where groups still take the per-group scan: a downsample
    report = _run(SEEDS[0], "five", "max-reads")[2]
    d = _cell(SEEDS[0], "five")[2]
    run = {"reports": [report, report], "traced_jobs": 2,
           "reads_per_job": d["n_reads"]}
    value = _reader(name).read(run)
    m, by_name = report["metrics"], report["spans"]["by_name"]
    want = {
        "simplex.legacy_group_share":
            100.0 * m["simplex.groups.legacy"] / m["simplex.groups"],
        "simplex.legacy_read_share":
            100.0 * m["simplex.reads.legacy"] / m["simplex.reads"],
        "simplex.legacy_prep_s_per_mread":
            by_name["process.prep.legacy"]["wall_s"] / (d["n_reads"] / 1e6),
        "simplex.filter_keep_all_share":
            100.0 * m["simplex.filter.segments_kept_all"]
            / m["simplex.filter.segments"]}[name]
    assert value == pytest.approx(want) and value > 0
    # the cell's command: no group leaves the whole-array preparation, so
    # the shares read 0.0 and the span's reader nothing; the filter's share
    # counts the input and is what it was
    cell = _run(SEEDS[0], "five", "default")[2]
    got = _reader(name).read({**run, "reports": [cell, cell]})
    mc = cell["metrics"]
    assert got == {
        "simplex.legacy_group_share": 0.0,
        "simplex.legacy_read_share": 0.0,
        "simplex.legacy_prep_s_per_mread": None,
        "simplex.filter_keep_all_share":
            pytest.approx(100.0 * mc["simplex.filter.segments_kept_all"]
                          / mc["simplex.filter.segments"])}[name]
    # a program from before the counters and spans: nothing, and no raise
    old = {k: v for k, v in report.items() if k != "spans"}
    old["metrics"] = {k: v for k, v in m.items()
                      if not k.startswith("simplex.")}
    assert _reader(name).read({**run, "reports": [old, old]}) is None
    bare = dict(report, metrics=old["metrics"], spans={"by_name": {
        k: v for k, v in by_name.items() if k not in NEW_SPANS}})
    assert _reader(name).read({**run, "reports": [bare, bare]}) is None


def test_the_configuration_file_and_the_benchmark_agree():
    bench, cell, config, _module, params = harness.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("simplex-c2", "alnpanel", 1)
    entry = [c for c in bench["configs"] if c["name"] == "simplex-c2"][0]
    assert entry["reduced"] == config["reduced"] == []
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmark/configs/simplex-c2.json"
    twin = harness.load_cell("simplex-c1.longtail")[2]
    assert config["command"] == twin["command"] \
        == ["simplex", "-i", "{in0}", "-o", "{out}", "--min-reads", "1",
            "--threads", "4"]
    assert config["assumed"]["consensus"] == twin["assumed"]["consensus"]
    assert config["assumed"]["threads"] == 4
    assert config["kernel_modules"] == twin["kernel_modules"]
    assert (config["precision"], config["warm_jobs"]) == ("float64", 2)
    for key in ("deployment", "guarantees", "assumed"):
        assert key in config, key
    assert params["kind"] == "aligned_bam"
    assert [params[k] for k in RATE_KEYS] == [0.03, 0.005, 0.01]
    for key in RATE_KEYS + ("softclip_length", "indel_length",
                            "indel_margin", "unchecked"):
        assert key in params["assumed"], key
    new = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in new] == list(READERS)
    for metric in new:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "reads_per_s"


# ------------------------------------------------ the filter's rules, by hand

def _m(n):
    return [("M", n)]


FILTER_CASES = {
    # name: ([(length, simplified CIGAR in read orientation)], kept)
    "one read": ([(100, _m(100))], [0]),
    "prefix-compatible lengths": (
        [(80, _m(80)), (100, _m(100)), (70, _m(70))], [0, 1, 2]),
    "the majority wins": (
        [(100, _m(100)), (100, [("M", 40), ("I", 2), ("M", 58)]),
         (90, _m(90)), (95, _m(95))], [0, 2, 3]),
    "a shorter read joins the indel's group while it is a prefix": (
        [(100, [("M", 40), ("D", 1), ("M", 60)]),
         (100, [("M", 40), ("D", 1), ("M", 60)]), (100, _m(100)),
         (30, _m(30))], [0, 1, 3]),
    "a read joins every group it is a prefix of": (
        # 30M joins both; each group then has two reads: the tie goes to
        # the smaller CIGAR, 40M2I58M before 100M (40 < 100)
        [(100, _m(100)), (100, [("M", 40), ("I", 2), ("M", 58)]),
         (30, _m(30))], [1, 2]),
    "a tie: the length decides first": (
        [(100, [("M", 50), ("D", 1), ("M", 50)]),
         (100, [("M", 60), ("I", 1), ("M", 39)])], [0]),
    "a tie: then the op, I before D": (
        [(100, [("M", 50), ("D", 1), ("M", 50)]),
         (100, [("M", 50), ("I", 1), ("M", 49)])], [1]),
    "a tie: the first difference decides": (
        [(100, [("M", 50), ("I", 1), ("M", 49)]),
         (100, [("M", 50), ("I", 1), ("M", 20), ("D", 2), ("M", 29)])], [1]),
    "equal lengths keep their order": (
        [(100, [("M", 98), ("I", 2)]), (100, _m(100)),
         (100, [("M", 98), ("I", 2)])], [0, 2]),
}


@pytest.mark.parametrize("name", FILTER_CASES)
def test_the_filters_rules(name):
    reads, kept = FILTER_CASES[name]
    assert ra.most_common_alignment(reads) == kept
    # the program's filter, given the same reads longest first
    order = sorted(range(len(reads)), key=lambda i: -reads[i][0])
    assert sorted(program_cigar.select_most_common_alignment_group(
        [(i, reads[i][0], reads[i][1]) for i in order])) == kept
    # and the program's native pass, on the same CIGARs as BAM words
    assert _native_kept([(cigar, False, length)
                         for length, cigar in reads]) == kept


RAW_CASES = {
    # name: ([(raw CIGAR, reverse strand, source read's length)], kept)
    "5S95M beside 100M keeps both": (
        [("5S95M", False, 100), ("100M", False, 100)], [0, 1]),
    "a clip and = X simplify to M": (
        [("10S50=1X39M", False, 100), ("100M", False, 100),
         ("3H100M", False, 100)], [0, 1, 2]),
    "a reverse-strand CIGAR is read from its end": (
        # in read orientation 30M2D70M and 70M2D30M: two groups, the tie
        # to 30M...; forward they would be one CIGAR and both kept
        [("70M2D30M", True, 100), ("70M2D30M", False, 100)], [0]),
    "reverse strand, a palindrome": (
        [("40M2I16M2I40M", True, 100), ("40M2I16M2I40M", False, 100)],
        [0, 1]),
    "truncated to the read's length": (
        # the trimmed read no longer reaches the insertion
        [("60M1I39M", False, 50), ("100M", False, 100)], [0, 1]),
    "a deletion before the cut stays": (
        [("50M2D50M", False, 51), ("100M", False, 100),
         ("90M", False, 90)], [1, 2]),
    "a deletion at the cut goes": (
        [("50M2D50M", False, 50), ("100M", False, 100),
         ("90M", False, 90)], [0, 1, 2]),
}


def _parse(text):
    """A CIGAR string as [(op, length)]."""
    return [(op, int(n)) for n, op in re.findall(r"(\d+)([MIDNSHP=X])", text)]


def _native_kept(reads):
    """The rows ``nb.alignment_filter`` keeps of one segment of
    (raw CIGAR, reverse strand, length) reads."""
    buf, cigar_off, n_cigar = cigar_buffer(
        [cigar for cigar, _rev, _n in reads], np.random.default_rng(7))
    keep = nb.alignment_filter(
        buf, cigar_off, n_cigar, np.array([rev for _c, rev, _n in reads]),
        np.array([n for _c, _rev, n in reads]), np.array([0, len(reads)]))
    return np.flatnonzero(keep).tolist()


@pytest.mark.parametrize("name", RAW_CASES)
def test_the_filter_on_raw_cigars(name):
    raw, kept = RAW_CASES[name]
    reads = []
    for text, reverse, length in raw:
        cigar = ra.simplify(_parse(text))
        if reverse:
            cigar = cigar[::-1]
        reads.append((length, ra.truncate(cigar, length)))
    assert ra.most_common_alignment(reads) == kept
    assert _native_kept([(_parse(text), reverse, length)
                         for text, reverse, length in raw]) == kept


MATE_CASES = {
    # name: ((CIGAR, pos, reverse, mate's CIGAR, mate's pos), bases clipped)
    "inside the mate's span": (("100M", 1001, False, "100M", 1101), 0),
    "forward read past the mate's end": (
        ("100M", 1001, False, "80M", 1011), 10),
    "the mate's soft clip counts": (("100M", 1001, False, "80M5S", 1011), 5),
    "only the excess of a trailing clip": (
        ("90M10S", 1001, False, "80M", 1015), 6),
    "reverse read before the mate's start": (
        ("100M", 1001, True, "100M", 1011), 10),
    "through an insertion": (("50M2I48M", 1001, False, "60M", 1021), 18),
    "the far end in a deletion clips it all": (
        ("50M5D50M", 1001, False, "52M", 1001), 100),
}


@pytest.mark.parametrize("name", MATE_CASES)
def test_bases_past_the_mate(name):
    (text, pos, reverse, mate_text, mate_pos), clipped = MATE_CASES[name]
    assert ra.bases_past_mate(_parse(text), pos, reverse, _parse(mate_text),
                              mate_pos) == clipped


# ------------------------------- the array path against the per-group scan

def _prepared(path, min_reads, scan):
    """The engine's job tables, a batch each, in a form that does not
    depend on how the row pool is laid out, its tallies, and its records:
    by the whole-array preparation, or (``scan``) by the per-group scan that
    ``--rejects`` forces."""
    caller = VanillaConsensusCaller(
        "fgumi", "A", VanillaOptions(min_reads=min_reads),
        track_rejects=scan)
    engine = fast.FastSimplexCaller(caller, b"MI")
    tables = []

    def prepare(*args):
        codes, quals, t = prepare_jobs(*args)
        tables.append([
            (int(t.read_type[j]), int(t.cons_len[j]), int(t.mi_rec[j]),
             t.pool_rows[t.vlo[j]:t.vlo[j] + t.count[j]].tolist(),
             t.pool_span[t.vlo[j]:t.vlo[j] + t.count[j]].tolist())
            for j in range(len(t))])
        return codes, quals, t

    prepare_jobs, engine._prepare_jobs = engine._prepare_jobs, prepare
    chunks = []
    with BamBatchReader(path, target_bytes=64 << 10) as reader:
        for batch in reader:
            chunks.extend(engine.process_batch(batch))
    chunks.extend(engine.flush())
    records = b"".join(map(fast.resolve_chunk, chunks))
    stats = caller.stats
    return tables, dict(stats.rejected), stats.input_reads, \
        stats.consensus_reads, records


@pytest.mark.parametrize("min_reads", [1, 2, 3])
@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_the_array_path_prepares_what_the_scan_prepares(seed, rates,
                                                        min_reads):
    path = _cell(seed, rates)[3]
    vec = _prepared(path, min_reads, scan=False)
    assert vec == _prepared(path, min_reads, scan=True)
    tables, rejected, _reads, _consensus, _records = vec
    assert sum(map(len, tables)) > FAMILIES / 4 and rejected[
        "MinorityAlignment"] > 0
    if min_reads > 1:
        assert rejected["InsufficientReads"] > 0


def _hand_bam(path):
    """Three paired families whose filter decides what the later steps see;
    a fourth of fragment reads with one CIGAR on both strands, which read
    from the reverse read's end is another CIGAR; and a fifth of three plain
    fragment reads for the batch's open tail (the last group goes through
    the per-group caller either way)."""
    rng = np.random.default_rng(5)
    m100, ins, dele = [("M", 100)], [("M", 40), ("I", 2), ("M", 58)], \
        [("M", 60), ("D", 3), ("M", 40)]
    families = [
        # R2's three CIGARs found three groups of one read: the filter
        # leaves one read, under --min-reads 2, and R1 is an orphan
        ([m100, m100, m100], [m100, ins, dele]),
        # the filter takes one read of each type and both stay
        ([m100, m100, ins], [dele, dele, dele, m100]),
        # a trimmed-to-nothing read goes before the filter sees the rest
        ([m100, ins, ins, m100, m100], [m100, m100, m100, m100, m100]),
    ]
    header = BamHeader(
        text="@HD\tVN:1.6\tSO:unsorted\tGO:query\n@SQ\tSN:c1\tLN:100000\n"
             "@RG\tID:A\tSM:s\n", ref_names=["c1"], ref_lengths=[100000])
    with BamWriter(path, header) as w:
        for f, (r1s, r2s) in enumerate(families):
            for mate, cigars in ((0, r1s), (1, r2s)):
                for r, cig in enumerate(cigars):
                    n = sum(k for op, k in cig if op in "MI")
                    quals = np.full(n, 35, np.uint8)
                    if f == 2 and mate == 0 and r == 0:
                        quals[:] = 2  # every base masked: zero length
                    seq = bytes(b"ACGT"[c] for c in rng.integers(0, 4, n))
                    flag = 0x1 | (0x40 if mate == 0 else 0x80 | 0x10) \
                        | (0x20 if mate == 0 else 0)
                    w.write_record_bytes(_build_mapped_record(
                        f"f{f}t{r}".encode(), flag, 0, 1000 + 300 * mate, 60,
                        cig, seq, quals, 0, 1300 - 300 * mate, 0,
                        [(b"MI", "Z", str(f).encode()),
                         (b"RG", "Z", b"A")]))
        for mi, cig, flags in ((b"3", [("M", 70), ("D", 2), ("M", 30)],
                                (0x10, 0, 0)), (b"4", m100, (0, 0, 0))):
            for r, flag in enumerate(flags):
                w.write_record_bytes(_build_mapped_record(
                    b"frag%s.%d" % (mi, r), flag, 0, 5000, 60, cig,
                    bytes(b"ACGT"[c] for c in rng.integers(0, 4, 100)),
                    np.full(100, 35, np.uint8), -1, -1, 0,
                    [(b"MI", "Z", mi), (b"RG", "Z", b"A")]))
    return path


HAND_REJECTS = {
    1: {"MinorityAlignment": 2 + 2 + 2 + 1, "ZeroLengthAfterTrimming": 1},
    2: {"MinorityAlignment": 2 + 2 + 2 + 1, "ZeroLengthAfterTrimming": 1,
        "InsufficientReads": 1, "OrphanConsensus": 3},
    3: {"MinorityAlignment": 2 + 2 + 2 + 1, "ZeroLengthAfterTrimming": 1,
        "InsufficientReads": 1 + 2 + 2 + 2, "OrphanConsensus": 3 + 3 + 5},
}


@pytest.mark.parametrize("min_reads", [1, 2, 3])
def test_the_filter_can_take_a_segment_under_min_reads(min_reads):
    path = _hand_bam(os.path.join(_WORK.name, "hand.bam"))
    vec = _prepared(path, min_reads, scan=False)
    assert vec == _prepared(path, min_reads, scan=True)
    assert vec[1] == HAND_REJECTS[min_reads]
    # every input read is rejected once or in one job (the tail's three
    # in the per-group caller's)
    assert vec[2] == 29 == sum(vec[1].values()) + 3 \
        + sum(len(rows) for t in vec[0] for *_job, rows, _span in t)


def test_the_reference_imports_nothing_of_the_program():
    for module in (ra, aligned):
        with open(module.__file__) as f:
            assert "fgumi_tpu" not in f.read().replace(
                "``python -m fgumi_tpu", "")
