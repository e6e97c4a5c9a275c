"""The deployment ``simplex`` on a BAM as an aligner writes it (benchmark
configuration ``simplex-c2``) on the CPU: the ``simplex`` CLI against the
benchmark's plain reference with the most-common-alignment filter, byte for
byte, on inputs of the cell's own layout (soft clips and indels inside the
families) at the cell's rates, at five times them and at none, and the spans
and counters of the per-group preparation in its run report.

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

from fgumi_tpu.consensus import fast  # noqa: E402
from fgumi_tpu.core import cigar as program_cigar  # noqa: E402
from fgumi_tpu.native import batch as nb  # noqa: E402
from fgumi_tpu.observe import trace  # noqa: E402

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
_WORK = tempfile.TemporaryDirectory(prefix="aligned_cell_")
aligned = traffic.kind_module("aligned_bam")

NEW_SPANS = ("process.prep.legacy", "process.prep.align_filter")
COUNTERS = ("simplex.groups", "simplex.reads", "simplex.input_reads",
            "simplex.consensus_reads")
LEGACY = ("simplex.groups.legacy", "simplex.groups.legacy.cigar",
          "simplex.reads.legacy", "simplex.filter.segments",
          "simplex.filter.segments_kept_all", "simplex.filter.reads_in",
          "simplex.filter.reads_rejected")
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
        + ENGINES[engine], check=True, cwd=_WORK.name,
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
    assert [n for n in LEGACY if n not in m] == []
    assert 0 < m["simplex.reads.legacy"] <= m["simplex.reads"]
    assert m["simplex.groups.legacy"] == m["simplex.groups.legacy.cigar"] \
        < m["simplex.groups"]
    assert m["simplex.filter.reads_rejected"] == tallies["MinorityAlignment"] \
        == m["simplex.rejected.MinorityAlignment"]
    assert 0 < m["simplex.filter.segments_kept_all"] \
        <= m["simplex.filter.segments"]
    assert m["simplex.filter.reads_rejected"] < m["simplex.filter.reads_in"] \
        <= m["simplex.reads.legacy"]
    # every input read accounted for
    assert sum(tallies.values()) == d["n_reads"]
    assert tallies["ConsensusReads"] + sum(
        v for k, v in m.items() if k.startswith("simplex.rejected.")) \
        == m["simplex.input_reads"]


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_run_report_names_the_per_group_preparation(seed, rates):
    _got, _header, report = _run(seed, rates, "default")
    by_name = report["spans"]["by_name"]
    assert [n for n in NEW_SPANS if n not in by_name] == []
    prep, legacy = by_name["process.prep"], by_name["process.prep.legacy"]
    # one span a batch, not one a group; a child of process.prep, whose
    # self time therefore does not hold it
    assert legacy["count"] == 1 < report["metrics"]["simplex.groups.legacy"]
    assert "utime_s" in legacy
    assert prep["wall_s"] - prep["self_s"] >= legacy["wall_s"] - 1e-5
    # the filter's seconds lie inside it
    assert 0 < by_name["process.prep.align_filter"]["wall_s"] \
        <= legacy["wall_s"]
    threads = {t for t, rec in report["threads"].items()
               if "process.prep" in rec["self_s"]}
    assert {t for t, rec in report["threads"].items()
            if "process.prep.legacy" in rec["self_s"]} == threads


def test_no_cigars_no_new_span():
    """What ``host.prep_s_per_mread`` reads in the accepted cells does not
    move: a job with no group on the per-group path opens neither span."""
    by_name = _run(SEEDS[0], "none", "default")[2]["spans"]["by_name"]
    assert "process.prep" in by_name
    assert [n for n in NEW_SPANS if n in by_name] == []


def test_spans_cost_nothing_when_not_armed():
    assert not trace.tracing_enabled()
    assert trace.span("process.prep.legacy", groups=3) is trace.NULL_SPAN
    caller = fast.FastSimplexCaller.__new__(fast.FastSimplexCaller)
    caller._filter_tally = fast._FilterTally(timed=False)
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
    report = _run(SEEDS[0], "five", "default")[2]
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


def test_the_reference_imports_nothing_of_the_program():
    for module in (ra, aligned):
        with open(module.__file__) as f:
            assert "fgumi_tpu" not in f.read().replace(
                "``python -m fgumi_tpu", "")
