"""The deployment ``duplex`` on a BAM as an aligner writes it (benchmark
configuration ``duplex-c3-aln``) on the CPU: the ``duplex`` CLI against the
benchmark's plain reference with the X/Y most-common-alignment filter, byte
for byte, on inputs of the cell's own layout (soft clips and indels inside
AB/BA molecules) at the cell's rates, at four times them and at none, on the
host and the device engine; the account of the reads, the spans and the
counters in the run report, and the filter's rules on hand-made molecules.

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
    import reference_aligned as ra
    import reference_duplex
    import reference_duplex_aligned as rda
    import run as harness
    import traffic
finally:
    sys.path.remove(BENCH)

from fgumi_tpu.cli import main as cli_main  # noqa: E402
from fgumi_tpu.io.bam import BamHeader, BamWriter  # noqa: E402
from fgumi_tpu.native import batch as nb  # noqa: E402
from fgumi_tpu.observe import trace  # noqa: E402
from fgumi_tpu.simulate import _build_mapped_record  # noqa: E402

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library required")

CELL = "duplex-c3-aln.panelaln"
SEEDS = [11, 2147483659, 3000000019]
FAMILIES = 400  # strand families: about 230 molecules, 3,100 reads
RATE_KEYS = ("softclip_read_rate", "indel_read_rate", "indel_molecule_rate")
#: the cell's rates times this
RATES = {"cell": 1, "four": 4, "none": 0}
#: engine -> (environment, extra arguments); one device, as the chip has
ENGINES = {
    "host": ({"FGUMI_TPU_ROUTE": "host"}, []),
    "device": ({"FGUMI_TPU_HOST_ENGINE": "0", "FGUMI_TPU_ROUTE": "device"},
               ["--devices", "1"]),
}
_WORK = tempfile.TemporaryDirectory(prefix="aligned_duplex_cell_")
layout = traffic.kind_module("aligned_duplex_bam")

#: what still takes the per-molecule caller, by first cause
REASONS = ("first_last", "downsample", "all")
FILTER = ("duplex.filter.sets", "duplex.filter.sets_kept_all",
          "duplex.filter.reads_in", "duplex.filter.reads_rejected")
READERS = ("duplex.fallback_s_per_mread", "duplex.filter_read_share",
           "duplex.filter_keep_all_share")


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


def _opts(config):
    return {**config["assumed"]["consensus"],
            "min_reads": config["assumed"]["min_reads"]}


@functools.lru_cache(maxsize=None)
def _expected(seed, rates, dtype=np.float64, alignment_filter=True,
              prove_plain=True):
    """(record bytes, records, the reference's tallies)."""
    config, _module, data, _path = _cell(seed, rates)
    flat, n_records, tallies = rda.duplex(
        data, _opts(config), dtype, alignment_filter, prove_plain)
    return np.ascontiguousarray(flat).tobytes(), n_records, tallies


def _cli(path, out, config, engine, extra=()):
    """(record bytes, header lines, run report) of the configuration's
    command on ``path``; its span events are left in ``out + ".trace.json"``
    (``_trace_events``)."""
    env, args = ENGINES[engine]
    report = out + ".report.json"
    argv = [a.format(in0=path, out=out) for a in config["command"]]
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", "--run-report", report,
         "--trace", out + ".trace.json"] + argv
        + args + list(extra), check=True, cwd=_WORK.name,
        env={**os.environ, **env, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": ""})
    payload = bamio.read_bgzf(out)
    text, start = bamio.split_bam(payload)
    lines = [ln for ln in text.splitlines() if not ln.startswith("@PG")]
    with open(report) as f:
        return payload[start:], lines, json.load(f)


def _out(seed, rates, engine):
    return os.path.join(_WORK.name, f"{engine}{rates}{seed}.bam")


@functools.lru_cache(maxsize=None)
def _run(seed, rates, engine):
    config, _module, _data, path = _cell(seed, rates)
    return _cli(path, _out(seed, rates, engine), config, engine)


def _trace_events(out, name):
    """The complete events named ``name`` in the trace of the run that
    wrote ``out``."""
    with open(out + ".trace.json") as f:
        events = json.load(f)
    if isinstance(events, dict):
        events = events["traceEvents"]
    return [e for e in events if e.get("ph") == "X" and e["name"] == name]


RUNS = [(seed, rates, engine) for engine in ENGINES for rates in RATES
        for seed in SEEDS]
WITH_CIGARS = [(seed, rates) for rates in ("cell", "four") for seed in SEEDS]


@pytest.mark.parametrize("seed,rates,engine", RUNS)
def test_cli_writes_the_reference_records(seed, rates, engine):
    got, header, _report = _run(seed, rates, engine)
    want, n_records, _tallies = _expected(seed, rates)
    assert len(bamio.record_offsets(got, 0)) - 1 == n_records > FAMILIES
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
def test_without_cigars_it_is_reference_duplex(seed):
    config, _module, data, path = _cell(seed, "none")
    assert (data["ncig1"] == 1).all() and (data["ncig2"] == 1).all()
    flat, n_records, reads = reference_duplex.duplex(data, _opts(config))
    want, n_aligned, tallies = _expected(seed, "none")
    assert np.ascontiguousarray(flat).tobytes() == want
    assert n_records == n_aligned
    assert tallies == {"ConsensusReads": reads}
    # and the input is duplex_bam's, byte for byte
    params = traffic.load("panel", BENCH)
    params["num_families"] = FAMILIES
    (plain,) = traffic.write_inputs(traffic.generate(params, seed),
                                    os.path.join(_WORK.name, f"panel{seed}"))
    assert bamio.read_bgzf(plain) == bamio.read_bgzf(path)


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_the_float32_control_differs(seed, rates):
    want, n_records, _tallies = _expected(seed, rates)
    low, n_low, _t = _expected(seed, rates, np.float32)
    assert n_low == n_records and low != want


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_the_reference_without_its_filter_differs(seed, rates):
    """Leaving the X/Y filter out cannot pass: a set's minority reads change
    its consensus."""
    want, _n_records, tallies = _expected(seed, rates)
    loose, _n_loose, loose_tallies = _expected(seed, rates,
                                               alignment_filter=False)
    assert tallies["MinorityAlignment"] > 0
    assert "MinorityAlignment" not in loose_tallies
    assert loose != want


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_walking_the_plain_molecules_changes_nothing(seed, rates):
    assert _expected(seed, rates, prove_plain=False) == _expected(seed, rates)


def _cigar(rec):
    return [(ra.OPS[w & 15], w >> 4) for w in rec["cigar"]]


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_the_input_has_the_cells_layout(seed, rates):
    _config, _module, d, path = _cell(seed, rates)
    payload = bamio.read_bgzf(path)
    _text, start = bamio.split_bam(payload)
    recs = [bamio.decode_record(payload, int(off))
            for off in bamio.record_offsets(payload, start)[:-1]]
    assert len(recs) == d["n_reads"]
    mol = d["mol_of_fam"][d["fam"]]
    shapes = {1: set(), 2: set()}
    carried = {}  # molecule -> strands whose reads carry its indel
    wrong = aligned_bases = 0
    for i, m in enumerate(mol):
        fwd, rev = recs[2 * i], recs[2 * i + 1]
        strand = d["strand_of_fam"][d["fam"][i]]
        assert (fwd["flag"], rev["flag"]) == ((97, 145), (161, 81))[strand]
        assert fwd["tags"]["MI"] == rev["tags"]["MI"] == f"{m}/{'AB'[strand]}"
        kind, n_mol, at = d["mol_indel"][m], d["mol_n"][m], d["mol_at"][m]
        for mate, rec, other in ((1, fwd, rev), (2, rev, fwd)):
            cigar = _cigar(rec)
            assert other["tags"]["MC"] == "".join(f"{n}{op}"
                                                  for op, n in cigar)
            assert other["next_pos"] == rec["pos"] == d[f"pos{mate}"][i]
            assert len(rec["seq"]) == 100 == sum(n for op, n in cigar
                                                 if op in "MIS")
            shapes[mate].add("".join(op for op, _n in cigar))
            if d[f"event{mate}"][i] == layout._aligned.MOL_CARRIED:
                carried.setdefault(m, set()).add(strand)
            # every M base is the molecule's base at its reference
            # position, but for the sequencer's substitutions
            ref, query = rec["pos"] - d["start"][m], 0
            for op, n in cigar:
                if op == "M":
                    for k in range(n):
                        j = ref + k
                        if kind == layout._aligned.OP_I and j >= at:
                            j += n_mol
                        elif kind == layout._aligned.OP_D:
                            assert not at <= j < at + n_mol
                            j -= n_mol if j >= at else 0
                        wrong += "ACGT"[d["truth"][m, j]] \
                            != rec["seq"][query + k]
                    aligned_bases += n
                if op in "MD":
                    ref += n
                if op in "MIS":
                    query += n
            if mate == 2:
                assert fwd["tlen"] == -rev["tlen"] \
                    == ref + d["start"][m] - fwd["pos"]
    assert 0.005 < wrong / aligned_bases < 0.015
    # the forward read's clip on its right, the reverse read's on its left
    for mate, clipped in ((1, "MS"), (2, "SM")):
        assert {"M", clipped} <= shapes[mate] \
            <= {"M", "MS", "SM", "MIM", "MDM"}
    # one draw a molecule: where a duplex molecule's indel is carried, its
    # AB and BA reads carry it alike
    duplex = [m for m in carried if d["mol_kind"][m] == layout.DUPLEX]
    assert duplex and any(carried[m] == {0, 1} for m in duplex)


def test_seeds_deal_panels_multiset_of_sizes():
    a, b = _cell(SEEDS[0], "cell")[2], _cell(SEEDS[1], "cell")[2]
    assert np.array_equal(np.sort(a["sizes"]), np.sort(b["sizes"]))
    assert not np.array_equal(a["sizes"], b["sizes"])
    # at the cell's size: the first 46,400 of panel's 116,000 draws
    cell = traffic.load("panelaln", BENCH)
    panel = traffic.load("panel", BENCH)
    for key in ("family_size", "family_size_distribution", "read_length",
                "read_length_jitter", "qual_slope", "error_rate",
                "base_quality", "qual_jitter", "duplex_share", "umi_length"):
        assert cell[key] == panel[key], key
    sizes = traffic.family_sizes(np.random.default_rng(1), cell)
    whole = traffic.family_sizes(np.random.default_rng(1), panel)
    assert cell["num_families"] == 46400 and panel["num_families"] == 116000
    assert np.array_equal(sizes, whole[:46400])
    assert 2 * sizes.sum() == 396326


# ------------------------------------------------- the counters and the spans

def _need_and_filter(d):
    """What the counters should read, worked out from the generator's arrays
    with the reference's rules: the molecules the need rule sends (a set of
    two reads or more whose CIGAR words differ and are not all one ``M``),
    and for each molecule its filter runs (sets of two reads or more: kept
    every read, reads in, reads rejected)."""
    mol = d["mol_of_fam"][d["fam"]]
    n_mol = len(d["mol_kind"])
    need = np.zeros(n_mol, dtype=bool)
    runs = [[] for _ in range(n_mol)]
    # a molecule's read pairs are one run of rows, AB's first: its X set is
    # their forward reads, its Y set their reverse ones
    by_mol = np.split(np.arange(len(mol)), np.flatnonzero(np.diff(mol)) + 1)
    for mate in (1, 2):
        cigar, ncig = d[f"cigar{mate}"], d[f"ncig{mate}"]
        for rows in by_mol:
            m = mol[rows[0]]
            words = {tuple(cigar[i, :ncig[i]]) for i in rows}
            plain = all(ncig[i] == 1 and cigar[i, 0] & 15 == 0 for i in rows)
            need[m] |= len(words) > 1 and not plain
            if len(rows) < 2:
                continue
            reads = []
            for i in rows:
                simple = ra.simplify(ra.decode(cigar[i, :ncig[i]]))
                reads.append((100, simple[::-1] if mate == 2 else simple))
            kept = len(ra.most_common_alignment(reads))
            runs[m].append((kept == len(rows), len(rows), len(rows) - kept))
    return mol, need, runs


@pytest.mark.parametrize("seed,rates", WITH_CIGARS + [(SEEDS[0], "none")])
def test_counters_add_up(seed, rates):
    _got, _header, report = _run(seed, rates, "host")
    _want, n_records, tallies = _expected(seed, rates)
    d = _cell(seed, rates)[2]
    m = report["metrics"]
    n_mol = len(d["mol_kind"])
    assert m["duplex.molecules"] == n_mol
    assert m["duplex.input_reads"] == d["n_reads"]
    assert m["duplex.consensus_reads"] == n_records
    # every input read accounted for, as the reference accounts for it
    assert sum(tallies.values()) == d["n_reads"]
    rejected = {k[len("duplex.reads.rejected."):]: v for k, v in m.items()
                if k.startswith("duplex.reads.rejected.")}
    assert rejected == {k: v for k, v in tallies.items()
                        if k != "ConsensusReads"}
    assert m["duplex.input_reads"] - sum(rejected.values()) \
        == tallies["ConsensusReads"]
    # the reasons by first cause, and the molecule a batch boundary cuts
    carried = m["duplex.molecules.carried"]
    assert carried == 1  # one batch: its last molecule
    assert [k for k in m if k.startswith("duplex.slow_molecules.")
            and k[len("duplex.slow_molecules."):] not in REASONS] == []
    assert sum(m.get("duplex.slow_molecules." + r, 0) for r in REASONS) \
        + carried == m["duplex.slow_molecules"]
    mol, need, runs = _need_and_filter(d)
    # the need rule: every molecule it sends but the carried last one, to
    # the native filter; the per-molecule caller takes the carried one only
    last = n_mol - 1
    sent = need.copy()
    sent[last] = False
    assert m.get("duplex.molecules.filtered", 0) == sent.sum()
    assert m.get("duplex.slow_molecules.filter", 0) == 0
    assert m["duplex.slow_molecules"] == carried
    sizes = np.bincount(mol, minlength=n_mol)
    assert m.get("duplex.reads.filtered", 0) == 2 * sizes[sent].sum()
    # the filter's runs: over every molecule the need rule sends, and the
    # carried one on the per-molecule caller
    slow = np.flatnonzero(sent)
    slow = np.append(slow, last)
    got = [m.get(name, 0) for name in FILTER]
    want = [sum(len(runs[g]) for g in slow)] + [
        sum(run[k] for g in slow for run in runs[g]) for k in range(3)]
    assert got == want
    assert m["duplex.filter.reads_rejected"] \
        == tallies.get("MinorityAlignment", 0)
    if rates == "none":
        assert not need.any()
        assert m["duplex.filter.sets_kept_all"] == m["duplex.filter.sets"]
    else:
        assert 0 < m["duplex.filter.sets_kept_all"] < m["duplex.filter.sets"]


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_run_report_names_the_fallback_loop(seed, rates):
    """The need rule's molecules take the native filter, not the
    per-molecule loop: no ``engine.duplex.fallback``, and one
    ``process.prep.align_filter`` a batch round the one native call, a
    child of ``process.prep`` on its thread, with the sets it filtered."""
    _got, _header, report = _run(seed, rates, "host")
    by_name = report["spans"]["by_name"]
    m = report["metrics"]
    assert "engine.duplex.fallback" not in by_name
    # the carried molecule is the one the per-molecule caller takes
    assert by_name["engine.duplex.slow_molecule"]["count"] \
        == m["duplex.slow_molecules"] == 1
    prep, filt = by_name["process.prep"], by_name["process.prep.align_filter"]
    assert filt["count"] == 1 < m["duplex.molecules.filtered"]
    assert "utime_s" in filt
    assert 0 < filt["wall_s"] <= prep["wall_s"] - prep["self_s"] + 1e-5
    holders = {t for t, rec in report["threads"].items()
               if "process.prep.align_filter" in rec["self_s"]}
    assert holders and holders == {
        t for t, rec in report["threads"].items()
        if "process.prep" in rec["self_s"]}
    # both sets of each molecule it sends (a read pair gives each set one
    # read, so neither is empty)
    (event,) = _trace_events(_out(seed, rates, "host"),
                             "process.prep.align_filter")
    assert event["args"]["sets"] == 2 * m["duplex.molecules.filtered"]


def test_the_clean_input_opens_no_fallback_span():
    """``duplex-c3.panel``'s input: no molecule for the need rule, so no
    fallback span and no filter counter; only the carried molecule is
    called one at a time."""
    _bench, _cell_entry, config, _module, params = harness.load_cell(
        "duplex-c3.panel")
    params["num_families"] = FAMILIES
    (path,) = traffic.write_inputs(traffic.generate(params, SEEDS[0]),
                                   os.path.join(_WORK.name, "panel"))
    _got, _header, report = _cli(path, os.path.join(_WORK.name, "panel.bam"),
                                 config, "host")
    m = report["metrics"]
    assert "engine.duplex.fallback" not in report["spans"]["by_name"]
    assert "process.prep.align_filter" not in report["spans"]["by_name"]
    assert [k for k in m if k.startswith(("duplex.molecules.filtered",
                                          "duplex.reads.filtered",
                                          "duplex.slow_molecules."))] == []
    assert m["duplex.slow_molecules"] == m["duplex.molecules.carried"] == 1
    # the carried molecule's sets, each kept whole
    assert m.get("duplex.filter.reads_rejected", 0) == 0
    assert m.get("duplex.filter.sets_kept_all") == m.get("duplex.filter.sets")


def test_spans_cost_nothing_when_not_armed():
    assert not trace.tracing_enabled()
    assert trace.span("engine.duplex.fallback", rusage=True,
                      molecules=3) is trace.NULL_SPAN


def _reader(name):
    sys.path.insert(0, BENCH)  # a reader imports the benchmark's ``spans``
    try:
        return harness.load_module(
            os.path.join(BENCH, "metrics", name + ".py"),
            "metric_" + name.replace(".", "_"))
    finally:
        sys.path.remove(BENCH)


def _synthetic_report(wall, filtered, sets, kept_all):
    return {"metrics": {"duplex.input_reads": 400000,
                        "duplex.reads.filtered": filtered,
                        "duplex.filter.sets": sets,
                        "duplex.filter.sets_kept_all": kept_all},
            "spans": {"by_name": {"engine.duplex.fallback": {
                "count": 2, "wall_s": wall, "self_s": 0.1}}}}


@pytest.mark.parametrize("name", READERS)
def test_the_readers(name):
    reports = [_synthetic_report(12.0, 192000, 30000, 27000),
               _synthetic_report(14.0, 194000, 30000, 27600)]
    run = {"reports": reports + [_synthetic_report(99.0, 0, 1, 0)],
           "traced_jobs": 2, "reads_per_job": 400000}
    want = {"duplex.fallback_s_per_mread": (12.0 + 14.0) / 0.8,
            "duplex.filter_read_share": 100.0 * (192000 + 194000 + 0)
            / 1200000,
            "duplex.filter_keep_all_share": 100.0 * (27000 + 27600)
            / 60001}[name]
    assert _reader(name).read(run) == pytest.approx(want)
    # a program from before the counters and the span: nothing, no raise
    old = [{"metrics": {"duplex.molecules": 10}},
           {"metrics": {}, "spans": {"by_name": {}}}]
    assert _reader(name).read({**run, "reports": old}) is None
    # a real job's report reads what its counters and span say
    report = _run(SEEDS[0], "cell", "host")[2]
    d = _cell(SEEDS[0], "cell")[2]
    value = _reader(name).read({"reports": [report], "traced_jobs": 1,
                                "reads_per_job": d["n_reads"]})
    if name == "duplex.fallback_s_per_mread":
        # no molecule of the cell's input is in a fallback set: no loop
        assert value is None
        return
    assert value > 0
    if name == "duplex.filter_read_share":
        assert 30 < value < 70
    if name == "duplex.filter_keep_all_share":
        assert 50 < value < 100


def test_the_configuration_file_and_the_benchmark_agree():
    bench, cell, config, _module, params = harness.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("duplex-c3-aln", "panelaln", 1)
    entry = [c for c in bench["configs"] if c["name"] == "duplex-c3-aln"][0]
    assert entry["reduced"] == config["reduced"] == ["input_scale"]
    assert "46,400" in config["input_scale"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmark/configs/duplex-c3-aln.json"
    twin = harness.load_cell("duplex-c3.panel")[2]
    assert config["command"] == twin["command"] \
        == ["duplex", "-i", "{in0}", "-o", "{out}", "--min-reads", "1", "1",
            "0", "--threads", "4"]
    for key in ("consensus", "min_reads", "threads"):
        assert config["assumed"][key] == twin["assumed"][key], key
    assert config["kernel_modules"] == "^jit_fn\\("
    assert (config["precision"], config["warm_jobs"]) == ("float64", 2)
    for key in ("deployment", "guarantees", "assumed"):
        assert key in config, key
    assert params["kind"] == "aligned_duplex_bam"
    assert [params[k] for k in RATE_KEYS] == [0.03, 0.005, 0.01]
    assert params["indel_margin"] == 10
    for key in RATE_KEYS + ("softclip_length", "indel_length",
                            "indel_margin", "unchecked", "one_event"):
        assert key in params["assumed"], key
    new = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in new] == list(READERS)
    for metric in new:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "reads_per_s"
    # appended after the eight cells that came before it
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 8


def test_the_reference_imports_nothing_of_the_program():
    for module in (rda, layout):
        with open(module.__file__) as f:
            assert "fgumi_tpu" not in f.read()


# ------------------------------------------- the X/Y filter's rules, by hand

def _parse(text):
    """A CIGAR string as [(op, length)]."""
    return [(op, int(n)) for n, op in re.findall(r"(\d+)([MIDNSHP=X])", text)]


def _words(text):
    ops = _parse(text)
    row = np.zeros(layout._aligned.MAX_OPS, dtype=np.uint32)
    row[:len(ops)] = [(n << 4) | ra.OPS.index(op) for op, n in ops]
    return row, len(ops)


def _hand(molecules, seed=3):
    """Arrays of an ``aligned_duplex_bam`` input from hand-made molecules:
    each ``(AB pairs, BA pairs)``, a pair ``(forward CIGAR, reverse
    CIGAR)``; a molecule's reverse reads start 150 bases after its forward
    ones, so no pair overlaps and no read passes its mate."""
    rng = np.random.default_rng(seed)
    sizes, a_fam, b_fam, mol_of_fam, strand, pairs = [], [], [], [], [], []
    for m, (ab, ba) in enumerate(molecules):
        a_fam.append(len(sizes) if ab else -1)
        for s, strand_pairs in enumerate((ab, ba)):
            if strand_pairs:
                if s == 1:
                    b_fam.append(len(sizes))
                sizes.append(len(strand_pairs))
                mol_of_fam.append(m)
                strand.append(s)
                pairs += strand_pairs
        if not ba:
            b_fam.append(-1)
    sizes = np.array(sizes)
    n, n_mol = len(pairs), len(molecules)
    fam = np.repeat(np.arange(len(sizes)), sizes)
    d = {"kind": "aligned_duplex_bam", "sizes": sizes, "fam": fam,
         "ordinal": np.arange(n) - np.repeat(np.cumsum(sizes) - sizes, sizes),
         "a_fam": np.array(a_fam), "b_fam": np.array(b_fam),
         "mol_of_fam": np.array(mol_of_fam), "strand_of_fam": np.array(strand),
         "mol_kind": np.zeros(n_mol, dtype=np.int64),
         "umi": rng.integers(0, 4, (n_mol, 8), dtype=np.uint8),
         "len1": np.full(n, 100), "len2": np.full(n, 100), "n_reads": 2 * n}
    start = 1000 + 1000 * d["mol_of_fam"][fam]
    for mate in (1, 2):
        words = [_words(pair[mate - 1]) for pair in pairs]
        d[f"cigar{mate}"] = np.array([w for w, _k in words])
        d[f"ncig{mate}"] = np.array([k for _w, k in words])
        d[f"codes{mate}"] = rng.integers(0, 4, (n, 100), dtype=np.uint8)
        d[f"quals{mate}"] = np.full((n, 100), 35, dtype=np.uint8)
        d[f"pos{mate}"] = start + 150 * (mate - 1)
    d["tlen"] = d["pos2"] + layout._aligned.ref_length(d["cigar2"]) \
        - d["pos1"]
    return d


M, INS, DEL = "100M", "40M2I58M", "50M1D50M"
#: X and Y of a molecule that loses all of BA's reads to them
BA_LOSES = [([(M, M)] * 3, [(INS, INS)] * 2)]
HAND = {
    # name: (molecules, MinorityAlignment, InsufficientReads, records[,
    # min-reads]); the configuration's min-reads where none is given
    "AB-R1 is filtered with BA-R2, not alone": (
        # AB's two forward reads agree with each other, but X pools them with
        # BA's three plain ones: they are the minority and go, AB-R1 is left
        # empty and the molecule is rejected (alone they would stay)
        [([(INS, M)] * 2, [(M, M)] * 3)], 2, 8, 0),
    "a tie goes to the smaller CIGAR, I before D": (
        # X: {AB, BA} with 50M1D50M and {AB, BA} with 50M1I49M, two each
        [([(DEL, M), ("50M1I49M", M)], [(DEL, M), ("50M1I49M", M)])],
        2, 0, 2),
    "Y is read from the reverse reads' ends": (
        # read from their ends, AB-R2 is 30M2D70M and BA-R1 70M2D30M: a tie
        # the smaller takes, so BA-R1 is left empty and the molecule goes
        [([(M, "70M2D30M")], [(M, "30M2D70M")])], 1, 3, 0),
    "a clip simplifies away, an indel does not": (
        [([(M, "10S90M"), (M, "60M2I38M")], [("90M10S", M), (M, M)])],
        1, 0, 2),
    "a lone strand is filtered too, then passes through": (
        [([(M, M), (INS, M), (INS, M)], [])], 1, 0, 2),
    "Y empties BA-R1: three of four left, rejected net of the minority": (
        # X keeps every read; Y's three plain AB reads outnumber BA's two
        [([(M, M)] * 3, [(M, INS)] * 2)], 2, 8, 0),
    "BA loses every read: AB passes through where YX may be 0": (
        BA_LOSES, 4, 0, 2),
    "BA loses every read: rejected where YX must be 1": (
        BA_LOSES, 4, 6, 0, ["1", "1", "1"]),
    "a tie: AB's first read founds the earlier group, the smaller wins": (
        # X in its order: AB's D, AB's I, BA's I, BA's D; two groups of two,
        # the first founded by AB's first read, and the smaller CIGAR (I)
        # takes the tie from it: AB-R1 and BA-R2 keep one read each
        [([(DEL, M), ("50M1I49M", M)], [("50M1I49M", M), (DEL, M)])],
        2, 0, 2),
}


@pytest.mark.parametrize("name", HAND)
def test_the_xy_filter_on_hand_made_molecules(name):
    molecules, minority, insufficient, records, *min_reads = HAND[name]
    d = _hand(molecules)
    config = _cell(SEEDS[0], "cell")[0]
    opts, extra = _opts(config), []
    if min_reads:
        opts["min_reads"] = [int(v) for v in min_reads[0]]
        extra = ["--min-reads", *min_reads[0]]
    flat, n_records, tallies = rda.duplex(d, opts)
    assert n_records == records
    assert tallies == {k: v for k, v in (
        ("MinorityAlignment", minority), ("InsufficientReads", insufficient),
        ("ConsensusReads", d["n_reads"] - minority - insufficient)) if v}
    # the program, on the same molecules written as the cell's layout, plus
    # a plain molecule behind them for the batch's open tail
    d = _hand(molecules + [([(M, M)], [(M, M)])])
    want, n_want, _t = rda.duplex(d, opts)
    (path,) = layout.write(d, os.path.join(
        _WORK.name, f"hand{list(HAND).index(name)}"), 1)
    for engine in ENGINES:
        got, _header, report = _cli(path, path + engine + ".out.bam", config,
                                    engine, extra)
        assert got == np.ascontiguousarray(want).tobytes()
        m = report["metrics"]
        assert m.get("duplex.reads.rejected.MinorityAlignment", 0) == minority
        assert m.get("duplex.reads.rejected.InsufficientReads", 0) \
            == insufficient
        assert m["duplex.molecules.filtered"] == len(molecules)
        # on the native filter: the carried plain molecule alone is called
        # one at a time
        assert m["duplex.slow_molecules"] == 1
        assert m["duplex.consensus_reads"] == n_want


def _mixed_strand_bam(path, molecules):
    """A BAM of one-strand molecules ``(MI, R1 flags)``, two read pairs
    each, every read ``70M2D30M``: a molecule whose R1 flags differ in
    their reverse bit has X and Y sets on both strands."""
    cigar = _parse("70M2D30M")
    header = BamHeader(
        text="@HD\tVN:1.6\tSO:unsorted\tGO:query\n@SQ\tSN:c1\tLN:100000\n"
             "@RG\tID:A\tSM:s\n", ref_names=["c1"], ref_lengths=[100000])
    rng = np.random.default_rng(5)
    with BamWriter(path, header) as w:
        for mi, r1_flags in molecules:
            for r, flag1 in enumerate(r1_flags):
                for flag in (flag1, 0x81 | (flag1 & 0x10) ^ 0x10):
                    w.write_record_bytes(_build_mapped_record(
                        b"m%sr%d" % (mi, r), flag, 0, 1000, 60, cigar,
                        bytes(b"ACGT"[c] for c in rng.integers(0, 4, 100)),
                        np.full(100, 35, np.uint8), 0, 1300, 0,
                        [(b"MI", "Z", mi), (b"RG", "Z", b"A"),
                         (b"RX", "Z", b"ACGT-TTGA")]))
    return cigar


def test_a_mixed_strand_set_that_is_no_palindrome():
    """A one-strand molecule whose AB-R1 reads lie on both strands with one
    CIGAR that reads otherwise from its end: the bytes are uniform, but the
    need rule sends it (the palindrome check), and the filter keeps the read
    whose CIGAR, in read orientation, is the smaller."""
    path = os.path.join(_WORK.name, "mixed.bam")
    cigar = _mixed_strand_bam(path, ((b"0/A", (0x41, 0x41 | 0x10)),
                                     (b"1/A", (0x41, 0x41))))
    reads = [(100, ra.simplify(cigar)), (100, ra.simplify(cigar)[::-1])]
    assert ra.most_common_alignment(reads) == [1]
    config = _cell(SEEDS[0], "cell")[0]
    got, _header, report = _cli(path, path + ".out.bam", config, "host")
    classic, _h, report_c = _cli(path, path + ".classic.bam", config, "host",
                                 ["--classic"])
    assert got == classic
    m = report["metrics"]
    assert m["duplex.molecules.filtered"] == 1
    # X and Y each keep the one read whose CIGAR is the smaller read from
    # its 5' end; the molecule passes its one strand through
    assert m["duplex.filter.reads_rejected"] == 2 \
        == m["duplex.reads.rejected.MinorityAlignment"] \
        == report_c["metrics"]["duplex.reads.rejected.MinorityAlignment"]
    assert m["duplex.consensus_reads"] == 4


@pytest.mark.parametrize("engine", ENGINES)
def test_mixed_strand_sets_on_both_strands_and_engines(engine):
    """Mixed-strand sets that are no palindrome, on an AB-only and a
    BA-only molecule, filtered on the arrays by both engines: the records
    and the account of the per-molecule caller (``--classic``), and no
    molecule of theirs on that caller."""
    path = os.path.join(_WORK.name, "mixed2.bam")
    _mixed_strand_bam(path, ((b"0/A", (0x41, 0x41 | 0x10)),
                             (b"1/B", (0x41 | 0x10, 0x41)),
                             (b"2/A", (0x41, 0x41))))
    config = _cell(SEEDS[0], "cell")[0]
    got, _header, report = _cli(path, f"{path}.{engine}.bam", config, engine)
    classic, _h, report_c = _cli(path, path + ".classic.bam", config, "host",
                                 ["--classic"])
    assert got == classic
    m, mc = report["metrics"], report_c["metrics"]
    assert m["duplex.molecules.filtered"] == 2
    assert m["duplex.slow_molecules"] == 1  # the carried 2/A
    for key in [k for k in mc if k.startswith(("duplex.reads.rejected.",
                                               "duplex.filter."))] \
            + ["duplex.input_reads", "duplex.consensus_reads"]:
        assert m.get(key) == mc[key], key
    assert mc["duplex.reads.rejected.MinorityAlignment"] == 4
    assert mc["duplex.consensus_reads"] == 6


@pytest.mark.parametrize("cell,calls", [("duplex-c3.panel", 0), (CELL, 1)])
def test_the_native_filter_is_called_once_a_batch_at_most(cell, calls,
                                                          monkeypatch):
    """``panel``'s input (one ``M`` run a read) makes no call of
    ``nb.alignment_filter``; the aligned cell's input, one batch, makes one,
    and its records are the reference's."""
    _bench, _entry, config, _module, params = harness.load_cell(cell)
    params["num_families"] = FAMILIES
    (path,) = traffic.write_inputs(
        traffic.generate(params, SEEDS[0]),
        os.path.join(_WORK.name, f"calls{calls}"))
    made = []
    real = nb.alignment_filter

    def counted(*args):
        made.append(len(args[-1]) - 1)
        return real(*args)

    monkeypatch.setattr(nb, "alignment_filter", counted)
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "host")
    out = os.path.join(_WORK.name, f"calls{calls}.bam")
    assert cli_main([a.format(in0=path, out=out)
                     for a in config["command"]]) == 0
    assert len(made) == calls
    if cell == CELL:
        payload = bamio.read_bgzf(out)
        _text, start = bamio.split_bam(payload)
        assert payload[start:] == _expected(SEEDS[0], "cell")[0]
