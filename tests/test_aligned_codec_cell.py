"""The deployment ``codec`` on a BAM as an aligner writes it (benchmark
configuration ``codec-c4-aln``) on the CPU: the ``codec`` CLI against the
benchmark's plain reference with codec's per-strand most-common-alignment
filter and its indel phase check, byte for byte, on inputs of the cell's own
layout (soft clips and indels inside CODEC molecules) at the cell's rates, at
four times them and at none, on the batch engine and the per-molecule one;
the account of the reads and the counters in the run report; the reference's
CIGAR rules against the program's; and the rules on hand-made molecules.

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
    import reference_codec
    import reference_codec_aligned as rca
    import run as harness
    import traffic
finally:
    sys.path.remove(BENCH)

from fgumi_tpu.core import cigar as cigar_utils  # noqa: E402
from fgumi_tpu.native import batch as nb  # noqa: E402

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library required")

CELL = "codec-c4-aln.linkedaln"
SEEDS = [11, 2147483659, 3000000019]
FAMILIES = 500  # molecules: about 3,100 reads
RATE_KEYS = ("softclip_read_rate", "indel_read_rate", "indel_molecule_rate")
#: the cell's rates times this
RATES = {"cell": 1, "four": 4, "none": 0}
#: engine -> (environment, extra arguments)
ENGINES = {
    "fast": ({"FGUMI_TPU_ROUTE": "host"}, []),
    "classic": ({}, ["--classic"]),
}
_WORK = tempfile.TemporaryDirectory(prefix="aligned_codec_cell_")
layout = traffic.kind_module("aligned_codec_bam")
_aligned = layout._aligned
READERS = ("codec.cigar_read_share", "codec.filter_keep_all_share",
           "codec.prep_wall_s_per_mread")


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
    return config["assumed"]["consensus"]


@functools.lru_cache(maxsize=None)
def _expected(seed, rates, dtype=np.float64, checks=True, prove_plain=True):
    """(record bytes, records, the reference's tallies)."""
    config, _module, data, _path = _cell(seed, rates)
    flat, n_records, tallies = rca.codec(data, _opts(config), dtype, checks,
                                         prove_plain)
    return np.ascontiguousarray(flat).tobytes(), n_records, tallies


def _cli(path, out, config, engine, extra=()):
    """(record bytes, header lines, run report) of the configuration's
    command on ``path``."""
    env, args = ENGINES[engine]
    report = out + ".report.json"
    argv = [a.format(in0=path, out=out) for a in config["command"]]
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", "--run-report", report] + argv
        + args + list(extra), check=True, cwd=_WORK.name,
        env={**os.environ, **env, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": ""})
    payload = bamio.read_bgzf(out)
    text, start = bamio.split_bam(payload)
    lines = [ln for ln in text.splitlines() if not ln.startswith("@PG")]
    with open(report) as f:
        return payload[start:], lines, json.load(f)


@functools.lru_cache(maxsize=None)
def _run(seed, rates, engine):
    config, _module, _data, path = _cell(seed, rates)
    return _cli(path, os.path.join(_WORK.name, f"{engine}{rates}{seed}.bam"),
                config, engine)


RUNS = [(seed, rates, engine) for engine in ENGINES for rates in RATES
        for seed in SEEDS]
WITH_CIGARS = [(seed, rates) for rates in ("cell", "four") for seed in SEEDS]


@pytest.mark.parametrize("seed,rates,engine", RUNS)
def test_cli_writes_the_reference_records(seed, rates, engine):
    got, header, _report = _run(seed, rates, engine)
    want, n_records, _tallies = _expected(seed, rates)
    assert len(bamio.record_offsets(got, 0)) - 1 == n_records \
        > 0.9 * FAMILIES
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
def test_without_cigars_it_is_reference_codec(seed):
    config, _module, data, path = _cell(seed, "none")
    assert (data["ncig1"] == 1).all() and (data["ncig2"] == 1).all()
    flat, n_records, reads, _counted = reference_codec.codec(
        data, _opts(config))
    want, n_aligned, tallies = _expected(seed, "none")
    assert np.ascontiguousarray(flat).tobytes() == want
    assert n_records == n_aligned
    assert tallies["reads"] == {"ConsensusReads": reads}
    assert tallies["molecules"] == {}
    assert tallies["filter"]["sets_kept_all"] == tallies["filter"]["sets"]
    # and the input is codec_bam's, byte for byte
    params = traffic.load("linked", BENCH)
    params["num_families"] = FAMILIES
    (plain,) = traffic.write_inputs(traffic.generate(params, seed),
                                    os.path.join(_WORK.name, f"linked{seed}"))
    assert bamio.read_bgzf(plain) == bamio.read_bgzf(path)


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_the_float32_control_differs(seed, rates):
    want, n_records, _tallies = _expected(seed, rates)
    low, n_low, _t = _expected(seed, rates, np.float32)
    assert n_low == n_records and low != want


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_the_reference_without_its_checks_differs(seed, rates):
    """Leaving the filter and the phase check out cannot pass: a strand's
    minority reads change its consensus, and a molecule whose strands are out
    of phase comes out."""
    want, n_records, tallies = _expected(seed, rates)
    loose, n_loose, loose_tallies = _expected(seed, rates, checks=False)
    assert tallies["reads"]["MinorityAlignment"] > 0
    if rates == "four":
        assert tallies["molecules"]["IndelErrorBetweenStrands"] > 0
    assert "MinorityAlignment" not in loose_tallies["reads"]
    assert n_loose >= n_records and loose != want


@pytest.mark.parametrize("seed,rates", WITH_CIGARS)
def test_walking_the_plain_molecules_changes_nothing(seed, rates):
    want, n_records, tallies = _expected(seed, rates)
    got, n_got, walked = _expected(seed, rates, prove_plain=False)
    assert (got, n_got) == (want, n_records)
    assert walked["reads"] == tallies["reads"]
    assert walked["filter"] == tallies["filter"]


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
    shapes = {1: set(), 2: set()}
    carried = {}  # molecule -> the sides whose reads carry its indel
    wrong = aligned_bases = 0
    for i, m in enumerate(d["fam"]):
        r1, r2 = recs[2 * i], recs[2 * i + 1]
        rev = bool(d["r1_reverse"][m])
        assert (r1["flag"], r2["flag"]) == ((97, 145), (81, 161))[rev]
        assert r1["name"] == r2["name"] \
            == f"codec{m}:{d['ordinal'][i]}"
        assert r1["tags"]["MI"] == r2["tags"]["MI"] == str(m)
        fwd, revr = (r2, r1) if rev else (r1, r2)
        kind, n_mol, at = d["mol_indel"][m], d["mol_n"][m], d["mol_at"][m]
        for mate, rec, other in ((1, fwd, revr), (2, revr, fwd)):
            cigar = _cigar(rec)
            assert other["tags"]["MC"] == "".join(f"{n}{op}"
                                                  for op, n in cigar)
            assert other["next_pos"] == rec["pos"] == d[f"pos{mate}"][i]
            assert len(rec["seq"]) == 150 == sum(n for op, n in cigar
                                                 if op in "MIS")
            shapes[mate].add("".join(op for op, _n in cigar))
            if d[f"event{mate}"][i] == _aligned.MOL_CARRIED:
                carried.setdefault(m, set()).add(mate)
            # every M base is the molecule's base at its reference position,
            # but for the sequencer's substitutions
            ref, query = rec["pos"] - d["start"][m], 0
            for op, n in cigar:
                if op == "M":
                    for k in range(n):
                        j = ref + k
                        if kind == _aligned.OP_I and j >= at:
                            j += n_mol
                        elif kind == _aligned.OP_D:
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
                tlen = ref + d["start"][m] - fwd["pos"]
                assert fwd["tlen"] == -revr["tlen"] == tlen
    assert 0.005 < wrong / aligned_bases < 0.015
    # the forward read's clip on its right, the reverse read's on its left
    for mate, clipped in ((1, "MS"), (2, "SM")):
        assert {"M", clipped} <= shapes[mate] \
            <= {"M", "MS", "SM", "MIM", "MDM"}
    # one draw a molecule: where its indel is carried, forward and reverse
    # reads carry it alike
    assert any(sides == {1, 2} for sides in carried.values())


def test_seeds_deal_linkeds_multiset_of_sizes():
    a, b = _cell(SEEDS[0], "cell")[2], _cell(SEEDS[1], "cell")[2]
    assert np.array_equal(np.sort(a["sizes"]), np.sort(b["sizes"]))
    assert not np.array_equal(a["sizes"], b["sizes"])
    cell = traffic.load("linkedaln", BENCH)
    linked = traffic.load("linked", BENCH)
    for key in ("num_families", "family_size", "family_size_distribution",
                "read_length", "read_length_jitter", "insert_min",
                "insert_max", "qual_slope", "error_rate", "base_quality",
                "qual_jitter", "umi_length"):
        assert cell[key] == linked[key], key
    panel = traffic.load("panelaln", BENCH)
    for key in RATE_KEYS + ("indel_margin",):
        assert cell[key] == panel[key], key
    sizes = traffic.family_sizes(np.random.default_rng(1), cell)
    assert 2 * sizes.sum() == 397372


# ----------------------------------------------- the counters and the account

def _cigar_molecules(d):
    """Whether each molecule has a read whose CIGAR is not one ``M`` run."""
    plain = np.ones(len(d["sizes"]), dtype=bool)
    for mate in (1, 2):
        one = (d[f"ncig{mate}"] == 1) & ((d[f"cigar{mate}"][:, 0] & 15) == 0)
        plain &= np.bincount(d["fam"][~one], minlength=len(plain)) == 0
    return ~plain


@pytest.mark.parametrize("seed,rates,engine", [
    (seed, rates, engine) for engine in ENGINES
    for seed, rates in WITH_CIGARS + [(SEEDS[0], "none")]])
def test_the_account_is_the_references(seed, rates, engine):
    _got, _header, report = _run(seed, rates, engine)
    _want, n_records, tallies = _expected(seed, rates)
    d = _cell(seed, rates)[2]
    m = report["metrics"]
    # every input read accounted for, as the reference accounts for it
    assert m["codec.input_reads"] == tallies["input_reads"] == d["n_reads"]
    assert m["codec.consensus_reads"] == n_records
    rejected = {k[len("codec.reads.rejected."):]: v for k, v in m.items()
                if k.startswith("codec.reads.rejected.")}
    assert rejected == {k: v for k, v in tallies["reads"].items()
                        if k != "ConsensusReads"}
    assert sum(tallies["reads"].values()) == d["n_reads"]
    filt = [m.get("codec.filter." + name, 0) for name in rca.FILTER]
    if engine == "classic":  # the filter runs on every molecule
        assert filt == [tallies["filter"][name] for name in rca.FILTER]
        return
    # the batch engine: molecules by reason, the per-molecule prepare's
    # molecules by cause, the CIGAR molecules the native pass prepared, and
    # the filter where a molecule with a CIGAR or the carried one runs it
    assert {k[len("codec.rejected."):]: v for k, v in m.items()
            if k.startswith("codec.rejected.")} == tallies["molecules"]
    cigar = _cigar_molecules(d)
    sizes = d["sizes"]
    assert m.get("codec.molecules.cigar", 0) == cigar.sum()
    assert m.get("codec.reads.cigar", 0) == 2 * sizes[cigar].sum()
    carried = m["codec.slow_molecules.carried"]
    assert carried == 1  # one batch: its last molecule
    assert m["codec.slow_molecules"] == carried
    assert "codec.slow_molecules.cigar" not in m
    assert m.get("codec.molecules.cigar_vec", 0) == cigar[:-1].sum()
    filtered = cigar.copy()
    filtered[-1] = True  # the carried molecule
    assert filt == list(tallies["filter_by_molecule"][filtered].sum(axis=0))
    if rates == "none":
        assert "codec.molecules.cigar" not in m
        assert filt[0] == filt[1]
    else:
        assert 0 < filt[1] < filt[0]


@pytest.mark.parametrize("seed", SEEDS)
def test_downsampled_cigar_molecules_take_the_classic_prepare(seed):
    """With ``--max-reads 1`` every molecule of two pairs or more draws its
    pair from the shared RNG stream, in input order: such a molecule with a
    CIGAR takes the classic ``prepare()``, one at a time, the others stay
    on the arrays, and the records are the classic engine's."""
    config, _module, d, path = _cell(seed, "cell")
    runs = {engine: _cli(path, os.path.join(_WORK.name,
                                            f"max1{engine}{seed}.bam"),
                         config, engine, ["--max-reads", "1"])
            for engine in ENGINES}
    assert runs["fast"][0] == runs["classic"][0]
    m = runs["fast"][2]["metrics"]
    cigar = _cigar_molecules(d)[:-1]  # the last molecule is carried
    many = d["sizes"][:-1] > 1
    assert m["codec.slow_molecules.cigar"] == (cigar & many).sum() > 0
    assert m["codec.molecules.cigar_vec"] == (cigar & ~many).sum() > 0


def test_the_clean_input_counts_no_cigar():
    """``codec-c4.linked``'s input: no molecule with a CIGAR, so only the
    molecule a batch boundary cuts takes the per-molecule prepare."""
    _bench, _cell_entry, config, _module, params = harness.load_cell(
        "codec-c4.linked")
    params["num_families"] = FAMILIES
    (path,) = traffic.write_inputs(traffic.generate(params, SEEDS[0]),
                                   os.path.join(_WORK.name, "linked"))
    _got, _header, report = _cli(path, os.path.join(_WORK.name, "linked.bam"),
                                 config, "fast")
    m = report["metrics"]
    assert [k for k in m if k.startswith(("codec.molecules.cigar",
                                          "codec.reads.cigar",
                                          "codec.slow_molecules.cigar"))] \
        == []
    assert m["codec.slow_molecules"] == m["codec.slow_molecules.carried"] == 1
    assert m["codec.input_reads"] \
        == 2 * traffic.generate(params, SEEDS[0])["sizes"].sum()
    assert m["codec.filter.sets_kept_all"] == m["codec.filter.sets"]


def _reader(name):
    sys.path.insert(0, BENCH)  # a reader imports the benchmark's ``spans``
    try:
        return harness.load_module(
            os.path.join(BENCH, "metrics", name + ".py"),
            "metric_" + name.replace(".", "_"))
    finally:
        sys.path.remove(BENCH)


def _synthetic_report(wall, cigar_reads, sets, kept_all):
    return {"metrics": {"codec.molecules": 64000,
                        "codec.input_reads": 400000,
                        "codec.reads.cigar": cigar_reads,
                        "codec.filter.sets": sets,
                        "codec.filter.sets_kept_all": kept_all},
            "spans": {"by_name": {"process.prep": {
                "count": 7, "wall_s": wall, "self_s": 0.4}}}}


@pytest.mark.parametrize("name", READERS)
def test_the_readers(name):
    reports = [_synthetic_report(4.0, 100000, 20000, 19000),
               _synthetic_report(5.0, 110000, 21000, 19500)]
    run = {"reports": reports + [_synthetic_report(99.0, 0, 1, 0)],
           "traced_jobs": 2, "reads_per_job": 400000}
    want = {"codec.cigar_read_share": 100.0 * 210000 / 1200000,
            "codec.filter_keep_all_share": 100.0 * 38500 / 41001,
            "codec.prep_wall_s_per_mread": 9.0 / 0.8}[name]
    assert _reader(name).read(run) == pytest.approx(want)
    # a program from before the counters: nothing, no raise; the prepare's
    # span reads on it where its jobs are codec's
    old = [{"metrics": {"codec.molecules": 10},
            "spans": {"by_name": {"process.prep": {"wall_s": 2.0}}}},
           {"metrics": {}, "spans": {"by_name": {}}}]
    value = _reader(name).read({**run, "reports": old})
    if name == "codec.prep_wall_s_per_mread":
        assert value == pytest.approx(2.0 / 0.8)
    else:
        assert value is None
    # another command's jobs: nothing
    other = [{"metrics": {"duplex.molecules": 10},
              "spans": {"by_name": {"process.prep": {"wall_s": 2.0}}}}]
    assert _reader(name).read({**run, "reports": other}) is None
    # a real job's report reads what its counters and span say
    report = _run(SEEDS[0], "cell", "fast")[2]
    d = _cell(SEEDS[0], "cell")[2]
    value = _reader(name).read({"reports": [report], "traced_jobs": 1,
                                "reads_per_job": d["n_reads"]})
    assert value > 0
    if name == "codec.cigar_read_share":
        assert 20 < value < 35
    if name == "codec.filter_keep_all_share":
        assert 80 < value < 100


def test_the_configuration_file_and_the_benchmark_agree():
    bench, cell, config, _module, params = harness.load_cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("codec-c4-aln", "linkedaln", 1)
    entry = [c for c in bench["configs"] if c["name"] == "codec-c4-aln"][0]
    assert entry["reduced"] == config["reduced"] == ["input_scale"]
    assert "64,000" in config["input_scale"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmark/configs/codec-c4-aln.json"
    twin = harness.load_cell("codec-c4.linked")[2]
    assert config["command"] == twin["command"] \
        == ["codec", "-i", "{in0}", "-o", "{out}", "--min-reads", "1",
            "--threads", "4"]
    for key in ("consensus", "threads"):
        assert config["assumed"][key] == twin["assumed"][key], key
    assert config["kernel_modules"] == twin["kernel_modules"]
    assert (config["precision"], config["warm_jobs"]) == ("float64", 2)
    for key in ("deployment", "guarantees", "assumed"):
        assert key in config, key
    assert params["kind"] == "aligned_codec_bam"
    assert [params[k] for k in RATE_KEYS] == [0.03, 0.005, 0.01]
    for key in RATE_KEYS + ("softclip_length", "indel_length",
                            "indel_margin", "unchecked", "one_event"):
        assert key in params["assumed"], key
    new = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in new] == list(READERS)
    for metric in new:
        assert metric["workloads"] == [CELL]
        assert (metric["moves"], metric["layer"]) \
            == ("reads_per_s", "batch engines")
    # appended after the nine cells that came before it
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 9
    assert [c["name"] for c in bench["configs"]].index("codec-c4-aln") == 8


def test_the_reference_imports_nothing_of_the_program():
    for module in (rca, layout):
        with open(module.__file__) as f:
            assert "fgumi_tpu" not in f.read()


# ------------------------------------------- the CIGAR rules, the program's

def _random_cigar(rng):
    """A CIGAR as an aligner writes it: clips outside, M runs between
    indels."""
    ops = []
    if rng.random() < 0.2:
        ops.append(("H", int(rng.integers(1, 6))))
    if rng.random() < 0.4:
        ops.append(("S", int(rng.integers(1, 21))))
    ops.append(("M", int(rng.integers(1, 60))))
    for _ in range(rng.integers(0, 4)):
        ops.append((str(rng.choice(["I", "D"])), int(rng.integers(1, 4))))
        ops.append(("M", int(rng.integers(1, 60))))
    if rng.random() < 0.4:
        ops.append(("S", int(rng.integers(1, 21))))
    if rng.random() < 0.2:
        ops.append(("H", int(rng.integers(1, 6))))
    return ops


@pytest.mark.parametrize("seed", SEEDS)
def test_the_references_cigar_rules_are_the_programs(seed):
    """The reference's clip and read-position walk, written again from the
    published descriptions, answer as ``core/cigar.py`` does on 2,000 random
    CIGARs, every clip amount and reference position of each."""
    rng = np.random.default_rng(seed)
    for _ in range(2000):
        cigar = _random_cigar(rng)
        query = sum(n for op, n in cigar if op in "MIS")
        for clip in range(0, query + 1, max(1, query // 12)):
            for from_start in (False, True):
                assert rca.clip_cigar(cigar, clip, from_start) \
                    == cigar_utils.clip_cigar(cigar, clip, from_start)
        start = int(rng.integers(1, 1000))
        span = sum(n for op, n in cigar if op in "MD")
        for pos in range(start - 2, start + span + 2):
            for last in (False, True):
                assert rca.read_pos_at(cigar, start, pos, last) \
                    == cigar_utils.read_pos_at_ref_pos(cigar, start, pos,
                                                       last)


# -------------------------------------- the rules on hand-made molecules

def _parse(text):
    """A CIGAR string as [(op, length)]."""
    return [(op, int(n)) for n, op in re.findall(r"(\d+)([MIDNSHP=X])", text)]


def _words(text):
    ops = _parse(text)
    row = np.zeros(_aligned.MAX_OPS, dtype=np.uint32)
    row[:len(ops)] = [(n << 4) | ra.OPS.index(op) for op, n in ops]
    return row, len(ops)


def _ref_len(text):
    return sum(n for op, n in _parse(text) if op in "MD")


def _hand(molecules, seed=3):
    """Arrays of an ``aligned_codec_bam`` input from hand-made molecules:
    each a list of pairs ``(forward CIGAR, reverse CIGAR[, last])``, R1 the
    forward read; the forward reads start at the molecule's first base, the
    reverse ones end at its base ``last`` (199: a 200-base molecule)."""
    rng = np.random.default_rng(seed)
    sizes = np.array([len(pairs) for pairs in molecules])
    pairs = [pair for mol in molecules for pair in mol]
    n, n_mol = len(pairs), len(molecules)
    fam = np.repeat(np.arange(n_mol), sizes)
    start = 1000 + 1000 * fam
    d = {"kind": "aligned_codec_bam", "sizes": sizes, "fam": fam,
         "ordinal": np.arange(n) - np.repeat(np.cumsum(sizes) - sizes, sizes),
         "r1_reverse": np.zeros(n_mol, dtype=bool),
         "umi": rng.integers(0, 4, (n_mol, 8), dtype=np.uint8),
         "len1": np.full(n, 150), "len2": np.full(n, 150), "n_reads": 2 * n}
    for mate in (1, 2):
        words = [_words(pair[mate - 1]) for pair in pairs]
        d[f"cigar{mate}"] = np.array([w for w, _k in words])
        d[f"ncig{mate}"] = np.array([k for _w, k in words])
        d[f"codes{mate}"] = rng.integers(0, 4, (n, 150), dtype=np.uint8)
        d[f"quals{mate}"] = np.full((n, 150), 35, dtype=np.uint8)
    d["pos1"] = start.copy()
    d["pos2"] = start + np.array([(pair[2] if len(pair) > 2 else 199) + 1
                                  - _ref_len(pair[1]) for pair in pairs])
    d["tlen"] = d["pos2"] + _aligned.ref_length(d["cigar2"]) - d["pos1"]
    return d


M = "150M"
HAND = {
    # name: (molecule, MinorityAlignment, {rejected reason: reads}, the
    # record's length or None[, --min-duplex-length])
    "a shared indel under every read": (
        # a 2-base deletion at the molecule's bases 100-101, in every read's
        # CIGAR: in phase, and the fragment is two bases shorter
        [("100M2D50M", "52M2D98M")] * 3, 0, {}, 198),
    "one R1's own deletion in the overlap: out of phase": (
        # the R1s tie, one group each, and the smaller CIGAR (the deletion)
        # wins: the longest R1 by reference length then reads the overlap
        # two bases off from the R2s
        [(M, M), ("100M2D50M", M)], 1,
        {"IndelErrorBetweenStrands": 3}, None),
    "a minority CIGAR among three R2s": (
        [(M, M), (M, "60M2I88M"), (M, M)], 1, {}, 200),
    "a 3' soft clip on the longest read moves the geometry": (
        # the R1s simplify alike and stay; the first is longest by query
        # length, the second by reference length, and the overlap ends
        # where the second ends: 100 bases, where the first's would be 80
        [("130M20S", M), (M, M)], 0, {}, 200, 90),
    "... and the overlap the first read would end is too short": (
        [("130M20S", M), ("130M20S", M)], 0, {"InsufficientOverlap": 4},
        None, 90),
    "a single pair with a clip": (
        # the reverse read starts 10 bases before the forward one and ends
        # at its soft clip: 10 bases of each pass the other's far end, the
        # forward read's clip takes its soft clip, the reverse read's moves
        # its first aligned base
        [("140M10S", M, 139)], 0, {}, 140),
}


@pytest.mark.parametrize("name", HAND)
def test_the_rules_on_hand_made_molecules(name):
    molecule, minority, rejected, length, *overlap = HAND[name]
    config = _cell(SEEDS[0], "cell")[0]
    opts, extra = dict(_opts(config)), []
    if overlap:
        opts["min_duplex_length"] = overlap[0]
        extra = ["--min-duplex-length", str(overlap[0])]
    d = _hand([molecule])
    flat, n_records, tallies = rca.codec(d, opts)
    assert n_records == (length is not None)
    reads = {k: v for k, v in (("MinorityAlignment", minority),
                                *rejected.items()) if v}
    assert {k: v for k, v in tallies["reads"].items()
            if k != "ConsensusReads"} == reads
    if length is not None:
        assert len(bamio.decode_record(
            np.ascontiguousarray(flat).tobytes(), 0)["seq"]) == length
    # the program, on the same molecule written as the cell's layout, plus
    # a plain molecule behind it for the batch's open tail
    d = _hand([molecule, [(M, M)]])
    want, n_want, _t = rca.codec(d, opts)
    (path,) = layout.write(d, os.path.join(
        _WORK.name, f"hand{list(HAND).index(name)}"), 1)
    for engine in ENGINES:
        got, _header, report = _cli(path, path + engine + ".out.bam", config,
                                    engine, extra)
        assert got == np.ascontiguousarray(want).tobytes()
        m = report["metrics"]
        assert {k[len("codec.reads.rejected."):]: v for k, v in m.items()
                if k.startswith("codec.reads.rejected.")} == reads
        assert m["codec.consensus_reads"] == n_want
        if engine == "fast":  # the molecule with a CIGAR, then the carried
            assert m["codec.molecules.cigar_vec"] \
                == m["codec.molecules.cigar"] == 1
            assert "codec.slow_molecules.cigar" not in m
            assert m["codec.slow_molecules"] \
                == m["codec.slow_molecules.carried"] == 1
