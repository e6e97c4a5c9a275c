"""The codec cell's own pieces (CPU): the reference's strand combine on
hand-worked columns, the ``codec_bam`` layout read back through ``bamio``,
one multiset of molecule sizes over seeds, the float32 control, the four
readers on a recorded run report, and a rehearsal run whose timed path is
broken underneath."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.dirname(ROOT)]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import bamio  # noqa: E402
import control  # noqa: E402
import reference_codec  # noqa: E402
import run as harness  # noqa: E402
import traffic  # noqa: E402
from test_correct import FAULTS  # noqa: E402

CELL = "codec-c4.linked"
A, C, G, T, N, PAD = 0, 1, 2, 3, traffic.N_CODE, reference_codec.PAD_CODE

#: name -> ((base, quality, depth, errors) of strand a, of strand b,
#:          (base, quality, depth, errors, both, disagree) combined)
COLUMNS = {
    "agree": ((A, 30, 3, 0), (A, 35, 2, 1), (A, 65, 5, 1, True, False)),
    "agree_capped": ((C, 60, 9, 0), (C, 50, 4, 0),
                     (C, 93, 13, 0, True, False)),
    "disagree_a_better": ((A, 40, 3, 0), (C, 25, 2, 0),
                          (A, 15, 5, 2, True, True)),
    "disagree_b_better": ((A, 20, 3, 1), (G, 30, 2, 0),
                          (G, 10, 5, 2, True, True)),
    "disagree_by_one": ((A, 31, 1, 0), (C, 30, 1, 0),
                        (N, 2, 2, 1, True, True)),
    "tie": ((A, 30, 2, 0), (T, 30, 2, 0), (N, 2, 4, 2, True, True)),
    "no_call_on_a": ((N, 2, 0, 0), (C, 30, 3, 0), (N, 2, 3, 0, False, False)),
    "no_call_on_b": ((G, 44, 6, 1), (N, 2, 2, 2), (N, 2, 6, 1, False, False)),
    "pad_on_a": ((PAD, 0, 0, 0), (C, 30, 3, 1), (C, 30, 3, 1, False, False)),
    "pad_on_b": ((T, 37, 1, 0), (PAD, 0, 0, 0), (T, 37, 1, 0, False, False)),
    "pad_beside_q2": ((PAD, 0, 0, 0), (C, 2, 3, 1),
                      (N, 2, 3, 1, False, False)),
    "pad_on_both": ((PAD, 0, 0, 0), (PAD, 0, 0, 0),
                    (N, 2, 0, 0, False, False)),
}


@pytest.mark.parametrize("name", list(COLUMNS))
def test_combine_on_a_hand_worked_column(name):
    a, b, want = COLUMNS[name]
    col = [np.array([v]) for pair in zip(a, b) for v in pair]
    got = reference_codec.combine(*col)
    assert tuple(x[0] for x in got) == want


def test_combine_caps_depth_and_errors():
    big = reference_codec.I16_MAX
    got = reference_codec.combine(*(np.array([v]) for v in (
        A, C, 40, 20, big, big, 5, 3)))
    # a wins: its errors and every b read that does not disagree with b's own
    assert (got[2][0], got[3][0]) == (2 * big, big)


def _linked(families):
    params = traffic.load("linked", traffic.ROOT)
    params["num_families"] = families
    return params


@pytest.mark.parametrize("seed", [7, 3000000019])
def test_layout_round_trips_through_bamio(seed, tmp_path):
    data = traffic.generate(_linked(60), seed)
    (path,) = traffic.write_inputs(data, str(tmp_path / "input"))
    payload = bamio.read_bgzf(path)
    _text, start = bamio.split_bam(payload)
    offs = bamio.record_offsets(payload, start)
    assert len(offs) - 1 == data["n_reads"]
    letters = "ACGT"
    mol = data["fam"]
    for pair in range(len(mol)):
        r1 = bamio.decode_record(payload, int(offs[2 * pair]))
        r2 = bamio.decode_record(payload, int(offs[2 * pair + 1]))
        m = int(mol[pair])
        flipped = bool(data["r1_reverse"][m])
        assert r1["name"] == r2["name"] \
            == f"codec{m}:{data['ordinal'][pair]}"
        assert (r1["flag"], r2["flag"]) == ((81, 161) if flipped
                                            else (97, 145))
        fwd, rev = (r2, r1) if flipped else (r1, r2)
        umi = "".join(letters[c] for c in data["umi"][m])
        for rec, key in ((fwd, "1"), (rev, "2")):
            assert rec["seq"] == "".join(
                letters[c] for c in data["codes" + key][pair])
            assert rec["qual"] == data["quals" + key][pair].tobytes()
            assert rec["cigar"] == (150 << 4,)
            assert rec["tags"] == {"MC": "150M", "RG": "A", "MI": str(m),
                                   "RX": umi}
        insert = int(data["insert"][m])
        assert 160 <= insert <= 280
        assert (fwd["pos"], fwd["tlen"], rev["tlen"]) == (
            int(data["start"][m]), insert, -insert)
        assert rev["pos"] == fwd["next_pos"] == fwd["pos"] + insert - 150
        assert rev["next_pos"] == fwd["pos"]
    assert list(mol) == sorted(mol)  # a molecule's records are consecutive


def test_seeds_permute_one_multiset_of_molecules():
    a = traffic.generate(_linked(400), 11)
    b = traffic.generate(_linked(400), 11)
    c = traffic.generate(_linked(400), 3000000019)
    assert all(np.array_equal(a[k], b[k]) for k in a if k != "kind")
    assert a["n_reads"] == c["n_reads"]
    assert np.array_equal(np.sort(a["sizes"]), np.sort(c["sizes"]))
    assert not np.array_equal(a["sizes"], c["sizes"])
    assert a["r1_reverse"].sum() == c["r1_reverse"].sum() == 200
    assert not np.array_equal(a["r1_reverse"], c["r1_reverse"])


@pytest.mark.parametrize("seed", [7, 2147483659])
def test_control_in_float32_is_not_correct(seed, tmp_path):
    verdict = control.control(CELL, seed, families=1500, work=str(tmp_path))
    assert verdict["correct"] is False
    assert verdict["compared"]["records_differing"]["value"] > 750


# ------------------------------------------------------------------ readers

def _reader(name):
    return harness.load_module(
        os.path.join(harness.ROOT, "metrics", name + ".py"), "m_" + name)


def _recorded():
    with open(os.path.join(ROOT, "tests", "data", "codec_report.json")) as f:
        return json.load(f)


def _run(reports, traced=2):
    return {"reports": reports, "traced_jobs": traced, "reads_per_job": 2964,
            "device": {"platform": "cpu", "kind": "cpu"},
            "params": {"read_length": 150}, "consensus_reads_per_row": 0.34}


def test_readers_on_a_recorded_run_report():
    report = _recorded()
    run = _run([report, report, {"metrics": {}}])
    by_name, m = report["spans"]["by_name"], report["metrics"]
    stage2 = sum(by_name[n]["self_s"] for n in (
        "engine.codec.single", "engine.codec.place", "engine.codec.combine",
        "engine.codec.gates", "resolve.serialize"))
    assert stage2 > 0
    assert _reader("codec.stage2_s_per_mread").read(run) \
        == pytest.approx(2 * stage2 / (2 * 2964 / 1e6))
    assert _reader("codec.single_strand_share").read(run) \
        == pytest.approx(100 * m["codec.single_strands"] / m["codec.strands"])
    assert m["codec.strands"] == 2 * m["codec.molecules"] == 1000
    assert _reader("codec.slow_molecule_share").read(run) \
        == pytest.approx(100 * 1 / 500)
    dev, host = m["codec.combine_cells_device"], m["codec.combine_cells_host"]
    assert dev > host > 0
    assert _reader("codec.device_combine_share").read(run) \
        == pytest.approx(100 * dev / (dev + host))


@pytest.mark.parametrize("name", [
    "codec.stage2_s_per_mread", "codec.single_strand_share",
    "codec.device_combine_share", "codec.slow_molecule_share"])
@pytest.mark.parametrize("reports", [
    [{"metrics": {"device.dispatches": 13}}] * 3,  # before the spans
    [{"metrics": {"duplex.molecules": 9}, "spans": {"by_name": {  # duplex
        "resolve.serialize": {"count": 1, "wall_s": 1.0, "self_s": 1.0,
                              "wait_s": 0.0, "p50_s": 1.0}}}}] * 3])
def test_readers_read_nothing_without_codecs_spans_and_counters(name, reports):
    assert _reader(name).read(_run(reports)) is None


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_broken_timed_path_is_seen(fault, monkeypatch, capsys):
    import fgumi_tpu.cli as cli

    real = cli.main

    def broken(argv):
        rc = real(argv)
        out = argv[argv.index("-o") + 1]
        if FAULTS[fault] and os.path.basename(out).startswith("job"):
            FAULTS[fault](out)
        return rc

    monkeypatch.setattr(cli, "main", broken)
    rc = harness.main(["--workload", CELL, "--seed", "11", "--seconds", "0.2",
                       "--trace", "0", "--rehearse"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["rehearsal"] and result["attempted"] >= 1
    assert result["correct"] is (fault == "sound")
    bad = {k for k, v in result["compared"].items() if v["value"] > v["limit"]}
    assert bad == {"sound": set(), "answer_altered": {"records_differing"},
                   "half_left_out": {"record_count_gap"},
                   "never_committed": {"jobs_failed"}}[fault]
