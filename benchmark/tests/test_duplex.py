"""The duplex cell's own pieces (CPU): the ``duplex_bam`` layout read back
through ``bamio``, one multiset of strand-family sizes over seeds, the float32
control, and a rehearsal run whose timed path is broken underneath."""

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
import run as harness  # noqa: E402
import traffic  # noqa: E402
from test_correct import FAULTS  # noqa: E402

CELL = "duplex-c3.panel"


def _panel(families):
    params = traffic.load("panel", traffic.ROOT)
    params["num_families"] = families
    return params


@pytest.mark.parametrize("seed", [7, 3000000019])
def test_layout_round_trips_through_bamio(seed, tmp_path):
    data = traffic.generate(_panel(70), seed)
    (path,) = traffic.write_inputs(data, str(tmp_path / "input"))
    payload = bamio.read_bgzf(path)
    _text, start = bamio.split_bam(payload)
    offs = bamio.record_offsets(payload, start)
    assert len(offs) - 1 == data["n_reads"]
    fam = data["fam"]
    mol = data["mol_of_fam"][fam]
    strand = data["strand_of_fam"][fam]
    letters = "ACGT"
    seen = []
    for pair in range(len(fam)):
        fwd = bamio.decode_record(payload, int(offs[2 * pair]))
        rev = bamio.decode_record(payload, int(offs[2 * pair + 1]))
        s = "AB"[strand[pair]]
        umi = "".join(letters[c] for c in data["umi"][mol[pair]])
        u1, u2 = umi[:4], umi[4:]
        assert fwd["name"] == rev["name"] \
            == f"m{mol[pair]}:{s}{data['ordinal'][pair]}"
        assert (fwd["flag"], rev["flag"]) == ((97, 145) if s == "A"
                                              else (161, 81))
        for rec, key in ((fwd, "1"), (rev, "2")):
            assert rec["seq"] == "".join(
                letters[c] for c in data["codes" + key][pair])
            assert rec["qual"] == data["quals" + key][pair].tobytes()
            assert rec["cigar"] == (100 << 4,)
            assert rec["tags"] == {
                "MC": "100M", "RG": "A", "MI": f"{mol[pair]}/{s}",
                "RX": f"{u1}-{u2}" if s == "A" else f"{u2}-{u1}"}
        insert = int(data["insert"][mol[pair]])
        assert (fwd["pos"], fwd["tlen"], rev["tlen"]) == (
            int(data["start"][mol[pair]]), insert, -insert)
        assert rev["pos"] == fwd["next_pos"] == fwd["pos"] + insert - 100
        seen.append((mol[pair], s))
    assert seen == sorted(seen)  # molecules in order, /A before /B


def test_seeds_permute_one_multiset_of_strand_families():
    a = traffic.generate(_panel(400), 11)
    b = traffic.generate(_panel(400), 11)
    c = traffic.generate(_panel(400), 3000000019)
    assert all(np.array_equal(a[k], b[k]) for k in a if k != "kind")
    assert a["n_reads"] == c["n_reads"]
    assert np.array_equal(np.sort(a["sizes"]), np.sort(c["sizes"]))
    assert np.array_equal(np.bincount(a["mol_kind"]),
                          np.bincount(c["mol_kind"]))
    assert not np.array_equal(a["mol_kind"], c["mol_kind"])
    kinds = np.bincount(a["mol_kind"], minlength=3)
    assert kinds[0] / kinds.sum() == pytest.approx(0.75, abs=0.01)
    assert abs(kinds[1] - kinds[2]) <= 1
    assert 2 * kinds[0] + kinds[1] + kinds[2] == 400


@pytest.mark.parametrize("seed", [7, 2147483659])
def test_control_in_float32_is_not_correct(seed, tmp_path):
    verdict = control.control(CELL, seed, families=1500, work=str(tmp_path))
    assert verdict["correct"] is False
    assert verdict["compared"]["records_differing"]["value"] > 0


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
