"""The group cell's own pieces (CPU): the ``amplicon_bam`` layout at the
cell's shape and read back through ``bamio``, ``reference_group`` on
hand-made position groups, the control at zero mismatches, the seven readers
on a recorded run report, ``roofline_hamming``'s work on a worked example,
and a rehearsal run whose timed path is broken underneath."""

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
import reference_group  # noqa: E402
import roofline  # noqa: E402
import roofline_hamming  # noqa: E402
import run as harness  # noqa: E402
import traffic  # noqa: E402
from test_correct import FAULTS  # noqa: E402

CELL = "group-adj.amplicon16k"
LETTERS = "ACGTN"


def _mix(**changes):
    return {**traffic.load("amplicon16k", traffic.ROOT), **changes}


# ------------------------------------------------------------------- layout

def test_the_mix_has_the_cells_position_groups():
    layout = traffic.kind_module("amplicon_bam")
    params = _mix()
    per_locus = layout.locus_molecules(params, np.random.default_rng(5))
    assert len(per_locus) == 40 and per_locus.sum() == 64000
    assert sorted(set(per_locus)) == [1000, 4000]
    assert (per_locus == 4000).sum() == 8 and (per_locus == 1000).sum() == 32
    other = layout.locus_molecules(params, np.random.default_rng(6))
    assert not np.array_equal(other, per_locus)  # the seed deals the sizes
    # a rehearsal's or a test's total scales every locus, none left empty
    small = layout.locus_molecules(_mix(num_families=1280),
                                   np.random.default_rng(5))
    assert small.sum() == 1280 and sorted(set(small)) == [20, 80]
    sizes = traffic.family_sizes(np.random.default_rng(1), params)
    assert 4.0 < sizes.mean() < 4.6 and sizes.min() == 1  # 16k and 4k a locus


@pytest.mark.parametrize("seed", [7, 3000000019])
def test_layout_round_trips_through_bamio(seed, tmp_path):
    params = _mix(groups=[[1, 40], [2, 10]], num_families=60,
                  r1_reverse_share=0.3)
    data = traffic.generate(params, seed)
    (path,) = traffic.write_inputs(data, str(tmp_path / "input"))
    payload = bamio.read_bgzf(path)
    text, start = bamio.split_bam(payload)
    assert text.splitlines()[0] == ("@HD\tVN:1.6\tSO:unsorted\tGO:query"
                                    "\tSS:unsorted:template-coordinate")
    assert text.splitlines()[1:] == ["@SQ\tSN:chr1\tLN:10000000",
                                     "@RG\tID:A\tSM:sample\tLB:lib"]
    offs = bamio.record_offsets(payload, start)
    assert len(offs) - 1 == data["n_reads"] == 2 * len(data["fam"])
    ends, names = [], []
    for t in range(len(data["fam"])):
        r1 = bamio.decode_record(payload, int(offs[2 * t]))
        r2 = bamio.decode_record(payload, int(offs[2 * t + 1]))
        mol = int(data["fam"][t])
        loc = int(data["locus"][mol])
        flipped = bool(data["r1_reverse"][t])
        assert r1["name"] == r2["name"] \
            == f"amp{mol:08d}:{data['ordinal'][t]:04d}"
        names.append((loc, r1["name"]))
        assert (r1["flag"], r2["flag"]) == ((81, 161) if flipped
                                            else (97, 145))
        fwd, rev = (r2, r1) if flipped else (r1, r2)
        umi = "".join(LETTERS[c] for c in data["umi_t"][t])
        for rec, key in ((fwd, "1"), (rev, "2")):
            assert rec["seq"] == "".join(
                LETTERS[c] for c in data["codes" + key][t])
            assert rec["qual"] == data["quals" + key][t].tobytes()
            assert rec["cigar"] == (100 << 4,) and rec["mapq"] == 60
            assert rec["tags"] == {"MC": "100M", "RG": "A", "RX": umi}
        insert = int(data["insert"][loc])
        assert 120 <= insert <= 180
        assert (fwd["pos"], fwd["tlen"], rev["tlen"]) == (
            int(data["start"][loc]), insert, -insert)
        assert rev["pos"] == fwd["next_pos"] == fwd["pos"] + insert - 100
        ends.append((loc, fwd["pos"], rev["pos"] + 100 - 1))
    assert len(set(ends)) == 3  # one pair of ends a locus
    assert names == sorted(names)  # template-coordinate order, as SS says
    keys, _orient = reference_group.template_keys(data)
    assert [(lo, hi) for _l, lo, hi in ends] == [tuple(k) for k in keys]


def test_seeds_permute_one_multiset_of_molecules():
    params = _mix(groups=[[2, 150], [4, 25]], num_families=400)
    a, b = traffic.generate(params, 11), traffic.generate(params, 11)
    c = traffic.generate(params, 3000000019)
    assert all(np.array_equal(a[k], b[k]) for k in a if k != "kind")
    assert a["n_reads"] == c["n_reads"]
    assert np.array_equal(np.sort(a["sizes"]), np.sort(c["sizes"]))
    assert not np.array_equal(a["umi"], c["umi"])
    assert not a["r1_reverse"].any()  # the cell's mix: every template F1R2


# ---------------------------------------------------------------- reference

def _codes(*umis):
    return np.array([[LETTERS.index(ch) for ch in u] for u in umis],
                    dtype=np.uint8)


def _assign(templates):
    """``templates``: (key, orientation rank, UMI) in stream order."""
    keys, orient, umis = zip(*templates)
    kept, ids, counted = reference_group.assign(
        np.array(keys), np.array(orient), _codes(*umis))
    return list(kept), list(ids), counted


def test_a_count_tie_is_broken_by_the_string():
    # 3 against 3 within one mismatch: neither captures the other
    # (3 > 3 // 2 + 1); AAAA ranks first and takes the first id, whatever
    # the stream's order
    kept, ids, counted = _assign([(5, 2, "CAAA")] * 3 + [(5, 2, "AAAA")] * 3)
    assert all(kept) and ids == [1, 1, 1, 0, 0, 0]
    assert (counted["molecules"], counted["unique_umis"],
            counted["graphs"]) == (2, 2, 1)


def test_a_child_at_exactly_half_plus_one_is_captured():
    # 3 <= 4 // 2 + 1: captured; 4 > 3: not
    _kept, ids, counted = _assign([(5, 2, "AAAA")] * 4 + [(5, 2, "AAAC")] * 3
                                  + [(9, 2, "AAAA")] * 4
                                  + [(9, 2, "AAAC")] * 4)
    assert ids == [0] * 7 + [1] * 4 + [2] * 4
    assert counted["position_groups"] == 2 and counted["molecules"] == 3


def test_capture_is_breadth_first_through_a_child():
    # AACC is two mismatches from the root and one from its child AAAC
    # (2 <= 3 // 2 + 1); GGGG stands alone and is minted after the root
    _kept, ids, _counted = _assign(
        [(5, 2, "AACC")] * 2 + [(5, 2, "GGGG")] + [(5, 2, "AAAC")] * 3
        + [(5, 2, "AAAA")] * 5)
    assert ids == [0, 0, 1, 0, 0, 0, 0, 0, 0, 0, 0]


def test_two_orientations_at_one_locus_are_two_sub_groups():
    # rank 1 (R1 reverse, R2 forward) is assigned before rank 2, whatever
    # the stream's order; the same UMI in both is two molecules
    _kept, ids, counted = _assign([(5, 2, "AAAA"), (5, 1, "AAAA"),
                                   (5, 2, "AAAA"), (5, 1, "CCCC")])
    assert ids == [2, 0, 2, 1]
    assert (counted["position_groups"], counted["subgroups"],
            counted["graphs"]) == (1, 2, 1)


def test_an_n_in_the_umi_drops_the_template():
    kept, ids, counted = _assign([(5, 2, "AAAA"), (5, 2, "ANAA"),
                                  (7, 2, "NNNN"), (9, 2, "AAAA")])
    assert kept == [True, False, False, True] and ids == [0, 1]
    assert (counted["templates"], counted["ns_in_umi"],
            counted["position_groups"]) == (2, 2, 2)


def test_ids_run_on_over_position_groups_in_stream_order():
    # the same key met again later is a new position group (a stream)
    _kept, ids, counted = _assign([(9, 2, "AAAA"), (5, 2, "AAAA"),
                                   (5, 2, "CCCC"), (9, 2, "AAAA")])
    assert ids == [0, 1, 2, 3] and counted["position_groups"] == 3


def test_the_control_rule_is_identity_in_rank_order():
    ints = np.array([7, 3, 3, 7, 7, 1])
    assert list(reference_group.identity_molecules(ints)) \
        == [0, 1, 1, 0, 0, 2]


def test_records_are_the_inputs_with_mi_appended(tmp_path):
    params = _mix(groups=[[1, 30], [1, 12]], num_families=42,
                  r1_reverse_share=0.4)
    data = traffic.generate(params, 23)
    data["umi_t"][5, 0] = traffic.N_CODE
    (path,) = traffic.write_inputs(data, str(tmp_path / "input"))
    payload = bamio.read_bgzf(path)
    _text, start = bamio.split_bam(payload)
    offs = bamio.record_offsets(payload, start)
    flat, n_records, counted = reference_group.group(data)
    want = np.ascontiguousarray(flat).tobytes()
    out = bamio.record_offsets(want, 0)
    assert len(out) - 1 == n_records == data["n_reads"] - 2
    assert counted["ns_in_umi"] == 1
    k = 0
    for i in range(len(offs) - 1):
        if i // 2 == 5:
            continue  # the template with the N
        got = bamio.decode_record(want, int(out[k]))
        src = bamio.decode_record(payload, int(offs[i]))
        mi = got["tags"].pop("MI")
        assert got == src and list(got["tags"]) == ["MC", "RG", "RX"]
        assert mi.isdigit() and 0 <= int(mi) < counted["molecules"]
        k += 1
    assert k == n_records


@pytest.mark.parametrize("seed", [7, 2147483659])
def test_control_at_zero_mismatches_is_not_correct(seed, tmp_path):
    verdict = control.control(CELL, seed, families=1500, work=str(tmp_path))
    assert verdict["correct"] is False
    assert verdict["compared"]["records_differing"]["value"] \
        > verdict["records"] // 2
    assert verdict["compared"]["record_count_gap"]["value"] == 0


# ------------------------------------------------------------------ readers

def _reader(name):
    return harness.load_module(
        os.path.join(harness.ROOT, "metrics", name + ".py"), "m_" + name)


def _recorded():
    with open(os.path.join(ROOT, "tests", "data", "group_report.json")) as f:
        return json.load(f)


def _run(reports, traced=2):
    return {"reports": reports, "traced_jobs": traced, "reads_per_job": 17584,
            "device": {"platform": "cpu", "kind": "cpu"},
            "params": {"umi_length": 8}}


NEW = ["group.assign_s_per_mread", "group.threshold_s_per_mread",
       "group.hamming_fetch_s_per_mread", "group.hamming_pad_cell_share",
       "group.device_graph_share", "kernel.hamming_ms_p50",
       "kernel.hamming_roofline"]


def test_readers_on_a_recorded_run_report():
    report = _recorded()
    run = _run([report, report, {"metrics": {}}])
    by_name, m = report["spans"]["by_name"], report["metrics"]
    mreads = 2 * 17584 / 1e6
    assign = by_name["group.assign"]
    assert assign["wait_s"] == 0 and assign["wall_s"] > 0
    assert _reader("group.assign_s_per_mread").read(run) \
        == pytest.approx(2 * assign["wall_s"] / mreads)
    own = sum(by_name["group.assign." + n]["self_s"]
              for n in ("umis", "threshold", "bfs", "ids"))
    assert _reader("group.threshold_s_per_mread").read(run) \
        == pytest.approx(2 * own / mreads)
    # one graph went to the device: its fetch is the one device.fetch
    assert m["group.hamming.dispatches"] == by_name["device.fetch"]["count"] \
        == 1
    assert _reader("group.hamming_fetch_s_per_mread").read(run) \
        == pytest.approx(2 * by_name["device.fetch"]["wall_s"] / mreads)
    assert _reader("group.hamming_pad_cell_share").read(run) \
        == pytest.approx(100 * (1 - 2193361 / 2048 ** 2))
    assert m["group.hamming.cells"] == 1481 ** 2 == 2193361
    assert m["group.hamming.rows"] == 2 * 1481
    assert _reader("group.device_graph_share").read(run) \
        == pytest.approx(100 * 1 / 12)
    # a CPU run has no device plane: the kernel's readers say nothing
    assert _reader("kernel.hamming_ms_p50").read(run) is None
    assert _reader("kernel.hamming_roofline").read(run) is None


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("reports", [
    [{"metrics": {"device.dispatches": 13}}] * 3,  # before the spans
    [{"metrics": {"codec.molecules": 9}, "spans": {"by_name": {  # codec
        "device.fetch": {"count": 7, "wall_s": 1.0, "self_s": 1.0,
                         "wait_s": 0.0, "p50_s": 0.1}}}}] * 3])
def test_readers_read_nothing_without_groups_spans_and_counters(name,
                                                                reports):
    assert _reader(name).read(_run(reports)) is None


def test_the_parents_report_has_group_assign_alone():
    """The tree before PR 37 has ``group.assign`` and none of its children
    or counters: one reader reads it, the others say nothing."""
    report = _recorded()
    old = {"metrics": {k: v for k, v in report["metrics"].items()
                       if k.startswith("device.")},
           "spans": {"by_name": {k: v for k, v in
                                 report["spans"]["by_name"].items()
                                 if not k.startswith(("group.assign.",
                                                      "group.hamming."))}}}
    run = _run([old] * 3)
    assert _reader("group.assign_s_per_mread").read(run) > 0
    for name in NEW[1:]:
        assert _reader(name).read(run) is None, name


def test_hamming_work_on_a_worked_example():
    # one search of 5,000 UMIs of 8 bases against themselves
    ops, moved = roofline_hamming.hamming_work(10000, 25_000_000, 8)
    assert ops == 2 * 25_000_000 * 8 == 400_000_000
    assert moved == 10000 * 8 + 25_000_000 / 8 == 3_205_000
    pk = roofline.PEAKS["TPU v5 lite"]
    least = roofline_hamming.least_seconds("TPU v5 lite", 10000, 25_000_000,
                                           8)
    assert least == pytest.approx(3_205_000 / pk["bytes_per_s"])  # memory
    assert least > ops / pk["flops_per_s"]
    with pytest.raises(KeyError):
        roofline_hamming.least_seconds("no such chip", 1, 1, 8)
    # the executable's name on the device plane, and not the others'
    assert roofline_hamming.HAMMING_MODULES.search("jit_dist(8213749)")
    for other in ("jit_fn(1)", "jit__duplex_combine_jit(2)", "jit_distance"):
        assert not roofline_hamming.HAMMING_MODULES.search(other)
    assert roofline_hamming.hamming_runs(_run([])) is None  # no device plane


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    bench, cell, config, _reference, params = harness.load_cell(CELL)
    assert cell["chips"] == 1 and params["kind"] == "amplicon_bam"
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == [CELL], name
        assert entries[name]["moves"] == "reads_per_s"
    assert config["kernel_modules"] \
        == roofline_hamming.HAMMING_MODULES.pattern


# --------------------------------------------------------------- rehearsal

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
