"""The adjacency assigner's integer path: a sub-group's UMIs as 2-bit codes
(``umi_codes``, ``AdjacencyUmiAssigner.assign_codes``) give the molecule ids
and counters the string path gives, on the sub-groups it takes; every other
sub-group keeps the strings and answers, or raises, as before. The string
path is forced by ``assigners.CODE_MAX_LEN = 0``, which leaves no UMI a code.
"""

import numpy as np
import pytest

from fgumi_tpu.cli import main
from fgumi_tpu.commands.fast_group import FastGrouper, _Umis
from fgumi_tpu.commands.group import run_group
from fgumi_tpu.io.bam import BamHeader, BamReader
from fgumi_tpu.io.batch_reader import BamBatchReader
from fgumi_tpu.native import batch as nb
from fgumi_tpu.simulate import simulate_mapped_bam
from fgumi_tpu.umi import assigners
from fgumi_tpu.umi.assigners import (AdjacencyUmiAssigner, make_assigner,
                                     render_mis, umi_codes)

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library unavailable")

THRESHOLD = AdjacencyUmiAssigner._VEC_THRESHOLD
HEADER = BamHeader(text="@HD\tVN:1.6\tSO:unsorted\n", ref_names=["chr1"],
                   ref_lengths=[10_000_000])


def _umis(strings, ori=None):
    """_Umis over one buffer of the strings' bytes, as a batch gives them."""
    raw = [s.encode() for s in strings]
    lens = np.array([len(r) for r in raw], dtype=np.int64)
    offs = np.concatenate(([0], np.cumsum(lens)[:-1])).astype(np.int64)
    buf = np.frombuffer(b"".join(raw), dtype=np.uint8)
    if ori is None:
        ori = np.zeros(len(strings), dtype=np.int8)
    return _Umis([(buf, offs, lens)], np.asarray(ori, dtype=np.int8),
                 len(strings))


def _assign(groups, coded, monkeypatch, strategy="adjacency", **kw):
    """ids, kinds of each (strings, ori) group through one grouper (one
    running counter), with its counters and its assigner's tallies."""
    with monkeypatch.context() as mp:
        if not coded:
            mp.setattr(assigners, "CODE_MAX_LEN", 0)
        grouper = FastGrouper(HEADER, make_assigner(strategy, 1), **kw)
        out = [grouper._assign_umis(_umis(s, o)) for s, o in groups]
    tally = grouper.assigner.counter
    return out, grouper._counts, (tally.value, tally.uniques)


def _sample(rng, n, length, lower=False):
    """n UMIs of ``length`` bases from a few true ones with one-base errors,
    counts drawn so that many uniques tie on count."""
    true = rng.integers(0, 4, size=(max(2, n // 40), length))
    rows = true[rng.integers(0, len(true), size=n)]
    err = rng.random(n) < 0.3
    rows[err, rng.integers(0, length, size=int(err.sum()))] = \
        rng.integers(0, 4, size=int(err.sum()))
    mat = np.frombuffer(b"ACGT", np.uint8)[rows]
    if lower:
        mat = np.where(rng.random(mat.shape) < 0.3, mat | 0x20, mat)
    return [bytes(r).decode() for r in mat]


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
@pytest.mark.parametrize("length", [6, 8, 12, 32])
def test_the_codes_give_the_strings_ids_and_counters(seed, length,
                                                     monkeypatch):
    rng = np.random.default_rng(seed * 100 + length)
    groups = []
    for n in (THRESHOLD - 1, THRESHOLD, THRESHOLD + 37, 3 * THRESHOLD):
        strings = _sample(rng, n, length, lower=seed % 2 == 0)
        ori = rng.integers(0, 2, size=n) * 3 if n > THRESHOLD + 37 else None
        groups.append((strings, ori))
    coded, coded_counts, coded_tally = _assign(groups, True, monkeypatch)
    plain, plain_counts, plain_tally = _assign(groups, False, monkeypatch)
    for (ids, kinds), (want, want_kinds) in zip(coded, plain):
        assert kinds is None and want_kinds is None
        np.testing.assert_array_equal(ids, want)
    assert coded_tally == plain_tally
    assert plain_counts["templates.coded"] == 0
    for key in ("position_groups", "subgroups", "templates"):
        assert coded_counts[key] == plain_counts[key]
    # under the threshold the strings; at or over it (the last group in two
    # orientation sub-groups, each over it) the codes
    assert coded_counts["subgroups.coded"] == 4
    assert coded_counts["templates.coded"] \
        == sum(len(s) for s, _ in groups) - (THRESHOLD - 1)


def test_ties_on_count_are_broken_by_the_string(monkeypatch):
    """Every unique twice: the (-count, string) order alone decides the
    roots, and code order has to be string order."""
    rng = np.random.default_rng(7)
    uniques = sorted({u for u in _sample(rng, 4 * THRESHOLD, 8)})
    strings = [u for u in uniques for _ in range(2)]
    rng.shuffle(strings)
    assert len(strings) >= THRESHOLD
    (coded,), counts, _ = _assign([(strings, None)], True, monkeypatch)
    (plain,), _, _ = _assign([(strings, None)], False, monkeypatch)
    assert counts["subgroups.coded"] == 1
    np.testing.assert_array_equal(coded[0], plain[0])


def test_codes_and_their_matrix():
    mat = np.frombuffer(b"ACGTacgtTTTTAAAANCGTACGT", np.uint8).reshape(6, 4)
    codes, valid = umi_codes(mat)
    assert valid.tolist() == [True, True, True, True, False, True]
    assert codes[0] == codes[1] == 0b00011011 == codes[5]
    assert (codes[2], codes[3]) == (255, 0)
    np.testing.assert_array_equal(assigners._code_matrix(codes[:4], 4),
                                  np.frombuffer(b"ACGTACGTTTTTAAAA", np.uint8)
                                  .reshape(4, 4))
    _codes, valid = umi_codes(np.full((2, 33), ord("A"), np.uint8))
    assert not valid.any()


@pytest.mark.parametrize("case", ["byte", "dash", "n", "length33", "prefix"])
def test_a_sub_group_without_codes_keeps_the_strings(case, monkeypatch):
    rng = np.random.default_rng(11)
    length = 33 if case == "length33" else 8
    strings = _sample(rng, THRESHOLD + 10, length)
    odd = {"byte": "ACGTXCGT", "dash": "ACGT-CGT", "n": "ACGTNCGT",
           "length33": strings[0], "prefix": "XY:ACGTA"}[case]
    strings[5] = odd
    (coded,), counts, tally = _assign([(strings, None)], True, monkeypatch)
    (plain,), _, plain_tally = _assign([(strings, None)], False, monkeypatch)
    assert counts["templates.coded"] == 0
    np.testing.assert_array_equal(coded[0], plain[0])
    assert tally == plain_tally


def test_mixed_lengths_raise_as_the_strings_do(monkeypatch):
    strings = _sample(np.random.default_rng(3), THRESHOLD + 10, 8)
    strings[:5] = ["ACGTACGTA"] * 5
    with pytest.raises(ValueError, match="Multiple UMI lengths: 9 vs 8"):
        _assign([(strings, None)], True, monkeypatch)
    with pytest.raises(ValueError, match="Multiple UMI lengths: 9 vs 8"):
        _assign([(strings, None)], False, monkeypatch)


def test_min_umi_length_codes_the_prefix_the_truncation_keeps(monkeypatch):
    rng = np.random.default_rng(5)
    strings = [u + "ACG"[: i % 3] + ("X" if i % 7 == 0 else "")
               for i, u in enumerate(_sample(rng, THRESHOLD + 50, 8))]
    (coded,), counts, tally = _assign([(strings, None)], True, monkeypatch,
                                      min_umi_length=8)
    (plain,), _, plain_tally = _assign([(strings, None)], False, monkeypatch,
                                       min_umi_length=8)
    assert counts["templates.coded"] == len(strings)
    np.testing.assert_array_equal(coded[0], plain[0])
    assert tally == plain_tally
    # one UMI shorter than the minimum: the strings, and their error
    strings[3] = "ACGTAC"
    for on in (True, False):
        with pytest.raises(ValueError, match=r"\(6 < 8\)"):
            _assign([(strings, None)], on, monkeypatch, min_umi_length=8)


@pytest.mark.parametrize("strategy", ["identity", "edit"])
def test_other_strategies_keep_the_strings(strategy, monkeypatch):
    strings = _sample(np.random.default_rng(9), THRESHOLD + 10, 8)
    (coded,), counts, _ = _assign([(strings, None)], True, monkeypatch,
                                  strategy=strategy)
    (plain,), _, _ = _assign([(strings, None)], False, monkeypatch,
                             strategy=strategy)
    assert counts["templates.coded"] == 0
    np.testing.assert_array_equal(coded[0], plain[0])


def test_render_mis():
    ids = np.array([7, 5, 5, 12, 7], dtype=np.int64)
    assert render_mis(ids).tolist() == [b"7", b"5", b"5", b"12", b"7"]
    kinds = np.frombuffer(b"ABSB\0", np.uint8)
    assert render_mis(ids, kinds).tolist() \
        == [b"7/A", b"5/B", b"5", b"12/B", b""]


# ------------------------------------------------------------ whole commands

@pytest.fixture(scope="module")
def grouped_input(tmp_path_factory):
    """Template-coordinate sorted mapped BAM with UMI errors."""
    tmp = tmp_path_factory.mktemp("codes")
    raw = str(tmp / "mapped.bam")
    simulate_mapped_bam(raw, num_families=300, family_size=5,
                        umi_error_rate=0.05, seed=23)
    out = str(tmp / "sorted.bam")
    assert main(["sort", "-i", raw, "-o", out,
                 "--order", "template-coordinate"]) == 0
    return out


@pytest.mark.parametrize("command", ["group", "dedup"])
def test_the_cli_writes_the_same_bam_with_the_codes_on_and_off(
        command, grouped_input, tmp_path, monkeypatch):
    # every sub-group of two templates or more over the codes
    monkeypatch.setattr(AdjacencyUmiAssigner, "_VEC_THRESHOLD", 2)
    coded = []
    real = AdjacencyUmiAssigner.assign_codes

    def spy(self, n, codes):
        ids = real(self, n, codes)
        if ids is not None:
            coded.append(n)
        return ids

    monkeypatch.setattr(AdjacencyUmiAssigner, "assign_codes", spy)
    outs = {}
    for on in (True, False):
        out = str(tmp_path / f"{command}{on}.bam")
        with monkeypatch.context() as mp:
            if not on:
                mp.setattr(assigners, "CODE_MAX_LEN", 0)
            assert main([command, "-i", grouped_input, "-o", out,
                         "--strategy", "adjacency"]) == 0
        with BamReader(out) as r:
            outs[on] = [x.data for x in r]
        if on:
            assert len(coded) > 0
            coded.clear()
    assert coded == []
    assert outs[True] == outs[False]
    assert any(b"MI" in rec for rec in outs[True])


def _run_fast(path, target_bytes):
    with BamBatchReader(path, target_bytes=target_bytes) as reader:
        grouper = FastGrouper(reader.header, make_assigner("adjacency", 1))
        chunks = []
        for batch in reader:
            chunks.extend(grouper.process_batch(batch))
        chunks.extend(grouper.flush())
    return b"".join(chunks), grouper.result()


def test_a_carried_group_of_array_and_python_segments(grouped_input,
                                                      monkeypatch):
    """Tiny batches split templates at their boundaries, so a carried
    position group holds batch segments and held-back python Templates; the
    codes take both, and the records are the reference's."""
    monkeypatch.setattr(AdjacencyUmiAssigner, "_VEC_THRESHOLD", 2)
    seen = []
    real = FastGrouper._flush_carry

    def spy(self):
        kinds = {type(seg).__name__ for seg in self._carry}
        before = self._counts["templates.coded"]
        out = real(self)
        seen.append((kinds, self._counts["templates.coded"] - before))
        return out

    monkeypatch.setattr(FastGrouper, "_flush_carry", spy)
    blob, result = _run_fast(grouped_input, 600)
    assert any(kinds == {"_ArrSeg", "_PySeg"} and coded >= 2
               for kinds, coded in seen)

    class ListWriter:
        def __init__(self):
            self.records = []

        def write_record_bytes(self, data):
            self.records.append(bytes(data))

    with BamReader(grouped_input) as reader:
        w = ListWriter()
        want = run_group(reader, w)
    assert blob == b"".join(len(r).to_bytes(4, "little") + r
                            for r in w.records)
    assert result == want


def test_the_coded_template_share_reader():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics",
        "group.coded_template_share.py")
    spec = importlib.util.spec_from_file_location("coded_share", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    job = {"group.templates": 400, "group.templates.coded": 300}
    assert reader.read({"reports": [{"metrics": job}] * 3}) == 75.0
    # a program without the counter, and a run that assigns nothing
    assert reader.read({"reports": [
        {"metrics": {"group.templates": 400}}]}) is None
    assert reader.read({"reports": [{"metrics": {}}, {}]}) is None
