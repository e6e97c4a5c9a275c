"""Parity: FastCodecCaller (vectorized prepare) vs classic CODEC engine."""

import functools

import numpy as np
import pytest

from fgumi_tpu.cli import main
from fgumi_tpu.io.bam import BamHeader, BamReader, BamWriter, RecordBuilder
from fgumi_tpu.native import batch as nb
from fgumi_tpu.simulate import simulate_codec_bam
from codec_placement import (I16_MAX, PAD, PLACE_CASES, numpy_place,
                             place_case)
from record_batches import record_batches

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library unavailable")


def records_of(path):
    with BamReader(path) as r:
        return [rec.data for rec in r]


def wire_of(chunks):
    """The bytes of the engine's pending chunks, resolved in order."""
    return b"".join(chunk.resolve() for chunk in chunks)


def assert_cli_parity(src, tmp_path, extra=()):
    fast = str(tmp_path / "fast.bam")
    classic = str(tmp_path / "classic.bam")
    assert main(["codec", "-i", src, "-o", fast] + list(extra)) == 0
    assert main(["codec", "-i", src, "-o", classic, "--classic"]
                + list(extra)) == 0
    assert records_of(fast) == records_of(classic)


@pytest.fixture(scope="module")
def codec_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("fc") / "codec.bam")
    simulate_codec_bam(path, num_molecules=300, pairs_per_molecule=3, seed=9)
    return path


@pytest.mark.parametrize("extra", [
    ["--min-reads", "1"],
    ["--min-reads", "2"],
    ["--min-reads", "1", "--min-duplex-length", "120"],
    ["--min-reads", "1", "--max-reads", "2"],
    ["--min-reads", "1", "--outer-bases-qual", "10",
     "--outer-bases-length", "4"],
])
def test_parity_simulated(codec_bam, tmp_path, extra):
    assert_cli_parity(codec_bam, tmp_path, extra)


@pytest.fixture(scope="module")
def adversarial_bam(tmp_path_factory):
    """Hand-built MI groups: fragments, secondary/supp, non-FR pairs,
    soft-clipped CIGARs (classic fallback), name triplets, dovetails,
    missing mates, 0-length overlap."""
    path = str(tmp_path_factory.mktemp("fc") / "adv.bam")
    rng = np.random.default_rng(33)
    header = BamHeader(
        text="@HD\tVN:1.6\tSO:unsorted\tGO:query\n@SQ\tSN:c\tLN:100000\n",
        ref_names=["c"], ref_lengths=[100000])

    def rec(name, flag, pos, length=60, mi=b"0", cigar=None, next_pos=None,
            tlen=0):
        cigar = cigar or [("M", length)]
        sq = bytes(rng.choice(np.frombuffer(b"ACGT", np.uint8), size=length))
        b = RecordBuilder().start_mapped(
            name, flag, 0, pos, 60, cigar, sq,
            rng.integers(10, 41, size=length).astype(np.uint8),
            next_ref_id=0 if next_pos is not None else -1,
            next_pos=next_pos if next_pos is not None else -1, tlen=tlen)
        b.tag_str(b"MI", mi)
        b.tag_str(b"RX", b"ACGTAC")
        return b.finish()

    def fr_pair(name, mi, p1, p2, length=60):
        tlen = p2 + length - p1
        return [rec(name, 0x1 | 0x40 | 0x20, p1, length, mi,
                    next_pos=p2, tlen=tlen),
                rec(name, 0x1 | 0x80 | 0x10, p2, length, mi,
                    next_pos=p1, tlen=-tlen)]

    records = []
    # mol 0: clean overlapping FR pairs
    for t in range(3):
        records += fr_pair(b"m0t%d" % t, b"0", 1000, 1020)
    # mol 1: dovetailing pairs (reads extend past mate ends -> clips)
    for t in range(2):
        records += fr_pair(b"m1t%d" % t, b"1", 2000, 1980)
    # mol 2: a fragment + a secondary + one good pair
    records.append(rec(b"m2f", 0, 3000, mi=b"2"))
    records.append(rec(b"m2s", 0x1 | 0x40 | 0x100, 3000, mi=b"2",
                       next_pos=3020))
    records += fr_pair(b"m2t0", b"2", 3000, 3020)
    # mol 3: same-strand pair (NotPrimaryFrPair)
    records.append(rec(b"m3t0", 0x1 | 0x40, 4000, mi=b"3", next_pos=4020,
                       tlen=80))
    records.append(rec(b"m3t0", 0x1 | 0x80, 4020, mi=b"3", next_pos=4000,
                       tlen=-80))
    # mol 4: soft-clipped pair (classic fallback path)
    records.append(rec(b"m4t0", 0x1 | 0x40 | 0x20, 5000, mi=b"4",
                       cigar=[("S", 4), ("M", 56)], next_pos=5010, tlen=70))
    records.append(rec(b"m4t0", 0x1 | 0x80 | 0x10, 5010, mi=b"4",
                       cigar=[("M", 56), ("S", 4)], next_pos=5000, tlen=-70))
    records += fr_pair(b"m4t1", b"4", 5000, 5010)
    # mol 5: widely separated pair (no overlap)
    records += fr_pair(b"m5t0", b"5", 6000, 9000)
    # mol 6: name triplet (rejected bucket)
    records += fr_pair(b"m6t0", b"6", 7000, 7020)
    records.append(rec(b"m6t0", 0x1 | 0x40, 7000, mi=b"6", next_pos=7020,
                       tlen=80))
    records += fr_pair(b"m6t1", b"6", 7000, 7020)
    with BamWriter(path, header) as w:
        for r in records:
            w.write_record_bytes(r)
    return path


@pytest.mark.parametrize("extra", [["--min-reads", "1"],
                                   ["--min-reads", "2"],
                                   ["--min-reads", "1", "--max-reads", "1"]])
def test_parity_adversarial(adversarial_bam, tmp_path, extra):
    # --max-reads on the mixed-shape fixture exercises the shared downsample
    # RNG stream across interleaved classic/vector molecules
    assert_cli_parity(adversarial_bam, tmp_path, extra)


@pytest.mark.parametrize("extra", [[], ["--min-reads", "2"],
                                   ["--min-duplex-length", "40"]])
def test_molecule_counters_add_up_on_mixed_shapes(adversarial_bam, tmp_path,
                                                  extra):
    """Every MI group of the mixed-shape fixture (fragments, broken pairs,
    clipped and indel CIGARs beside plain pairs) is emitted or rejected under
    one reason, whichever prepare it went down."""
    import json

    report = str(tmp_path / "report.json")
    assert main(["--run-report", report, "codec", "-i", adversarial_bam,
                 "-o", str(tmp_path / "out.bam"), "--min-reads", "1"]
                + extra) == 0
    with open(report) as f:
        m = json.load(f)["metrics"]
    with BamReader(adversarial_bam) as r:
        groups = len({rec.get_str(b"MI") for rec in r})
    assert m["codec.molecules"] == groups
    assert m["codec.emitted"] + m["codec.rejected"] == groups
    assert sum(v for k, v in m.items()
               if k.startswith("codec.rejected.")) == m["codec.rejected"] > 0
    assert m["codec.emitted"] == len(records_of(str(tmp_path / "out.bam")))
    assert 0 < m["codec.slow_molecules"] < groups
    assert m["codec.strands"] == 2 * (
        m["codec.emitted"] + m.get("codec.rejected.ClipOverlapFailed", 0)
        + m.get("codec.rejected.HighDuplexDisagreement", 0))


def test_all_m_filter_keeps_all():
    """Single-op M CIGARs of any length mix form one prefix-compatible
    group (the vector path's keep-all assumption for phase 3)."""
    from fgumi_tpu.core.cigar import select_most_common_alignment_group

    entries = [(i, L, [("M", L)]) for i, L in
               enumerate([60, 55, 60, 40, 58, 60, 1])]
    entries.sort(key=lambda t: -t[1])
    keep = select_most_common_alignment_group(entries)
    assert sorted(keep) == list(range(7))


@pytest.mark.parametrize("n_records", [4, 7, 50])
def test_parity_tiny_batches(codec_bam, n_records):
    """Molecules spanning batch boundaries: carry merge + deferred flush.
    Batches of 4 records end inside every molecule of three pairs but one in
    three, of 7 inside most, of 50 inside some: the carried molecule goes
    down the classic prepare() and joins the next batch's device pass."""
    from fgumi_tpu.consensus.codec import CodecConsensusCaller, CodecOptions
    from fgumi_tpu.consensus.fast_codec import FastCodecCaller
    from fgumi_tpu.core.grouper import iter_mi_group_batches
    from fgumi_tpu.observe.metrics import METRICS

    import struct

    caller = CodecConsensusCaller("fgumi", "A", CodecOptions())
    with BamReader(codec_bam) as r:
        expected = []
        for batch in iter_mi_group_batches(r, 50, tag=b"MI"):
            expected.extend(caller.call_groups(batch))
    expected_wire = b"".join(struct.pack("<I", len(r)) + r for r in expected)

    fast_caller = CodecConsensusCaller("fgumi", "A", CodecOptions())
    fast = FastCodecCaller(fast_caller, b"MI")
    before = METRICS.snapshot().get("codec.slow_molecules", 0)
    got = []
    for batch in record_batches(codec_bam, n_records):
        got.extend(fast.process_batch(batch))
    got.extend(fast.flush())
    assert wire_of(got) == expected_wire
    assert fast_caller.stats.rejection_reasons \
        == caller.stats.rejection_reasons
    # 300 molecules of 6 records: the molecule that holds a batch's last
    # record is carried (the engine cannot know it is complete) and goes
    # down the slow path, once however many batches it spans
    slow = METRICS.snapshot()["codec.slow_molecules"] - before
    assert slow == len({(min(end, 1800) - 1) // 6
                        for end in range(n_records, 1800 + n_records,
                                         n_records)})


@pytest.mark.parametrize("seed", [101, 202, 303])
def test_parity_randomized(tmp_path, seed):
    """Randomized simulate params + disagreement thresholds sweep the batched
    finish (combine / masks / thresholds run concatenated across molecules)."""
    rng = np.random.default_rng(seed)
    src = str(tmp_path / "r.bam")
    simulate_codec_bam(src, num_molecules=int(rng.integers(40, 120)),
                       pairs_per_molecule=int(rng.integers(1, 5)),
                       read_length=int(rng.integers(40, 120)),
                       error_rate=float(rng.uniform(0, 0.06)),
                       overlap_fraction=float(rng.uniform(0.2, 1.0)),
                       seed=seed)
    extra = ["--min-reads", str(int(rng.integers(1, 3))),
             "--max-duplex-disagreement-rate", str(float(rng.uniform(0.001, 0.05))),
             "--single-strand-qual", str(int(rng.integers(0, 20)))]
    if rng.integers(0, 2):
        extra += ["--per-base-tags"]
    if rng.integers(0, 2):
        extra += ["--outer-bases-qual", "5", "--outer-bases-length",
                  str(int(rng.integers(1, 12)))]
    assert_cli_parity(src, tmp_path, extra)


def test_parity_cell_tag(codec_bam, tmp_path):
    """--cell-tag takes the RecordBuilder fallback branch in _finish_batch."""
    assert_cli_parity(codec_bam, tmp_path, ["--min-reads", "1",
                                            "--cell-tag", "CB"])


def test_parity_count_threshold(codec_bam, tmp_path):
    """--max-duplex-disagreements exercises the vectorized count-threshold
    reject (classic raises DuplexDisagreementError('count'))."""
    assert_cli_parity(codec_bam, tmp_path,
                      ["--min-reads", "1", "--max-duplex-disagreements", "1"])
    assert_cli_parity(codec_bam, tmp_path,
                      ["--min-reads", "1", "--max-duplex-disagreements", "0"])


def test_carry_reads_longer_than_span(tmp_path):
    """A carried molecule's reads can be longer than every read in the next
    batch's span, pushing the dispatch L_max past the span's pack stride;
    the dense gather must clamp its width (N/Q0 tails) instead of crashing.
    Drives _run directly with a mixed vec + classic molecule table and checks
    it against the same molecules run classic-only."""
    from fgumi_tpu.consensus.codec import CodecConsensusCaller, CodecOptions
    from fgumi_tpu.consensus.fast_codec import FastCodecCaller, _Molecules
    from fgumi_tpu.consensus.vanilla import ConsensusJob, R1

    rng = np.random.default_rng(8)

    def strand_rows(n, length, stride):
        codes = np.full((n, stride), 4, dtype=np.uint8)
        quals = np.zeros((n, stride), dtype=np.uint8)
        codes[:, :length] = rng.integers(0, 4, size=(n, length))
        quals[:, :length] = rng.integers(10, 41, size=(n, length))
        return codes, quals

    stride = 64          # short span: 40bp reads
    long_len = 200       # carried molecule: 200bp reads -> L_max 208 > 64
    c1, q1 = strand_rows(2, 40, stride)
    c2, q2 = strand_rows(2, 40, stride)
    codes_pk = np.vstack([c1, c2])
    quals_pk = np.vstack([q1, q2])
    lc, lq = strand_rows(4, long_len, long_len)

    def classic_mol(umi, codes, quals, length):
        def job(rows):
            return ConsensusJob(
                umi=umi, read_type=R1,
                codes=[codes[r, :length] for r in rows],
                quals=[quals[r, :length] for r in rows],
                consensus_len=length, original_raws=[])

        return {
            "umi": umi, "records": [], "source_raws": [],
            "job_r1": job([0, 1]), "job_r2": job([2, 3]),
            "n_r1": 2, "n_r2": 2,
            "r1_is_negative": False, "r2_is_negative": True,
            "consensus_length": length,
        }

    class OneGroupBatch:
        """The vec molecule's group: one record, MI 7, no RX."""
        buf = np.frombuffer(b"7", dtype=np.uint8)

        def tag_locs_str(self, tag):
            if tag == b"MI":
                return np.array([0]), np.array([1], np.int32), None
            return np.array([-1]), np.array([0], np.int32), None

    # the carried molecule (classic jobs) first, then a vec molecule whose
    # four strand rows lie in the pack arrays
    mols = _Molecules(2, [classic_mol("9", lc, lq, long_len)],
                      OneGroupBatch())
    mols.set_vec(1, 0, 2, 2, 40, 40, False, True, 40)
    mols.row_hi[1] = 1

    caller = CodecConsensusCaller("fgumi", "A", CodecOptions())
    fast = FastCodecCaller(caller, b"MI")
    mixed = wire_of(fast._run(mols, codes_pk, quals_pk))

    # reference: the same two molecules, both via the classic-job path
    caller2 = CodecConsensusCaller("fgumi", "A", CodecOptions())
    fast2 = FastCodecCaller(caller2, b"MI")
    ref = wire_of(fast2._run(_Molecules(
        2, [classic_mol("9", lc, lq, long_len),
            classic_mol("7", codes_pk, quals_pk, 40)])))
    assert mixed == ref


def test_threaded_matches_inline(codec_bam, tmp_path):
    """--threads pipeline output is byte-identical to the inline run."""
    inline = str(tmp_path / "inl.bam")
    threaded = str(tmp_path / "thr.bam")
    assert main(["codec", "-i", codec_bam, "-o", inline,
                 "--min-reads", "1"]) == 0
    assert main(["codec", "-i", codec_bam, "-o", threaded, "--min-reads",
                 "1", "--threads", "4", "--batch-bytes", "20000"]) == 0
    assert records_of(inline) == records_of(threaded)


def test_batch_bytes_zero_not_silent(codec_bam, tmp_path):
    """--batch-bytes 0 must not silently produce an empty BAM (reader clamps
    to one chunk)."""
    out = str(tmp_path / "z.bam")
    assert main(["codec", "-i", codec_bam, "-o", out, "--min-reads", "1",
                 "--batch-bytes", "0"]) == 0
    assert len(records_of(out)) > 0


def test_all_groups_shape_ineligible(tmp_path):
    """A span where EVERY group is shape-ineligible (soft-clipped CIGARs)
    drives _pair_span's empty-eligible early return — it must hand back a
    3-tuple (None geometry), not crash, and match the classic engine."""
    path = str(tmp_path / "allsoft.bam")
    rng = np.random.default_rng(7)
    header = BamHeader(
        text="@HD\tVN:1.6\tSO:unsorted\tGO:query\n@SQ\tSN:c\tLN:100000\n",
        ref_names=["c"], ref_lengths=[100000])

    def rec(name, flag, pos, mi, cigar, next_pos, tlen):
        length = sum(n for _, n in cigar)
        sq = bytes(rng.choice(np.frombuffer(b"ACGT", np.uint8), size=length))
        b = RecordBuilder().start_mapped(
            name, flag, 0, pos, 60, cigar, sq,
            rng.integers(10, 41, size=length).astype(np.uint8),
            next_ref_id=0, next_pos=next_pos, tlen=tlen)
        b.tag_str(b"MI", mi)
        b.tag_str(b"RX", b"ACGTAC")
        return b.finish()

    records = []
    for g in range(4):
        mi = str(g).encode()
        p1, p2 = 1000 + g * 500, 1012 + g * 500
        for t in range(2):
            name = b"g%dt%d" % (g, t)
            records.append(rec(name, 0x1 | 0x40 | 0x20, p1, mi,
                               [("S", 5), ("M", 55)], p2, p2 + 60 - p1))
            records.append(rec(name, 0x1 | 0x80 | 0x10, p2, mi,
                               [("M", 55), ("S", 5)], p1, -(p2 + 60 - p1)))
    with BamWriter(path, header) as w:
        for r in records:
            w.write_record_bytes(r)
    assert_cli_parity(path, tmp_path, ["--min-reads", "1"])


# ---------------------------------------------------------------- columns
#
# The batch engine carries a batch's molecules as columns and loops only over
# the molecules its closed forms do not cover. One stream holds every shape
# those two paths have to agree on, and every case below runs it through both
# engines, cut into batches of ``n_records`` so that molecules of every shape
# get carried across a boundary too.

def _mixed_stream(path):
    """An MI-grouped BAM whose reads come from one reference (so a
    molecule's strands agree where they should): single- and multi-pair
    molecules, R1 forward and R1 reverse, a soft-clipped group, an empty MI,
    RX values equal / differing / absent / lower case / dual / on one record
    only / not ASCII, a deep molecule (downsampling), a short overlap, a
    molecule out of phase, strands that disagree, fragments and a
    same-strand pair, cell tags on some reads."""
    rng = np.random.default_rng(5)
    header = BamHeader(
        text="@HD\tVN:1.6\tSO:unsorted\tGO:query\n@SQ\tSN:c\tLN:100000\n",
        ref_names=["c"], ref_lengths=[100000])
    bases = np.frombuffer(b"ACGT", np.uint8)
    truth = rng.choice(bases, size=100000)

    def rec(name, flag, pos, length, mi, next_pos, tlen, cigar=None,
            rx=b"ACGTAC", cb=None, noise=0.0):
        sq = truth[pos:pos + length].copy()
        wrong = rng.random(length) < noise
        sq[wrong] = rng.choice(bases, size=int(wrong.sum()))
        b = RecordBuilder().start_mapped(
            name, flag, 0, pos, 60, cigar or [("M", length)], bytes(sq),
            rng.integers(20, 41, size=length).astype(np.uint8),
            next_ref_id=0, next_pos=next_pos, tlen=tlen)
        b.tag_str(b"MI", mi)
        if rx is not None:
            b.tag_str(b"RX", rx)
        if cb is not None:
            b.tag_str(b"CB", cb)
        return b.finish()

    def fr_pair(name, mi, p1, p2, length=60, length2=None, r1_reverse=False,
                rx=b"ACGTAC", rx2=0, cigars=(None, None), **kw):
        """The forward read at p1, the reverse at p2 (``length2`` long if it
        differs); R1 is the forward read unless ``r1_reverse``. ``rx2``: the
        second record's RX if it differs from the first's."""
        length2 = length2 or length
        tlen = p2 + length2 - p1
        f1, f2 = (0x80, 0x40) if r1_reverse else (0x40, 0x80)
        rx2 = rx if rx2 == 0 else rx2
        fwd = rec(name, 0x1 | f1 | 0x20, p1, length, mi, p2, tlen,
                  cigar=cigars[0], rx=rx2 if r1_reverse else rx, **kw)
        rev = rec(name, 0x1 | f2 | 0x10, p2, length2, mi, p1, -tlen,
                  cigar=cigars[1], rx=rx if r1_reverse else rx2, **kw)
        return [rev, fwd] if r1_reverse else [fwd, rev]

    records = []
    mol = [0]

    def molecule(pairs, mi=None, **kw):
        m = mol[0]
        mol[0] += 1
        mi = b"%d" % m if mi is None else mi
        p1 = 1000 + 300 * m
        for t in range(pairs):
            records.extend(fr_pair(b"m%dt%d" % (m, t), mi, p1, p1 + 25, **kw))

    for _ in range(3):  # the common shapes, several times over
        molecule(3)
        molecule(1)
        molecule(2, r1_reverse=True)
        molecule(1, r1_reverse=True)
    molecule(2, cigars=([("S", 4), ("M", 56)], [("M", 56), ("S", 4)]))
    molecule(2, mi=b"")                        # named by the counter
    molecule(1, rx=b"")                        # an empty UMI is no UMI
    molecule(3, rx=b"ACGTAC", rx2=b"ACGTTC")   # differing RX: likelihood
    molecule(2, rx=None)                       # no RX at all
    molecule(2, rx=b"acgtna")                  # equal, lower case
    molecule(1, rx=b"acgtna", rx2=None)        # one RX: taken as it is
    molecule(2, rx=b"ACG-TTA", r1_reverse=True)  # a dual UMI's separator
    molecule(2, rx=b"AC\xc3\x28GT")            # not ASCII, equal
    molecule(1, rx=b"A\xffGT", rx2=None)       # not ASCII, one RX
    molecule(6)                                # deep: --max-reads samples it
    molecule(5, r1_reverse=True)
    molecule(2, noise=0.4)                     # the strands disagree
    molecule(2, cb=b"CELL1")
    m = mol[0]
    mol[0] += 4
    p = 1000 + 300 * m
    # 12 bases of overlap; then out of phase (the longest reverse read
    # starts before the longest forward read)
    records.extend(fr_pair(b"m%dt0" % m, b"%d" % m, p, p + 48))
    records.extend(fr_pair(b"m%dt0" % (m + 1), b"%d" % (m + 1), p + 30,
                           p + 50, length2=30)
                   + fr_pair(b"m%dt1" % (m + 1), b"%d" % (m + 1), p, p + 10,
                             length=30, length2=60))
    # a fragment and a secondary beside a good pair; fragments only
    records.append(rec(b"m%df" % (m + 2), 0, p, 60, b"%d" % (m + 2), -1, 0))
    records.append(rec(b"m%ds" % (m + 2), 0x1 | 0x40 | 0x100, p, 60,
                       b"%d" % (m + 2), p + 25, 85))
    records.extend(fr_pair(b"m%dt0" % (m + 2), b"%d" % (m + 2), p, p + 25))
    records.append(rec(b"m%df" % (m + 3), 0, p, 60, b"%d" % (m + 3), -1, 0))
    # a same-strand pair beside a good one and alone, then the common shapes
    records.append(rec(b"m%dx" % (m + 4), 0x1 | 0x40, p, 60, b"%d" % (m + 4),
                       p + 25, 85))
    records.append(rec(b"m%dx" % (m + 4), 0x1 | 0x80, p + 25, 60,
                       b"%d" % (m + 4), p, -85))
    records.extend(fr_pair(b"m%dt0" % (m + 4), b"%d" % (m + 4), p, p + 25))
    records.append(rec(b"m%dx" % (m + 5), 0x1 | 0x40, p, 60, b"%d" % (m + 5),
                       p + 25, 85))
    records.append(rec(b"m%dx" % (m + 5), 0x1 | 0x80, p + 25, 60,
                       b"%d" % (m + 5), p, -85))
    mol[0] += 2
    molecule(4)
    molecule(1, r1_reverse=True)
    with BamWriter(path, header) as w:
        for r in records:
            w.write_record_bytes(r)
    return path


@pytest.fixture(scope="module")
def mixed_bam(tmp_path_factory):
    return _mixed_stream(str(tmp_path_factory.mktemp("fc") / "mixed.bam"))


#: the molecule-level reason of a reject, latest phase first (as the batch
#: engine's counters name it)
_REASONS = ("HighDuplexDisagreement", "ClipOverlapFailed",
            "IndelErrorBetweenStrands", "InsufficientOverlap",
            "InsufficientReads", "MinorityAlignment", "NotPrimaryFrPair",
            "FragmentRead")


def _classic_by_molecule(path, options):
    """The classic engine over the stream, one molecule a call: the records'
    wire bytes, the caller's stats, and how many molecules were rejected
    under each reason."""
    import struct
    from collections import Counter

    from fgumi_tpu.consensus.codec import CodecConsensusCaller
    from fgumi_tpu.core.grouper import iter_mi_groups

    caller = CodecConsensusCaller("fgumi", "A", options)
    wire, rejected = [], Counter()
    with BamReader(path) as r:
        for group in iter_mi_groups(r, b"MI"):
            before = dict(caller.stats.rejection_reasons)
            out = caller.call_groups([group])
            wire.extend(struct.pack("<I", len(x)) + x for x in out)
            if not out:
                after = caller.stats.rejection_reasons
                grew = [x for x in _REASONS
                        if after.get(x, 0) > before.get(x, 0)]
                rejected[grew[0] if grew else "NoUsableReads"] += 1
    return b"".join(wire), caller.stats, rejected


def _batch_engine(path, options, n_records):
    """The batch engine over the stream in batches of ``n_records``: wire
    bytes, the caller's stats, its ``codec.*`` counters."""
    from fgumi_tpu.consensus.codec import CodecConsensusCaller
    from fgumi_tpu.consensus.fast_codec import FastCodecCaller
    from fgumi_tpu.observe.metrics import METRICS

    caller = CodecConsensusCaller("fgumi", "A", options)
    fast = FastCodecCaller(caller, b"MI")
    before = METRICS.snapshot()
    got = []
    for batch in record_batches(path, n_records):
        got.extend(fast.process_batch(batch))
    got.extend(fast.flush())
    wire = wire_of(got)
    after = METRICS.snapshot()
    counters = {k: v - before.get(k, 0) for k, v in after.items()
                if k.startswith("codec.") and v != before.get(k, 0)}
    return wire, caller.stats, counters


def _codec_options(**kw):
    from fgumi_tpu.consensus.codec import CodecOptions

    return CodecOptions(**kw)


@pytest.mark.parametrize("n_records", [10 ** 9, 7, 10])
@pytest.mark.parametrize("case", [
    "defaults", "min_reads_2", "hash_collision", "max_reads",
    "max_reads_hash_collision", "cell_tag", "per_base_tags",
    "rate_and_length_gates", "count_gate", "quality_masks",
    "hash_collision_gates", "max_reads_gates"])
def test_columns_match_classic_on_mixed_stream(mixed_bam, monkeypatch, case,
                                               n_records):
    """Wire bytes and `CodecStats` of the batch engine equal the classic
    engine's on the mixed stream, whichever path a molecule takes (columns,
    one at a time, carried), and the molecules the masks reject are counted
    as the per-molecule path counts them."""
    options = {
        "defaults": {},
        "min_reads_2": dict(min_reads_per_strand=2),
        "hash_collision": {},
        "max_reads": dict(max_reads_per_strand=2),
        "max_reads_hash_collision": dict(max_reads_per_strand=3),
        "cell_tag": dict(cell_tag="CB"),
        "per_base_tags": dict(produce_per_base_tags=True),
        "rate_and_length_gates": dict(max_duplex_disagreement_rate=0.05,
                                      min_duplex_length=20),
        "count_gate": dict(max_duplex_disagreements=2,
                           min_reads_per_strand=2),
        "quality_masks": dict(single_strand_qual=7, outer_bases_qual=5,
                              outer_bases_length=3),
        "hash_collision_gates": dict(min_reads_per_strand=2,
                                     min_duplex_length=20,
                                     max_duplex_disagreement_rate=0.05),
        "max_reads_gates": dict(max_reads_per_strand=1, min_duplex_length=20,
                                max_duplex_disagreements=2),
    }[case]
    want, want_stats, want_rejected = _classic_by_molecule(
        mixed_bam, _codec_options(**options))
    if "hash_collision" in case:
        # every read name of a group lands in one bucket: its byte check
        # fails and the group is paired by the python fallback
        monkeypatch.setattr(
            nb, "hash_ranges",
            lambda buf, off, length: np.full(len(off), 7, dtype=np.uint64))
    got, got_stats, counters = _batch_engine(
        mixed_bam, _codec_options(**options), n_records)
    assert got == want
    assert got_stats == want_stats
    rejected = {k[len("codec.rejected."):]: v for k, v in counters.items()
                if k.startswith("codec.rejected.")}
    assert rejected == dict(want_rejected)
    assert counters["codec.molecules"] \
        == counters["codec.emitted"] + counters.get("codec.rejected", 0)
    assert counters["codec.strands"] == 2 * (
        counters["codec.emitted"]
        + rejected.get("HighDuplexDisagreement", 0))
    assert counters["codec.row_molecules"] >= counters["codec.slow_molecules"]
    if case == "defaults" and n_records == 10 ** 9:
        # one batch: the soft-clipped group and the batch's last molecule
        # (carried to the flush) leave the columns, nothing else
        assert counters["codec.row_molecules"] == 2
    if "hash_collision" in case:
        assert counters["codec.row_molecules"] \
            > counters["codec.slow_molecules"]


def _cell_layout_bam(tmp_path, molecules, seed):
    """An input of the benchmark cell ``codec-c4.linked``'s layout
    (``benchmark/traffic/codec_bam.py``) at ``molecules`` molecules."""
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        import traffic
    finally:
        sys.path.remove(bench)
    params = traffic.load("linked", bench)
    params["num_families"] = molecules
    (path,) = traffic.write_inputs(traffic.generate(params, seed),
                                   str(tmp_path / "linked"))
    return path


@pytest.mark.parametrize("n_records", [2500, 6000])
def test_row_molecules_are_the_carried_ones_on_the_cells_layout(tmp_path,
                                                                n_records):
    """On the cell's traffic every molecule goes through the columns end to
    end but the one a batch boundary cuts: no hash collision, no downsample,
    no CIGAR other than one M run."""
    path = _cell_layout_bam(tmp_path, 3000, 2147483659)
    with BamReader(path) as r:
        mi_of = [rec.get_str(b"MI") for rec in r]
    n = len(mi_of)
    # the molecule that holds a batch's last record is carried (the engine
    # cannot know it is complete), once however many batches it spans
    carried = len({mi_of[min(end, n) - 1]
                   for end in range(n_records, n + n_records, n_records)})
    assert carried >= 2
    _wire, stats, counters = _batch_engine(path, _codec_options(), n_records)
    assert counters["codec.row_molecules"] == carried
    assert counters["codec.slow_molecules"] == carried
    assert counters["codec.molecules"] == len(set(mi_of)) == 3000
    assert counters["codec.molecules"] == counters["codec.emitted"] \
        + counters.get("codec.rejected", 0)
    assert counters["codec.emitted"] == stats.consensus_reads_generated
    assert stats.total_input_reads == n


@pytest.mark.parametrize("family", [
    [b"ACGTACGT"],                               # one RX: as it is
    [b"acgtnacg"],                               # one RX, lower case: kept
    [b"ACGTACGT"] * 4,                           # n identical
    [b"acgtacgt"] * 4,                           # identical, lower case
    [b"ACNTACGN"] * 6,                           # identical, with N
    [b"ACG-TTA", b"ACG-TTA"],                    # a dual UMI's separator
    [b"acg-tnx", b"acg-tnx"],                    # a byte the table leaves
    [b"ACGTACGT", b"ACGTACGT", b"ACGTTCGT", b"ACGTACGT"],   # differing
    [b"ACGTAC", b"ACGTAC", b"ACGTAA", b"ACTTAC", b"ACGTAC", b"ACGTAC"],
    [b"AC\xc3\x28GT"] * 2,                       # not ASCII, identical
    [b"A\xffGT"],                                # not ASCII, one RX
    [b"ACGT", None, b"ACGT", None],              # absent on some records
    [None, None],                                # absent
    [b"", b""],                                  # empty: no RX
], ids=lambda f: "+".join("none" if u is None else u.decode("latin-1")
                          for u in f) or "empty")
def test_rx_on_bytes_equals_consensus_umis_batch(tmp_path, family):
    """The RX consensus the batch engine takes on bytes (one RX as it is,
    identical ones through the ACGTN uppercase table, the rest handed to
    the likelihood as strings) is `consensus_umis_batch` on the strings the
    classic path reads."""
    import struct

    from fgumi_tpu.consensus.simple_umi import consensus_umis_batch
    from fgumi_tpu.io.bam import RawRecord

    rng = np.random.default_rng(len(family))
    header = BamHeader(
        text="@HD\tVN:1.6\tSO:unsorted\tGO:query\n@SQ\tSN:c\tLN:100000\n",
        ref_names=["c"], ref_lengths=[100000])
    truth = rng.choice(np.frombuffer(b"ACGT", np.uint8), size=2000)

    def rec(name, flag, pos, mi, next_pos, tlen, rx):
        b = RecordBuilder().start_mapped(
            name, flag, 0, pos, 60, [("M", 50)], bytes(truth[pos:pos + 50]),
            np.full(50, 30, dtype=np.uint8), next_ref_id=0,
            next_pos=next_pos, tlen=tlen)
        b.tag_str(b"MI", mi)
        if rx is not None:
            b.tag_str(b"RX", rx)
        return b.finish()

    # the family's molecule first (one RX a record, pair by pair), then two
    # plain ones: a batch's last molecule is carried and read as strings
    values = list(family) + [None] * (len(family) % 2)
    records = []
    for mi, rxs in ((b"0", values), (b"1", [b"TTTT"] * 2),
                    (b"2", [b"GGGG"] * 2)):
        for t in range(len(rxs) // 2):
            name = b"m%st%d" % (mi, t)
            records.append(rec(name, 0x1 | 0x40 | 0x20, 100, mi, 120, 70,
                               rxs[2 * t]))
            records.append(rec(name, 0x1 | 0x80 | 0x10, 120, mi, 100, -70,
                               rxs[2 * t + 1]))
    path = str(tmp_path / "rx.bam")
    with BamWriter(path, header) as w:
        for r in records:
            w.write_record_bytes(r)

    wire, _stats, counters = _batch_engine(path, _codec_options(), 10 ** 9)
    assert counters["codec.row_molecules"] == 1  # the last one alone
    n = struct.unpack_from("<I", wire, 0)[0]
    first = RawRecord(wire[4:4 + n])
    assert first.get_str(b"MI") == "0"
    strings = [u.decode(errors="replace") for u in family if u]
    want = consensus_umis_batch([strings])[0] if strings else None
    assert first.get_str(b"RX") == (want or None)


def _clip_overlap_table():
    """Six hand-built molecules of 40-base strands, three of them in a
    fragment shorter than a strand (`ClipOverlapFailed`; no all-M geometry
    gives it): ``(mols, classic, codes_pk, quals_pk)``, the batch engine's
    columns and the classic engine's molecules over the same pack rows."""
    from fgumi_tpu.consensus.fast_codec import _Molecules
    from fgumi_tpu.consensus.vanilla import ConsensusJob, R1

    rng = np.random.default_rng(12)
    # (pairs, fragment length, r1 negative): 40-base strands
    shapes = [(2, 60, False), (2, 30, False), (1, 52, True), (1, 39, True),
              (3, 40, False), (3, 12, True)]
    stride = 64
    n_rows = sum(2 * n for n, _, _ in shapes)
    codes_pk = np.full((n_rows, stride), 4, dtype=np.uint8)
    quals_pk = np.zeros((n_rows, stride), dtype=np.uint8)
    codes_pk[:, :40] = rng.integers(0, 4, size=(n_rows, 40))
    quals_pk[:, :40] = rng.integers(10, 41, size=(n_rows, 40))

    class Batch:
        """One record a molecule: MI its index, no RX."""
        buf = np.frombuffer(b"012345", dtype=np.uint8)

        def tag_locs_str(self, tag):
            n = len(shapes)
            if tag == b"MI":
                return np.arange(n), np.ones(n, np.int32), None
            return np.full(n, -1), np.zeros(n, np.int32), None

    mols = _Molecules(len(shapes), batch=Batch())
    classic = []
    pk0 = 0
    for i, (n, length, r1_neg) in enumerate(shapes):
        mols.set_vec(i, pk0, n, n, 40, 40, r1_neg, not r1_neg, length)
        mols.row_lo[i], mols.row_hi[i] = i, i + 1

        def job(first):
            return ConsensusJob(
                umi=str(i), read_type=R1,
                codes=[codes_pk[r, :40] for r in range(first, first + n)],
                quals=[quals_pk[r, :40] for r in range(first, first + n)],
                consensus_len=40, original_raws=[])

        classic.append({
            "umi": str(i), "records": [], "source_raws": [],
            "job_r1": job(pk0), "job_r2": job(pk0 + n), "n_r1": n, "n_r2": n,
            "r1_is_negative": r1_neg, "r2_is_negative": not r1_neg,
            "consensus_length": length})
        pk0 += 2 * n
    return mols, classic, codes_pk, quals_pk


def test_clip_overlap_failed_by_mask_equals_per_molecule():
    """A fragment shorter than one of its strands (`ClipOverlapFailed`; no
    all-M geometry gives it, so the table is built by hand): the batch
    engine's mask and summed counts against the classic `_finish`, molecule
    by molecule, among molecules that pass."""
    import struct

    from fgumi_tpu.consensus.codec import CodecConsensusCaller, CodecOptions
    from fgumi_tpu.consensus.fast_codec import FastCodecCaller

    mols, classic, codes_pk, quals_pk = _clip_overlap_table()
    want_caller = CodecConsensusCaller("fgumi", "A", CodecOptions())
    ss = want_caller.ss
    results = ss._run_jobs([j for m in classic
                            for j in (m["job_r1"], m["job_r2"])])
    want = []
    for i, m in enumerate(classic):
        rec = want_caller._finish(
            m, ss.result_to_consensus_read(m["job_r1"], results[2 * i]),
            ss.result_to_consensus_read(m["job_r2"], results[2 * i + 1]))
        if rec is not None:
            want.append(struct.pack("<I", len(rec)) + rec)
    assert len(want) == 3

    caller = CodecConsensusCaller("fgumi", "A", CodecOptions())
    got = FastCodecCaller(caller, b"MI")._run(mols, codes_pk, quals_pk)
    assert wire_of(got) == b"".join(want)
    assert caller.stats.rejection_reasons == {"ClipOverlapFailed": 4 + 2 + 6}
    assert caller.stats == want_caller.stats


# --------------------------------------------------------------- placement
#
# A strand's bases, qualities, depths and errors go from the row of the
# result matrix that holds them to the oriented, padded per-molecule arrays
# in one native ragged copy (`nb.codec_place`; ISSUE 41 / ROADMAP S14). The
# numpy placement the engine ran before it lives on as the oracle
# (`codec_placement.numpy_place`).

@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("case", list(PLACE_CASES))
def test_codec_place_equals_the_numpy_placement(case, reverse):
    """Every output byte and every pad byte of the native ragged copy, on
    memory that held something else before the call."""
    from fgumi_tpu.consensus.fast_codec import (_BASE_OF_CODE,
                                                _COMPLEMENT_OF_CODE)

    table = _COMPLEMENT_OF_CODE if reverse else _BASE_OF_CODE
    args = place_case(case) + (table, reverse, I16_MAX, PAD)
    want = numpy_place(*args)
    # dirty the allocator's free lists: `np.empty` must not be relied on
    junk = [np.full(int(args[5][-1]), 0xAB, np.uint8) for _ in range(4)]
    del junk
    got = nb.codec_place(*args)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        assert np.array_equal(g, w)
    if case == "capped_over_i16_max":
        assert got[2].max() == I16_MAX and got[3].max() == I16_MAX
    if case == "molecules_of_length_one":
        assert (np.diff(args[5]) == 1).all()


@pytest.mark.parametrize("fault", ["row", "length", "before", "past",
                                   "source", "absent_source"])
def test_codec_place_refuses_a_strand_outside_its_source_or_molecule(fault):
    sources, sid, rows, ks, base, offs = place_case("both_sources")
    if fault == "row":
        rows[5] = 23 if sid[5] == 0 else 31
    elif fault == "length":
        ks[5], base[5] = 65, offs[5]
    elif fault == "before":
        base[5] -= 1
    elif fault == "past":
        base[5] = offs[6] - ks[5] + 1
    elif fault == "source":
        sid[5] = 2
    else:
        sources[int(sid[5])] = None
    from fgumi_tpu.consensus.fast_codec import _BASE_OF_CODE

    with pytest.raises(ValueError, match="codec_place"):
        nb.codec_place(sources, sid, rows, ks, base, offs, _BASE_OF_CODE,
                       False, I16_MAX, PAD)


@pytest.fixture
def placement_checked(monkeypatch):
    """Every `nb.codec_place` call of the engine is held to the numpy
    placement; yields the calls' ``(sid, ks)``."""
    real = nb.codec_place
    calls = []

    def checked(sources, sid, rows, ks, *rest):
        got = real(sources, sid, rows, ks, *rest)
        want = numpy_place(sources, sid, rows, ks, *rest)
        assert all(np.array_equal(g, w) and g.dtype == w.dtype
                   for g, w in zip(got, want))
        calls.append((np.array(sid), np.array(ks)))
        return got

    monkeypatch.setattr(nb, "codec_place", checked)
    return calls


def test_clip_overlap_failed_molecules_leave_before_the_placement(
        placement_checked):
    """The three failed molecules of the hand table are taken out of the
    columns before the call: each side places the other three, both sides
    from the dense batch (two or three reads a strand) or the single-read
    pass (one), the R2 side left-padded."""
    from fgumi_tpu.consensus.codec import CodecConsensusCaller, CodecOptions
    from fgumi_tpu.consensus.fast_codec import FastCodecCaller
    from fgumi_tpu.observe.metrics import METRICS

    mols, _, codes_pk, quals_pk = _clip_overlap_table()
    before = METRICS.snapshot()
    caller = CodecConsensusCaller("fgumi", "A", CodecOptions())
    wire_of(FastCodecCaller(caller, b"MI")._run(mols, codes_pk, quals_pk))
    assert [list(sid) for sid, _ in placement_checked] \
        == [[0, 1, 0], [0, 1, 0]]
    assert all((ks == 40).all() for _, ks in placement_checked)
    after = METRICS.snapshot()
    grew = lambda k: after.get(k, 0) - before.get(k, 0)
    assert grew("codec.place.strands") == 6
    assert grew("codec.place.cells") == 6 * 40


@pytest.mark.parametrize("n_records", [10 ** 9, 7])
def test_placement_on_the_mixed_stream(mixed_bam, placement_checked,
                                       n_records):
    """The engine's own calls on the mixed stream: strands from the dense
    batch, from the single-read pass and, for the carried and the
    soft-clipped molecule, materialised ones; the counters say what the
    native pass placed."""
    from fgumi_tpu.consensus.fast_codec import _SRC_ARRAYS

    _, _, counters = _batch_engine(mixed_bam, _codec_options(), n_records)
    sids = np.concatenate([sid for sid, _ in placement_checked])
    assert set(np.minimum(sids, _SRC_ARRAYS)) == {0, 1, 2}
    placed = counters["codec.emitted"] \
        + counters.get("codec.rejected.HighDuplexDisagreement", 0)
    assert counters["codec.place.strands"] == 2 * placed == len(sids)
    assert counters["codec.place.cells"] \
        == sum(int(ks.sum()) for _, ks in placement_checked)


@pytest.mark.parametrize("per_base_tags", [False, True])
def test_build_codec_records_reads_int32_rows(per_base_tags):
    """`nb.build_codec_records` on the int32 depth and error rows that
    `nb.codec_place` and `nb.codec_combine` leave, against the classic
    `_build_record` a molecule; values past I16_MAX are capped in the tags,
    an empty MI takes the counter's name, an RX is copied."""
    import struct

    from fgumi_tpu.consensus.codec import (_SS, CodecConsensusCaller,
                                           CodecOptions)
    from fgumi_tpu.io.bam import FLAG_UNMAPPED

    rng = np.random.default_rng(19)
    Ls = np.array([1, 2, 37, 150, 64, 5], dtype=np.int64)
    offs = np.zeros(len(Ls) + 1, dtype=np.int64)
    np.cumsum(Ls, out=offs[1:])
    T = int(offs[-1])
    u8 = lambda lo, hi: rng.integers(lo, hi, T).astype(np.uint8)
    i32 = lambda: rng.integers(0, 2 * I16_MAX, T).astype(np.int32)
    bases = lambda: np.frombuffer(b"ACGTNn", np.uint8)[rng.integers(0, 6, T)]
    cb, b1, b2 = bases(), bases(), bases()
    cq, q1, q2 = u8(0, 94), u8(0, 94), u8(0, 94)
    ce, d1, e1, d2, e2 = i32(), i32(), i32(), i32(), i32()
    d1[offs[3]:offs[4]] = 0   # a molecule with no depth at all: rate 0
    d2[offs[3]:offs[4]] = 0
    umis = ["7", "", "AAC-GGT", "12", "", "x" * 40]
    rx = [b"ACGT-TTGA", b"", b"NNNN", b"", b"A", b"ACGT"]

    caller = CodecConsensusCaller(
        "fgumi", "A", CodecOptions(produce_per_base_tags=per_base_tags))
    want = []
    for j, (umi, r) in enumerate(zip(umis, rx)):
        sl = slice(int(offs[j]), int(offs[j + 1]))
        rec = caller._build_record(
            _SS(cb[sl], cq[sl], d1[sl] + d2[sl], ce[sl], 4),
            _SS(b1[sl], q1[sl], d1[sl], e1[sl], 2),
            _SS(b2[sl], q2[sl], d2[sl], e2[sl], 2), umi or None, [], [],
            rx_umis=[r.decode()] if r else [], number=j + 1)
        want.append(struct.pack("<I", len(rec)) + rec)

    names = [f"fgumi:{umi or j + 1}".encode() for j, umi in enumerate(umis)]
    blob = np.frombuffer(b"".join(names) + b"".join(rx), dtype=np.uint8)
    name_len = np.array([len(n) for n in names], dtype=np.int32)
    name_addr = blob.ctypes.data + np.cumsum(name_len) - name_len
    mi_len = np.array([len(u) if u else -1 for u in umis], dtype=np.int32)
    rx_len = np.array([len(r) for r in rx], dtype=np.int32)
    rx_addr = np.where(rx_len > 0, blob.ctypes.data + int(name_len.sum())
                       + np.cumsum(rx_len) - rx_len, 0)
    og = offs[:-1]
    wire, rec_end = nb.build_codec_records(
        cb.ctypes.data + og, cq.ctypes.data + og, ce.ctypes.data + 4 * og,
        b1.ctypes.data + og, q1.ctypes.data + og, d1.ctypes.data + 4 * og,
        e1.ctypes.data + 4 * og, b2.ctypes.data + og, q2.ctypes.data + og,
        d2.ctypes.data + 4 * og, e2.ctypes.data + 4 * og, Ls, name_addr,
        name_len, name_addr + len(b"fgumi:"), mi_len, rx_addr, rx_len,
        caller.read_group_id.encode(), FLAG_UNMAPPED, per_base_tags)
    assert wire == b"".join(want)
    assert list(rec_end) == list(np.cumsum([len(w) for w in want]))


# ---------------------------------------------------------------- hand-off
#
# `process_batch` returns a pending chunk straight after the dispatch and
# whichever thread `run_stages` resolves on does the fetch, the thresholds
# and stage 2 (ISSUE 34 / ROADMAP S13). The cases below hold the `codec`
# command to one record stream, one `CodecStats` and one set of `codec.*`
# counters whatever the thread count, the route, the batch size and the
# order in which chunks finish.

#: the gates bite, so a chunk emits fewer molecules than it was given
GATES = ["--min-reads", "1", "--max-duplex-disagreement-rate", "0.05",
         "--min-duplex-length", "20"]
HANDOFF_ROUTES = {
    "host": {"FGUMI_TPU_HOST_ENGINE": "1"},
    "device": {"FGUMI_TPU_HOST_ENGINE": "0", "FGUMI_TPU_ROUTE": "device"},
}
#: molecules with an empty MI value (named by the counter): with batches of
#: 4, 7 or 50 records, each in a batch of its own
UNNAMED = (11, 41, 71)
N_GATED = 360  # the gated input's records


def _gated_stream(path, molecules=90):
    """An MI-grouped BAM of plain FR pairs from one reference: one to three
    pairs a molecule, R1 forward or reverse; every 7th molecule's strands
    disagree (rate gate), every 5th overlaps 12 bases (length gate), three
    carry an empty MI, every 4th a cell tag, the last one passes (the flush
    has a chunk)."""
    rng = np.random.default_rng(17)
    header = BamHeader(
        text="@HD\tVN:1.6\tSO:unsorted\tGO:query\n@SQ\tSN:c\tLN:100000\n",
        ref_names=["c"], ref_lengths=[100000])
    bases = np.frombuffer(b"ACGT", np.uint8)
    truth = rng.choice(bases, size=100000)
    n_records = 0

    def rec(name, flag, pos, mi, next_pos, tlen, noise, cb):
        sq = truth[pos:pos + 60].copy()
        wrong = rng.random(60) < noise
        sq[wrong] = rng.choice(bases, size=int(wrong.sum()))
        b = RecordBuilder().start_mapped(
            name, flag, 0, pos, 60, [("M", 60)], bytes(sq),
            rng.integers(20, 41, size=60).astype(np.uint8), next_ref_id=0,
            next_pos=next_pos, tlen=tlen)
        b.tag_str(b"MI", mi)
        b.tag_str(b"RX", b"ACGTAC")
        if cb:
            b.tag_str(b"CB", b"CELL%d" % (pos % 3))
        return b.finish()

    with BamWriter(path, header) as w:
        for m in range(molecules):
            last = m == molecules - 1
            mi = b"" if m in UNNAMED else b"%d" % m
            p1 = 1000 + 300 * m
            p2 = p1 + (48 if m % 5 == 4 and not last else 25)
            noise = 0.4 if m % 7 == 3 and not last else 0.0
            f1, f2 = (0x80, 0x40) if m % 2 else (0x40, 0x80)
            for t in range(1 + m % 3):
                name = b"m%dt%d" % (m, t)
                fwd = rec(name, 0x1 | f1 | 0x20, p1, mi, p2, p2 + 60 - p1,
                          noise, m % 4 == 0)
                rev = rec(name, 0x1 | f2 | 0x10, p2, mi, p1, p1 - p2 - 60,
                          noise, m % 4 == 0)
                for r in ((rev, fwd) if m % 2 else (fwd, rev)):
                    w.write_record_bytes(r)
                    n_records += 1
    return n_records


@pytest.fixture(scope="module")
def gated_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("fc") / "gated.bam")
    assert _gated_stream(path) == N_GATED
    return path


def _run_codec(monkeypatch, tmp_path, bam, name, route, threads, n_records,
               extra=(), expect_rc=0):
    """One in-process `codec` run over ``bam`` cut into batches of
    ``n_records`` -> (records, the caller's `CodecStats`, the run report,
    how many chunks the engine made)."""
    import json

    from fgumi_tpu.consensus import codec as codec_mod
    from fgumi_tpu.consensus.fast_codec import FastCodecCaller
    from fgumi_tpu.io import batch_reader as batch_reader_mod

    for key in ("FGUMI_TPU_HOST_ENGINE", "FGUMI_TPU_ROUTE",
                "FGUMI_TPU_INLINE_FLIGHT", "FGUMI_TPU_HYBRID",
                "FGUMI_TPU_MAX_INFLIGHT"):
        monkeypatch.delenv(key, raising=False)
    for key, value in HANDOFF_ROUTES[route].items():
        monkeypatch.setenv(key, value)
    made, chunks, written = [], [], []  # chunks: how many each call made

    class Spy(codec_mod.CodecConsensusCaller):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    class CutReader:
        """`BamBatchReader`'s part in `cmd_codec`, cut by record count."""

        def __init__(self, path, target_bytes=None):
            self.path = path

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def __iter__(self):
            return record_batches(self.path, n_records)

    real_process, real_flush = FastCodecCaller.process_batch, \
        FastCodecCaller.flush
    real_write = BamWriter.write_serialized

    def counted(real):
        def call(self, *a, **kw):
            out = real(self, *a, **kw)
            chunks.append(len(out))  # the chunks themselves are not kept
            return out
        return call

    def write_serialized(self, blob):
        written.append(type(blob))
        return real_write(self, blob)

    monkeypatch.setattr(codec_mod, "CodecConsensusCaller", Spy)
    if "--classic" not in extra:  # the classic reader wraps the real one
        monkeypatch.setattr(batch_reader_mod, "BamBatchReader", CutReader)
    monkeypatch.setattr(FastCodecCaller, "process_batch",
                        counted(real_process))
    monkeypatch.setattr(FastCodecCaller, "flush", counted(real_flush))
    monkeypatch.setattr(BamWriter, "write_serialized", write_serialized)
    out = str(tmp_path / (name + ".bam"))
    report = out + ".json"
    rc = main(["--run-report", report, "codec", "-i", bam, "-o", out,
               "--threads", str(threads), "--devices", "1", *GATES, *extra])
    assert rc == expect_rc
    with open(report) as f:
        rep = json.load(f)
    if rc:
        return None, None, rep, sum(chunks)
    # every chunk, the flush's too, reached the writer as bytes
    assert set(written) <= {bytes}
    return records_of(out), made[-1].stats, rep, sum(chunks)


def _codec_counters(metrics):
    """The engine's counters; the combine's cells as one sum, since the
    chooser places them by what it measures."""
    c = {k: v for k, v in metrics.items() if k.startswith("codec.")}
    c["codec.combine_cells"] = c.pop("codec.combine_cells_host", 0) \
        + c.pop("codec.combine_cells_device", 0)
    return c


@pytest.fixture(scope="module")
def gated_classic(gated_bam, tmp_path_factory):
    """The per-molecule caller on the same input under the same gates: what
    every run below has to write and to count."""
    mp = pytest.MonkeyPatch()
    try:
        recs, stats, _rep, _n = _run_codec(
            mp, tmp_path_factory.mktemp("fc_ref"), gated_bam, "classic",
            "host", 0, 10 ** 9, extra=("--classic",))
    finally:
        mp.undo()
    return recs, stats


def test_the_gated_input_rejects_at_both_gates(gated_classic):
    recs, stats = gated_classic
    assert stats.total_input_reads == N_GATED
    assert stats.rejection_reasons.keys() \
        >= {"HighDuplexDisagreement", "InsufficientOverlap"}
    assert 40 < len(recs) == stats.consensus_reads_generated < 90
    # the three unnamed molecules are named by their place in the output,
    # which the molecules rejected before them shift
    numbered = [int(r[32:r.index(b"\0", 32)].split(b":")[1]) for r in recs
                if b"MI" not in r]
    assert len(numbered) == len(UNNAMED)
    assert all(n < m + 1 for n, m in zip(numbered, UNNAMED))


@functools.lru_cache(maxsize=None)
def _inline_counters(gated_bam, n_records):
    """The counters of the `--threads 0` run at this batch size."""
    import tempfile

    mp = pytest.MonkeyPatch()
    try:
        with tempfile.TemporaryDirectory(prefix="fc_inline_") as d:
            from pathlib import Path

            _r, _s, rep, _n = _run_codec(mp, Path(d), gated_bam, "inline",
                                         "host", 0, n_records)
    finally:
        mp.undo()
    return _codec_counters(rep["metrics"])


@pytest.mark.parametrize("route,threads,n_records", [
    ("host", threads, n) for n in (4, 7, 50) for threads in (0, 1, 2, 4, 5)]
    # one batch size is enough to compile the device route's shapes for
    + [("device", threads, 50) for threads in (0, 1, 2, 4)])
def test_bytes_and_stats_at_every_thread_count(monkeypatch, tmp_path,
                                               gated_bam, gated_classic,
                                               route, threads, n_records):
    recs, stats, rep, n_chunks = _run_codec(
        monkeypatch, tmp_path, gated_bam, "fast", route, threads, n_records)
    assert recs == gated_classic[0]
    assert stats == gated_classic[1]
    m = rep["metrics"]
    # what was counted does not depend on where a chunk resolved
    counters = _codec_counters(m)
    counters.pop("codec.stage2_off_thread", None)
    assert counters == _inline_counters(gated_bam, n_records)
    # carried molecules cross chunk boundaries, and the flush has a chunk
    assert m["codec.slow_molecules"] >= N_GATED // max(n_records, 6)
    assert m["codec.stage2_batches"] == n_chunks >= 8
    # inline every chunk resolves where it was made; with a writer or
    # workers every chunk but the flush's, which `cmd_codec` resolves
    assert m.get("codec.stage2_off_thread", 0) \
        == (0 if threads <= 1 else n_chunks - 1)
    if threads >= 4:
        assert rep["stages"]["resolve[0]"]["busy_s"] > 0
    if route == "device":
        assert m["device.dispatches"] > 0
        assert m.get("device.resident_bytes", 0) == 0


@pytest.mark.parametrize("cell_tag", [False, True])
@pytest.mark.parametrize("route,threads", [("device", 4), ("host", 5)])
def test_counter_names_when_later_chunks_run_ahead(monkeypatch, tmp_path,
                                                   gated_bam, gated_classic,
                                                   route, threads, cell_tag):
    """Two workers, and every even chunk held back: a chunk that names a
    molecule by the counter gets there before the chunk in front of it has
    published, waits for it, and writes the synchronous run's name. Under
    ``--cell-tag`` every chunk numbers its records, so every chunk waits."""
    import itertools
    import time

    from fgumi_tpu.consensus.fast_codec import (FastCodecCaller,
                                                _EmittedOrder)

    waited = []  # (chunk, chunks published when it asked) of a waiting chunk
    real_publish = _EmittedOrder.publish
    real_process = FastCodecCaller.process_batch
    places = itertools.count()

    def publish(self, serial, emitted, wait=False):
        if wait:
            waited.append((serial, self._next))
        return real_publish(self, serial, emitted, wait)

    class Held:
        def __init__(self, chunk):
            self.chunk, self.i = chunk, next(places)

        def resolve(self):
            if self.i % 2 == 0:
                time.sleep(0.15)
            return self.chunk.resolve()

    def process_batch(self, batch, *a, **kw):
        return [Held(c) for c in real_process(self, batch, *a, **kw)]

    extra = ("--cell-tag", "CB") if cell_tag else ()
    want = gated_classic
    if cell_tag:
        want = _run_codec(monkeypatch, tmp_path, gated_bam, "classic", "host",
                          0, 10 ** 9, extra=extra + ("--classic",))[:2]
        assert any(b"CBZCELL" in r for r in want[0])
    monkeypatch.setattr(_EmittedOrder, "publish", publish)
    monkeypatch.setattr(FastCodecCaller, "process_batch", process_batch)
    recs, stats, _rep, n_chunks = _run_codec(
        monkeypatch, tmp_path, gated_bam, "ahead", route, threads, 50,
        extra=extra)
    assert recs == want[0]
    assert stats == want[1]
    # the three unnamed molecules lie in three chunks, and only they wait
    assert len(waited) == (n_chunks if cell_tag else len(UNNAMED))
    assert any(published < serial for serial, published in waited)


def _gated_chunks(monkeypatch, bam, route, n_records=50, batches=None):
    """The engine's chunks over the input's first ``batches`` batches (all,
    and the flush's, by default), none resolved."""
    import itertools

    from fgumi_tpu.consensus.codec import CodecConsensusCaller, CodecOptions
    from fgumi_tpu.consensus.fast_codec import FastCodecCaller

    for key, value in HANDOFF_ROUTES[route].items():
        monkeypatch.setenv(key, value)
    caller = CodecConsensusCaller(
        "fgumi", "A", CodecOptions(max_duplex_disagreement_rate=0.05,
                                   min_duplex_length=20))
    fast = FastCodecCaller(caller, b"MI")
    chunks = []
    for batch in itertools.islice(record_batches(bam, n_records), batches):
        chunks.extend(fast.process_batch(batch))
    if batches is None:
        chunks.extend(fast.flush())
    return caller, fast, chunks


def _metric_counters():
    from fgumi_tpu.observe.metrics import METRICS

    return _codec_counters(METRICS.snapshot())


@pytest.mark.parametrize("route", list(HANDOFF_ROUTES))
def test_concurrent_stage2_tallies(monkeypatch, gated_bam, gated_classic,
                                   route):
    """Every chunk of the input (48 of batches of 7 records on the host
    engine, 9 of 50 on the device route) resolved at once on four threads,
    under a switch interval short enough to interleave them, against the
    same chunks resolved in turn: bytes, `CodecStats` and counters."""
    import sys
    from concurrent.futures import ThreadPoolExecutor

    n = 50 if route == "device" else 7
    c0 = _metric_counters()
    caller_a, _fast, chunks_a = _gated_chunks(monkeypatch, gated_bam, route,
                                              n)
    turn = [c.resolve() for c in chunks_a]
    c1 = _metric_counters()
    caller_b, _fast, chunks_b = _gated_chunks(monkeypatch, gated_bam, route,
                                              n)
    assert len(chunks_b) == len(chunks_a) >= 8
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(max_workers=4) as pool:
            futures = [pool.submit(c.resolve) for c in chunks_b]
            pooled = [f.result(timeout=120) for f in futures]
    finally:
        sys.setswitchinterval(old)
    c2 = _metric_counters()
    assert pooled == turn
    assert b"".join(pooled) == b"".join(
        len(r).to_bytes(4, "little") + r for r in gated_classic[0])
    assert caller_b.stats == caller_a.stats == gated_classic[1]
    assert caller_b._counter == caller_a._counter == len(gated_classic[0])
    delta = lambda after, before: {k: v - before.get(k, 0)
                                   for k, v in after.items()
                                   if v != before.get(k, 0)}
    in_turn, at_once = delta(c1, c0), delta(c2, c1)
    assert at_once.pop("codec.stage2_off_thread") \
        == at_once["codec.stage2_batches"] == len(chunks_b)
    assert "codec.stage2_off_thread" not in in_turn
    assert at_once == in_turn


def test_a_dropped_chunk_gives_back_its_dispatch(monkeypatch, gated_bam):
    """Chunks nobody resolves (a run that failed with them in flight) hand
    their dispatches back without waiting for them (no dispatch in flight,
    no feeder slot held, no resident bytes) and publish no molecules, so
    the chunk behind them that names a molecule by the counter goes on."""
    import threading

    from fgumi_tpu.ops import kernel as K

    _caller, _fast, chunks = _gated_chunks(monkeypatch, gated_bam, "device",
                                           batches=5)
    assert len(chunks) == 5 and K.DEVICE_STATS.in_flight_count() == 5
    first = chunks.pop(0).resolve()  # one ends as it should
    assert first and K.DEVICE_STATS.in_flight_count() == 4
    del chunks[0], chunks[0]
    assert K.DEVICE_STATS.in_flight_count() == 2
    # molecule 41, unnamed, lies in the fourth batch: its chunk waits for
    # the two dropped ones, which have published nothing emitted
    got = []
    t = threading.Thread(
        target=lambda: got.extend(c.resolve() for c in chunks), daemon=True)
    t.start()
    t.join(timeout=60)
    assert not t.is_alive() and len(got) == 2 and all(got)
    assert K.DEVICE_STATS.in_flight_count() == 0
    K.DEVICE_FEEDER.drain(timeout=30)
    assert K.DEVICE_STATS.resident_bytes == 0
    assert K.DEVICE_FEEDER._inflight == 0


@pytest.mark.parametrize("threads", [1, 2, 4])
@pytest.mark.parametrize("where", ["process", "resolve"])
def test_a_failed_run_drains_and_exits_with_its_code(monkeypatch, tmp_path,
                                                     gated_bam, where,
                                                     threads):
    """The third batch fails, at process time or where its chunk resolves,
    with chunks before and behind it and unnamed molecules among them: the
    run ends with the fault's exit code (no waiter hangs) and every
    dispatch it had started has been completed or handed back."""
    from fgumi_tpu.consensus.fast_codec import FastCodecCaller
    from fgumi_tpu.ops import kernel as K
    from fgumi_tpu.utils.faults import InjectedFault

    calls = {"process": 0, "resolve": 0}
    fault = InjectedFault("the third batch")
    real = {"process": FastCodecCaller._prepare_span,
            "resolve": FastCodecCaller._finish_batch}

    def failing(kind):
        def call(self, *a, **kw):
            calls[kind] += 1
            if kind == where and calls[kind] == 3:
                raise fault
            return real[kind](self, *a, **kw)
        return call

    monkeypatch.setattr(FastCodecCaller, "_prepare_span", failing("process"))
    monkeypatch.setattr(FastCodecCaller, "_finish_batch", failing("resolve"))
    _recs, _stats, rep, n_chunks = _run_codec(
        monkeypatch, tmp_path, gated_bam, "fault", "device", threads, 50,
        expect_rc=3)
    assert n_chunks >= 2 and calls[where] >= 3
    assert rep["metrics"]["device.dispatches"] >= 2
    # pytest keeps the logged error, whose traceback holds the inline run's
    # frames, and they the chunk it dropped: a process would have exited
    fault.__traceback__ = None
    assert K.DEVICE_STATS.in_flight_count() == 0
