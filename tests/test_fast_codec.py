"""Parity: FastCodecCaller (vectorized prepare) vs classic CODEC engine."""

import numpy as np
import pytest

from fgumi_tpu.cli import main
from fgumi_tpu.io.bam import BamHeader, BamReader, BamWriter, RecordBuilder
from fgumi_tpu.native import batch as nb
from fgumi_tpu.simulate import simulate_codec_bam
from record_batches import record_batches

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library unavailable")


def records_of(path):
    with BamReader(path) as r:
        return [rec.data for rec in r]


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
    assert b"".join(got) == expected_wire
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
    Drives _run directly with a mixed vec + classic molecule list and checks
    it against the same molecules run classic-only."""
    from fgumi_tpu.consensus.codec import CodecConsensusCaller, CodecOptions
    from fgumi_tpu.consensus.fast_codec import FastCodecCaller
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
    vec_mol = {
        "umi": "7", "records": None, "source_raws": None, "rx_umis": [],
        "pk0": 0, "n_r1": 2, "n_r2": 2,
        "r1_flens": np.array([40, 40], dtype=np.int64),
        "r2_flens": np.array([40, 40], dtype=np.int64),
        "r1_is_negative": False, "r2_is_negative": True,
        "consensus_length": 40,
    }
    lc, lq = strand_rows(4, long_len, long_len)

    def long_mol():
        def job(rows):
            return ConsensusJob(
                umi="9", read_type=R1,
                codes=[lc[r, :long_len] for r in rows],
                quals=[lq[r, :long_len] for r in rows],
                consensus_len=long_len, original_raws=[])

        return {
            "umi": "9", "records": [], "source_raws": [], "rx_umis": [],
            "job_r1": job([0, 1]), "job_r2": job([2, 3]),
            "n_r1": 2, "n_r2": 2,
            "r1_is_negative": False, "r2_is_negative": True,
            "consensus_length": long_len,
        }

    caller = CodecConsensusCaller("fgumi", "A", CodecOptions())
    fast = FastCodecCaller(caller, b"MI")
    mixed = b"".join(fast._run([long_mol(), vec_mol], codes_pk, quals_pk))

    # reference: the same two molecules, both via the classic-job path
    def vec_as_classic():
        def job(base):
            return ConsensusJob(
                umi="7", read_type=R1,
                codes=[codes_pk[base + k, :40] for k in range(2)],
                quals=[quals_pk[base + k, :40] for k in range(2)],
                consensus_len=40, original_raws=[])

        m = dict(vec_mol)
        for k in ("pk0", "r1_flens", "r2_flens"):
            del m[k]
        m["job_r1"], m["job_r2"] = job(0), job(2)
        return m

    caller2 = CodecConsensusCaller("fgumi", "A", CodecOptions())
    fast2 = FastCodecCaller(caller2, b"MI")
    ref = b"".join(fast2._run([long_mol(), vec_as_classic()], None, None))
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
