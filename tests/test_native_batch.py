"""Differential tests: native batch record layer vs the Python record layer.

Every native batch op must agree with the per-record Python implementation it
replaces (io/bam.py accessors, core/overlap.py clip math,
consensus/overlapping.py correction) on simulated and adversarial records.
"""

import numpy as np
import pytest

from fgumi_tpu.constants import BASE_TO_CODE, N_CODE, reverse_complement_codes
from fgumi_tpu.consensus.overlapping import (
    OverlappingBasesConsensusCaller, apply_overlapping_consensus_python)
from fgumi_tpu.core.overlap import num_bases_extending_past_mate
from fgumi_tpu.io.bam import FLAG_REVERSE, BamReader, RawRecord
from fgumi_tpu.native import batch
from fgumi_tpu.simulate import simulate_grouped_bam, simulate_mapped_bam

from cigar_segments import cigar_buffer, filter_oracle, random_segment

pytestmark = pytest.mark.skipif(not batch.available(),
                                reason="native library unavailable")


@pytest.fixture(scope="module")
def sim_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("nb") / "sim.bam")
    simulate_grouped_bam(path, num_families=60, family_size=4,
                         family_size_distribution="lognormal", read_length=80,
                         error_rate=0.02, seed=7)
    return path


@pytest.fixture(scope="module")
def mapped_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("nb") / "mapped.bam")
    simulate_mapped_bam(path, num_families=40, family_size=3, read_length=70,
                        seed=11)
    return path


def _load_concatenated(path):
    """(buf uint8, rec_off int64[n], [RawRecord]) for a whole BAM."""
    recs = []
    chunks = []
    offsets = []
    off = 0
    with BamReader(path) as reader:
        for rec in reader:
            data = rec.data
            chunks.append(len(data).to_bytes(4, "little") + data)
            offsets.append(off)
            off += 4 + len(data)
            recs.append(rec)
    buf = np.frombuffer(b"".join(chunks), dtype=np.uint8)
    return buf, np.asarray(offsets, dtype=np.int64), recs


def _derived_offsets(f):
    cigar_off = f["data_off"] + 32 + f["l_read_name"]
    seq_off = cigar_off + 4 * f["n_cigar"].astype(np.int64)
    qual_off = seq_off + (f["l_seq"] + 1) // 2
    aux_off = qual_off + f["l_seq"]
    return cigar_off, seq_off, qual_off, aux_off


@pytest.mark.parametrize("fixture", ["sim_bam", "mapped_bam"])
def test_decode_fields_matches_rawrecord(fixture, request):
    buf, rec_off, recs = _load_concatenated(request.getfixturevalue(fixture))
    f = batch.decode_fields(buf, rec_off)
    for i, rec in enumerate(recs):
        assert f["ref_id"][i] == rec.ref_id
        assert f["pos"][i] == rec.pos
        assert f["mapq"][i] == rec.mapq
        assert f["flag"][i] == rec.flag
        assert f["l_seq"][i] == rec.l_seq
        assert f["n_cigar"][i] == rec.n_cigar_op
        assert f["l_read_name"][i] == rec.l_read_name
        assert f["next_ref_id"][i] == rec.next_ref_id
        assert f["next_pos"][i] == rec.next_pos
        assert f["tlen"][i] == rec.tlen
        assert f["data_end"][i] - f["data_off"][i] == len(rec.data)


@pytest.mark.parametrize("fixture", ["sim_bam", "mapped_bam"])
def test_scan_tags_matches_find_tag(fixture, request):
    buf, rec_off, recs = _load_concatenated(request.getfixturevalue(fixture))
    f = batch.decode_fields(buf, rec_off)
    _, _, _, aux_off = _derived_offsets(f)
    tags = [b"MI", b"RX", b"MC", b"ZZ"]
    val_off, val_len, val_type = batch.scan_tags(buf, aux_off, f["data_end"],
                                                 tags)
    for i, rec in enumerate(recs):
        for j, tag in enumerate(tags):
            expected = rec.get_str(tag)
            if expected is None:
                got = rec.find_tag(tag)
                if got is None:
                    assert val_off[i, j] == -1
                continue
            assert val_off[i, j] >= 0
            got = bytes(buf[val_off[i, j]: val_off[i, j] + val_len[i, j]])
            assert got.decode() == expected
            assert chr(val_type[i, j]) == "Z"


def test_group_starts_matches_python_grouping(sim_bam):
    from fgumi_tpu.core.grouper import iter_mi_groups

    buf, rec_off, recs = _load_concatenated(sim_bam)
    f = batch.decode_fields(buf, rec_off)
    _, _, _, aux_off = _derived_offsets(f)
    val_off, val_len, _ = batch.scan_tags(buf, aux_off, f["data_end"], [b"MI"])
    starts = batch.group_starts(buf, val_off[:, 0].copy(),
                                val_len[:, 0].copy())
    py_groups = list(iter_mi_groups(iter(recs)))
    assert len(starts) == len(py_groups)
    sizes = np.diff(np.append(starts, len(recs)))
    assert [len(g) for _, g in py_groups] == sizes.tolist()


def test_group_starts_raises_on_missing():
    buf = np.zeros(4, dtype=np.uint8)
    with pytest.raises(ValueError, match="missing grouping tag"):
        batch.group_starts(buf, np.array([0, -1], dtype=np.int64),
                           np.array([1, 1], dtype=np.int32))


@pytest.mark.parametrize("min_q", [0, 10, 25])
def test_pack_reads_matches_source_read_conversion(sim_bam, min_q):
    """Native pack == the code/qual/final_len logic of _create_source_read
    (mask -> clip -> trailing-N trim) with trim disabled."""
    buf, rec_off, recs = _load_concatenated(sim_bam)
    f = batch.decode_fields(buf, rec_off)
    _, seq_off, qual_off, _ = _derived_offsets(f)
    rng = np.random.default_rng(3)
    clip = rng.integers(0, 12, size=len(recs)).astype(np.int32)
    reverse = ((f["flag"] & FLAG_REVERSE) != 0).astype(np.uint8)
    stride = int(f["l_seq"].max())
    codes, quals, final_len = batch.pack_reads(
        buf, seq_off, qual_off, f["l_seq"], reverse, clip, min_q, stride)

    for i, rec in enumerate(recs):
        exp_codes = BASE_TO_CODE[np.frombuffer(rec.seq_bytes(), np.uint8)]
        exp_quals = rec.quals()
        if rec.flag & FLAG_REVERSE:
            exp_codes = reverse_complement_codes(exp_codes)
            exp_quals = exp_quals[::-1].copy()
        else:
            exp_codes = exp_codes.copy()
        if (exp_quals == 0xFF).all():
            assert final_len[i] == -1
            continue
        mask = exp_quals < min_q
        exp_codes[mask] = N_CODE
        exp_quals[mask] = 2
        fl = max(rec.l_seq - int(clip[i]), 0)
        while fl > 0 and exp_codes[fl - 1] == N_CODE:
            fl -= 1
        assert final_len[i] == fl
        np.testing.assert_array_equal(codes[i, :fl], exp_codes[:fl])
        np.testing.assert_array_equal(quals[i, :fl], exp_quals[:fl])
        # padded tail is N/0
        assert (codes[i, fl:] == N_CODE).all()
        assert (quals[i, fl:] == 0).all()


def test_pack_reads_rejects_all_ff_quals():
    from fgumi_tpu.io.bam import RecordBuilder

    rec = RecordBuilder().start_unmapped(
        b"q1", 4, b"ACGT", np.full(4, 0xFF, np.uint8)).finish()
    raw = len(rec).to_bytes(4, "little") + rec
    buf = np.frombuffer(raw, dtype=np.uint8)
    f = batch.decode_fields(buf, np.array([0], dtype=np.int64))
    _, seq_off, qual_off, _ = _derived_offsets(f)
    _, _, final_len = batch.pack_reads(
        buf, seq_off, qual_off, f["l_seq"], np.zeros(1, np.uint8),
        np.zeros(1, np.int32), 10, 4)
    assert final_len[0] == -1


def _random_fr_pairs(n_pairs, seed):
    """Adversarial overlapping FR pairs: random cigars (S/I/D), dovetails,
    short inserts, MC tags — the cases that produce nonzero clips and real
    overlap corrections."""
    from fgumi_tpu.io.bam import RecordBuilder

    rng = np.random.default_rng(seed)
    recs = []
    for t in range(n_pairs):
        rlen = int(rng.integers(30, 70))
        insert = int(rng.integers(rlen // 2, 2 * rlen))
        p1 = int(rng.integers(1000, 2000))

        def rand_cigar(read_len):
            ops = []
            remaining = read_len
            if rng.random() < 0.4:
                s = int(rng.integers(1, 8))
                ops.append(("S", s))
                remaining -= s
            m1 = remaining
            mid = None
            if rng.random() < 0.4 and remaining > 10:
                mid = ("I", int(rng.integers(1, 4))) if rng.random() < 0.5 \
                    else ("D", int(rng.integers(1, 4)))
                m1 = int(rng.integers(5, remaining - 5))
            tail_s = 0
            if rng.random() < 0.3 and remaining - m1 == 0 and mid is None:
                tail_s = int(rng.integers(1, 6))
                m1 = remaining - tail_s
            ops.append(("M", m1))
            used = m1 + (mid[1] if mid and mid[0] == "I" else 0)
            if mid is not None:
                ops.append(mid)
                rest = remaining - used
                if rest > 0:
                    ops.append(("M", rest))
                elif rest < 0:
                    ops[-2] = ("M", m1 + rest)  # shrink to fit
            if tail_s:
                ops.append(("S", tail_s))
            # normalize: query length must equal read_len
            q = sum(ln for op, ln in ops if op in "MIS")
            if q != read_len:
                ops = [("M", read_len)]
            return ops

        c1 = rand_cigar(rlen)
        c2 = rand_cigar(rlen)
        ref1 = sum(ln for op, ln in c1 if op in "MDN")
        ref2 = sum(ln for op, ln in c2 if op in "MDN")
        p2 = p1 + insert - ref2  # r2 reverse aligned so insert ends at p1+insert
        if p2 < 0:
            p2 = p1
        tlen = (p2 + ref2) - p1

        def cigar_str(c):
            return "".join(f"{ln}{op}" for op, ln in c)

        seq1 = rng.choice(np.frombuffer(b"ACGTN", np.uint8), size=rlen,
                          p=[0.24, 0.24, 0.24, 0.24, 0.04]).tobytes()
        seq2 = rng.choice(np.frombuffer(b"ACGTN", np.uint8), size=rlen,
                          p=[0.24, 0.24, 0.24, 0.24, 0.04]).tobytes()
        q1 = rng.integers(2, 41, size=rlen).astype(np.uint8)
        q2 = rng.integers(2, 41, size=rlen).astype(np.uint8)
        name = f"pair{t}".encode()
        b1 = RecordBuilder().start_mapped(
            name, 0x1 | 0x2 | 0x20 | 0x40, 0, p1, 60, c1, seq1, q1,
            next_ref_id=0, next_pos=p2, tlen=tlen)
        b1.tag_str(b"MC", cigar_str(c2).encode())
        b2 = RecordBuilder().start_mapped(
            name, 0x1 | 0x2 | 0x10 | 0x80, 0, p2, 60, c2, seq2, q2,
            next_ref_id=0, next_pos=p1, tlen=-tlen)
        b2.tag_str(b"MC", cigar_str(c1).encode())
        recs.append(RawRecord(b1.finish()))
        recs.append(RawRecord(b2.finish()))
    return recs


def _concat_records(recs):
    chunks, offsets = [], []
    off = 0
    for rec in recs:
        chunks.append(len(rec.data).to_bytes(4, "little") + rec.data)
        offsets.append(off)
        off += 4 + len(rec.data)
    return (np.frombuffer(b"".join(chunks), dtype=np.uint8),
            np.asarray(offsets, dtype=np.int64))


def test_mate_clips_matches_python_random_pairs():
    recs = _random_fr_pairs(150, seed=5)
    buf, rec_off = _concat_records(recs)
    f = batch.decode_fields(buf, rec_off)
    cigar_off, _, _, aux_off = _derived_offsets(f)
    mc_off, mc_len, _ = batch.scan_tags(buf, aux_off, f["data_end"], [b"MC"])
    clips = batch.mate_clips(buf, cigar_off, f["n_cigar"], f["flag"],
                             f["ref_id"], f["pos"], f["next_ref_id"],
                             f["next_pos"], f["tlen"], mc_off[:, 0].copy(),
                             mc_len[:, 0].copy())
    expected = [num_bases_extending_past_mate(rec) for rec in recs]
    assert clips.tolist() == expected
    assert sum(1 for c in expected if c) > 10  # the fixture exercises clips


@pytest.mark.parametrize("agreement,disagreement", [
    ("consensus", "consensus"), ("max-qual", "mask-both"),
    ("pass-through", "mask-lower-qual")])
def test_overlap_correct_matches_python_random_pairs(agreement, disagreement):
    recs = _random_fr_pairs(120, seed=9)
    buf, rec_off = _concat_records(recs)
    f = batch.decode_fields(buf, rec_off)
    r1_off = f["data_off"][0::2].copy()
    r2_off = f["data_off"][1::2].copy()
    mutable = buf.copy()
    ag = {"consensus": 0, "max-qual": 1, "pass-through": 2}[agreement]
    dg = {"consensus": 0, "mask-both": 1, "mask-lower-qual": 2}[disagreement]
    stats = batch.overlap_correct_pairs(mutable, r1_off, r2_off, ag, dg)

    caller = OverlappingBasesConsensusCaller(agreement, disagreement)
    corrected = apply_overlapping_consensus_python(
        list(recs), [(i, i + 1) for i in range(0, len(recs), 2)], caller)
    for i in range(len(recs)):
        got = bytes(mutable[f["data_off"][i]:f["data_end"][i]])
        assert got == corrected[i].data, f"record {i} mismatch"
    assert stats[0] == caller.stats.overlapping_bases
    assert stats[1] == caller.stats.bases_agreeing
    assert stats[2] == caller.stats.bases_disagreeing
    assert stats[3] == caller.stats.bases_corrected
    assert stats[0] > 100  # the fixture exercises real overlaps


def test_mate_clips_accepts_nonnative_dtypes():
    """Regression: dtype-converted temporaries must outlive the foreign call
    (int64 inputs once produced silently-wrong all-zero clips)."""
    recs = _random_fr_pairs(60, seed=5)
    buf, rec_off = _concat_records(recs)
    f = batch.decode_fields(buf, rec_off)
    cigar_off, _, _, aux_off = _derived_offsets(f)
    mc_off, mc_len, _ = batch.scan_tags(buf, aux_off, f["data_end"], [b"MC"])
    clips = batch.mate_clips(
        buf, cigar_off, f["n_cigar"].astype(np.int64),
        f["flag"].astype(np.int64), f["ref_id"].astype(np.int64),
        f["pos"].astype(np.int64), f["next_ref_id"].astype(np.int64),
        f["next_pos"].astype(np.int64), f["tlen"].astype(np.int64),
        mc_off[:, 0].copy(), mc_len[:, 0].astype(np.int64))
    expected = [num_bases_extending_past_mate(rec) for rec in recs]
    assert clips.tolist() == expected
    assert any(expected)


def test_mate_clips_matches_python(mapped_bam):
    buf, rec_off, recs = _load_concatenated(mapped_bam)
    f = batch.decode_fields(buf, rec_off)
    cigar_off, _, _, aux_off = _derived_offsets(f)
    mc_off, mc_len, _ = batch.scan_tags(buf, aux_off, f["data_end"], [b"MC"])
    clips = batch.mate_clips(buf, cigar_off, f["n_cigar"], f["flag"],
                             f["ref_id"], f["pos"], f["next_ref_id"],
                             f["next_pos"], f["tlen"], mc_off[:, 0].copy(),
                             mc_len[:, 0].copy())
    expected = [num_bases_extending_past_mate(rec) for rec in recs]
    assert clips.tolist() == expected


def test_mate_clips_adversarial_mc_strings():
    """Malformed MC strings fail closed to clip 0, like the Python parser."""
    from fgumi_tpu.io.bam import RecordBuilder

    cases = [b"", b"abc", b"100", b"M", b"0M", b"10M5S3M",  # S not at end
             b"10S", b"5H10M", b"10M2I5D", b"1000000000M", b"10m"]
    chunks, offsets = [], []
    off = 0
    for i, mc in enumerate(cases):
        b = RecordBuilder().start_mapped(
            b"r%d" % i, 0x1 | 0x20, 0, 100, 60, [("M", 20)], b"A" * 20,
            np.full(20, 30, np.uint8), next_ref_id=0, next_pos=90, tlen=-30)
        b.tag_str(b"MC", mc)
        rec = b.finish()
        chunks.append(len(rec).to_bytes(4, "little") + rec)
        offsets.append(off)
        off += 4 + len(rec)
    buf = np.frombuffer(b"".join(chunks), dtype=np.uint8)
    rec_off = np.asarray(offsets, dtype=np.int64)
    f = batch.decode_fields(buf, rec_off)
    cigar_off, _, _, aux_off = _derived_offsets(f)
    mc_off, mc_len, _ = batch.scan_tags(buf, aux_off, f["data_end"], [b"MC"])
    clips = batch.mate_clips(buf, cigar_off, f["n_cigar"], f["flag"],
                             f["ref_id"], f["pos"], f["next_ref_id"],
                             f["next_pos"], f["tlen"], mc_off[:, 0].copy(),
                             mc_len[:, 0].copy())
    expected = [num_bases_extending_past_mate(
        RawRecord(bytes(buf[f["data_off"][i]:f["data_end"][i]])))
        for i in range(len(cases))]
    assert clips.tolist() == expected


@pytest.mark.parametrize("agreement,disagreement", [
    ("consensus", "consensus"), ("max-qual", "mask-both"),
    ("pass-through", "mask-lower-qual")])
def test_overlap_correct_matches_python(mapped_bam, agreement, disagreement):
    buf, rec_off, recs = _load_concatenated(mapped_bam)
    f = batch.decode_fields(buf, rec_off)

    # pair primary R1/R2 by name, like apply_overlapping_consensus
    pairs = {}
    for i, rec in enumerate(recs):
        if rec.flag & 0x900:
            continue
        slot = pairs.setdefault(rec.name, [None, None])
        if rec.flag & 0x40:
            slot[0] = i
        elif rec.flag & 0x80:
            slot[1] = i
    idx_pairs = [(a, b) for a, b in pairs.values()
                 if a is not None and b is not None]
    r1_off = f["data_off"][[a for a, _ in idx_pairs]].copy()
    r2_off = f["data_off"][[b for _, b in idx_pairs]].copy()

    mutable = buf.copy()
    codes = {"consensus": 0, "max-qual": 1, "pass-through": 2,
             "mask-both": 1, "mask-lower-qual": 2}
    stats = batch.overlap_correct_pairs(
        mutable, r1_off, r2_off, codes[agreement],
        {"consensus": 0, "mask-both": 1, "mask-lower-qual": 2}[disagreement])

    caller = OverlappingBasesConsensusCaller(agreement, disagreement)
    corrected = apply_overlapping_consensus_python(list(recs), idx_pairs,
                                                  caller)

    for i, rec in enumerate(corrected):
        got = bytes(mutable[f["data_off"][i]:f["data_end"][i]])
        assert got == rec.data, f"record {i} mismatch"
    assert stats[0] == caller.stats.overlapping_bases
    assert stats[1] == caller.stats.bases_agreeing
    assert stats[2] == caller.stats.bases_disagreeing
    assert stats[3] == caller.stats.bases_corrected


def test_bktree_pairs_native():
    """fgumi_umi_bktree_pairs matches brute force (also exercises the tree
    under the ASAN/UBSAN lane, tests/test_native_asan.py)."""
    nb = pytest.importorskip("fgumi_tpu.native.batch")
    if not nb.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(3)
    mat = rng.integers(0, 4, size=(120, 9)).astype(np.uint8)
    for d in (1, 3):
        i, j = nb.umi_neighbor_pairs(mat, None, d, index="bktree")
        truth = {(a, b) for a in range(120) for b in range(a + 1, 120)
                 if int((mat[a] != mat[b]).sum()) <= d}
        assert set(zip(i.tolist(), j.tolist())) == truth


def _codec_combine_inputs(case, rng):
    """(b1, b2, q1, q2, d1, d2, e1, e2) batches for the codec combine
    parity test: ``random`` draws 20 batches of edge values (lowercase
    pads, N masks, Q0/Q2, depths past I16_MAX); ``adversarial`` is one
    1000-position batch over every base in both cases with uniform
    qualities (ties), single-strand positions and a fifth of each side
    floored at Q2 — the input the retired device combine was held to."""
    if case == "random":
        letters = np.array([ord(c) for c in "ACGTNn"], dtype=np.uint8)
        for _ in range(20):
            n = int(rng.integers(1, 2000))
            yield (rng.choice(letters, size=n), rng.choice(letters, size=n),
                   rng.choice([0, 2, 3, 20, 93], size=n).astype(np.uint8),
                   rng.choice([0, 2, 3, 20, 93], size=n).astype(np.uint8),
                   rng.integers(0, 70000, size=n).astype(np.int32),
                   rng.integers(0, 70000, size=n).astype(np.int32),
                   rng.integers(0, 40000, size=n).astype(np.int32),
                   rng.integers(0, 40000, size=n).astype(np.int32))
        return
    T = 1000
    bases = np.frombuffer(b"ACGTNacgtn", np.uint8)
    ba = rng.choice(bases, size=T)
    bb = rng.choice(bases, size=T)
    qa = rng.integers(0, 94, size=T).astype(np.uint8)
    qb = rng.integers(0, 94, size=T).astype(np.uint8)
    qa[rng.random(T) < 0.2] = 2  # MIN_PHRED floors
    qb[rng.random(T) < 0.2] = 2
    yield (ba, bb, qa, qb,
           rng.integers(0, 40000, size=T).astype(np.int32),
           rng.integers(0, 40000, size=T).astype(np.int32),
           rng.integers(0, 33000, size=T).astype(np.int32),
           rng.integers(0, 33000, size=T).astype(np.int32))


@pytest.mark.parametrize("case", ["random", "adversarial"])
def test_codec_combine_matches_numpy_oracle(case):
    """fgumi_codec_combine must be bit-exact with consensus/codec.py
    combine_arrays (the classic-path oracle) — the one strand combine
    codec runs."""
    from fgumi_tpu.consensus.codec import combine_arrays
    from fgumi_tpu.constants import (MIN_PHRED, NO_CALL_BASE,
                                     NO_CALL_BASE_LOWER)
    from fgumi_tpu.native import batch as nb

    rng = np.random.default_rng(5)
    for trial, args in enumerate(_codec_combine_inputs(case, rng)):
        ref = combine_arrays(*args)
        got = nb.codec_combine(*args, MIN_PHRED, NO_CALL_BASE,
                               NO_CALL_BASE_LOWER, 32767)
        for k, (r, g) in enumerate(zip(ref, got)):
            np.testing.assert_array_equal(
                np.asarray(r, np.int64), np.asarray(g, np.int64),
                err_msg=f"trial {trial} output {k}")


def _depth_errors_numpy(codes, rows, winner, lo, hi):
    """What fgumi_segment_depth_errors_ranges counts, in numpy."""
    J, L = winner.shape
    depth = np.zeros((J, L), dtype=np.int32)
    errors = np.zeros((J, L), dtype=np.int32)
    for j in range(J):
        seg = codes[rows[lo[j]:hi[j]], :L]
        valid = seg != 4
        depth[j] = valid.sum(axis=0)
        errors[j] = (valid & (seg != winner[j])).sum(axis=0)
    return depth, errors


@pytest.mark.parametrize("case", ["random", "empty_ranges", "repeated_rows",
                                  "no_outputs"])
def test_segment_depth_errors_ranges_reads_rows_in_place(case):
    """The duplex error recount over a row list into the batch's packed
    codes (stride wider than L) equals the dense form on a copy of those
    rows, and numpy: random ranges, empty ones, a row listed twice, J = 0."""
    from fgumi_tpu.native import batch as nb

    rng = np.random.default_rng(17)
    R, stride, L, n = 60, 40, 24, 45
    codes = rng.integers(0, 5, size=(R, stride)).astype(np.uint8)
    rows = rng.permutation(R)[:n].astype(np.int64)
    if case == "repeated_rows":
        rows[1::2] = rows[::2][:len(rows[1::2])]
    J = 0 if case == "no_outputs" else 30
    lo = rng.integers(0, n + 1, size=J).astype(np.int64)
    hi = np.minimum(lo + rng.integers(0, 7, size=J), n).astype(np.int64)
    if case == "empty_ranges":
        hi[::2] = lo[::2]
    winner = rng.integers(0, 5, size=(J, L)).astype(np.uint8)
    depth, errors = nb.segment_depth_errors_ranges(codes, rows, winner, lo, hi)
    want_d, want_e = _depth_errors_numpy(codes, rows, winner, lo, hi)
    np.testing.assert_array_equal(depth, want_d)
    np.testing.assert_array_equal(errors, want_e)
    dense = np.ascontiguousarray(codes[rows, :L])
    dense_d, dense_e = nb.segment_depth_errors_ranges(
        dense, np.arange(n, dtype=np.int64), winner, lo, hi)
    np.testing.assert_array_equal(depth, dense_d)
    np.testing.assert_array_equal(errors, dense_e)
    assert depth.shape == errors.shape == (J, L)


@pytest.mark.parametrize("bad", ["row_past_end", "negative_row",
                                 "range_past_rows", "rows_int32"])
def test_segment_depth_errors_ranges_refuses_what_it_cannot_read(bad):
    from fgumi_tpu.native import batch as nb

    codes = np.zeros((8, 16), dtype=np.uint8)
    rows = np.arange(8, dtype=np.int64)
    winner = np.zeros((2, 12), dtype=np.uint8)
    lo, hi = np.array([0, 4]), np.array([4, 8])
    if bad == "row_past_end":
        rows[3] = 8
    elif bad == "negative_row":
        rows[0] = -1
    elif bad == "range_past_rows":
        hi[1] = 9
    else:
        rows = rows.astype(np.int32)
    with pytest.raises(ValueError, match="segment_depth_errors_ranges"):
        nb.segment_depth_errors_ranges(codes, rows, winner, lo, hi)


# ------------------------------------------ the most-common-alignment filter

def _filter_segments(seed):
    """150 random segments of seed ``seed`` laid out for the filter ->
    (segments, buf, cigar_off, n_cigar, reverse, lens, starts)."""
    rng = np.random.default_rng(1000 + seed)
    segs = [random_segment(rng) for _ in range(150)]
    cigars = [c for seg in segs for c in seg[0]]
    reverse = np.array([r for seg in segs for r in seg[1]], dtype=np.uint8)
    lens = np.array([ln for seg in segs for ln in seg[2]], dtype=np.int32)
    starts = np.concatenate(([0], np.cumsum([len(seg[0]) for seg in segs])))
    buf, cigar_off, n_cigar = cigar_buffer(cigars, rng)
    return segs, buf, cigar_off, n_cigar, reverse, lens, starts


@pytest.mark.parametrize("seed", range(12))
def test_alignment_filter_matches_the_python_filter(seed):
    segs, buf, cigar_off, n_cigar, reverse, lens, starts = \
        _filter_segments(seed)
    keep = batch.alignment_filter(buf, cigar_off, n_cigar, reverse, lens,
                                  starts)
    rejected_some = 0
    for s, (cg, rv, ln) in enumerate(segs):
        want = filter_oracle(cg, rv, ln)
        got = keep[starts[s]:starts[s + 1]]
        assert np.array_equal(got, want), (seed, s, cg, rv, ln)
        rejected_some += not want.all()
    assert 20 < rejected_some < 150  # both answers come up
    # one segment alone is the same answer (the engine's --rejects path)
    for s in range(0, len(segs), 10):
        lo, hi = starts[s], starts[s + 1]
        one = batch.alignment_filter(
            buf, cigar_off[lo:hi], n_cigar[lo:hi], reverse[lo:hi],
            lens[lo:hi], np.array([0, hi - lo]))
        assert np.array_equal(one, keep[lo:hi])


#: the keep masks `batch.alignment_filter` gives on `_filter_segments`,
#: a seed, as the first 16 hex digits of their sha256: simplex's and
#: duplex's filter answers, which may not move when the group selection
#: they share with codec's native prepare (`batch.codec_cigar_prepare`)
#: does
_PINNED_MASKS = {
    0: "3af1fa89cef2e21a", 1: "46a519cc5f882280", 2: "8566abe1f60a4a36",
    3: "b6ad16e0a15f4059", 4: "cfca0f687ec2d231", 5: "6458cdaf05b01025",
    6: "c5e321dfe240df1c", 7: "9718c176e15623c0", 8: "7fe1444220ecdea8",
    9: "55c98ce6b5e9a077", 10: "0840d95486a21b1b", 11: "616c8d7e40a95e7e"}


@pytest.mark.parametrize("seed", sorted(_PINNED_MASKS))
def test_alignment_filter_masks_are_pinned(seed):
    import hashlib

    _segs, buf, cigar_off, n_cigar, reverse, lens, starts = \
        _filter_segments(seed)
    keep = batch.alignment_filter(buf, cigar_off, n_cigar, reverse, lens,
                                  starts)
    assert hashlib.sha256(keep.tobytes()).hexdigest()[:16] \
        == _PINNED_MASKS[seed]


def test_alignment_filter_small_and_empty_segments():
    rng = np.random.default_rng(5)
    cigars = [[("M", 50)], [("M", 20), ("I", 2), ("M", 28)], [],
              [("M", 50)], []]
    buf, cigar_off, n_cigar = cigar_buffer(cigars, rng)
    rev = np.zeros(5, dtype=np.uint8)
    lens = np.array([50, 50, 10, 50, 10], dtype=np.int32)
    # segments: one row; none; an empty CIGAR beside a real one (the empty
    # CIGAR prefixes everything); an empty CIGAR alone
    starts = np.array([0, 1, 1, 4, 5])
    keep = batch.alignment_filter(buf, cigar_off, n_cigar, rev, lens, starts)
    assert keep.tolist() == [1] + filter_oracle(
        cigars[1:4], rev[1:4], lens[1:4]).tolist() + [1]
    assert batch.alignment_filter(
        buf, cigar_off[:0], n_cigar[:0], rev[:0], lens[:0],
        np.array([0])).tolist() == []
    # nonnative dtypes are converted
    keep2 = batch.alignment_filter(
        buf, cigar_off.astype(np.int32), n_cigar.astype(np.int64),
        rev.astype(bool), lens.astype(np.int64), starts.astype(np.int32))
    assert np.array_equal(keep, keep2)
    for bad in ([0, 1, 1, 4], [1, 4, 5], [0, 4, 1, 5]):
        with pytest.raises(ValueError):
            batch.alignment_filter(buf, cigar_off, n_cigar, rev, lens,
                                   np.array(bad))
    with pytest.raises(ValueError):
        batch.alignment_filter(buf, cigar_off, n_cigar, rev, lens[:4], starts)
