"""Parity: FastDuplexCaller (vectorized batch path) vs DuplexConsensusCaller.

Byte-identical consensus records, identical statistics and rejection counts
across batch-boundary-spanning molecules, overlap correction, single-strand
molecules, and min-reads gating.
"""

import numpy as np
import pytest

from fgumi_tpu.consensus.duplex import (DuplexConsensusCaller,
                                        iter_duplex_groups, parse_min_reads)
from fgumi_tpu.consensus.fast import resolve_chunk
from fgumi_tpu.consensus.fast_duplex import (AB_R1, AB_R2, BA_R1, BA_R2,
                                             FastDuplexCaller,
                                             output_read_columns)
from fgumi_tpu.consensus.vanilla import R1, R2, _TYPE_FLAGS
from fgumi_tpu.consensus.overlapping import (OverlappingBasesConsensusCaller,
                                             apply_overlapping_consensus)
from fgumi_tpu.core.grouper import consensus_pregroup_keep
from fgumi_tpu.io.bam import BamHeader, BamReader, BamWriter, RecordBuilder
from fgumi_tpu.io.batch_reader import BamBatchReader
from fgumi_tpu.native import batch as nb
from fgumi_tpu.simulate import simulate_duplex_bam
from record_batches import record_batches

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library unavailable")


def make_caller(min_reads=(1,), **kw):
    return DuplexConsensusCaller("fgumi", "A", min_reads=min_reads, **kw)


def run_slow(path, min_reads=(1,), overlap=False, **kw):
    caller = make_caller(min_reads, **kw)
    oc = OverlappingBasesConsensusCaller("consensus", "consensus") \
        if overlap else None
    out = []
    with BamReader(path) as reader:
        pregroup = lambda r: consensus_pregroup_keep(r.flag, False)
        for base_mi, a, b in iter_duplex_groups(reader,
                                                record_filter=pregroup):
            if oc is not None and a and b:
                a = apply_overlapping_consensus(a, oc)
                b = apply_overlapping_consensus(b, oc)
            out.extend(caller.call_groups([(base_mi, a, b)]))
    return out, caller, oc


def batches_of(path, target_bytes, n_records):
    """The reader's batches, or batches of ``n_records`` records."""
    if n_records:
        yield from record_batches(path, n_records)
        return
    with BamBatchReader(path, target_bytes=target_bytes) as reader:
        yield from reader


def run_fast(path, min_reads=(1,), overlap=False, target_bytes=4096,
             n_records=None, **kw):
    caller = make_caller(min_reads, **kw)
    oc = OverlappingBasesConsensusCaller("consensus", "consensus") \
        if overlap else None
    fast = FastDuplexCaller(caller, b"MI", overlap_caller=oc)
    chunks = []
    for batch in batches_of(path, target_bytes, n_records):
        chunks.extend(fast.process_batch(batch))
    chunks.extend(fast.flush())
    recs = []
    for blob in map(resolve_chunk, chunks):
        off = 0
        while off < len(blob):
            n = int.from_bytes(blob[off:off + 4], "little")
            recs.append(blob[off + 4:off + 4 + n])
            off += 4 + n
        assert off == len(blob)
    return recs, caller, oc


def assert_parity(path, min_reads=(1,), overlap=False, target_bytes=4096,
                  n_records=None, **kw):
    slow_out, slow_caller, slow_oc = run_slow(path, min_reads, overlap, **kw)
    fast_out, fast_caller, fast_oc = run_fast(path, min_reads, overlap,
                                              target_bytes, n_records, **kw)
    assert len(fast_out) == len(slow_out)
    for i, (f, s) in enumerate(zip(fast_out, slow_out)):
        assert f == s, f"consensus record {i} differs"
    sm, fm = slow_caller.merged_stats(), fast_caller.merged_stats()
    assert fm.input_reads == sm.input_reads
    assert fm.consensus_reads == sm.consensus_reads
    assert fm.rejected == sm.rejected
    if overlap:
        assert fast_oc.stats.overlapping_bases == slow_oc.stats.overlapping_bases
        assert fast_oc.stats.bases_corrected == slow_oc.stats.bases_corrected
    return slow_out


@pytest.fixture(scope="module")
def duplex_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("fd") / "duplex.bam")
    simulate_duplex_bam(path, num_molecules=150, reads_per_strand=3, seed=11)
    return path


@pytest.mark.parametrize("min_reads", [(1,), (2,), (3, 2, 1), (4, 2, 2)])
def test_parity_simulated(duplex_bam, min_reads):
    out = assert_parity(duplex_bam, min_reads)
    if min_reads == (1,):
        assert len(out) == 300


def test_parity_with_overlap_correction(duplex_bam):
    assert_parity(duplex_bam, overlap=True)


def test_parity_large_batches(duplex_bam):
    assert_parity(duplex_bam, target_bytes=64 << 20)


@pytest.mark.parametrize("n_records", [4, 7, 50])
def test_parity_tiny_batches(duplex_bam, n_records):
    """Molecules really cross batch boundaries (the batch reader itself
    cuts no finer than one decoded chunk, which is this whole file). A
    molecule is 12 records: batches of 4 and 7 end inside every one, so
    each is carried and called by the per-molecule caller; batches of 50
    carry one molecule in four or five, and its bytes are interleaved
    between the records the columns built."""
    from fgumi_tpu.observe.metrics import METRICS

    before = METRICS.snapshot()
    assert_parity(duplex_bam, n_records=n_records)
    after = METRICS.snapshot()
    m = {k: after.get(k, 0) - before.get(k, 0)
         for k in ("duplex.molecules", "duplex.slow_molecules",
                   "duplex.full")}
    # the molecule that holds a batch's last record is carried, once
    # however many batches it spans
    carried = len({(min(end, 1800) - 1) // 12
                   for end in range(n_records, 1800 + n_records, n_records)})
    assert m["duplex.molecules"] == 150
    assert m["duplex.slow_molecules"] == carried
    assert m["duplex.full"] == 150 - carried


def test_parity_max_reads_per_strand(duplex_bam):
    """Per-strand downsampling routes molecules through the slow fallback."""
    assert_parity(duplex_bam, max_reads_per_strand=2)


@pytest.fixture(scope="module")
def adversarial_bam(tmp_path_factory):
    """Molecules exercising: single-strand (A-only / B-only), fragments,
    missing read types, strand-collisions, zero-quality reads, lowercase
    and divergent RX, FIRST|LAST flags."""
    path = str(tmp_path_factory.mktemp("fd") / "adv.bam")
    rng = np.random.default_rng(29)
    header = BamHeader(
        text="@HD\tVN:1.6\tSO:unsorted\tGO:query\n@SQ\tSN:chr1\tLN:100000\n",
        ref_names=["chr1"], ref_lengths=[100000])

    def seq(n):
        return rng.choice(np.frombuffer(b"ACGTN", np.uint8), size=n,
                          p=[0.24, 0.24, 0.24, 0.24, 0.04]).tobytes()

    def quals(n, lo=10, hi=41):
        return rng.integers(lo, hi, size=n).astype(np.uint8)

    records = []

    def pair(name, mi, pos, rx=b"AAT-CCG", rev_r1=False, frag=False,
             qual_lo=10, qual_hi=41):
        out = []
        if frag:
            b1 = RecordBuilder().start_mapped(name, 0x10 if rev_r1 else 0, 0,
                                              pos, 60, [("M", 60)], seq(60),
                                              quals(60, qual_lo, qual_hi))
            b1.tag_str(b"MI", mi)
            b1.tag_str(b"RX", rx)
            out.append(b1.finish())
            return out
        f1 = 0x1 | 0x40 | (0x10 if rev_r1 else 0x20)
        f2 = 0x1 | 0x80 | (0x20 if rev_r1 else 0x10)
        for flags in (f1, f2):
            b1 = RecordBuilder().start_mapped(name, flags, 0, pos, 60,
                                              [("M", 60)], seq(60),
                                              quals(60, qual_lo, qual_hi))
            b1.tag_str(b"MI", mi)
            b1.tag_str(b"RX", rx)
            out.append(b1.finish())
        return out

    # molecule 0: normal 3+3 duplex
    for t in range(3):
        records += pair(b"m0a%d" % t, b"0/A", 1000)
    for t in range(3):
        records += pair(b"m0b%d" % t, b"0/B", 1000, rx=b"CCG-AAT",
                        rev_r1=True)
    # molecule 1: A-only
    for t in range(2):
        records += pair(b"m1a%d" % t, b"1/A", 2000)
    # molecule 2: B-only
    for t in range(2):
        records += pair(b"m2b%d" % t, b"2/B", 3000, rev_r1=True)
    # molecule 3: fragments only (all rejected as FragmentRead)
    records += pair(b"m3f0", b"3/A", 4000, frag=True)
    records += pair(b"m3f1", b"3/B", 4000, frag=True)
    # molecule 4: strand collision (mixed orientation within X set)
    records += pair(b"m4a0", b"4/A", 5000)
    records += pair(b"m4a1", b"4/A", 5000, rev_r1=True)
    records += pair(b"m4b0", b"4/B", 5000, rev_r1=True)
    # molecule 5: divergent RX within strand
    records += pair(b"m5a0", b"5/A", 6000, rx=b"AAT-CCG")
    records += pair(b"m5a1", b"5/A", 6000, rx=b"AAT-CCC")
    records += pair(b"m5b0", b"5/B", 6000, rx=b"CCG-AAT", rev_r1=True)
    # molecule 6: lowercase RX (unanimous)
    records += pair(b"m6a0", b"6/A", 7000, rx=b"aat-ccg")
    records += pair(b"m6a1", b"6/A", 7000, rx=b"aat-ccg")
    records += pair(b"m6b0", b"6/B", 7000, rx=b"ccg-aat", rev_r1=True)
    # molecule 7: FIRST|LAST flagged read (fallback)
    b1 = RecordBuilder().start_mapped(b"m7x", 0x1 | 0x40 | 0x80, 0, 8000, 60,
                                      [("M", 60)], seq(60), quals(60))
    b1.tag_str(b"MI", b"7/A")
    b1.tag_str(b"RX", b"AAT-CCG")
    records.append(b1.finish())
    records += pair(b"m7a0", b"7/A", 8000)
    records += pair(b"m7b0", b"7/B", 8000, rev_r1=True)
    # molecule 8: all-0xFF-quality reads on one strand (zero-len conversion)
    b1 = RecordBuilder().start_mapped(b"m8a0", 0x1 | 0x40 | 0x20, 0, 9000, 60,
                                      [("M", 60)], seq(60),
                                      np.full(60, 0xFF, np.uint8))
    b1.tag_str(b"MI", b"8/A")
    records.append(b1.finish())
    records += pair(b"m8a1", b"8/A", 9000)
    records += pair(b"m8b0", b"8/B", 9000, rev_r1=True)
    # molecule 9: missing R2s (unpaired flags on one strand read)
    records += pair(b"m9a0", b"9/A", 9500)
    records += pair(b"m9b0", b"9/B", 9500, rev_r1=True)
    # molecule 10: one strand entirely below min_input_base_quality — its
    # SS consensus is depth-dead, but its reads' RX values still contribute
    # to the output RX consensus (duplex.py:421-434)
    records += pair(b"m10a0", b"10/A", 9700, rx=b"GGG-TTT", qual_lo=2,
                    qual_hi=9)
    records += pair(b"m10a1", b"10/A", 9700, rx=b"GGG-TTT", qual_lo=2,
                    qual_hi=9)
    records += pair(b"m10b0", b"10/B", 9700, rx=b"CCG-AAT", rev_r1=True)
    records += pair(b"m10b1", b"10/B", 9700, rx=b"CCG-AAT", rev_r1=True)

    with BamWriter(path, header) as w:
        for rec in records:
            w.write_record_bytes(rec)
    return path


@pytest.mark.parametrize("overlap", [False, True])
@pytest.mark.parametrize("min_reads", [(1,), (2, 1, 1)])
def test_parity_adversarial(adversarial_bam, overlap, min_reads):
    assert_parity(adversarial_bam, min_reads, overlap=overlap,
                  target_bytes=2048)


def test_every_molecule_a_fallback(duplex_bam):
    """With the rejects tracked every molecule of a span goes to the
    per-molecule caller: stage 2 has no output read of its own (K = 0) and
    only puts the fallback molecules' bytes in order."""
    assert_parity(duplex_bam, track_rejects=True)


# --------------------------------------------------- stage 2's column build

#: the width of the synthetic segs' arrays
_L = 8


def oracle_output_reads(seg_map, seg_len, d16, live_mol, min_total, min_xy,
                        min_yx):
    """The per-molecule rule stage 2 ran as a Python loop until PR 36
    (duplex.py _combine_molecule and _has_min_reads), kept as the oracle
    of ``output_read_columns``: the output reads as 8-tuples ``(mol, read
    flags, kind, aseg, bseg, length, rx_a, rx_b)`` in output order, and
    the emitted molecules."""
    def alive(s, limit):
        return bool((d16[s, :limit] > 0).any())

    def classify(a_s, b_s):
        La = int(seg_len[a_s]) if a_s >= 0 else 0
        Lb = int(seg_len[b_s]) if b_s >= 0 else 0
        if a_s >= 0 and b_s >= 0:
            length = min(La, Lb)
            aa, ba = alive(a_s, length), alive(b_s, length)
            if aa and ba:
                return (2, a_s, b_s, length)
            if aa:
                return (1, a_s, -1, La)
            if ba:
                return (0, b_s, -1, Lb)
            return None
        if a_s >= 0:
            return (1, a_s, -1, La) if alive(a_s, La) else None
        if b_s >= 0:
            return (0, b_s, -1, Lb) if alive(b_s, Lb) else None
        return None

    specs, emitted = [], []
    for g in range(len(seg_map)):
        if not live_mol[g]:
            continue
        ab1, ab2, ba1, ba2 = (int(x) for x in seg_map[g])
        has = [x >= 0 for x in (ab1, ab2, ba1, ba2)]
        if all(has):
            sides = ((ab1, ba2), (ab2, ba1))
        elif has == [True, True, False, False] and min_yx == 0:
            sides = ((ab1, -1), (ab2, -1))
        elif has == [False, False, True, True] and min_yx == 0:
            sides = ((-1, ba2), (-1, ba1))
        else:
            continue
        reads = [classify(*side) for side in sides]
        if None in reads:
            continue
        if all(has):
            ok = True
            for kind, s1, s2, length in reads:
                na = int(d16[s1, :length].max()) if length else 0
                nb_ = int(d16[s2, :length].max()) \
                    if kind == 2 and length else 0
                xy, yx = max(na, nb_), min(na, nb_)
                ok &= min_total <= xy + yx and min_xy <= xy and min_yx <= yx
            if not ok:
                continue
        emitted.append(g)
        for flags, read, side in zip((_TYPE_FLAGS[R1], _TYPE_FLAGS[R2]),
                                     reads, sides):
            specs.append((g, flags) + read + side)
    return specs, emitted


def make_span(molecules):
    """``(seg_map, seg_len, d16, live_mol)`` of a span whose molecules are
    dicts seg type -> depths by column (the seg's length is the list's);
    a molecule under the key ``"dead"`` failed a gate before stage 2."""
    seg_map = np.full((len(molecules), 4), -1, dtype=np.int64)
    live = np.ones(len(molecules), dtype=bool)
    lens, rows = [], []
    for g, mol in enumerate(molecules):
        for t, depths in mol.items():
            if t == "dead":
                live[g] = False
                continue
            seg_map[g, t] = len(rows)
            lens.append(len(depths))
            rows.append(list(depths) + [0] * (_L - len(depths)))
    d16 = np.array(rows, dtype=np.int32).reshape(len(rows), _L)
    return seg_map, np.array(lens, dtype=np.int64), d16, live


def check_columns(span, min_reads):
    gates = parse_min_reads(min_reads)
    reads, emitted, full, ab_only, ba_only = output_read_columns(*span,
                                                                 *gates)
    specs, want_emitted = oracle_output_reads(*span, *gates)
    got = list(zip(*(col.tolist() for col in reads)))
    assert got == specs
    assert np.nonzero(emitted)[0].tolist() == want_emitted
    assert [c.dtype for c in reads] == [np.int64, np.int32, np.int8,
                                        np.int64, np.int64, np.int32,
                                        np.int64, np.int64]
    # each molecule is of one shape at most, and only a live one of any
    assert not (full & ab_only).any() and not (full & ba_only).any() \
        and not (ab_only & ba_only).any()
    assert not ((full | ab_only | ba_only) & ~span[3]).any()
    return specs, want_emitted


def _mol(ab1=None, ab2=None, ba1=None, ba2=None, dead=False):
    mol = {t: d for t, d in ((AB_R1, ab1), (AB_R2, ab2), (BA_R1, ba1),
                             (BA_R2, ba2)) if d is not None}
    if dead:
        mol["dead"] = True
    return mol


_D = [3, 3, 3, 3, 3, 3]   # a seg alive from its first column
_Z = [0, 0, 0, 0, 0, 0]   # a seg with no depth anywhere

#: name -> (molecules, the kinds of the output reads expected at 1 1 0)
_COLUMN_CASES = {
    "both_alive": ([_mol(_D, _D, _D, _D)], [2, 2]),
    "ab_dead_in_full": ([_mol(_Z, _D, _D, _D)], [0, 2]),
    "ba_dead_in_full": ([_mol(_D, _D, _Z, _D)], [2, 1]),
    "each_read_loses_a_strand": ([_mol(_Z, _D, _Z, _D)], [0, 1]),
    "both_dead_in_one_read": ([_mol(_Z, _D, _D, _Z)], []),
    "all_dead": ([_mol(_Z, _Z, _Z, _Z)], []),
    "ab_only": ([_mol(_D, _D)], [1, 1]),
    "ab_only_dead_read": ([_mol(_D, _Z)], []),
    "ba_only": ([_mol(ba1=_D, ba2=_D)], [0, 0]),
    "ba_only_dead_read": ([_mol(ba1=_Z, ba2=_D)], []),
    # R1 is 4 long where AB_R1 is 7: combined at the shorter seg's length
    "unequal_lengths": ([_mol([2] * 7, [2] * 5, [1] * 6, [1] * 4)], [2, 2]),
    # AB_R1's first depth is at column 4 = the combined length: dead within
    # it, alive within its own 7, but the other strand is what passes
    "first_depth_at_length": ([_mol([0, 0, 0, 0, 5, 5, 5], _D, _D,
                                    [1, 1, 1, 1])], [0, 2]),
    # ... beyond it, beside a strand with none: AB_R1 would be alive alone,
    # but within the combined length neither is, and the molecule goes
    "first_depth_beyond_length": ([_mol([0, 0, 0, 0, 0, 5, 5], _D, _D,
                                        [0, 0, 0, 0])], []),
    # the first depth in the last column inside the combined length
    "first_depth_inside_length": ([_mol([0, 0, 0, 5, 5, 5, 5], _D, _D,
                                        [1, 1, 1, 1])], [2, 2]),
    "three_segs": ([_mol(_D, _D, _D)], []),
    "failed_a_gate": ([_mol(_D, _D, _D, _D, dead=True)], []),
    "mixed_span": ([_mol(_D, _D, _D, _D), _mol(_D, _D), _mol(_Z, _D, _D, _Z),
                    _mol(_D, _D, _D, _D, dead=True), _mol(ba1=_D, ba2=_D),
                    _mol(_D, _D, _Z, _D), _mol()], [2, 2, 1, 1, 0, 0, 2, 1]),
    "no_molecules": ([], []),
    "every_molecule_a_fallback": ([_mol(), _mol(), _mol()], []),
}


@pytest.mark.parametrize("case", list(_COLUMN_CASES))
def test_output_read_columns(case):
    """The column build against the per-molecule rule it replaced."""
    molecules, kinds = _COLUMN_CASES[case]
    specs, _emitted = check_columns(make_span(molecules), (1, 1, 0))
    assert [s[2] for s in specs] == kinds


@pytest.mark.parametrize("min_reads,emitted", [
    ((1,), [0, 1, 2, 3, 4]), ((1, 1, 0), [0, 1, 2, 3, 4, 5, 6, 7]),
    ((3, 2, 1), [0, 1, 2]), ((4, 2, 2), [0, 1])])
def test_output_read_columns_min_reads(min_reads, emitted):
    """_has_min_reads on the output reads' depths: the largest depth of
    each side within the read's length, not the seg's; a passed-through
    read's other side counts 0; one-strand molecules are not gated, and
    exist only where YX may be 0."""
    molecules = [
        _mol([2] * 6, [2] * 6, [2] * 6, [2] * 6),     # 2 + 2 everywhere
        _mol([3] * 6, [2] * 6, [2] * 6, [2] * 6),     # 3 + 2 and 2 + 2
        _mol([2] * 6, [2] * 6, [1] * 6, [1] * 6),     # 2 + 1
        # AB_R1's depth 4 lies beyond the combined length 3: 1 + 1
        _mol([1, 1, 1, 4, 4, 4], [1] * 6, [1] * 6, [1, 1, 1]),
        _mol([1] * 6, [1] * 6, [1] * 6, [1] * 6),     # 1 + 1
        # R1 loses its BA strand and passes AB_R1 through: 3 + 0
        _mol([3] * 6, [3] * 6, [3] * 6, _Z),
        _mol([9] * 6, [9] * 6),                       # /A only
        _mol(ba1=[9] * 6, ba2=[9] * 6),               # /B only
    ]
    assert check_columns(make_span(molecules), min_reads)[1] == emitted


@pytest.mark.parametrize("min_reads", [(1,), (1, 1, 0), (3, 2, 1), (4, 2, 2)])
@pytest.mark.parametrize("seed", [3, 17, 101])
def test_output_read_columns_random_spans(seed, min_reads):
    """200 molecules of random shape, seg lengths and depth runs."""
    rng = np.random.default_rng(seed)

    def seg():
        n = int(rng.integers(1, _L + 1))
        depths = rng.integers(0, 5, size=n)
        depths[:int(rng.integers(0, n + 1))] = 0  # a dead head, often all
        return depths.tolist()

    shapes = [(1, 1, 1, 1)] * 5 + [(1, 1, 0, 0), (0, 0, 1, 1), (1, 1, 1, 0),
                                   (0, 1, 1, 0), (0, 0, 0, 0)]
    molecules = []
    for _ in range(200):
        shape = shapes[int(rng.integers(len(shapes)))]
        molecules.append(_mol(*(seg() if present else None
                                for present in shape),
                              dead=rng.random() < 0.1))
    specs, _emitted = check_columns(make_span(molecules), min_reads)
    if min_reads == (1, 1, 0):
        assert {s[2] for s in specs} == {0, 1, 2}


def test_missing_suffix_raises(tmp_path):
    path = str(tmp_path / "bad.bam")
    header = BamHeader(
        text="@HD\tVN:1.6\n@SQ\tSN:chr1\tLN:100000\n",
        ref_names=["chr1"], ref_lengths=[100000])
    b = RecordBuilder().start_mapped(b"r0", 0x1 | 0x40, 0, 100, 60,
                                     [("M", 30)], b"A" * 30,
                                     np.full(30, 30, np.uint8))
    b.tag_str(b"MI", b"77")
    with BamWriter(path, header) as w:
        w.write_record_bytes(b.finish())
    with pytest.raises(ValueError, match="without /A or /B"):
        run_fast(path)


def test_sharded_matches_single_device(tmp_path):
    """8-device dp-sharded SS dispatch == single device, byte-identical
    (VERDICT r1 item 4: mesh wired into the duplex caller too)."""
    from fgumi_tpu.parallel.mesh import make_mesh

    path = str(tmp_path / "dup.bam")
    simulate_duplex_bam(path, num_molecules=120, reads_per_strand=4, seed=77)

    def run(mesh, tb):
        caller = make_caller((1,))
        fast = FastDuplexCaller(caller, b"MI", mesh=mesh)
        chunks = []
        with BamBatchReader(path, target_bytes=tb) as reader:
            for batch in reader:
                chunks.extend(fast.process_batch(batch))
        chunks.extend(fast.flush())
        return b"".join(map(resolve_chunk, chunks))

    import jax

    mesh = make_mesh(dp=min(8, len(jax.devices())))
    for tb in (4096, 1 << 20):
        assert run(None, tb) == run(mesh, tb), tb


#: what a batch's segments hold -> the simulator's parameters for it
_SEGMENT_MIXES = {
    "single_only": dict(reads_per_strand=1),
    "multi_only": dict(reads_per_strand=3),
    "both": dict(reads_per_strand=2, strand_bias_alpha=1.0,
                 strand_bias_beta=1.0),
}


@pytest.mark.parametrize("devices", [1, 2])
@pytest.mark.parametrize("mix", list(_SEGMENT_MIXES))
def test_device_entry_follows_the_mesh(tmp_path, monkeypatch, mix, devices):
    """On the device route a batch of single-read segments only, of
    multi-read segments only, and of both gives the per-molecule caller's
    bytes; its multi-read rows enter through submit_ragged on one device
    (``engine.pack`` counts ``entry_ragged`` a dispatch, ``entry_dense``
    never) and through submit_dense on a two-device mesh (the reverse)."""
    import jax

    from fgumi_tpu.observe import trace
    from fgumi_tpu.parallel.mesh import make_mesh

    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "device")
    path = str(tmp_path / "dup.bam")
    simulate_duplex_bam(path, num_molecules=90, seed=5, **_SEGMENT_MIXES[mix])
    slow_out, _caller, _oc = run_slow(path)
    mesh = make_mesh(jax.devices()[:2], dp=2) if devices == 2 else None

    caller = make_caller()
    fast = FastDuplexCaller(caller, b"MI", mesh=mesh)
    trace.stop_trace()
    trace.arm_spans()
    try:
        chunks = []
        for batch in record_batches(path, 100):
            chunks.extend(fast.process_batch(batch))
        chunks.extend(fast.flush())
        blob = b"".join(map(resolve_chunk, chunks))
        by_name = trace.current_aggregate().snapshot()["by_name"]
    finally:
        trace.stop_trace()
    assert blob == b"".join(len(r).to_bytes(4, "little") + r
                            for r in slow_out)
    if mix == "single_only":
        assert "engine.pack" not in by_name
        return
    pack = by_name["engine.pack"]
    took, other = (("entry_ragged", "entry_dense") if devices == 1
                   else ("entry_dense", "entry_ragged"))
    assert pack[took] == pack["count"] > 1
    assert other not in pack
    wires = pack.get("wire_native", 0) + pack.get("wire_numpy", 0)
    assert wires == pack["count"]
    if devices == 1:
        assert pack["wire_native"] == pack["entry_ragged"]
