"""Parity: FastDuplexCaller (vectorized batch path) vs DuplexConsensusCaller.

Byte-identical consensus records, identical statistics and rejection counts
across batch-boundary-spanning molecules, overlap correction, single-strand
molecules, and min-reads gating.
"""

import numpy as np
import pytest

from fgumi_tpu.consensus.duplex import DuplexConsensusCaller, iter_duplex_groups
from fgumi_tpu.consensus.fast import resolve_chunk
from fgumi_tpu.consensus.fast_duplex import FastDuplexCaller
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


def run_fast(path, min_reads=(1,), overlap=False, target_bytes=4096, **kw):
    caller = make_caller(min_reads, **kw)
    oc = OverlappingBasesConsensusCaller("consensus", "consensus") \
        if overlap else None
    fast = FastDuplexCaller(caller, b"MI", overlap_caller=oc)
    chunks = []
    with BamBatchReader(path, target_bytes=target_bytes) as reader:
        for batch in reader:
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
                  **kw):
    slow_out, slow_caller, slow_oc = run_slow(path, min_reads, overlap, **kw)
    fast_out, fast_caller, fast_oc = run_fast(path, min_reads, overlap,
                                              target_bytes, **kw)
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


def test_parity_tiny_batches(duplex_bam):
    """Every molecule crosses a batch boundary (full carry coverage)."""
    assert_parity(duplex_bam, target_bytes=512)


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
