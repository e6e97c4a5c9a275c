"""Codec's native prepare for molecules whose CIGARs are not one ``M`` run
(`nb.codec_cigar_prepare`, the route `FastCodecCaller._prepare_cigar`
takes) against the classic `CodecConsensusCaller.prepare()`: read by read
(the mate clip, the clipped CIGAR, the reference the clip took, the
adjusted position, the clipped lengths, the per-strand filter's mask) and
molecule by molecule (the longest strands, the verdicts, the consensus
length, the rejects and the filter's tally), on random molecules and
hand-made ones; then the batch engine against the classic engine on a BAM
of such molecules, by batch sizes and options."""

import struct
from collections import Counter

import numpy as np
import pytest

from fgumi_tpu.consensus.codec import CodecConsensusCaller, CodecOptions
from fgumi_tpu.core import cigar as cigar_utils
from fgumi_tpu.core.overlap import (is_primary_fr_pair,
                                    num_bases_extending_past_mate_vs_mate)
from fgumi_tpu.io.bam import (FLAG_FIRST, FLAG_REVERSE, BamHeader,
                              BamReader, BamWriter, RawRecord, RecordBuilder)
from fgumi_tpu.io.batch_reader import RecordBatch
from fgumi_tpu.native import batch as nb
from record_batches import record_batches

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library required")

OPS = "MIDNSHP=X"
#: verdict code -> prepare()'s reason
VERDICTS = {1: "InsufficientReads", 2: "InsufficientOverlap",
            3: "IndelErrorBetweenStrands"}
#: name -> (min_reads_per_strand, min_duplex_length)
OPTIONS = {"defaults": (1, 1), "min_reads_2": (2, 1),
           "min_duplex_length_90": (1, 90)}


def _parse(text):
    out, num = [], ""
    for ch in text:
        if ch.isdigit():
            num += ch
        else:
            out.append((ch, int(num)))
            num = ""
    return out


def _ref_len(cigar):
    return cigar_utils.reference_length(cigar)


def _random_cigar(rng):
    """A CIGAR as an aligner writes it: clips outside (H now and then), M
    runs between insertions and deletions."""
    ops = []
    if rng.random() < 0.15:
        ops.append(("H", int(rng.integers(1, 6))))
    if rng.random() < 0.35:
        ops.append(("S", int(rng.integers(1, 21))))
    ops.append(("M", int(rng.integers(5, 70))))
    for _ in range(int(rng.integers(0, 3))):
        ops.append((str(rng.choice(["I", "D"])), int(rng.integers(1, 4))))
        ops.append(("M", int(rng.integers(5, 70))))
    if rng.random() < 0.35:
        ops.append(("S", int(rng.integers(1, 21))))
    if rng.random() < 0.15:
        ops.append(("H", int(rng.integers(1, 6))))
    return ops


#: hand-made molecules: (R1 reverse, fragment end past the forward reads'
#: start, [(forward CIGAR, reverse CIGAR)]): every forward read starts at
#: the molecule's start, every reverse read ends at its ``end``'s base
HAND = {
    # the reverse read ends at the forward read's last base before its
    # deletion: the forward read's clip ends there, and the deletion that
    # abuts the clip point goes with it
    "a deletion at an end clip": (False, 50, [("50M2D98M", "100M")]),
    # the forward read starts at the reverse read's first base past its
    # deletion: the reverse read's start clip takes the deletion's
    # reference too
    "a deletion at a start clip": (False, 98, [("100M", "50M2D98M")]),
    # the forward read's hard clip absorbs 5 of its 50 clipped bases, so the
    # clip point falls inside its insertion, which goes whole
    "an insertion swallowed at the clip point": (
        False, 50, [("50M10I40M5H", "100M")]),
    # one R2 of three has a minority CIGAR: R1 keeps 3, R2 keeps 2
    "R1 and R2 keep different counts": (
        False, 200, [("150M", "150M"), ("150M", "60M2I88M"),
                     ("150M", "150M")]),
    "R1 reverse, soft clips both sides": (
        True, 160, [("5S140M5S", "10S135M5S"), ("150M", "3S147M")]),
}


def _record(rng, name, flag, pos, cigar, mi, mate_pos, tlen, cb=None):
    n = sum(ln for op, ln in cigar if op in "MIS=X")
    seq = bytes(rng.choice(np.frombuffer(b"ACGT", np.uint8), size=n))
    b = RecordBuilder().start_mapped(
        name, flag, 0, pos, 60, cigar, seq,
        rng.integers(2, 41, size=n).astype(np.uint8), next_ref_id=0,
        next_pos=mate_pos, tlen=tlen)
    b.tag_str(b"MI", mi)
    if cb is not None:
        b.tag_str(b"CB", cb)
    return b.finish()


def _molecule(rng, m, r1_reverse, end, pairs, noise=False):
    """One molecule's records, the pairs' records in a random order (their
    names' first appearance is prepare()'s pair order)."""
    start = 1000 + 1000 * m
    mi = b"%d" % m
    recs = []
    for t, (fwd, rev) in enumerate(pairs):
        rpos = start + end - _ref_len(rev)
        tlen = end
        name = b"m%dp%d" % (m, t)
        f_flag = 0x1 | 0x20 | (0x80 if r1_reverse else 0x40)
        r_flag = 0x1 | 0x10 | (0x40 if r1_reverse else 0x80)
        cb = b"C%d" % int(rng.integers(0, 3)) if rng.random() < 0.5 else None
        recs.append(_record(rng, name, f_flag, start, fwd, mi, rpos, tlen,
                            cb))
        recs.append(_record(rng, name, r_flag, rpos, rev, mi, start, -tlen,
                            cb))
    if noise:
        u = rng.random()
        if u < 0.1:  # a fragment
            recs.append(_record(rng, b"m%df" % m, 0, start, [("M", 60)], mi,
                                -1, 0))
        elif u < 0.2:  # a same-strand pair
            for flag in (0x1 | 0x40, 0x1 | 0x80):
                recs.append(_record(rng, b"m%dx" % m, flag, start,
                                    [("M", 60)], mi, start, 60))
        elif u < 0.25:  # a lone read of a pair
            recs.append(_record(rng, b"m%dl" % m, 0x1 | 0x40 | 0x20, start,
                                [("S", 5), ("M", 55)], mi,
                                start + 20, 80))
    order = rng.permutation(len(recs))
    return [RawRecord(recs[i]) for i in order]


def _random_molecule(rng, m, noise=False, cigar=False):
    """A molecule of 1-6 pairs round a template pair of CIGARs; with
    ``cigar`` at least one read's CIGAR is not one M run."""
    fwd_t, rev_t = _random_cigar(rng), _random_cigar(rng)
    pairs = []
    for _ in range(int(rng.choice([1, 1, 2, 3, 4, 6]))):
        fwd = fwd_t if rng.random() < 0.6 else _random_cigar(rng)
        rev = rev_t if rng.random() < 0.6 else _random_cigar(rng)
        pairs.append((fwd, rev))
    if cigar and all(len(c) == 1 for pair in pairs for c in pair):
        pairs[0] = ([("S", 3)] + pairs[0][0], pairs[0][1])
    end = int(rng.integers(60, 260))
    return _molecule(rng, m, bool(rng.integers(0, 2)), end, pairs, noise)


def _hand_molecules(rng, m0):
    return [_molecule(rng, m0 + k, r1_reverse, end,
                      [(_parse(f), _parse(r)) for f, r in pairs])
            for k, (r1_reverse, end, pairs) in enumerate(HAND.values())]


def _pairs_of(records):
    """prepare()'s phases 1-2 pairing: the FR pairs (R1, R2), in its
    order."""
    by_name = {}
    for rec in records:
        if rec.flag & 0x1 and not rec.flag & 0x900:
            by_name.setdefault(rec.name, []).append(rec)
    out = []
    for bucket in by_name.values():
        if len(bucket) == 2 and is_primary_fr_pair(*bucket):
            a, b = bucket
            out.append((a, b) if a.flag & FLAG_FIRST else (b, a))
    return out


def _native(molecule_pairs, opts):
    """The native pass over the molecules' pairs -> (its outputs, the reads
    in its order: every R1, then every R2)."""
    recs = [p[0] for pairs in molecule_pairs for p in pairs] \
        + [p[1] for pairs in molecule_pairs for p in pairs]
    wire = [struct.pack("<I", len(r.data)) + r.data for r in recs]
    rec_off = np.cumsum([0] + [len(w) for w in wire[:-1]]).astype(np.int64)
    batch = RecordBatch(bytearray(b"".join(wire)), rec_off)
    starts = np.cumsum([0] + [len(p) for p in molecule_pairs])
    res = nb.codec_cigar_prepare(
        batch.buf, batch.cigar_off, batch.n_cigar, batch.flag, batch.pos,
        batch.l_seq, starts, opts.min_reads_per_strand,
        opts.min_duplex_length)
    return res, recs, starts


def _words(res, i):
    lo, n = int(res["word_lo"][i]), int(res["word_n"][i])
    return [(OPS[int(w) & 15], int(w) >> 4) for w in res["words"][lo:lo + n]]


@pytest.mark.parametrize("option", OPTIONS)
@pytest.mark.parametrize("seed", [1, 2, 3, 2147483659, 3000000019])
def test_native_prepare_is_prepares(seed, option):
    min_reads, min_duplex = OPTIONS[option]
    opts = CodecOptions(min_reads_per_strand=min_reads,
                        min_duplex_length=min_duplex)
    rng = np.random.default_rng(seed)
    molecules = _hand_molecules(rng, 0) + [
        _random_molecule(rng, m) for m in range(len(HAND), 160)]
    molecule_pairs = [_pairs_of(records) for records in molecules]
    assert all(molecule_pairs)  # FR pairs only: prepare() pairs them all
    res, recs, starts = _native(molecule_pairs, opts)
    P = len(recs) // 2

    # read by read: the clip, the clipped CIGAR and what it took
    clipper = CodecConsensusCaller("t", "A", opts)
    infos = []
    for i, rec in enumerate(recs):
        mate = recs[(i + P) % (2 * P)]
        clip = num_bases_extending_past_mate_vs_mate(rec, mate)
        info = clipper._build_clipped_info(rec, i, clip)
        _cig, ref_consumed = cigar_utils.clip_cigar(
            rec.cigar(), clip, bool(rec.flag & FLAG_REVERSE))
        assert res["clip"][i] == clip
        assert _words(res, i) == info.clipped_cigar
        assert res["adj_pos"][i] - rec.pos - 1 == ref_consumed
        assert res["adj_pos"][i] == info.adjusted_pos
        assert res["clipped_len"][i] == info.clipped_seq_len
        assert res["ref_len"][i] \
            == cigar_utils.reference_length(info.clipped_cigar)
        infos.append(info)
    # the hand-made clips: a deletion abutting an end clip and a start
    # clip goes, an insertion across the clip point goes whole
    hand = {name: int(starts[k]) for k, name in enumerate(HAND)}
    p = hand["a deletion at an end clip"]
    assert _words(res, p) == [("M", 50), ("H", 98)]
    p = hand["a deletion at a start clip"]
    assert _words(res, P + p) == [("H", 50), ("M", 98)]
    assert res["adj_pos"][P + p] == recs[P + p].pos + 1 + 52
    p = hand["an insertion swallowed at the clip point"]
    assert _words(res, p) == [("M", 50), ("H", 55)]

    # molecule by molecule: the filter's mask and the longest strands
    checker = CodecConsensusCaller("t", "A", opts)
    for s in range(len(molecule_pairs)):
        lo, hi = int(starts[s]), int(starts[s + 1])
        n = hi - lo
        if n < min_reads:
            assert res["verdict"][s] == 1
            assert res["keep"][[*range(lo, hi), *range(P + lo, P + hi)]] \
                .all()
            continue
        longest = []
        for side in (0, 1):
            side_infos = infos[side * P + lo:side * P + hi]
            kept = {info.raw_idx for info in
                    checker._filter_most_common_alignment(side_infos)}
            mask = [info.raw_idx in kept for info in side_infos]
            assert res["keep"][side * P + lo:side * P + hi].tolist() \
                == [int(k) for k in mask]
            assert res[f"n_kept{side + 1}"][s] == sum(mask)
            longest.append(max((i for i in side_infos if i.raw_idx in kept),
                               key=lambda i: cigar_utils.reference_length(
                                   i.clipped_cigar)).raw_idx)
        if min(res["n_kept1"][s], res["n_kept2"][s]) >= min_reads:
            assert [res["longest1"][s], res["longest2"][s]] == longest
    assert checker.stats.filter_tally == res["tally"].tolist()

    # the verdicts, the columns and the rejects against prepare() itself
    caller = CodecConsensusCaller("t", "A", opts)
    minority = 0
    for s, records in enumerate(molecules):
        before = Counter(caller.stats.rejection_reasons)
        mol = caller.prepare(records)
        grew = Counter(caller.stats.rejection_reasons) - before
        minority += grew.pop("MinorityAlignment", 0)
        n_reads = int(res["n_kept1"][s] + res["n_kept2"][s])
        verdict = int(res["verdict"][s])
        if verdict:
            assert mol is None
            assert grew == {VERDICTS[verdict]: n_reads}
            continue
        assert not grew
        lo, hi = int(starts[s]), int(starts[s + 1])
        kept = [recs[i] for i in range(lo, hi) if res["keep"][i]] \
            + [recs[i] for i in range(P + lo, P + hi) if res["keep"][i]]
        assert [r.data for r in mol["source_raws"]] == [r.data for r in kept]
        assert (mol["n_r1"], mol["n_r2"]) == (res["n_kept1"][s],
                                              res["n_kept2"][s])
        assert (mol["job_r1"].consensus_len, mol["job_r2"].consensus_len) \
            == (res["len1"][s], res["len2"][s])
        assert mol["consensus_length"] == res["length"][s]
        neg = [bool(recs[int(res[f"longest{k}"][s])].flag & FLAG_REVERSE)
               for k in (1, 2)]
        assert [mol["r1_is_negative"], mol["r2_is_negative"]] == neg
    assert minority == res["tally"][3]
    assert caller.stats.filter_tally == res["tally"].tolist()
    # what the molecules show: every verdict but the one the options cannot
    # give, a strand that lost reads, R1 and R2 that kept different counts
    shown = set(res["verdict"].tolist())
    assert {0, 3} <= shown
    assert 2 in shown or min_duplex == 1
    assert (1 in shown) == (min_reads > 1)
    assert 0 < res["tally"][1] < res["tally"][0]
    assert (res["n_kept1"] != res["n_kept2"]).any()


# ------------------------------------ the batch engine against the classic

_HEADER = BamHeader(
    text="@HD\tVN:1.6\tSO:unsorted\tGO:query\n@SQ\tSN:c\tLN:500000\n",
    ref_names=["c"], ref_lengths=[500000])


@pytest.fixture(scope="module")
def cigar_bam(tmp_path_factory):
    """250 molecules, each with a read whose CIGAR is not one M run, a
    fragment, a same-strand pair or a lone read in some; the hand-made ones
    first."""
    rng = np.random.default_rng(47)
    molecules = _hand_molecules(rng, 0) + [
        _random_molecule(rng, m, noise=True, cigar=True)
        for m in range(len(HAND), 250)]
    path = str(tmp_path_factory.mktemp("cigar") / "cigar.bam")
    with BamWriter(path, _HEADER) as w:
        for records in molecules:
            for rec in records:
                w.write_record_bytes(rec.data)
    return path


#: case -> CodecOptions keywords
ENGINE_CASES = {
    "defaults": {},
    "min_reads_2": dict(min_reads_per_strand=2),
    "min_duplex_length_90": dict(min_duplex_length=90),
    "max_reads_2": dict(max_reads_per_strand=2),
    "cell_tag": dict(cell_tag="CB"),
}


def _classic(path, options):
    """The classic engine a molecule a call: wire bytes, stats, molecules
    rejected by reason (the latest phase's)."""
    from fgumi_tpu.core.grouper import iter_mi_groups

    reasons = ("HighDuplexDisagreement", "ClipOverlapFailed",
               *reversed(VERDICTS.values()), "MinorityAlignment",
               "NotPrimaryFrPair", "FragmentRead")
    caller = CodecConsensusCaller("fgumi", "A", options)
    wire, rejected = [], Counter()
    with BamReader(path) as r:
        for group in iter_mi_groups(r, b"MI"):
            before = dict(caller.stats.rejection_reasons)
            out = caller.call_groups([group])
            wire.extend(struct.pack("<I", len(x)) + x for x in out)
            if not out:
                after = caller.stats.rejection_reasons
                grew = [x for x in reasons
                        if after.get(x, 0) > before.get(x, 0)]
                rejected[grew[0] if grew else "NoUsableReads"] += 1
    return b"".join(wire), caller.stats, rejected


def _engine(path, options, n_records):
    from fgumi_tpu.consensus.fast_codec import FastCodecCaller
    from fgumi_tpu.observe.metrics import METRICS

    caller = CodecConsensusCaller("fgumi", "A", options)
    fast = FastCodecCaller(caller, b"MI")
    before = METRICS.snapshot()
    chunks = []
    for batch in record_batches(path, n_records):
        chunks.extend(fast.process_batch(batch))
    chunks.extend(fast.flush())
    wire = b"".join(chunk.resolve() for chunk in chunks)
    after = METRICS.snapshot()
    counters = {k: v - before.get(k, 0) for k, v in after.items()
                if k.startswith("codec.") and v != before.get(k, 0)}
    return wire, caller.stats, counters


@pytest.mark.parametrize("n_records", [10 ** 9, 9])
@pytest.mark.parametrize("case", ENGINE_CASES)
def test_the_engine_is_the_classic_engine(cigar_bam, case, n_records):
    options = CodecOptions(**ENGINE_CASES[case])
    want, want_stats, want_rejected = _classic(cigar_bam, options)
    got, got_stats, counters = _engine(cigar_bam, options, n_records)
    assert got == want
    assert got_stats == want_stats
    # every molecule has a CIGAR, so the filter runs where prepare() runs it
    assert got_stats.filter_tally == want_stats.filter_tally
    assert 0 < got_stats.filter_tally[1] < got_stats.filter_tally[0]
    assert {k[len("codec.rejected."):]: v for k, v in counters.items()
            if k.startswith("codec.rejected.")} == dict(want_rejected)
    carried = counters["codec.slow_molecules.carried"]
    slow = counters.get("codec.slow_molecules.cigar", 0)
    assert counters["codec.molecules"] == counters["codec.molecules.cigar"] \
        == counters["codec.molecules.cigar_vec"] + slow + carried
    if case == "max_reads_2":  # a molecule of three pairs or more samples
        assert slow > 0
    else:
        assert slow == 0


@pytest.mark.parametrize("bad", ["odd_reads", "short_flags", "step_back",
                                 "past_the_pairs", "not_from_zero"])
def test_the_native_prepare_refuses_what_it_cannot_read(bad):
    rng = np.random.default_rng(5)
    molecule_pairs = [_pairs_of(_random_molecule(rng, m)) for m in range(3)]
    recs = [p[0] for pairs in molecule_pairs for p in pairs] \
        + [p[1] for pairs in molecule_pairs for p in pairs]
    wire = [struct.pack("<I", len(r.data)) + r.data for r in recs]
    rec_off = np.cumsum([0] + [len(w) for w in wire[:-1]]).astype(np.int64)
    batch = RecordBatch(bytearray(b"".join(wire)), rec_off)
    args = [batch.cigar_off, batch.n_cigar, batch.flag, batch.pos,
            batch.l_seq]
    starts = np.cumsum([0] + [len(p) for p in molecule_pairs])
    if bad == "odd_reads":
        args = [a[:-1] for a in args]
    elif bad == "short_flags":
        args[2] = args[2][:-2]
    elif bad == "step_back":
        starts[1], starts[2] = starts[2], starts[1]
    elif bad == "past_the_pairs":
        starts[-1] += 1
    else:
        starts = starts[1:]
    with pytest.raises(ValueError, match="codec_cigar_prepare"):
        nb.codec_cigar_prepare(batch.buf, *args, starts, 1, 1)
