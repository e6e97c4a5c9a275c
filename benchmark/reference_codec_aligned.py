"""The plain reference of ``codec`` on a BAM as an aligner writes it (layout
``aligned_codec_bam``): soft clips and indels inside CODEC molecules. The
single-strand model, the combine and the record are ``reference_codec.py``'s
(``reference.call_jobs`` on the source reads, ``_block`` with the consensus
length in the insert's place), the CIGAR rules ``reference_aligned.py``'s
(``bases_past_mate``, ``simplify``, ``most_common_alignment`` with fgumi's
tie-breaks); what is here is how fgbio's CallCodecConsensusReads (fgumi
``codec_caller.rs``, its phases 2-4) puts them together for a CIGAR other
than one ``M`` run, written again from the published descriptions. float64,
numpy over arrays and plain Python over one molecule's reads; nothing of the
program is imported and nothing it made is read. ``dtype=np.float32`` is the
control, and so is ``checks=False`` (no filter, no phase check).

Per molecule (MI), its read pairs in record order, R1 and R2 of a pair the
forward read (``<m>`` 1) and the reverse-flagged one (2):

1. the mate clip: the bases of each read past its mate's far end, soft
   clips of both counted, the mate's ends from its CIGAR (``MC``);
2. each read's CIGAR clipped by that many query bases at its 3' end (the
   reverse read's left end) as a virtual hard clip (``clip_cigar``): a soft
   clip there is turned hard first, an insertion the clip point falls in goes
   whole, a deletion the clip point abuts goes too; the reverse read's first
   aligned base moves by the reference bases clipped;
3. the most-common-alignment filter over the R1s and, separately, over the
   R2s, each set in record order, every read as (clipped length, clipped
   CIGAR simplified, reversed on the reverse strand); **not** truncated to
   the clipped length, so a hard clip counts as ``M``. ``MinorityAlignment``
   for what it sets aside;
4. of what is left, the longest R1 and the longest R2 by **reference**
   length, first maximum in record order: the forward one ends the overlap
   (``pos_end``: its last aligned base), the reverse one starts it
   (``overlap_start``: its first); ``duplex_length = pos_end - overlap_start
   + 1`` must reach ``min_duplex_length`` (``InsufficientOverlap``);
5. the phase check: the two reads' 1-based read positions at
   ``overlap_start`` and at ``pos_end``, walked through their CIGARs (a
   position inside a deletion reads as the last read base before it, 1 at
   the start), must exist and differ by the same amount at both ends, else
   the molecule goes as ``IndelErrorBetweenStrands``;
6. the consensus length: the forward read's position at ``pos_end`` plus
   the reverse read's clipped length minus its position there (a deletion
   at ``pos_end`` in either rejects, ``IndelErrorBetweenStrands``);
7. then ``reference_codec``'s steps 3-8 with that length in the insert's
   place: one single-strand consensus of the kept R1s and one of the kept R2s
   (each read in sequencing orientation, its clipped length long, soft-clipped
   bases included), a strand longer than the length rejects
   (``ClipOverlapFailed``), the combine, the gates, the record.

The account (``tallies``): ``reads`` counts every input read once, under
``ConsensusReads`` (kept by the filter in an emitted molecule),
``MinorityAlignment`` or the reason its molecule was rejected (the reads the
filter kept); ``molecules`` counts the rejected molecules by reason;
``filter`` sums the filter's runs on sets of two reads or more (``sets``,
``sets_kept_all``, ``reads_in``, ``reads_rejected``), and
``filter_by_molecule`` holds them a molecule a row.

What is assumed of the inputs (true of ``traffic/aligned_codec_bam.py``):
``reference_codec.py``'s, but the one ``M`` run, a read's length and the
insert; ``reference_aligned.py``'s CIGAR inputs (``pos<m>``, ``cigar<m>``,
``ncig<m>``); every pair a primary FR pair on one contig (the program's
``is_primary_fr_pair``; an ``aligned_codec_bam`` pair always is), every R1 of
a molecule on one strand, no read trimmed to nothing and none with an ``N``
at its 3' end.
"""

import numpy as np

import reference
import reference_aligned as ra
import reference_codec as rc
import traffic

#: the reasons a molecule is rejected for, in the order they are decided
REASONS = ("InsufficientReads", "InsufficientOverlap",
           "IndelErrorBetweenStrands", "ClipOverlapFailed",
           "HighDuplexDisagreement")
FILTER = ("sets", "sets_kept_all", "reads_in", "reads_rejected")
_QUERY = frozenset("MIS=X")  # ops that take read bases, a clip's S included
_REF = frozenset("MDN=X")
_ALIGNED = frozenset("M=X")


def clip_cigar(cigar, clip, from_start):
    """``cigar`` with ``clip`` query bases taken off one end as a hard clip
    (fgumi ``clip_cigar_ops_raw``, ``cigar.rs:404-446``). Returns (the new
    CIGAR, the reference bases taken off, counted only ``from_start``: the
    first aligned base moves by them)."""
    if not clip or not cigar:
        return list(cigar), 0
    ops = list(cigar) if from_start else list(cigar)[::-1]
    hard = soft = k = 0
    while k < len(ops) and ops[k][0] == "H":
        hard, k = hard + ops[k][1], k + 1
    while k < len(ops) and ops[k][0] == "S":
        soft, k = soft + ops[k][1], k + 1
    rest = ops[k:]
    if clip <= hard + soft:  # the clips at that end hold it
        turned = min(soft, max(clip - hard, 0))
        out = [("H", hard + turned)] + (
            [("S", soft - turned)] if soft > turned else []) + rest
        return (out if from_start else out[::-1]), 0
    need = clip - hard - soft  # read bases to take off the alignment
    taken = ref_taken = 0
    kept = []
    k = 0
    while k < len(rest):
        op, n = rest[k]
        if taken == need and not kept and op == "D":  # the clip point abuts
            ref_taken += n
            k += 1
            continue
        if taken >= need:
            break
        takes_read = op in "MI=X"
        if takes_read and n > need - taken:
            if op == "I":  # an insertion the clip point falls in goes whole
                taken += n
            else:
                cut = need - taken
                taken += cut
                ref_taken += cut if op in _REF else 0
                kept.append((op, n - cut))
        else:
            taken += n if takes_read else 0
            ref_taken += n if op in _REF else 0
        k += 1
    out = [("H", hard + soft + taken)] + kept + rest[k:]
    if from_start:
        return out, ref_taken
    return out[::-1], 0


def read_pos_at(cigar, start, ref_pos, last_if_deleted):
    """1-based read position aligned to 1-based reference position
    ``ref_pos`` (``start`` the first aligned base), ``None`` outside the
    alignment; inside a deletion the last read base before it (1 at the
    read's start) where ``last_if_deleted``, else ``None`` (fgumi
    ``read_pos_at_ref_pos_raw``, ``cigar.rs:461-506``)."""
    if ref_pos < start:
        return None
    ref, read = start, 0
    for op, n in cigar:
        if op in _REF:
            if ref <= ref_pos < ref + n:
                if op in _ALIGNED:
                    return read + ref_pos - ref + 1
                return max(read, 1) if last_if_deleted else None
            ref += n
        if op in _QUERY:
            read += n
    return None


def _ref_length(cigar):
    return sum(n for op, n in cigar if op in _REF)


def _geometry(fwd, rev, opts, phase_check):
    """Steps 4-6 of one molecule: ``fwd`` / ``rev`` its kept forward and
    reverse reads, each (clipped CIGAR, first aligned base 1-based, clipped
    length), in record order. Returns (consensus length, None) or (0, the
    reason it is rejected)."""
    lp = max(fwd, key=lambda r: _ref_length(r[0]))  # max(): first maximum
    ln = max(rev, key=lambda r: _ref_length(r[0]))
    overlap_start = ln[1]
    pos_end = lp[1] + max(_ref_length(lp[0]) - 1, 0)
    if pos_end - overlap_start + 1 < opts["min_duplex_length"]:
        return 0, "InsufficientOverlap"
    if phase_check:
        at = [[read_pos_at(r[0], r[1], p, True) for r in (lp, ln)]
              for p in (overlap_start, pos_end)]
        if None in at[0] + at[1] or at[0][0] - at[0][1] != at[1][0] - at[1][1]:
            return 0, "IndelErrorBetweenStrands"
    p = read_pos_at(lp[0], lp[1], pos_end, False)
    q = read_pos_at(ln[0], ln[1], pos_end, False)
    if p is None or q is None:
        return 0, "IndelErrorBetweenStrands"
    return p + ln[2] - q, None


def _strands(codes, quals, final, keep, mol, n_mol, opts, dtype):
    """The single-strand consensus of every molecule's kept reads of one
    side as ``reference_codec._block`` takes it: (bases, quals, depth,
    errors, length), a molecule a row (zeros where none is kept)."""
    rows = np.flatnonzero(keep)
    count = np.bincount(mol[rows], minlength=n_mol)
    width = codes.shape[1]
    out = [np.full((n_mol, width), reference.N_CODE, dtype=np.uint8),
           np.zeros((n_mol, width), dtype=np.uint8),
           np.zeros((n_mol, width), dtype=np.int32),
           np.zeros((n_mol, width), dtype=np.int32),
           np.zeros(n_mol, dtype=np.int64)]
    jobs = np.flatnonzero(count)
    if len(jobs):
        start = (np.cumsum(count) - count)[jobs]
        for whole, part in zip(out, reference.call_jobs(
                codes[rows], quals[rows], final[rows], start, count[jobs],
                opts, dtype)):
            whole[jobs] = part
    return tuple(out)


def codec(d, opts, dtype=np.float64, checks=True, prove_plain=True):
    """Expected output records of ``codec`` on an ``aligned_codec_bam``
    input. ``checks=False`` leaves out the filter and the phase check.
    Returns (flat record bytes, records, tallies)."""
    opts = {**rc.CODEC_DEFAULTS, **opts}
    sizes, mol = d["sizes"], d["fam"]
    n_mol, n_pairs = len(sizes), len(mol)
    first = np.cumsum(sizes) - sizes
    reason = np.full(n_mol, -1, dtype=np.int64)  # index into REASONS
    reason[sizes < opts["min_reads"]] = 0
    # steps 1-2, a side a read: 1 the forward reads (3' end on the right),
    # 2 the reverse ones (3' end on the left)
    side = {}
    for m in (1, 2):
        clip = ra.mate_clips(d, m)
        codes, quals, final = ra.source_reads(
            d[f"codes{m}"], d[f"quals{m}"], d[f"len{m}"], m == 2, clip, 0)
        if (final != d[f"len{m}"] - clip).any():
            raise NotImplementedError("a read trimmed to nothing or ending "
                                      "in N")
        cigar, ncig, pos = d[f"cigar{m}"], d[f"ncig{m}"], d[f"pos{m}"]
        plain = (ncig == 1) & ((cigar[:, 0] & 0xF) == 0)
        side[m] = dict(codes=codes, quals=quals, final=final, clip=clip,
                       plain=plain, cigar=cigar, ncig=ncig, pos=pos)

    def read(m, i):
        """(clipped CIGAR, first aligned base 1-based, clipped length)."""
        s = side[m]
        cigar, moved = clip_cigar(ra.decode(s["cigar"][i, :s["ncig"][i]]),
                                  int(s["clip"][i]), m == 2)
        return cigar, int(s["pos"][i]) + 1 + moved, int(s["final"][i])

    # steps 3-6, a molecule at a time
    keep = {m: np.ones(n_pairs, dtype=bool) for m in (1, 2)}
    filt = np.zeros((n_mol, len(FILTER)), dtype=np.int64)
    length = np.zeros(n_mol, dtype=np.int64)
    for g in np.flatnonzero(reason < 0):
        rows = range(first[g], first[g] + sizes[g])
        kept = {}
        for m in (1, 2):
            reads = [read(m, i) for i in rows]
            idx = list(range(len(reads)))
            plain = all(side[m]["plain"][i] for i in rows)
            if checks and len(reads) >= 2 and not (prove_plain and plain):
                simple = [ra.simplify(c) for c, _p, _l in reads]
                idx = ra.most_common_alignment(
                    [(r[2], s[::-1] if m == 2 else s)
                     for r, s in zip(reads, simple)])
            if len(reads) >= 2:
                filt[g] += (1, len(idx) == len(reads), len(reads),
                            len(reads) - len(idx))
            for k in set(range(len(reads))) - set(idx):
                keep[m][first[g] + k] = False
            kept[m] = [reads[k] for k in idx]
        if min(len(kept[1]), len(kept[2])) < opts["min_reads"]:
            reason[g] = 0
            continue
        length[g], why = _geometry(kept[1], kept[2], opts, checks)
        if why is not None:
            reason[g] = REASONS.index(why)
    # step 7: the single-strand consensuses of the kept reads
    ss_opts = {"error_rate_pre_umi": opts["error_rate_pre_umi"],
               "error_rate_post_umi": opts["error_rate_post_umi"],
               "min_reads": 1, "min_consensus_base_quality": 0}
    live_pair = (reason < 0)[mol]
    strands = [_strands(s["codes"], s["quals"], s["final"],
                        keep[m] & live_pair, mol, n_mol, ss_opts, dtype)
               for m, s in side.items()]
    placed = {**d, "insert": length}
    alive = np.flatnonzero(reason < 0)
    flats = []
    for lo in range(0, len(alive), rc._BLOCK):
        rows = alive[lo:lo + rc._BLOCK]
        segs, clipped, high, _bases, _disagree = rc._block(
            placed, rows, strands, opts)
        reason[rows[clipped]] = REASONS.index("ClipOverlapFailed")
        reason[rows[high]] = REASONS.index("HighDuplexDisagreement")
        good = ~(clipped | high)
        flats.append(traffic.pack_rows([
            (seg[good], None if lens is None else lens[good])
            for seg, lens in segs])[0])
    # the account
    minority = filt[:, 3]
    kept_reads = 2 * sizes - minority
    reads = {"MinorityAlignment": int(minority.sum())}
    molecules = {}
    for k, name in enumerate(REASONS):  # a rejected molecule's kept reads
        hit = reason == k
        reads[name] = int(kept_reads[hit].sum())
        molecules[name] = int(hit.sum())
    reads["ConsensusReads"] = int(kept_reads[reason < 0].sum())
    tallies = {"reads": {k: v for k, v in reads.items() if v},
               "molecules": {k: v for k, v in molecules.items() if v},
               "filter": dict(zip(FILTER, map(int, filt.sum(axis=0)))),
               "filter_by_molecule": filt,
               "input_reads": int(2 * sizes.sum())}
    flat = np.concatenate(flats) if flats else np.zeros(0, dtype=np.uint8)
    return flat, int((reason < 0).sum()), tallies

