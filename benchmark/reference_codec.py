"""The plain reference of ``codec`` (fgbio's CallCodecConsensusReads as
fgumi's ``codec_caller.rs`` has it), worked out from the generator's arrays
in straightforward numpy. Like ``reference.py``, whose single-strand model it
reuses, it imports nothing of the program and takes nothing the program made;
``dtype=np.float32`` is the control.

Per molecule (MI), whose read pairs each cover both strands of the duplex:

1. pair the records by name: a row of the generator's arrays *is* a pair (the
   name is a function of molecule and ordinal), its forward read in
   ``codes1`` and its reverse-flagged read in ``codes2``; ``r1_reverse`` says
   which of them is R1. A molecule needs ``min_reads`` pairs
   (``InsufficientReads``);
2. adjusted positions of the two ``M`` runs and their overlap: the forward
   reads start at the molecule's first base, the reverse ones end at its last,
   so the duplex region is ``len1 + len2 - insert`` bases and must reach
   ``min_duplex_length`` (``InsufficientOverlap``); the consensus (fragment)
   length is the insert;
3. one single-strand consensus of the R1s and one of the R2s, the vanilla
   model at ``min_reads`` 1 and quality threshold 0, each in its reads'
   sequencing orientation (a reverse-flagged read is reverse complemented
   first, as the order of the lanes decides the last ulp); no base is masked
   by input quality and no trailing N is trimmed;
4. the negative strand's consensus is reverse complemented back, and both are
   padded onto the fragment with lowercase ``n`` / Q0 / depth 0 (the forward
   strand on the right, the reverse strand on the left); a strand longer than
   the fragment rejects the molecule (``ClipOverlapFailed``);
5. the combine, R1's strand as ``a`` and R2's as ``b``: where both have a
   base, agreement sums the qualities (cap 93), disagreement keeps the better
   base with the difference, a tie keeps base ``a`` at Q2, and Q2 masks the
   base to N; where one has a base it passes through (N where its quality is
   Q2); an uppercase N on either side masks to (N, Q2); depths add, and the
   errors are fgbio's recount (agreement adds them, else the chosen side's
   errors plus the other side's agreeing reads);
6. the gates on the whole molecule: more than ``max_duplex_disagreements``
   disagreeing duplex bases, or a rate above
   ``max_duplex_disagreement_rate`` (``HighDuplexDisagreement``);
7. the masks, on the qualities only: ``outer_bases_qual`` over the first and
   last ``outer_bases_length`` bases, then ``single_strand_qual`` wherever
   either strand has no base;
8. one unmapped fragment record a molecule, in R1's sequencing orientation,
   molecules in stream order, tags RG MI cD cM cE aD aM aE bD bM bE RX (the
   strand aggregates run over the padded strands, so a padded strand's ``M``
   is 0).

Departures from ``codec_caller.rs`` as SURVEY.md and the docstring of
``fgumi_tpu/consensus/codec.py`` record it: no downsampling
(``--max-reads``; the program pins its own random stream there, upstream
seeds another), no ``--cell-tag``, no per-base tags, no rejects stream; the
most-common-alignment filter keeps every read, all CIGARs being one ``M``
run; RX is the molecule's one UMI (the consensus of equal strings).

What is assumed of the inputs (true of ``traffic/codec_bam.py``): what
``reference.py`` assumes, every record a primary read of an FR pair on one
contig with one ``M`` run and no N, one read length a side, a molecule's
pairs PCR copies (same start, same insert), and no read extending past its
mate's far end (nothing is clipped).
"""

import numpy as np

import reference
import traffic
from reference import I16_MAX, MAX_PHRED, MIN_PHRED
from traffic import COMPLEMENT, N_CODE, const, ints, pack_rows

PAD_CODE = 5  # lowercase n: a position the strand does not reach
REASONS = ("InsufficientReads", "InsufficientOverlap", "ClipOverlapFailed",
           "HighDuplexDisagreement")
CODEC_DEFAULTS = {
    "error_rate_pre_umi": 45, "error_rate_post_umi": 40, "min_reads": 1,
    "min_duplex_length": 1, "max_duplex_disagreements": None,
    "max_duplex_disagreement_rate": 1.0, "outer_bases_length": 5,
    "outer_bases_qual": None, "single_strand_qual": None}
_BLOCK = 16384  # molecules placed, combined and serialised at a time
_FAR = 1 << 30


def combine(a_b, b_b, a_q, b_q, a_d, b_d, a_e, b_e):
    """The per-position strand combine over arrays of one shape: base codes
    (0-3, ``N_CODE``, ``PAD_CODE``), qualities, depths and errors (capped at
    ``I16_MAX``) of the ``a`` and ``b`` strands. Returns (base, quality,
    depth, errors, both strands have a base, they disagree)."""
    a_q, b_q = a_q.astype(np.int32), b_q.astype(np.int32)
    a_d, b_d = a_d.astype(np.int32), b_d.astype(np.int32)
    a_e, b_e = a_e.astype(np.int32), b_e.astype(np.int32)
    a_has, b_has = a_b < N_CODE, b_b < N_CODE
    both, one = a_has & b_has, a_has ^ b_has
    agree = both & (a_b == b_b)
    b_wins = both & ~agree & (b_q > a_q)  # a tie keeps base a
    dup_q = np.where(agree, np.minimum(a_q + b_q, MAX_PHRED),
                     np.maximum(np.abs(a_q - b_q), MIN_PHRED))
    dup_e = np.where(agree, a_e + b_e,
                     np.where(b_wins, b_e + np.maximum(a_d - a_e, 0),
                              a_e + np.maximum(b_d - b_e, 0)))
    lone_b, lone_q = np.where(a_has, a_b, b_b), np.where(a_has, a_q, b_q)
    base = np.where(both, np.where(b_wins, b_b, a_b), lone_b)
    qual = np.where(both, dup_q, np.where(one, lone_q, MIN_PHRED))
    base = np.where((both | one) & (qual != MIN_PHRED), base, N_CODE)
    depth = np.where(both, a_d + b_d,
                     np.where(one, np.where(a_has, a_d, b_d), 0))
    errors = np.where(both, dup_e,
                      np.where(one, np.where(a_has, a_e, b_e), a_e + b_e))
    no_call = (a_b == N_CODE) | (b_b == N_CODE)
    return (np.where(no_call, N_CODE, base).astype(np.uint8),
            np.where(no_call, MIN_PHRED, qual).astype(np.uint8),
            np.minimum(depth, 2 * I16_MAX), np.minimum(errors, I16_MAX),
            both, both & ~agree)


def _place(strand, rows, offset, flip, width):
    """The strand consensus of the molecules ``rows`` laid onto fragments of
    ``width`` columns from column ``offset`` on; ``flip``: the strand is held
    in the sequencing orientation of reverse reads and is reverse
    complemented back. Columns it does not reach are pad / Q0 / depth 0."""
    bases, quals, depth, errors, clen = (x[rows] for x in strand)
    k = np.arange(width)[None, :] - offset[:, None]
    live = (k >= 0) & (k < clen[:, None])
    src = np.clip((clen[:, None] - 1 - k) if flip else k, 0,
                  bases.shape[1] - 1)
    b = np.take_along_axis(bases, src, axis=1)
    return (np.where(live, COMPLEMENT[b] if flip else b, PAD_CODE),
            np.where(live, np.take_along_axis(quals, src, axis=1), 0),
            np.where(live, np.minimum(np.take_along_axis(depth, src, axis=1),
                                      I16_MAX), 0),
            np.where(live, np.minimum(np.take_along_axis(errors, src, axis=1),
                                      I16_MAX), 0))


def _aggregates(letter, depth, errors, in_len):
    """``<l>D <l>M <l>E`` of one strand (or of the consensus): the largest and
    the least depth inside the fragment and errors over depth in float32."""
    n = len(depth)
    total_d, total_e = depth.sum(axis=1), errors.sum(axis=1)
    rate = np.where(total_d > 0, total_e.astype(np.float32)
                    / np.maximum(total_d, 1).astype(np.float32),
                    np.float32(0)).astype(np.float32)
    tag = letter.encode()
    return [(const(n, tag + b"Di"), None),
            (ints(("<i4",), depth.max(axis=1)), None),
            (const(n, tag + b"Mi"), None),
            (ints(("<i4",), np.where(in_len, depth, _FAR).min(axis=1)), None),
            (const(n, tag + b"Ef"), None), (ints(("<f4",), rate), None)]


def _block(d, rows, strands, opts):
    """Steps 4-8 for the molecules ``rows`` (none rejected so far). Returns
    (record segments of every molecule of the block, the block's rejects by
    step 4 and by step 6, duplex bases, disagreements)."""
    insert, r1_rev = d["insert"][rows], d["r1_reverse"][rows][:, None]
    n, width = len(rows), int(insert.max())
    col = np.arange(width)[None, :]
    in_len = col < insert[:, None]
    clipped = (insert < strands[0][4][rows]) | (insert < strands[1][4][rows])
    fwd = _place(strands[0], rows, np.zeros(n, dtype=np.int64), False, width)
    rev = _place(strands[1], rows, insert - strands[1][4][rows], True, width)
    a_b, a_q, a_d, a_e = (np.where(r1_rev, r, f) for f, r in zip(fwd, rev))
    b_b, b_q, b_d, b_e = (np.where(r1_rev, f, r) for f, r in zip(fwd, rev))
    base, qual, _depth, errors, both, differ = combine(
        a_b, b_b, a_q, b_q, a_d, b_d, a_e, b_e)
    duplex_bases = (both & in_len).sum(axis=1)
    disagreements = (differ & in_len).sum(axis=1)
    high = np.zeros(n, dtype=bool)
    if opts["max_duplex_disagreements"] is not None:
        high |= disagreements > opts["max_duplex_disagreements"]
    high |= disagreements / np.maximum(duplex_bases, 1) \
        > opts["max_duplex_disagreement_rate"]
    high &= duplex_bases > 0
    if opts["outer_bases_length"] > 0 and opts["outer_bases_qual"] is not None:
        outer = np.minimum(opts["outer_bases_length"], insert)[:, None]
        qual[(col < outer) | (col >= insert[:, None] - outer)] = \
            opts["outer_bases_qual"]
    if opts["single_strand_qual"] is not None:
        qual[(a_b >= N_CODE) | (b_b >= N_CODE)] = opts["single_strand_qual"]
    # the record is in R1's sequencing orientation
    src = np.clip(np.where(r1_rev, insert[:, None] - 1 - col, col), 0,
                  width - 1)
    seq = np.take_along_axis(base, src, axis=1)
    seq = np.where(r1_rev, COMPLEMENT[seq], seq)
    qual = np.take_along_axis(qual, src, axis=1)
    digits, ndig = traffic.digits(rows, 8)
    body = [
        (const(n, b"fgumi:"), None), (digits, ndig), (const(n, b"\x00"), None),
        (traffic.pack_seq(seq, insert), (insert + 1) // 2), (qual, insert),
        (const(n, b"RGZA\x00MIZ"), None), (digits, ndig),
        (const(n, b"\x00"), None)]
    body += _aggregates("c", np.where(in_len, a_d + b_d, 0),
                        np.where(in_len, errors, 0), in_len)
    body += _aggregates("a", a_d, a_e, in_len)
    body += _aggregates("b", b_d, b_e, in_len)
    body += [(const(n, b"RXZ"), None),
             (traffic.CODE_TO_ASCII[d["umi"][rows]], None),
             (const(n, b"\x00"), None)]
    segs = traffic.bam_record(body, -1, -1, 6 + ndig + 1, 0, 4680, 0, 4,
                              insert, -1, -1, 0)
    return segs, clipped, high & ~clipped, duplex_bases, disagreements


def codec(d, opts, dtype=np.float64):
    """Expected output records of ``codec`` on a ``codec_bam`` input. Returns
    (flat record bytes, records, input reads accounted for, a dict of what
    was counted: molecules rejected by each reason, duplex bases and
    disagreements of the molecules that reached the combine)."""
    opts = {**CODEC_DEFAULTS, **opts}
    sizes, fam, insert = d["sizes"], d["fam"], d["insert"]
    n_mol = len(sizes)
    fam_start = np.cumsum(sizes) - sizes
    len_f, len_r = d["len1"][fam_start], d["len2"][fam_start]
    if (d["len1"] != len_f[fam]).any() or (d["len2"] != len_r[fam]).any():
        raise NotImplementedError("read lengths differ inside a molecule")
    if (np.maximum(len_f, len_r) > insert).any():
        raise NotImplementedError("a read extends past its mate's far end")
    reason = np.full(n_mol, -1, dtype=np.int64)  # index into REASONS
    reason[sizes < opts["min_reads"]] = 0
    reason[(reason < 0)
           & (len_f + len_r - insert < opts["min_duplex_length"])] = 1
    ss_opts = {"error_rate_pre_umi": opts["error_rate_pre_umi"],
               "error_rate_post_umi": opts["error_rate_post_umi"],
               "min_reads": 1, "min_consensus_base_quality": 0}
    strands = []
    for key, reverse in (("1", False), ("2", True)):
        codes, quals, final = reference.source_reads(
            d["codes" + key], d["quals" + key], d["len" + key], reverse, 0)
        if (final != d["len" + key]).any():
            raise NotImplementedError("a read ends in N")
        strands.append(reference.call_jobs(codes, quals, final, fam_start,
                                           sizes, ss_opts, dtype))
    alive = np.flatnonzero(reason < 0)
    flats, counted = [], {"duplex_bases": 0, "disagreements": 0}
    for lo in range(0, len(alive), _BLOCK):
        rows = alive[lo:lo + _BLOCK]
        segs, clipped, high, duplex_bases, disagreements = _block(
            d, rows, strands, opts)
        reason[rows[clipped]] = 2
        reason[rows[high]] = 3
        counted["duplex_bases"] += int(duplex_bases[~clipped].sum())
        counted["disagreements"] += int(disagreements[~clipped].sum())
        keep = ~(clipped | high)
        flats.append(pack_rows([
            (seg[keep], None if lens is None else lens[keep])
            for seg, lens in segs])[0])
    for k, name in enumerate(REASONS):
        counted[name] = int((reason == k).sum())
    flat = np.concatenate(flats) if flats else np.zeros(0, dtype=np.uint8)
    return flat, int((reason < 0).sum()), int(2 * sizes.sum()), counted
