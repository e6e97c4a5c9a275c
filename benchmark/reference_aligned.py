"""The plain reference of ``simplex`` on a BAM as an aligner writes it
(layout ``aligned_bam``): reads with soft clips and indels inside a UMI
family. The consensus arithmetic and the record layout are
``reference.py``'s (``call_jobs``, ``record_segments``); what is here is what
a CIGAR other than one ``M`` run asks for, written again from the published
descriptions, float64, numpy where a rule is over arrays and plain Python
where it is a read's or a family's. Nothing of the program is imported, and
nothing it made is read: the input is the generator's arrays (``pos<m>``,
``cigar<m>`` as BAM CIGAR words with ``ncig<m>`` of them, ``len<m>``,
``codes<m>``, ``quals<m>``, R1 forward and R2 reverse on one contig, both
mapped, primary, with ``MC`` = the mate's CIGAR).

The steps, in the caller's order (fgbio ``CallMolecularConsensusReads`` /
``VanillaUmiConsensusCaller``; fgumi ``vanilla_caller.rs``, SURVEY.md 2.5):

1. the overlapping-bases correction of each pair, walked by reference
   position (``overlap_correct``);
2. source reads: orientation, the input-quality mask, the clip of bases past
   the mate's far end, the trailing-N trim (``source_reads``);
3. the most-common-alignment filter of each family's R1s and of its R2s
   (``most_common_alignment``), with its ``MinorityAlignment`` tally;
4. consensus of what is left, R1 and R2 of a molecule both or neither.

Departures are marked ``Departure:`` where they are made.
"""

from collections import Counter

import numpy as np

import reference
import traffic
from reference import MIN_PHRED, N_CODE

OPS = "MIDNSHP=X"  # BAM op codes 0-8 (SAM spec v1, 4.2)
_M, _I, _D, _N, _S, _EQ, _X = 0, 1, 2, 3, 4, 7, 8
_QUERY_OPS = frozenset("MIS=X")
_REF_OPS = frozenset("MDN=X")
#: the same classes as op codes, for the array passes
_QUERY_CODES, _REF_CODES = (_M, _I, _S, _EQ, _X), (_M, _D, _N, _EQ, _X)
_ALIGNED_CODES = (_M, _EQ, _X)


# ------------------------------------------------- CIGAR rules, a read each

def decode(words):
    """BAM CIGAR words -> [(op letter, length)]."""
    return [(OPS[int(w) & 0xF], int(w) >> 4) for w in words]


def simplify(cigar):
    """``S``, ``=``, ``X`` and ``H`` become ``M``, equal neighbours merge
    (fgbio ``UmiConsensusCaller.simplifyCigar``: "S/EQ/X/H -> M" and
    coalesce; fgumi ``noodles_compat.rs:10-55``)."""
    out = []
    for op, n in cigar:
        op = "M" if op in "S=XH" else op
        if out and out[-1][0] == op:
            out[-1] = (op, out[-1][1] + n)
        else:
            out.append((op, n))
    return out


def truncate(cigar, query_length):
    """The leading part of ``cigar`` that covers ``query_length`` read bases:
    the source read's CIGAR after its 3' end was clipped or trimmed (fgbio
    ``Cigar.truncateToQueryLength``; ``vanilla_caller.rs:893-927``). An op
    that takes no read base (``D``) is kept while read bases remain."""
    out, left = [], query_length
    for op, n in cigar:
        if left == 0:
            break
        if op in _QUERY_OPS:
            n = min(n, left)
            left -= n
        out.append((op, n))
    return out


def is_prefix(a, b):
    """fgbio ``Cigar.isPrefixOf`` (``clipper.rs:2705-2728``): every op of
    ``a`` equals ``b``'s, every length too but the last of ``a``, which may
    be shorter."""
    if len(a) > len(b):
        return False
    for i, ((op_a, n_a), (op_b, n_b)) in enumerate(zip(a, b)):
        if op_a != op_b:
            return False
        if n_a > n_b if i == len(a) - 1 else n_a != n_b:
            return False
    return True


def _tie_key(cigar):
    """The order that breaks a tie between two groups of one size
    (``vanilla_caller.rs:79-111``): element by element the length first,
    then the op by its BAM code, and of two CIGARs one of which is the
    other's leading part the shorter is smaller. A tuple of (length, code)
    pairs orders exactly so."""
    return tuple((n, OPS.index(op)) for op, n in cigar)


def most_common_alignment(reads):
    """fgbio ``UmiConsensusCaller.filterToMostCommonAlignment``
    (``vanilla_caller.rs:50-127,1038``). ``reads`` is [(length, CIGAR)] of
    one family's source reads of one type, the CIGAR simplified, in read
    orientation, truncated to the read's length. Returns the indices kept,
    ascending.

    The reads are taken longest first (a stable sort: equal lengths keep
    their order). A read joins **every** group whose CIGAR it is a prefix
    of, and founds a group of its own if it joined none. The largest group
    wins; of equal sizes the one with the smaller CIGAR (``_tie_key``)."""
    if len(reads) < 2:
        return list(range(len(reads)))
    groups = []  # [CIGAR of the founder, its members]
    for i in sorted(range(len(reads)), key=lambda i: -reads[i][0]):
        cigar = reads[i][1]
        joined = False
        for group in groups:
            if is_prefix(cigar, group[0]):
                group[1].append(i)
                joined = True
        if not joined:
            groups.append((cigar, [i]))
    best = min(groups, key=lambda g: (-len(g[1]), _tie_key(g[0])))
    return sorted(best[1])


def _soft_ends(cigar):
    """(leading, trailing) soft-clipped bases, hard clips outside them."""
    def leading(ops):
        total = 0
        for op, n in ops:
            if op == "S":
                total += n
            elif op != "H":
                break
        return total
    return leading(cigar), leading(cigar[::-1])


def _read_base_at(cigar, pos, target):
    """1-based read base aligned to reference position ``target`` (1-based;
    ``pos`` is the first aligned base), 0 inside a deletion or outside."""
    ref, read = pos, 0
    for op, n in cigar:
        if op in "M=X":
            if target < ref:
                return 0
            if target < ref + n:
                return read + target - ref + 1
            read, ref = read + n, ref + n
        elif op in "IS":
            read += n
        elif op in "DN":
            if ref <= target < ref + n:
                return 0
            ref += n
    return 0


def bases_past_mate(cigar, pos, reverse, mate_cigar, mate_pos):
    """How many bases at a read's 3' end lie past its FR mate's far end,
    soft clips of both counted (fgbio ``numBasesExtendingPastMate``; fgumi
    ``overlap.rs:172-231``, the mate's ends from ``MC``, ``:233-247``).
    Positions 1-based."""
    lead, trail = _soft_ends(mate_cigar)
    mate_ref = sum(n for op, n in mate_cigar if op in _REF_OPS)
    mate_start, mate_end = mate_pos - lead, mate_pos - 1 + mate_ref + trail
    lead, trail = _soft_ends(cigar)
    if reverse:
        if pos <= mate_start:
            return max(_read_base_at(cigar, pos, mate_start) - 1, 0)
        return max(lead - (pos - mate_start), 0)
    end = pos - 1 + sum(n for op, n in cigar if op in _REF_OPS)
    if end >= mate_end:
        length = sum(n for op, n in cigar if op in _QUERY_OPS)
        return max(length - _read_base_at(cigar, pos, mate_end), 0)
    return max(trail - (mate_end - end), 0)


# ------------------------------------------------------ over arrays, numpy

def _op_len(cigar):
    return cigar & 0xF, (cigar >> 4).astype(np.int64)


def ref_positions(cigar, pos, width):
    """(reads, width) reference position (0-based) of every read base an
    ``M``, ``=`` or ``X`` op aligns, -1 for every other base (inserted,
    soft-clipped, past the read's end)."""
    op, n = _op_len(cigar)
    query = np.isin(op, _QUERY_CODES) * n
    ref = np.isin(op, _REF_CODES) * n
    q0 = np.cumsum(query, axis=1) - query  # each op's first read base
    r0 = pos[:, None] + np.cumsum(ref, axis=1) - ref
    cols = np.arange(width)[None, :]
    out = np.full((len(pos), width), -1, dtype=np.int64)
    for j in range(cigar.shape[1]):
        aligned = np.isin(op[:, j], _ALIGNED_CODES)[:, None] \
            & (cols >= q0[:, j, None]) & (cols < (q0[:, j] + n[:, j])[:, None])
        out = np.where(aligned, r0[:, j, None] + cols - q0[:, j, None], out)
    return out


def ref_span(cigar):
    """Reference bases each row of CIGAR words covers."""
    op, n = _op_len(cigar)
    return (np.isin(op, _REF_CODES) * n).sum(axis=1)


def overlap_correct(d):
    """Correct, in place, the bases a pair's two reads both align to one
    reference position (fgbio ``OverlappingBasesConsensusCaller``; fgumi
    ``overlapping.rs``: the merge walk ``:560-620``, the strategies
    ``:20-39``, both ``consensus``): only ``M`` bases pair, an inserted or
    soft-clipped base has no partner, a no-call on either side is skipped.
    Agreement: the qualities sum, capped at 93. Disagreement: the better
    base wins with the difference (at least 2); equal qualities mask both
    to N/Q2."""
    c1, q1, c2, q2 = d["codes1"], d["quals1"], d["codes2"], d["quals2"]
    width = c1.shape[1]
    # pairs whose aligned spans meet at all
    rows = np.flatnonzero(
        (d["pos1"] < d["pos2"] + ref_span(d["cigar2"]))
        & (d["pos2"] < d["pos1"] + ref_span(d["cigar1"])))
    if not len(rows):
        return
    ref1 = ref_positions(d["cigar1"][rows], d["pos1"][rows], width)
    ref2 = ref_positions(d["cigar2"][rows], d["pos2"][rows], width)
    cols = np.arange(width)[None, :]
    ref1 = np.where(cols < d["len1"][rows, None], ref1, -1)
    ref2 = np.where(cols < d["len2"][rows, None], ref2, -1)
    # R2's read base at each reference offset from its first aligned base
    base = d["pos2"][rows, None]
    span = int((ref2.max(axis=1) - base[:, 0]).max()) + 1
    at2 = np.full((len(rows), span), -1, dtype=np.int64)
    r, o = np.nonzero(ref2 >= 0)
    at2[r, ref2[r, o] - base[r, 0]] = o
    rel = ref1 - base
    live = (ref1 >= 0) & (rel >= 0) & (rel < span)
    o2 = np.where(live, np.take_along_axis(at2, np.clip(rel, 0, span - 1),
                                           axis=1), -1)
    live &= o2 >= 0
    r, o1 = np.nonzero(live)
    o2 = o2[r, o1]
    r = rows[r]
    b1, b2 = c1[r, o1], c2[r, o2]
    called = (b1 != N_CODE) & (b2 != N_CODE)
    r, o1, o2 = r[called], o1[called], o2[called]
    b1, b2 = b1[called], b2[called]
    qa, qb = q1[r, o1].astype(np.int32), q2[r, o2].astype(np.int32)
    agree, tie = b1 == b2, qa == qb
    base = np.where(agree, b1,
                    np.where(tie, N_CODE, np.where(qa > qb, b1, b2)))
    qual = np.where(agree, np.minimum(qa + qb, 93),
                    np.where(tie, MIN_PHRED,
                             np.maximum(np.abs(qa - qb), MIN_PHRED)))
    c1[r, o1] = c2[r, o2] = base
    q1[r, o1] = q2[r, o2] = qual


def mate_clips(d, mate):
    """``bases_past_mate`` of every read of one mate. Only a read whose
    unclipped end passes its mate's can have any, so only those are walked
    (the test is the rule's own first comparison, made over arrays)."""
    other = 3 - mate
    cigar, mate_cigar = d[f"cigar{mate}"], d[f"cigar{other}"]
    ncig, mate_ncig = d[f"ncig{mate}"], d[f"ncig{other}"]
    pos, mate_pos = d[f"pos{mate}"], d[f"pos{other}"]

    def soft(words, col):
        """Length of op ``col`` of each row where it is a soft clip."""
        op, n = _op_len(words[np.arange(len(words)), col])
        return np.where(op == _S, n, 0)

    if mate == 2:  # reverse: its unclipped start against the mate's
        passes = pos - soft(cigar, 0) < mate_pos - soft(mate_cigar, 0)
    else:
        passes = pos + ref_span(cigar) + soft(cigar, ncig - 1) \
            > mate_pos + ref_span(mate_cigar) + soft(mate_cigar, mate_ncig - 1)
    clips = np.zeros(len(pos), dtype=np.int64)
    for i in np.flatnonzero(passes):
        clips[i] = bases_past_mate(
            decode(cigar[i, :ncig[i]]), int(pos[i]) + 1, mate == 2,
            decode(mate_cigar[i, :mate_ncig[i]]), int(mate_pos[i]) + 1)
    return clips


def source_reads(codes, quals, lens, reverse, clip, min_input_q):
    """fgbio ``UmiConsensusCaller.toSourceRead``
    (``vanilla_caller.rs:940-1032``): the read in sequencing orientation,
    bases under the input quality masked to N/Q2, ``clip`` bases taken off
    its 3' end, then trailing N trimmed. Returns (codes, quals, length)."""
    width = codes.shape[1]
    cols = np.arange(width)[None, :]
    if reverse:
        idx = np.clip(lens[:, None] - 1 - cols, 0, width - 1)
        codes = traffic.COMPLEMENT[np.take_along_axis(codes, idx, axis=1)]
        quals = np.take_along_axis(quals, idx, axis=1)
    kept = cols < np.maximum(lens - clip, 0)[:, None]
    low = (quals < min_input_q) & kept
    codes = np.where(low | ~kept, N_CODE, codes).astype(np.uint8)
    quals = np.where(low, MIN_PHRED, quals).astype(np.uint8)
    called = codes != N_CODE
    final = np.where(called.any(axis=1),
                     width - np.argmax(called[:, ::-1], axis=1), 0)
    return codes, quals, final


def filter_families(d, mate, final, candidate, alignment_filter=True,
                    prove_plain=True):
    """``most_common_alignment`` over every family's reads of one mate.
    ``candidate`` marks the source reads (of families still in the running).
    Returns the reads kept.

    Departure: a family whose candidate reads all have a CIGAR of one ``M``
    op is not walked when ``prove_plain``: simplified and truncated they are
    ``<length>M`` each, every one a prefix of the longest, so the first group
    takes them all and no second is founded. ``prove_plain=False`` walks them
    too (the tests do, and find the same)."""
    keep = candidate.copy()
    if not alignment_filter:
        return keep
    fam, sizes = d["fam"], d["sizes"]
    cigar, ncig = d[f"cigar{mate}"], d[f"ncig{mate}"]
    plain = (ncig == 1) & ((cigar[:, 0] & 0xF) == _M)
    walk = np.bincount(fam[candidate], minlength=len(sizes)) >= 2
    if prove_plain:
        walk &= np.bincount(fam[candidate & ~plain], minlength=len(sizes)) > 0
    first = np.cumsum(sizes) - sizes
    for f in np.flatnonzero(walk):
        rows = first[f] + np.flatnonzero(
            candidate[first[f]:first[f] + sizes[f]])
        reads = []
        for i in rows:
            simple = simplify(decode(cigar[i, :ncig[i]]))
            if mate == 2:  # a reverse-strand read, in read orientation
                simple = simple[::-1]
            reads.append((int(final[i]), truncate(simple, int(final[i]))))
        kept = set(most_common_alignment(reads))
        for k, i in enumerate(rows):
            if k not in kept:
                keep[i] = False
    return keep


def simplex(d, opts, dtype=np.float64, alignment_filter=True,
            prove_plain=True):
    """Expected output records of ``simplex`` on an ``aligned_bam`` input:
    for every molecule (MI) whose R1s and R2s both leave a consensus, one R1
    and one R2 consensus read, in input order. Returns (flat record bytes,
    records, tallies): ``tallies`` counts every input read once, under
    ``ConsensusReads`` (it went into a consensus) or under the reason it was
    set aside (fgbio's names)."""
    opts = {**reference.SIMPLEX_DEFAULTS, **opts}
    min_reads = opts["min_reads"]
    d = dict(d)
    for key in ("codes1", "codes2", "quals1", "quals2"):
        d[key] = d[key].copy()
    overlap_correct(d)
    sizes, fam = d["sizes"], d["fam"]
    n_fam = len(sizes)
    tally = Counter()

    def per_family(mask):
        return np.bincount(fam[mask], minlength=n_fam)

    def set_aside(reason, counts):
        if counts.sum():
            tally[reason] += int(counts.sum())

    # a family with too few reads in all (both mates counted) ends here
    running = 2 * sizes >= min_reads
    set_aside("InsufficientReads", 2 * sizes[~running])
    sources, alive = {}, {}
    for mate, reverse in ((1, False), (2, True)):
        codes, quals, final = source_reads(
            d[f"codes{mate}"], d[f"quals{mate}"], d[f"len{mate}"], reverse,
            mate_clips(d, mate), opts["min_input_base_quality"])
        ok = running & (sizes >= min_reads)
        set_aside("InsufficientReads", sizes[running & ~ok])
        candidate = ok[fam] & (final > 0)
        set_aside("ZeroLengthAfterTrimming",
                  per_family(ok[fam] & (final == 0)))
        count = per_family(candidate)
        set_aside("InsufficientReads", count[ok & (count < min_reads)])
        ok &= count >= min_reads
        candidate &= ok[fam]
        keep = filter_families(d, mate, final, candidate, alignment_filter,
                               prove_plain)
        set_aside("MinorityAlignment", per_family(candidate & ~keep))
        count = per_family(keep)
        set_aside("InsufficientReads", count[ok & (count < min_reads)])
        ok &= count >= min_reads
        sources[mate] = (codes, quals, final, keep & ok[fam])
        alive[mate] = ok
    # R1 and R2 of a molecule both, or neither (vanilla_caller.rs:1166-1185)
    both = alive[1] & alive[2]
    live = np.flatnonzero(both)
    results = []
    for mate in (1, 2):
        codes, quals, final, keep = sources[mate]
        set_aside("OrphanConsensus", per_family(keep & ~both[fam]))
        rows = np.flatnonzero(keep & both[fam])
        tally["ConsensusReads"] += len(rows)
        count = per_family(keep & both[fam])[live]
        results.append(reference.call_jobs(
            codes[rows], quals[rows], final[rows], np.cumsum(count) - count,
            count, opts, dtype))
    digits, ndig = traffic.digits(live, 8)
    # one row per molecule: R1's record, then R2's
    flat, _ = traffic.pack_rows(
        reference.record_segments(digits, ndig, 77, results[0])
        + reference.record_segments(digits, ndig, 141, results[1]))
    return flat, 2 * len(live), dict(tally)
