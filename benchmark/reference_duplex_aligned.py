"""The plain reference of ``duplex`` on a BAM as an aligner writes it (layout
``aligned_duplex_bam``): reads with soft clips and indels inside AB/BA
molecules. The single-strand model and the strand combine are
``reference_duplex.py``'s (``_combine``, ``_record_segments``, the min-reads
gate), the CIGAR rules ``reference_aligned.py``'s (``overlap_correct`` walked
by reference position, ``mate_clips`` from ``MC``, ``source_reads``,
``simplify``, ``truncate``, ``most_common_alignment`` with fgumi's
tie-breaks); what is here is how fgbio's ``DuplexConsensusCaller`` puts them
together. float64, numpy over arrays and plain Python over one set's reads;
nothing of the program is imported and nothing it made is read.
``dtype=np.float32`` is the control, and so is ``alignment_filter=False``.

Per molecule (base MI), with AB = its ``/A`` reads and BA = its ``/B`` reads,
in the caller's order (fgbio ``DuplexConsensusCaller
.consensusReadsFromSamRecords``; fgumi ``duplex_caller.rs:1755-1983``):

1. where both strands are present, each read pair is overlap corrected;
2. the min-reads gate ``[total, XY, YX]`` on the input R1 counts;
3. every read to its source read: orientation, the input-quality mask, the
   bases past the mate's far end (from ``MC``) and trailing N off;
4. the most-common-alignment filter over **X = AB-R1 + BA-R2** and over
   **Y = AB-R2 + BA-R1**, each set's reads in input order, AB's first (the
   forward reads of the molecule, and its reverse reads), with its
   ``MinorityAlignment`` tally;
5. each filtered set split back by strand: AB-R1 and BA-R2 from X, AB-R2 and
   BA-R1 from Y; then the four single-strand consensuses of what is left
   (vanilla model, ``min_reads`` 1, consensus quality threshold Q2);
6. a molecule with all four goes to the combine and the gate on its output
   depths (``reference_duplex``); one with AB's two and none of BA's, or
   BA's two and none of AB's, passes that strand through where YX = 0; any
   other molecule is rejected (``InsufficientReads``), even one the filter
   left with three of the four;
7. one record per output read, R1 then R2, molecules in stream order.

The account of the reads (``tallies``): every input read once, under
``ConsensusReads`` (kept by the filter in an emitted molecule),
``MinorityAlignment`` (set aside by the filter) or ``InsufficientReads`` (the
rest of a rejected molecule). Departure: fgbio rejects a failed molecule's
records as a whole; counted so, the reads the filter had already set aside
would count twice, so here, as in the program's rejects stream, they count
once, under ``MinorityAlignment``.

What is assumed of the inputs (true of ``traffic/aligned_duplex_bam.py``):
what ``reference_duplex.py`` assumes but the one ``M`` run, and
``reference_aligned.py``'s CIGAR inputs (``pos<m>``, ``cigar<m>``,
``ncig<m>``; ``<m>`` 1 the forward read of a pair, 2 the reverse one); no
read is trimmed to nothing.
"""

from collections import Counter

import numpy as np

import reference
import reference_aligned as ra
import reference_duplex as rd
import traffic
from reference import MIN_PHRED, N_CODE

_CORRECTED = ("codes1", "codes2", "quals1", "quals2")
_PAIR_KEYS = _CORRECTED + ("pos1", "pos2", "cigar1", "cigar2", "len1", "len2")


def _overlap_correct(d, rows):
    """``reference_aligned.overlap_correct`` over the read pairs ``rows``."""
    sub = {key: d[key][rows] for key in _PAIR_KEYS}
    ra.overlap_correct(sub)
    for key in _CORRECTED:
        d[key][rows] = sub[key]


def _strand_consensus(codes, quals, final, keep, fam, n_fam, opts, dtype):
    """Single-strand consensus of every strand family's kept reads of one
    type, as ``reference_duplex._combine`` takes it: (bases, quals, depth,
    errors, length), a strand family a row (zeros where none is kept), with
    the kept rows and their count and first row by strand family."""
    rows = np.flatnonzero(keep)
    count = np.bincount(fam[rows], minlength=n_fam)
    start = np.cumsum(count) - count
    codes, quals, final = codes[rows], quals[rows], final[rows]
    width = codes.shape[1]
    out = [np.full((n_fam, width), N_CODE, dtype=np.uint8),
           np.zeros((n_fam, width), dtype=np.uint8),
           np.zeros((n_fam, width), dtype=np.int32),
           np.zeros((n_fam, width), dtype=np.int32),
           np.zeros(n_fam, dtype=np.int64)]
    jobs = np.flatnonzero(count)
    if len(jobs):
        for whole, part in zip(out, reference.call_jobs(
                codes, quals, final, start[jobs], count[jobs], opts, dtype)):
            whole[jobs] = part
    return tuple(out), (codes, final), count, start


def duplex(d, opts, dtype=np.float64, alignment_filter=True,
           prove_plain=True):
    """Expected output records of ``duplex`` on an ``aligned_duplex_bam``
    input. Returns (flat record bytes, records, tallies)."""
    opts = {**rd.DUPLEX_DEFAULTS, **opts}
    gate = rd.min_reads_gate(opts["min_reads"])
    sizes, a_fam, b_fam = d["sizes"], d["a_fam"], d["b_fam"]
    fam, mol_of_fam = d["fam"], d["mol_of_fam"]
    n_fam, n_mol = len(sizes), len(a_fam)
    mol = mol_of_fam[fam]  # each read pair's molecule
    has_a, has_b = a_fam >= 0, b_fam >= 0
    d = dict(d)
    for key in _CORRECTED:
        d[key] = d[key].copy()
    if opts["consensus_call_overlapping_bases"]:
        _overlap_correct(d, np.flatnonzero((has_a & has_b)[mol]))
    tally = Counter()
    # the input gate on R1 counts (every pair has one R1 on its strand)
    n_a = np.where(has_a, sizes[np.maximum(a_fam, 0)], 0)
    n_b = np.where(has_b, sizes[np.maximum(b_fam, 0)], 0)
    emit = rd._passes(n_a, n_b, gate)
    if (~emit).any():
        tally["InsufficientReads"] += int(2 * (n_a + n_b)[~emit].sum())
    ss_opts = {"error_rate_pre_umi": opts["error_rate_pre_umi"],
               "error_rate_post_umi": opts["error_rate_post_umi"],
               "min_reads": 1, "min_consensus_base_quality": MIN_PHRED}
    # X (the forward reads) and Y (the reverse ones), filtered a molecule
    # each: its read pairs are one run of rows, AB's before BA's
    by_mol = {"fam": mol, "sizes": np.bincount(mol, minlength=n_mol)}
    strands, minority = [], np.zeros(n_mol, dtype=np.int64)
    for mate, reverse in ((1, False), (2, True)):
        codes, quals, final = ra.source_reads(
            d[f"codes{mate}"], d[f"quals{mate}"], d[f"len{mate}"], reverse,
            ra.mate_clips(d, mate), opts["min_input_base_quality"])
        if (final == 0).any():
            raise NotImplementedError("a read trimmed to nothing")
        candidate = emit[mol]
        keep = ra.filter_families(
            {**by_mol, f"cigar{mate}": d[f"cigar{mate}"],
             f"ncig{mate}": d[f"ncig{mate}"]},
            mate, final, candidate, alignment_filter, prove_plain)
        minority += np.bincount(mol[candidate & ~keep], minlength=n_mol)
        strands.append(_strand_consensus(codes, quals, final, keep, fam,
                                         n_fam, ss_opts, dtype))
    # which of the four single-strand reads each molecule still has
    present = [(has_a & (count[np.maximum(a_fam, 0)] > 0),
                has_b & (count[np.maximum(b_fam, 0)] > 0))
               for _ss, _src, count, _start in strands]
    (a1, b1), (a2, b2) = present
    four = a1 & a2 & b1 & b2
    lone = (a1 & a2 & ~b1 & ~b2) | (~a1 & ~a2 & b1 & b2)
    emit &= four | (lone & (gate[2] == 0))
    outs = []
    for (ss, src, count, start), (pa, pb) in zip(strands, present):
        out = rd._combine(ss, src, np.where(pa, a_fam, -1),
                          np.where(pb, b_fam, -1), count, start)
        # the gate again, on the depths the output read's strands reached
        depth = ss[2]
        live = np.arange(depth.shape[1]) < out["length"][:, None]
        reach = [np.where(live, rd._take(out[s], depth, depth.shape[1]), 0)
                 .max(axis=1) for s in ("a", "b")]
        emit &= (out["kind"] >= 0) & (~four
                                      | rd._passes(reach[0], reach[1], gate))
        outs.append(out)
    # the account of the reads
    kept = 2 * (n_a + n_b) - minority
    tally["MinorityAlignment"] += int(minority.sum())
    tally["ConsensusReads"] += int(kept[emit].sum())
    failed = ~emit & rd._passes(n_a, n_b, gate)
    tally["InsufficientReads"] += int(kept[failed].sum())
    tallies = {k: v for k, v in tally.items() if v}
    keep = np.flatnonzero(emit)
    if not len(keep):
        return np.zeros(0, dtype=np.uint8), 0, tallies
    digits, ndig = traffic.digits(keep, 8)
    umi = traffic.CODE_TO_ASCII[d["umi"][keep]]
    half = umi.shape[1] // 2
    rx = np.concatenate((umi[:, :half], traffic.const(len(keep), b"-"),
                         umi[:, half:]), axis=1)
    segs = []
    for flag, out, (ss, *_rest) in zip((77, 141), outs, strands):
        out = {key: val[keep] for key, val in out.items()}
        segs += rd._record_segments(digits, ndig, flag, out, ss, rx)
    # one row per molecule: R1's record, then R2's
    flat, _ = traffic.pack_rows(segs)
    return flat, 2 * len(keep), tallies
