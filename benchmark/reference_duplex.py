"""The plain reference of ``duplex`` (fgbio's CallDuplexConsensusReads as
fgumi's ``duplex_caller.rs`` has it), worked out from the generator's arrays
in straightforward numpy. Like ``reference.py``, whose single-strand model it
reuses, it imports nothing of the program and takes nothing the program made;
``dtype=np.float32`` is the control.

Per molecule (base MI), with AB = its ``/A`` reads and BA = its ``/B`` reads:

1. where both strands are present, each strand's read pairs are overlap
   corrected (``reference.overlap_correct``);
2. four single-strand consensuses (AB-R1, AB-R2, BA-R1, BA-R2) with the
   vanilla model at ``min_reads`` 1 and consensus quality threshold Q2;
3. the min-reads gate ``[total, XY, YX]`` on the input pair counts;
4. output R1 = AB-R1 with BA-R2, output R2 = AB-R2 with BA-R1, each cut to
   the shorter side; a side without a positive depth inside that length
   drops out and the other passes through whole; agreement sums the
   qualities (cap 93), disagreement keeps the better base with the
   difference, equal qualities or an N on either side give (N, Q2); the
   errors of a combined read are recounted from both strands' source reads
   against the combined base before masking;
5. a molecule with one strand passes that strand through when YX = 0; the
   gate is applied again on the output reads' depths;
6. one record per output read, R1 then R2, molecules in stream order, tags
   MI RG aD aE aM ac ad ae aq bD bE bM [bc bd be bq] cD cE cM RX.

What is assumed of the inputs (true of ``traffic/duplex_bam.py``): what
``reference.py`` assumes, every read paired with one ``M`` run (so the
alignment filter keeps all), forward reads first-of-pair on ``/A`` and
second on ``/B``, and every read of a molecule carrying the molecule's UMI
pair, flipped on ``/B`` (so the RX consensus is ``u1-u2``).
"""

import numpy as np

import reference
import traffic
from reference import I16_MAX, MAX_PHRED, MIN_PHRED
from traffic import N_CODE, const, ints, pack_rows

DUPLEX_DEFAULTS = {
    "error_rate_pre_umi": 45, "error_rate_post_umi": 40,
    "min_input_base_quality": 10, "min_reads": [1],
    "consensus_call_overlapping_bases": True}
_FAR = 1 << 30


def min_reads_gate(values):
    """``--min-reads`` of 1-3 values -> (total, XY, YX), padded with the
    last."""
    values = list(values)
    total = values[0]
    xy = values[1] if len(values) > 1 else values[-1]
    yx = values[2] if len(values) > 2 else values[-1]
    return total, xy, yx


def _passes(na, nb, gate):
    total, xy, yx = gate
    hi, lo = np.maximum(na, nb), np.minimum(na, nb)
    return (total <= hi + lo) & (xy <= hi) & (yx <= lo)


def _take(rows, arr, width):
    """``arr[rows]`` cut to ``width`` columns; a row of -1 reads as zeros."""
    out = arr[np.maximum(rows, 0), :width]
    return np.where((rows >= 0)[:, None], out, 0)


def _combine(ss, src, a_fam, b_fam, sizes, fam_start):
    """Output reads of one read type for every molecule. ``ss`` holds the
    strand families' single-strand consensus (bases, quals, depth, errors,
    length), ``src`` their oriented source reads (codes, lengths). Returns a
    dict of (molecules, ...) arrays: ``kind`` (2 combined, 1 the AB side
    alone, 0 the BA side alone, -1 none), the read's bases, quals, errors and
    length, and the strand family on its a and b side (-1: none)."""
    bases, quals, depth, errors, clen = ss
    width = bases.shape[1]
    col = np.arange(width)[None, :]
    has_a, has_b = a_fam >= 0, b_fam >= 0
    len_a = np.where(has_a, clen[np.maximum(a_fam, 0)], _FAR)
    len_b = np.where(has_b, clen[np.maximum(b_fam, 0)], _FAR)
    length = np.minimum(len_a, len_b)
    positive = depth > 0
    first = np.where(positive.any(axis=1), positive.argmax(axis=1), _FAR)
    alive_a = has_a & (first[np.maximum(a_fam, 0)] < length)
    alive_b = has_b & (first[np.maximum(b_fam, 0)] < length)
    kind = np.where(alive_a & alive_b, 2,
                    np.where(alive_a, 1, np.where(alive_b, 0, -1)))
    # the side a pass-through read shows as its "a" strand, at its own length
    side = np.where(kind == 0, b_fam, a_fam)
    length = np.where(kind == 2, length, np.where(kind == 1, len_a, len_b))
    length = np.where(kind >= 0, length, 0)
    in_len = col < length[:, None]

    a_b, b_b = _take(a_fam, bases, width), _take(b_fam, bases, width)
    a_q = _take(a_fam, quals, width).astype(np.int32)
    b_q = _take(b_fam, quals, width).astype(np.int32)
    agree = a_b == b_b
    a_wins, b_wins = ~agree & (a_q > b_q), ~agree & (b_q > a_q)
    raw_base = np.where(agree | a_wins, a_b, b_b)
    raw_qual = np.where(
        agree, np.clip(a_q + b_q, MIN_PHRED, MAX_PHRED),
        np.where(a_wins, np.clip(a_q - b_q, MIN_PHRED, MAX_PHRED),
                 np.where(b_wins, np.clip(b_q - a_q, MIN_PHRED, MAX_PHRED),
                          MIN_PHRED)))
    masked = (a_b == N_CODE) | (b_b == N_CODE) | (raw_qual == MIN_PHRED) \
        | (~agree & (a_q == b_q))
    out_b = np.where(masked, N_CODE, raw_base)
    out_q = np.where(masked, MIN_PHRED, raw_qual)
    # errors of a combined read: every source read of both strands against
    # the base before masking, inside the read's and the combined length.
    # A molecule's strand families are neighbours, so its rows are one run.
    codes, lens = src
    combined = np.flatnonzero(kind == 2)
    recount = np.zeros((len(kind), width), dtype=np.int64)
    if len(combined):
        row0 = fam_start[a_fam[combined]]
        count = sizes[a_fam[combined]] + sizes[b_fam[combined]]
        rows = np.repeat(row0 - (np.cumsum(count) - count), count) \
            + np.arange(count.sum())
        of = np.repeat(combined, count)
        seen = codes[rows]
        wrong = (seen != N_CODE) & (raw_base[of] != N_CODE) \
            & (seen != raw_base[of]) \
            & (col < np.minimum(lens[rows], length[of])[:, None])
        recount[combined] = np.add.reduceat(
            wrong, np.cumsum(count) - count, axis=0, dtype=np.int64)
    both = (kind == 2)[:, None]
    return {
        "kind": kind, "length": length, "a": np.where(kind >= 0, side, -1),
        "b": np.where(kind == 2, b_fam, -1),
        "bases": np.where(in_len, np.where(both, out_b, _take(side, bases,
                                                               width)),
                          N_CODE).astype(np.uint8),
        "quals": np.where(in_len, np.where(both, out_q, _take(side, quals,
                                                               width)),
                          0).astype(np.uint8),
        "errors": np.where(in_len, np.where(
            both, np.minimum(recount, I16_MAX), _take(side, errors, width)),
            0)}


def _strand_tags(letter, fam, length, ss, present):
    """One strand's tags as ``pack_rows`` segments: ``<l>D <l>E <l>M`` always
    (zeros where the strand is missing), ``<l>c <l>d <l>e <l>q`` where it is
    present, all cut to ``length``."""
    bases, quals, depth, errors, _clen = ss
    n, width = len(fam), bases.shape[1]
    length = np.where(present, length, 0)
    live = np.arange(width)[None, :] < length[:, None]
    d16 = np.where(live, np.minimum(_take(fam, depth, width), I16_MAX), 0)
    e16 = np.where(live, np.minimum(_take(fam, errors, width), I16_MAX), 0)
    total_d, total_e = d16.sum(axis=1), e16.sum(axis=1)
    rate = np.where(total_d > 0, total_e.astype(np.float32)
                    / np.maximum(total_d, 1).astype(np.float32),
                    np.float32(0)).astype(np.float32)
    d_max = d16.max(axis=1)
    d_min = np.where(length > 0,
                     np.where(live, d16, I16_MAX + 1).min(axis=1), 0)
    tag = letter.encode()
    head = 3 * present  # a tag's three lead bytes, or nothing
    return [
        (const(n, tag + b"Di"), None), (ints(("<i4",), d_max), None),
        (const(n, tag + b"Ef"), None), (ints(("<f4",), rate), None),
        (const(n, tag + b"Mi"), None), (ints(("<i4",), d_min), None),
        (const(n, tag + b"cZ"), head),
        (traffic.CODE_TO_ASCII[_take(fam, bases, width)], length),
        (const(n, b"\x00" + tag + b"dBs"), (1 + 4) * present),
        (ints(("<u4",), length), 4 * present),
        (d16.astype("<i2").view(np.uint8).reshape(n, -1), 2 * length),
        (const(n, tag + b"eBs"), 4 * present),
        (ints(("<u4",), length), 4 * present),
        (e16.astype("<i2").view(np.uint8).reshape(n, -1), 2 * length),
        (const(n, tag + b"qZ"), head),
        ((_take(fam, quals, width) + 33).astype(np.uint8), length),
        (const(n, b"\x00"), 1 * present)]


def _record_segments(mol_digits, mol_ndig, flag, out, ss, rx):
    """BAM records of one read type's output reads (the rows of ``out``)."""
    n, length = len(out["kind"]), out["length"]
    _bases, _quals, depth, _errors, _clen = ss
    width = depth.shape[1]
    live = np.arange(width)[None, :] < length[:, None]
    comb = np.where(live, np.minimum(_take(out["a"], depth, width), I16_MAX)
                    + np.minimum(_take(out["b"], depth, width), I16_MAX), 0)
    total_d = comb.sum(axis=1)
    total_e = np.minimum(out["errors"], I16_MAX).sum(axis=1)
    rate = np.where(total_d > 0, total_e.astype(np.float32)
                    / np.maximum(total_d, 1).astype(np.float32),
                    np.float32(0)).astype(np.float32)
    c_min = np.where(live, comb, 2 * I16_MAX + 1).min(axis=1)
    yes = np.ones(n, dtype=np.int64)
    body = [
        (const(n, b"fgumi:"), None), (mol_digits, mol_ndig),
        (const(n, b"\x00"), None),
        (traffic.pack_seq(out["bases"], length), (length + 1) // 2),
        (out["quals"], length),
        (const(n, b"MIZ"), None), (mol_digits, mol_ndig),
        (const(n, b"\x00RGZA\x00"), None)]
    body += _strand_tags("a", out["a"], length, ss, yes)
    body += _strand_tags("b", out["b"], length, ss,
                         (out["b"] >= 0).astype(np.int64))
    body += [
        (const(n, b"cDi"), None), (ints(("<i4",), comb.max(axis=1)), None),
        (const(n, b"cEf"), None), (ints(("<f4",), rate), None),
        (const(n, b"cMi"), None), (ints(("<i4",), c_min), None),
        (const(n, b"RXZ"), None), (rx, None), (const(n, b"\x00"), None)]
    return traffic.bam_record(body, -1, -1, 6 + mol_ndig + 1, 0, 4680, 0,
                              flag, length, -1, -1, 0)


def duplex(d, opts, dtype=np.float64):
    """Expected output records of ``duplex`` on a ``duplex_bam`` input.
    Returns (flat record bytes, records, input reads accounted for)."""
    opts = {**DUPLEX_DEFAULTS, **opts}
    gate = min_reads_gate(opts["min_reads"])
    sizes, a_fam, b_fam = d["sizes"], d["a_fam"], d["b_fam"]
    fam_start = np.cumsum(sizes) - sizes
    has_a, has_b = a_fam >= 0, b_fam >= 0
    work = {key: d[key].copy()
            for key in ("codes1", "codes2", "quals1", "quals2")}
    if opts["consensus_call_overlapping_bases"]:
        # only where both strands are present: elsewhere no pair overlaps
        both = (has_a & has_b)[d["mol_of_fam"]]
        work.update(len1=d["len1"], len2=d["len2"], fam=d["fam"],
                    insert=np.where(both, d["insert"][d["mol_of_fam"]], _FAR))
        reference.overlap_correct(work)
    ss_opts = {"error_rate_pre_umi": opts["error_rate_pre_umi"],
               "error_rate_post_umi": opts["error_rate_post_umi"],
               "min_reads": 1, "min_consensus_base_quality": MIN_PHRED}
    # input gate on read-pair counts (every pair has one R1 on its strand)
    n_a = np.where(has_a, sizes[np.maximum(a_fam, 0)], 0)
    n_b = np.where(has_b, sizes[np.maximum(b_fam, 0)], 0)
    emit = _passes(n_a, n_b, gate)  # a lone strand passes only at YX = 0
    outs, strands = [], []
    for mate, reverse in ((1, False), (2, True)):
        codes, quals, final = reference.source_reads(
            work[f"codes{mate}"], work[f"quals{mate}"], d[f"len{mate}"],
            reverse, opts["min_input_base_quality"])
        if (final == 0).any():
            raise NotImplementedError("a read trimmed to nothing")
        ss = reference.call_jobs(codes, quals, final, fam_start, sizes,
                                 ss_opts, dtype)
        out = _combine(ss, (codes, final), a_fam, b_fam, sizes, fam_start)
        # the gate again, on the depths the output read's strands reached
        depth, live = ss[2], np.arange(ss[2].shape[1]) < out["length"][:, None]
        reach = [np.where(live, _take(out[s], depth, depth.shape[1]), 0)
                 .max(axis=1) for s in ("a", "b")]
        emit &= (out["kind"] >= 0) & (~(has_a & has_b)
                                      | _passes(reach[0], reach[1], gate))
        outs.append(out)
        strands.append(ss)
    keep = np.flatnonzero(emit)
    digits, ndig = traffic.digits(keep, 8)
    umi = traffic.CODE_TO_ASCII[d["umi"][keep]]
    half = umi.shape[1] // 2
    rx = np.concatenate((umi[:, :half], const(len(keep), b"-"),
                         umi[:, half:]), axis=1)
    segs = []
    for flag, out, ss in zip((77, 141), outs, strands):
        out = {key: val[keep] for key, val in out.items()}
        segs += _record_segments(digits, ndig, flag, out, ss, rx)
    # one row per molecule: R1's record, then R2's
    flat, _ = pack_rows(segs)
    return flat, 2 * len(keep), int(2 * sizes.sum())
