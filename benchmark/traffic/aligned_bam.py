"""Layout ``aligned_bam``: ``grouped_bam``'s MI-grouped mapped FR pairs as an
aligner writes them, the input of ``simplex`` after bwa mem + zipper +
``group``: some reads soft-clipped at their 3' end, some with an indel of
their own, some molecules with an indel every read over it shares.

The molecules, reads, qualities and substitutions are ``grouped_bam``'s, draw
for draw (with every rate 0 the arrays of a seed are its arrays); the CIGAR
model is drawn after them. A molecule is ``truth[:insert]``; R1 reads its
first ``len1`` bases, R2 its last ``len2`` (stored forward, flagged reverse).
A read has **one** event at most, the first of these that applies:

- the molecule's indel (``indel_molecule_rate`` of the molecules carry one:
  an insertion, bases ``[at, at + n)`` of the molecule that the reference
  lacks, or a deletion of ``n`` reference bases before molecule base ``at``):
  a read over it with ``margin`` bases or more on both sides carries it in
  its CIGAR (``<l>M<n>I<r>M`` / ``<l>M<n>D<r>M``); a read that reaches it
  nearer one of its ends is soft-clipped from the indel outward, as a local
  aligner does. The bases stay the molecule's either way;
- ``softclip_read_rate`` of the other reads: the 3' end in sequencing order
  (R1: the record's right end, ``<m>M<k>S``; R2: its left end, ``<k>S<m>M``
  with ``pos`` moved by ``k``) is clipped and its ``k`` bases are random;
- ``indel_read_rate`` of them: one insertion (random bases) or deletion (the
  read goes on that much further along the molecule, away from its 5' end)
  at a query offset in ``[margin, length - margin)``.

(A read within three bases of the molecule's indel draws no indel of its
own, so the two never meet.) ``pos`` is the first aligned base, ``bin`` and
``tlen`` follow the CIGARs (``tlen``: R1's ``pos`` to R2's end), ``l_seq``
is the query length and ``MC`` is the mate's CIGAR. ``event1``/``event2``
say which event a read drew and ``truth`` holds the molecules, for the
tests."""

import numpy as np

import bamio
import traffic as t

_grouped = t.kind_module("grouped_bam")  # one reference, header and bin rule
REF_NAME, REF_LENGTH = _grouped.REF_NAME, _grouped.REF_LENGTH
HEADER = _grouped.HEADER

OP_M, OP_I, OP_D, OP_S = 0, 1, 2, 4
OP_CHARS = np.frombuffer(b"MIDNSHP=X", dtype=np.uint8)
MAX_OPS = 3
#: ``event<mate>``: what a read drew
PLAIN, CLIP3, READ_INS, READ_DEL, MOL_CARRIED, MOL_CLIPPED = range(6)
_INDEL_LENGTHS, _INDEL_P = (1, 2, 3), (0.6, 0.3, 0.1)


def _indel_draws(rng, n):
    """(is a deletion, length) of ``n`` indels."""
    return (rng.integers(0, 2, n).astype(bool),
            rng.choice(_INDEL_LENGTHS, n, p=_INDEL_P))


def generate(params, rng, common):
    sizes, fam = common["sizes"], common["fam"]
    n, n_mol, length = len(fam), len(sizes), params["read_length"]
    # ---- grouped_bam's draws, in its order
    insert = rng.integers(int(length * 1.5), 3 * length, n_mol)
    start = rng.integers(0, REF_LENGTH - insert - 1)
    truth = rng.integers(0, 4, (n_mol, 3 * length), dtype=np.uint8)
    len1, len2 = t.lengths(rng, n, params), t.lengths(rng, n, params)
    t1 = truth[:, :length][fam]
    t2 = np.take_along_axis(
        truth, (insert - length)[:, None] + np.arange(length), axis=1)[fam]
    for cut in np.unique(length - len2):  # a shorter R2 starts later
        if cut:
            rows = np.flatnonzero(length - len2 == cut)
            t2[rows, :length - cut] = t2[rows, cut:]
    d = dict(
        insert=insert, start=start, len1=len1, len2=len2,
        codes1=t.mutate(rng, t1, params["error_rate"]),
        codes2=t.mutate(rng, t2, params["error_rate"]),
        quals1=t.quals(rng, n, length, params),
        quals2=t.quals(rng, n, length, params),
        n_reads=2 * n)
    # ---- the CIGAR model
    margin = params.get("indel_margin", 10)
    mol_has = rng.random(n_mol) < params.get("indel_molecule_rate", 0.0)
    mol_del, mol_n = _indel_draws(rng, n_mol)
    mol_n = np.where(mol_has, mol_n, 0)
    mol_at = 1 + (rng.random(n_mol) * (insert - mol_n - 1)).astype(np.int64)
    d.update(mol_indel=np.where(mol_has, np.where(mol_del, OP_D, OP_I), 0),
             mol_n=mol_n, mol_at=mol_at, truth=truth)
    for mate, first_base in ((1, np.zeros(n, dtype=np.int64)),
                             (2, insert[fam] - len2)):
        d.update(_align(params, rng, d, truth, fam, mate, first_base, margin))
    # R1's first aligned base to R2's last, as an aligner reports it
    d["tlen"] = d["pos2"] + ref_length(d["cigar2"]) - d["pos1"]
    return d


def ref_length(cigar):
    """Reference bases each row of BAM CIGAR words consumes (M and D)."""
    op, length = cigar & 0xF, (cigar >> 4).astype(np.int64)
    return np.where((op == OP_M) | (op == OP_D), length, 0).sum(axis=1)


def _align(params, rng, d, truth, fam, mate, a, margin):
    """One mate's CIGARs, positions and the bases the events change. ``a``
    is the molecule offset of each read's first base."""
    ln = d[f"len{mate}"]
    codes = d[f"codes{mate}"]
    n = len(ln)
    b = a + ln
    kind, m_n, m_at = d["mol_indel"][fam], d["mol_n"][fam], d["mol_at"][fam]
    is_ins, is_del = kind == OP_I, kind == OP_D
    # molecule bases left and right of the indel inside the read
    left = m_at - a
    right = b - m_at - np.where(is_ins, m_n, 0)
    touched = (is_ins & (a < m_at + m_n) & (m_at < b)) \
        | (is_del & (a < m_at) & (m_at < b))
    near = (kind != 0) & (a - 3 < m_at + m_n) & (m_at < b + 3)
    clip_left = touched & (left < margin)
    clip_right = touched & ~clip_left & (right < margin)
    carried = touched & ~clip_left & ~clip_right

    # the read's own draw (one uniform a read decides between the events)
    u = rng.random(n)
    p_clip = params.get("softclip_read_rate", 0.0)
    clip3 = ~touched & (u < p_clip)
    own = ~near & (u >= p_clip) & (u < p_clip + params.get("indel_read_rate",
                                                           0.0))
    clip_k = rng.integers(1, 21, n)
    is_deletion, own_n = _indel_draws(rng, n)
    own_at = margin + (rng.random(n) * (ln - 2 * margin)).astype(np.int64)
    own_ins, own_del = own & ~is_deletion, own & is_deletion

    event = np.select(
        [carried, clip_left | clip_right, clip3, own_ins, own_del],
        [MOL_CARRIED, MOL_CLIPPED, CLIP3, READ_INS, READ_DEL], PLAIN)

    # ---- CIGAR: up to three (op, length) in record order
    ops = np.zeros((n, MAX_OPS), dtype=np.int64)
    lens = np.zeros((n, MAX_OPS), dtype=np.int64)
    lens[:, 0] = ln  # PLAIN: <ln>M
    lead_s = np.where(is_ins, left + m_n, left)  # a left clip up to the indel

    def put(rows, *cols):
        """The CIGAR of ``rows``: ``cols``, and no op after them."""
        for j, (op, length) in enumerate(cols):
            ops[rows, j] = np.broadcast_to(op, (n,))[rows]
            lens[rows, j] = np.broadcast_to(length, (n,))[rows]
        lens[rows, len(cols):] = 0

    put(carried, (OP_M, left), (kind, m_n), (OP_M, right))
    put(clip_left, (OP_S, lead_s), (OP_M, right))
    put(clip_right, (OP_M, left), (OP_S, ln - left))
    if mate == 1:
        put(clip3, (OP_M, ln - clip_k), (OP_S, clip_k))
    else:
        put(clip3, (OP_S, clip_k), (OP_M, ln - clip_k))
    put(own_ins, (OP_M, own_at), (OP_I, own_n), (OP_M, ln - own_at - own_n))
    put(own_del, (OP_M, own_at), (OP_D, own_n), (OP_M, ln - own_at))
    ncig = (lens > 0).sum(axis=1)

    # ---- pos: the reference base under the first aligned molecule base
    first = a + np.where(clip_left, lead_s, 0)
    if mate == 2:  # R2 is anchored at its right end, the molecule's
        first = first + np.where(clip3, clip_k, 0) \
            + np.where(own_ins, own_n, 0) - np.where(own_del, own_n, 0)
    shift = np.where(is_ins & (first >= m_at + m_n), -m_n,
                     np.where(is_del & (first >= m_at), m_n, 0))
    pos = d["start"][fam] + first + shift

    # ---- bases: a clip's are random; an own indel shifts the rest of the
    # read along the molecule, away from its 5' end, under the read's own
    # substitutions
    cols = np.arange(codes.shape[1])[None, :]
    rows = np.flatnonzero(clip3)
    k = clip_k[rows, None]
    lost = cols >= (ln[rows, None] - k) if mate == 1 else cols < k
    lost &= cols < ln[rows, None]
    codes[rows] = np.where(
        lost, rng.integers(0, 4, lost.shape, dtype=np.uint8), codes[rows])
    rows = np.flatnonzero(own)
    at, num = own_at[rows, None], own_n[rows, None]
    step = np.where(own_del[rows, None], num, -num)
    if mate == 1:
        moved = cols >= at + np.where(own_ins[rows, None], num, 0)
    else:
        moved, step = cols < at, -step
    fresh = own_ins[rows, None] & (cols >= at) & (cols < at + num)
    mol = a[rows, None] + cols
    plain = np.take_along_axis(truth[fam[rows]], np.clip(
        mol, 0, truth.shape[1] - 1), axis=1)
    shifted = np.take_along_axis(truth[fam[rows]], np.clip(
        mol + np.where(moved, step, 0), 0, truth.shape[1] - 1), axis=1)
    subst = (codes[rows] - plain) % 4  # the read's substitutions, by offset
    codes[rows] = np.where(
        fresh, rng.integers(0, 4, fresh.shape, dtype=np.uint8),
        (shifted + subst) % 4).astype(np.uint8)
    return {f"cigar{mate}": ((lens << 4) | ops).astype(np.uint32),
            f"ncig{mate}": ncig, f"pos{mate}": pos, f"event{mate}": event}


def cigar_text(cigar, ncig):
    """The CIGAR strings of BAM CIGAR words as ``pack_rows`` segments."""
    segs = []
    for j in range(cigar.shape[1]):
        live = (j < ncig).astype(np.int64)
        dig, ndig = t.digits(cigar[:, j] >> 4, 3)
        segs += [(dig, ndig * live),
                 (OP_CHARS[cigar[:, j] & 0xF][:, None], live)]
    return segs


def _records(sl, d, mate):
    """BAM records of one mate (1 or 2) for the read pairs in slice ``sl``."""
    other = 3 - mate
    fam, ordinal = d["fam"][sl], d["ordinal"][sl]
    n = len(fam)
    ln, pos = d[f"len{mate}"][sl], d[f"pos{mate}"][sl]
    mpos = d[f"pos{other}"][sl]
    cigar, ncig = d[f"cigar{mate}"][sl], d[f"ncig{mate}"][sl]
    tlen, flag = (d["tlen"][sl], 97) if mate == 1 else (-d["tlen"][sl], 145)
    fam_dig, fam_n = t.digits(fam, 8)
    ord_dig, ord_n = t.digits(ordinal, 4)
    name_len = 3 + fam_n + 2 + ord_n + 1
    body = [
        (t.const(n, b"fam"), None), (fam_dig, fam_n),
        (t.const(n, b":r"), None),
        (ord_dig, ord_n), (t.const(n, b"\x00"), None),
        (cigar.astype("<u4").view(np.uint8).reshape(n, -1), 4 * ncig),
        (t.pack_seq(d[f"codes{mate}"][sl], ln), (ln + 1) // 2),
        (d[f"quals{mate}"][sl], ln),
        (t.const(n, b"MCZ"), None),
        *cigar_text(d[f"cigar{other}"][sl], d[f"ncig{other}"][sl]),
        (t.const(n, b"\x00RGZA\x00MIZ"), None), (fam_dig, fam_n),
        (t.const(n, b"\x00"), None)]
    return t.bam_record(
        body, 0, pos, name_len, 60,
        _grouped._reg2bin(pos, pos + ref_length(cigar)), ncig, flag, ln, 0,
        mpos, tlen)


def write(d, prefix, level):
    path = prefix + ".bam"
    chunks = [bamio.bam_header(HEADER, [(REF_NAME, REF_LENGTH)])]
    n = len(d["fam"])
    for lo in range(0, n, 65536):
        sl = slice(lo, min(lo + 65536, n))
        # one row per pair: R1's record, then R2's
        flat, _ = t.pack_rows(_records(sl, d, 1) + _records(sl, d, 2))
        chunks.append(flat.tobytes())
    bamio.write_bgzf(path, b"".join(chunks), level=level)
    return [path]
