"""Layout ``amplicon_bam``: a mapped, template-coordinate-sorted BAM of an
ultra-deep amplicon panel, the input of ``group``. One entry of the family
sizes is one **molecule**; its size is the number of templates (read pairs,
PCR copies) that sequenced it. The molecules are dealt to a few dozen
**loci** in consecutive runs, ``groups`` giving ``[loci, molecules a locus]``
pairs (scaled to ``num_families`` when a rehearsal or a test asks for
another total); which locus has which size is the seed's draw. Every template
of a locus starts at the same primer pair, so all of them have the same two
ends and the locus is one position group of ``group``.

Record shape: FR pairs on one contig, MAPQ 60, one ``<length>M`` CIGAR, tags
``MC``, ``RG`` and ``RX`` (the template's UMI, the same on both reads: the
molecule's UMI with the sequencer's substitutions, drawn once a template).
``r1_reverse_share`` of a locus's templates are F2R1 (R1 on the reverse
strand at the far primer): the same two ends, so the same position group, and
another orientation sub-group. Order: the loci along the reference; inside a
locus by name, ``amp<molecule>:<ordinal>`` zero-padded, so the file is
template-coordinate sorted as its header says (``SS:unsorted:template-
coordinate``); a template's R1 before its R2. Both arrays hold forward-strand
bases as the BAM stores them (``codes1`` the forward reads at the locus's
start, ``codes2`` the reverse-flagged ones at its end)."""

import numpy as np

import bamio
import traffic as t

_grouped = t.kind_module("grouped_bam")  # one reference, the bin rule
REF_NAME, REF_LENGTH = _grouped.REF_NAME, _grouped.REF_LENGTH
HEADER = ("@HD\tVN:1.6\tSO:unsorted\tGO:query"
          "\tSS:unsorted:template-coordinate\n"
          f"@SQ\tSN:{REF_NAME}\tLN:{REF_LENGTH}\n"
          "@RG\tID:A\tSM:sample\tLB:lib\n")
#: spacing of the loci along the reference, far more than an insert
LOCUS_STEP = 100_000

#: (read is R2, read is reverse-flagged) -> flag: paired + reverse or
#: mate-reverse + first or last
_FLAGS = np.array([[97, 81], [161, 145]], dtype=np.int64)


def locus_molecules(params, rng):
    """Molecules of each locus, in the seed's order along the reference:
    ``groups``' sizes scaled to ``num_families``, the rounding's rest on the
    last locus."""
    sizes = np.repeat([m for _n, m in params["groups"]],
                      [n for n, _m in params["groups"]]).astype(np.int64)
    total = params["num_families"]
    if sizes.sum() != total:
        sizes = np.maximum(1, sizes * total // sizes.sum())
        sizes[-1] += total - sizes.sum()
        if sizes[-1] < 1:
            raise ValueError("num_families is under one molecule a locus")
    return rng.permutation(sizes)


def generate(params, rng, common):
    sizes, fam = common["sizes"], common["fam"]
    n, n_mol, length = len(fam), len(sizes), params["read_length"]
    if params["read_length_jitter"]:
        raise ValueError("amplicon_bam has one read length (no jitter)")
    lo, hi = params["insert_min"], params["insert_max"]
    if not length <= lo <= hi:
        raise ValueError("an insert must hold a read")
    per_locus = locus_molecules(params, rng)
    n_loci = len(per_locus)
    if (n_loci + 1) * LOCUS_STEP > REF_LENGTH:
        raise ValueError("more loci than the reference has room for")
    locus = np.repeat(np.arange(n_loci), per_locus)  # of each molecule
    insert = rng.integers(lo, hi + 1, n_loci)
    start = (np.arange(n_loci) + 1) * LOCUS_STEP
    truth = rng.integers(0, 4, (n_loci, hi), dtype=np.uint8)
    umi = rng.integers(0, 4, (n_mol, params["umi_length"]), dtype=np.uint8)
    r1_reverse = rng.random(n) < params.get("r1_reverse_share", 0.0)
    loc = locus[fam]
    t2 = np.take_along_axis(
        truth, (insert - length)[:, None] + np.arange(length), axis=1)[loc]
    lens = np.full(n, length, dtype=np.int64)
    return dict(
        locus=locus, insert=insert, start=start, umi=umi,
        r1_reverse=r1_reverse, len1=lens, len2=lens,
        umi_t=t.mutate(rng, umi[fam], params["error_rate"]),
        codes1=t.mutate(rng, truth[:, :length][loc], params["error_rate"]),
        codes2=t.mutate(rng, t2, params["error_rate"]),
        quals1=t.quals(rng, n, length, params),
        quals2=t.quals(rng, n, length, params),
        n_reads=2 * n)


def zero_padded(values, width):
    """(n, width) ASCII digits of ``values`` with leading zeros."""
    pows = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    return ((np.asarray(values, dtype=np.int64)[:, None] // pows) % 10
            + ord("0")).astype(np.uint8)


def records(sl, d, second, tail=()):
    """BAM records (``traffic.bam_record`` segments) of R1 (``second`` 0) or
    R2 (1) of the templates in slice ``sl``: the forward read or the
    reverse-flagged one, by the template; ``tail`` is appended to each
    record's tags (what the reference of ``group`` adds)."""
    mol, ordinal = d["fam"][sl], d["ordinal"][sl]
    n = len(mol)
    reverse = d["r1_reverse"][sl] ^ bool(second)
    rev2d = reverse[:, None]
    ln = np.where(reverse, d["len2"][sl], d["len1"][sl])
    mate_ln = np.where(reverse, d["len1"][sl], d["len2"][sl])
    loc = d["locus"][mol]
    start, insert = d["start"][loc], d["insert"][loc]
    rev_pos = start + insert - d["len2"][sl]
    pos = np.where(reverse, rev_pos, start)
    mpos = np.where(reverse, start, rev_pos)
    tlen = np.where(reverse, -insert, insert)
    codes = np.where(rev2d, d["codes2"][sl], d["codes1"][sl])
    quals = np.where(rev2d, d["quals2"][sl], d["quals1"][sl])
    mc_dig, mc_n = t.digits(mate_ln, 4)
    body = [
        (t.const(n, b"amp"), None), (zero_padded(mol, 8), None),
        (t.const(n, b":"), None), (zero_padded(ordinal, 4), None),
        (t.const(n, b"\x00"), None),
        (t.ints(("<u4",), (ln << 4)), None),  # one CIGAR op: <ln>M
        (t.pack_seq(codes, ln), (ln + 1) // 2), (quals, ln),
        (t.const(n, b"MCZ"), None), (mc_dig, mc_n),
        (t.const(n, b"M\x00RGZA\x00RXZ"), None),
        (t.CODE_TO_ASCII[d["umi_t"][sl]], None),
        (t.const(n, b"\x00"), None)] + list(tail)
    return t.bam_record(body, 0, pos, 3 + 8 + 1 + 4 + 1, 60,
                        _grouped._reg2bin(pos, pos + ln), 1,
                        _FLAGS[int(second), reverse.astype(np.int64)], ln, 0,
                        mpos, tlen)


def write(d, prefix, level):
    path = prefix + ".bam"
    chunks = [bamio.bam_header(HEADER, [(REF_NAME, REF_LENGTH)])]
    n = len(d["fam"])
    for lo in range(0, n, 65536):
        sl = slice(lo, min(lo + 65536, n))
        # one row per template: R1's record, then R2's
        flat, _ = t.pack_rows(records(sl, d, 0) + records(sl, d, 1))
        chunks.append(flat.tobytes())
    bamio.write_bgzf(path, b"".join(chunks), level=level)
    return [path]
