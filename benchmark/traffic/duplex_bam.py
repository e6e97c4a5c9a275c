"""Layout ``duplex_bam``: the output of ``group --strategy paired``, the input
of ``duplex``. One entry of the family sizes is one **strand family**: the
read pairs of one molecule's ``/A`` or ``/B`` strand, so the two strands of a
molecule draw their depths independently. ``duplex_share`` of the molecules
have both strands; the rest have one, ``/A``-only and ``/B``-only in equal
numbers, placed by the seed.

Geometry as ``fgumi_tpu/simulate.py simulate_duplex_bam``: AB-R1 and BA-R2
read the top strand forward from the molecule's first base; AB-R2 and BA-R1
read the bottom strand and are stored reverse-complemented with the reverse
flag, so both arrays here hold forward-strand bases (``codes1`` the forward
reads, ``codes2`` the reverse-flagged ones). Tags MC, RG, ``MI:<mol>/A|/B``
and ``RX:<u1>-<u2>``, flipped on ``/B``; a molecule's ``/A`` records come
before its ``/B`` records, a pair's forward read before its reverse read."""

import numpy as np

import bamio
import traffic as t

_grouped = t.kind_module("grouped_bam")  # one reference, header and bin rule
REF_LENGTH = _grouped.REF_LENGTH
DUPLEX, A_ONLY, B_ONLY = 0, 1, 2

#: (strand, reverse) -> flag: paired + mate-reverse/reverse + first/last
_FLAGS = np.array([[97, 145], [161, 81]], dtype=np.int64)


def molecules(n_families, share, rng):
    """Split ``n_families`` strand families over molecules: each molecule's
    kind, and the strand family of its ``/A`` and ``/B`` reads (-1: none)."""
    n_duplex = int(n_families * share / (1.0 + share))
    n_single = n_families - 2 * n_duplex
    kind = np.concatenate((
        np.full(n_duplex, DUPLEX), np.full(n_single - n_single // 2, A_ONLY),
        np.full(n_single // 2, B_ONLY)))
    kind = rng.permutation(kind)
    n_of = np.where(kind == DUPLEX, 2, 1)
    first = np.cumsum(n_of) - n_of
    a_fam = np.where(kind != B_ONLY, first, -1)
    b_fam = np.where(kind != A_ONLY, first + (kind == DUPLEX), -1)
    return kind, a_fam, b_fam


def generate(params, rng, common):
    sizes, fam = common["sizes"], common["fam"]
    n, length = len(fam), params["read_length"]
    half = params["umi_length"] // 2
    kind, a_fam, b_fam = molecules(len(sizes), params["duplex_share"], rng)
    n_mol = len(kind)
    mol_of_fam = np.repeat(np.arange(n_mol), np.where(kind == DUPLEX, 2, 1))
    strand_of_fam = np.zeros(len(sizes), dtype=np.int64)
    strand_of_fam[b_fam[b_fam >= 0]] = 1
    insert = rng.integers(int(length * 1.5), 3 * length, n_mol)
    start = rng.integers(0, REF_LENGTH - insert - 1)
    truth = rng.integers(0, 4, (n_mol, 3 * length), dtype=np.uint8)
    umi = rng.integers(0, 4, (n_mol, 2 * half), dtype=np.uint8)
    mol = mol_of_fam[fam]
    t2 = np.take_along_axis(
        truth, (insert - length)[:, None] + np.arange(length), axis=1)[mol]
    lens = np.full(n, length, dtype=np.int64)
    return dict(
        mol_kind=kind, a_fam=a_fam, b_fam=b_fam, mol_of_fam=mol_of_fam,
        strand_of_fam=strand_of_fam, insert=insert, start=start, umi=umi,
        len1=lens, len2=lens,
        codes1=t.mutate(rng, truth[:, :length][mol], params["error_rate"]),
        codes2=t.mutate(rng, t2, params["error_rate"]),
        quals1=t.quals(rng, n, length, params),
        quals2=t.quals(rng, n, length, params),
        n_reads=2 * n)


def _records(sl, d, reverse):
    """BAM records of the forward (0) or reverse-flagged (1) read of the
    read pairs in slice ``sl``."""
    fam, ordinal = d["fam"][sl], d["ordinal"][sl]
    n = len(fam)
    mol, strand = d["mol_of_fam"][fam], d["strand_of_fam"][fam]
    ln, mate_ln = (d["len2"][sl], d["len1"][sl]) if reverse \
        else (d["len1"][sl], d["len2"][sl])
    start, insert = d["start"][mol], d["insert"][mol]
    r2_pos = start + insert - d["len2"][sl]
    pos, mpos, tlen = (r2_pos, start, -insert) if reverse \
        else (start, r2_pos, insert)
    codes = d["codes2" if reverse else "codes1"][sl]
    quals = d["quals2" if reverse else "quals1"][sl]
    mol_dig, mol_n = t.digits(mol, 8)
    ord_dig, ord_n = t.digits(ordinal, 4)
    mc_dig, mc_n = t.digits(mate_ln, 4)
    letter = (ord("A") + strand).astype(np.uint8)[:, None]
    umi = t.CODE_TO_ASCII[d["umi"][mol]]
    half = umi.shape[1] // 2
    u1, u2 = umi[:, :half], umi[:, half:]
    flip = strand[:, None] == 1
    dash = t.const(n, b"-")
    rx = np.concatenate((np.where(flip, u2, u1), dash,
                         np.where(flip, u1, u2)), axis=1)
    name_len = 1 + mol_n + 1 + 1 + ord_n + 1
    body = [
        (t.const(n, b"m"), None), (mol_dig, mol_n), (t.const(n, b":"), None),
        (letter, None), (ord_dig, ord_n), (t.const(n, b"\x00"), None),
        (t.ints(("<u4",), (ln << 4)), None),  # one CIGAR op: <ln>M
        (t.pack_seq(codes, ln), (ln + 1) // 2), (quals, ln),
        (t.const(n, b"MCZ"), None), (mc_dig, mc_n),
        (t.const(n, b"M\x00RGZA\x00MIZ"), None), (mol_dig, mol_n),
        (t.const(n, b"/"), None), (letter, None),
        (t.const(n, b"\x00RXZ"), None), (rx, None),
        (t.const(n, b"\x00"), None)]
    return t.bam_record(body, 0, pos, name_len, 60,
                        _grouped._reg2bin(pos, pos + ln), 1,
                        _FLAGS[strand, reverse], ln, 0, mpos, tlen)


def write(d, prefix, level):
    path = prefix + ".bam"
    chunks = [bamio.bam_header(_grouped.HEADER,
                               [(_grouped.REF_NAME, REF_LENGTH)])]
    n = len(d["fam"])
    for lo in range(0, n, 65536):
        sl = slice(lo, min(lo + 65536, n))
        # one row per pair: the forward read's record, then the reverse's
        flat, _ = t.pack_rows(_records(sl, d, 0) + _records(sl, d, 1))
        chunks.append(flat.tobytes())
    bamio.write_bgzf(path, b"".join(chunks), level=level)
    return [path]
