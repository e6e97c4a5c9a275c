"""Layout ``codec_bam``: an MI-grouped BAM of a CODEC library, the input of
``codec``. One entry of the family sizes is one **molecule**; its size is the
number of read pairs that sequenced it (PCR copies: same start, same insert).
One read pair covers both strands of the duplex: the forward read starts at
the molecule's first base, the reverse-flagged read ends at its last, and with
``insert_min <= insert < 2 * read_length`` the two overlap in the middle and
leave single-strand ends. Half the molecules have R1 forward / R2 reverse,
half R1 reverse / R2 forward, placed by the seed.

Record shape as ``fgumi_tpu/simulate.py simulate_codec_bam`` (nothing of it is
imported): one ``<length>M`` CIGAR, tags MC, RG, a plain-integer ``MI:<mol>``
and ``RX:<umi>`` (one UMI a molecule, on every read). Both arrays here hold
forward-strand bases as the BAM stores them (``codes1`` the forward reads,
``codes2`` the reverse-flagged ones; ``r1_reverse`` says which of the two is
R1). A molecule's records are consecutive, a pair's R1 before its R2."""

import numpy as np

import bamio
import traffic as t

_grouped = t.kind_module("grouped_bam")  # one reference, header and bin rule
REF_LENGTH = _grouped.REF_LENGTH

#: (read is R2, read is reverse-flagged) -> flag: paired + reverse or
#: mate-reverse + first or last
_FLAGS = np.array([[97, 81], [161, 145]], dtype=np.int64)


def generate(params, rng, common):
    sizes, fam = common["sizes"], common["fam"]
    n, n_mol, length = len(fam), len(sizes), params["read_length"]
    if params["read_length_jitter"]:
        raise ValueError("codec_bam has one read length (no jitter)")
    lo, hi = params["insert_min"], params["insert_max"]
    if not length <= lo <= hi < 2 * length:
        raise ValueError("an insert must hold a read and make a pair overlap")
    insert = rng.integers(lo, hi + 1, n_mol)
    start = rng.integers(0, REF_LENGTH - insert - 1)
    truth = rng.integers(0, 4, (n_mol, hi), dtype=np.uint8)
    umi = rng.integers(0, 4, (n_mol, params["umi_length"]), dtype=np.uint8)
    r1_reverse = rng.permutation(np.arange(n_mol) % 2 == 1)
    t2 = np.take_along_axis(
        truth, (insert - length)[:, None] + np.arange(length), axis=1)[fam]
    lens = np.full(n, length, dtype=np.int64)
    return dict(
        insert=insert, start=start, umi=umi, r1_reverse=r1_reverse,
        len1=lens, len2=lens,
        codes1=t.mutate(rng, truth[:, :length][fam], params["error_rate"]),
        codes2=t.mutate(rng, t2, params["error_rate"]),
        quals1=t.quals(rng, n, length, params),
        quals2=t.quals(rng, n, length, params),
        n_reads=2 * n)


def _records(sl, d, second):
    """BAM records of R1 (``second`` 0) or R2 (1) of the read pairs in slice
    ``sl``: the forward read or the reverse-flagged one, by the molecule."""
    mol, ordinal = d["fam"][sl], d["ordinal"][sl]
    n = len(mol)
    reverse = d["r1_reverse"][mol] ^ bool(second)
    rev2d = reverse[:, None]
    ln = np.where(reverse, d["len2"][sl], d["len1"][sl])
    mate_ln = np.where(reverse, d["len1"][sl], d["len2"][sl])
    start, insert = d["start"][mol], d["insert"][mol]
    rev_pos = start + insert - d["len2"][sl]
    pos = np.where(reverse, rev_pos, start)
    mpos = np.where(reverse, start, rev_pos)
    tlen = np.where(reverse, -insert, insert)
    codes = np.where(rev2d, d["codes2"][sl], d["codes1"][sl])
    quals = np.where(rev2d, d["quals2"][sl], d["quals1"][sl])
    mol_dig, mol_n = t.digits(mol, 8)
    ord_dig, ord_n = t.digits(ordinal, 4)
    mc_dig, mc_n = t.digits(mate_ln, 4)
    name_len = 5 + mol_n + 1 + ord_n + 1
    body = [
        (t.const(n, b"codec"), None), (mol_dig, mol_n),
        (t.const(n, b":"), None), (ord_dig, ord_n),
        (t.const(n, b"\x00"), None),
        (t.ints(("<u4",), (ln << 4)), None),  # one CIGAR op: <ln>M
        (t.pack_seq(codes, ln), (ln + 1) // 2), (quals, ln),
        (t.const(n, b"MCZ"), None), (mc_dig, mc_n),
        (t.const(n, b"M\x00RGZA\x00MIZ"), None), (mol_dig, mol_n),
        (t.const(n, b"\x00RXZ"), None), (t.CODE_TO_ASCII[d["umi"][mol]], None),
        (t.const(n, b"\x00"), None)]
    return t.bam_record(body, 0, pos, name_len, 60,
                        _grouped._reg2bin(pos, pos + ln), 1,
                        _FLAGS[int(second), reverse.astype(np.int64)], ln, 0,
                        mpos, tlen)


def write(d, prefix, level):
    path = prefix + ".bam"
    chunks = [bamio.bam_header(_grouped.HEADER,
                               [(_grouped.REF_NAME, REF_LENGTH)])]
    n = len(d["fam"])
    for lo in range(0, n, 65536):
        sl = slice(lo, min(lo + 65536, n))
        # one row per pair: R1's record, then R2's
        flat, _ = t.pack_rows(_records(sl, d, 0) + _records(sl, d, 1))
        chunks.append(flat.tobytes())
    bamio.write_bgzf(path, b"".join(chunks), level=level)
    return [path]
