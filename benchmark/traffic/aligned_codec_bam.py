"""Layout ``aligned_codec_bam``: ``codec_bam``'s MI-grouped BAM of a CODEC
library as an aligner writes it, the input of ``codec`` after bwa mem +
zipper + ``group``: some reads soft-clipped at their 3' end, some with an
indel of their own, some molecules with an indel every read over it shares,
R1s and R2s alike.

The molecules, pairs, qualities and substitutions are ``codec_bam``'s, draw
for draw and in its order (with every rate 0 the input of a seed is its
input, byte for byte); then ``aligned_bam``'s CIGAR model is drawn over them,
by its own code (``aligned_bam._align``), with the **molecule** in the place
of ``aligned_bam``'s family: a molecule's indel is drawn once, so every read
over it shares it. The forward read of a pair (``codes1``) reads the
molecule from its first base and has its 3' end on the right, as
``aligned_bam``'s R1; the reverse-flagged read (``codes2``) ends at the
molecule's last base and has its 3' end on the left, as ``aligned_bam``'s
R2; ``r1_reverse`` still says which of the two is R1. ``codec_bam``'s
``truth`` (``insert_max`` wide) holds every base ``_align`` reads: a read's
own deletion moves it at most 3 bases along the molecule, which stays inside
``[0, insert)``.

``pos``, ``bin``, ``tlen`` (the forward read's first aligned base to the
reverse read's last, negative on the reverse read), ``l_seq`` (the query
length) and ``MC`` (the mate's CIGAR) follow the CIGARs; names, flags and the
MI, RG and RX tags are ``codec_bam``'s. ``cigar<m>`` / ``ncig<m>`` /
``pos<m>`` / ``event<m>`` hold the model's draws (``<m>`` 1 the forward
read, 2 the reverse one) and ``truth`` the molecules, for the reference and
the tests."""

import numpy as np

import bamio
import traffic as t

_codec = t.kind_module("codec_bam")
_aligned = t.kind_module("aligned_bam")
_grouped = t.kind_module("grouped_bam")  # one reference, header and bin rule
REF_NAME, REF_LENGTH = _grouped.REF_NAME, _grouped.REF_LENGTH


def generate(params, rng, common):
    sizes, fam = common["sizes"], common["fam"]
    n, n_mol, length = len(fam), len(sizes), params["read_length"]
    if params["read_length_jitter"]:
        raise ValueError("aligned_codec_bam has one read length (no jitter)")
    lo, hi = params["insert_min"], params["insert_max"]
    if not length <= lo <= hi < 2 * length:
        raise ValueError("an insert must hold a read and make a pair overlap")
    # ---- codec_bam's draws, in its order
    insert = rng.integers(lo, hi + 1, n_mol)
    start = rng.integers(0, REF_LENGTH - insert - 1)
    truth = rng.integers(0, 4, (n_mol, hi), dtype=np.uint8)
    umi = rng.integers(0, 4, (n_mol, params["umi_length"]), dtype=np.uint8)
    r1_reverse = rng.permutation(np.arange(n_mol) % 2 == 1)
    t2 = np.take_along_axis(
        truth, (insert - length)[:, None] + np.arange(length), axis=1)[fam]
    lens = np.full(n, length, dtype=np.int64)
    d = dict(
        insert=insert, start=start, umi=umi, r1_reverse=r1_reverse,
        len1=lens, len2=lens,
        codes1=t.mutate(rng, truth[:, :length][fam], params["error_rate"]),
        codes2=t.mutate(rng, t2, params["error_rate"]),
        quals1=t.quals(rng, n, length, params),
        quals2=t.quals(rng, n, length, params),
        n_reads=2 * n)
    # ---- aligned_bam's CIGAR model, a molecule where it has a family
    margin = params.get("indel_margin", 10)
    mol_has = rng.random(n_mol) < params.get("indel_molecule_rate", 0.0)
    mol_del, mol_n = _aligned._indel_draws(rng, n_mol)
    mol_n = np.where(mol_has, mol_n, 0)
    mol_at = 1 + (rng.random(n_mol) * (insert - mol_n - 1)).astype(np.int64)
    d.update(mol_indel=np.where(mol_has, np.where(mol_del, _aligned.OP_D,
                                                  _aligned.OP_I), 0),
             mol_n=mol_n, mol_at=mol_at, truth=truth)
    for mate, first_base in ((1, np.zeros(n, dtype=np.int64)),
                             (2, insert[fam] - lens)):
        d.update(_aligned._align(params, rng, d, truth, fam, mate, first_base,
                                 margin))
    d["tlen"] = d["pos2"] + _aligned.ref_length(d["cigar2"]) - d["pos1"]
    return d


def _records(sl, d, second):
    """BAM records of R1 (``second`` 0) or R2 (1) of the read pairs in slice
    ``sl``: ``codec_bam``'s, with the CIGAR model's CIGAR, position, bin,
    template length and ``MC``; the forward read or the reverse-flagged one,
    by the molecule."""
    mol, ordinal = d["fam"][sl], d["ordinal"][sl]
    n = len(mol)
    reverse = d["r1_reverse"][mol] ^ bool(second)

    def own(key, mate_of=False):
        """Column ``key<m>`` of this read (of its mate with ``mate_of``)."""
        fwd, rev = d[key + "1"][sl], d[key + "2"][sl]
        flip = reverse != mate_of
        return np.where(flip[:, None] if fwd.ndim == 2 else flip, rev, fwd)

    ln, pos, mpos = own("len"), own("pos"), own("pos", True)
    cigar, ncig = own("cigar"), own("ncig")
    tlen = np.where(reverse, -d["tlen"][sl], d["tlen"][sl])
    mol_dig, mol_n = t.digits(mol, 8)
    ord_dig, ord_n = t.digits(ordinal, 4)
    name_len = 5 + mol_n + 1 + ord_n + 1
    body = [
        (t.const(n, b"codec"), None), (mol_dig, mol_n),
        (t.const(n, b":"), None), (ord_dig, ord_n),
        (t.const(n, b"\x00"), None),
        (cigar.astype("<u4").view(np.uint8).reshape(n, -1), 4 * ncig),
        (t.pack_seq(own("codes"), ln), (ln + 1) // 2), (own("quals"), ln),
        (t.const(n, b"MCZ"), None),
        *_aligned.cigar_text(own("cigar", True), own("ncig", True)),
        (t.const(n, b"\x00RGZA\x00MIZ"), None), (mol_dig, mol_n),
        (t.const(n, b"\x00RXZ"), None), (t.CODE_TO_ASCII[d["umi"][mol]], None),
        (t.const(n, b"\x00"), None)]
    return t.bam_record(
        body, 0, pos, name_len, 60,
        _grouped._reg2bin(pos, pos + _aligned.ref_length(cigar)), ncig,
        _codec._FLAGS[int(second), reverse.astype(np.int64)], ln, 0, mpos,
        tlen)


def write(d, prefix, level):
    path = prefix + ".bam"
    chunks = [bamio.bam_header(_grouped.HEADER, [(REF_NAME, REF_LENGTH)])]
    n = len(d["fam"])
    for lo in range(0, n, 65536):
        sl = slice(lo, min(lo + 65536, n))
        # one row per pair: R1's record, then R2's
        flat, _ = t.pack_rows(_records(sl, d, 0) + _records(sl, d, 1))
        chunks.append(flat.tobytes())
    bamio.write_bgzf(path, b"".join(chunks), level=level)
    return [path]
