"""Layout ``aligned_duplex_bam``: ``duplex_bam``'s paired-strategy grouped
BAM as an aligner writes it, the input of ``duplex`` after bwa mem + zipper
+ ``group --strategy paired``: some reads soft-clipped at their 3' end, some
with an indel of their own, some molecules with an indel every read over it
shares, AB and BA reads alike.

The molecules, strands, reads, qualities and substitutions are
``duplex_bam``'s, draw for draw and in its order (with every rate 0 the input
of a seed is its input, byte for byte); then ``aligned_bam``'s CIGAR model is
drawn over them, by its own code (``aligned_bam._align``), with the
**molecule** in the place of ``aligned_bam``'s family: a molecule's indel is
drawn once (``mol_of_fam``), so its ``/A`` and ``/B`` reads over it share it.
The forward read of a pair (``codes1``: AB-R1 and BA-R2) reads the molecule
from its first base and has its 3' end on the right, as ``aligned_bam``'s R1;
the reverse read (``codes2``: AB-R2 and BA-R1) ends at the molecule's last
base and has its 3' end on the left, as ``aligned_bam``'s R2.

``pos``, ``bin``, ``tlen`` (the forward read's first aligned base to the
reverse read's last, negative on the reverse read), ``l_seq`` (the query
length) and ``MC`` (the mate's CIGAR) follow the CIGARs; names, flags and the
MI, RG and RX tags are ``duplex_bam``'s. ``cigar<m>`` / ``ncig<m>`` /
``pos<m>`` / ``event<m>`` hold the model's draws and ``truth`` the
molecules, for the reference and the tests."""

import numpy as np

import bamio
import traffic as t

_duplex = t.kind_module("duplex_bam")
_aligned = t.kind_module("aligned_bam")
_grouped = t.kind_module("grouped_bam")  # one reference, header and bin rule
REF_NAME, REF_LENGTH = _grouped.REF_NAME, _grouped.REF_LENGTH
DUPLEX = _duplex.DUPLEX


def generate(params, rng, common):
    sizes, fam = common["sizes"], common["fam"]
    n, length = len(fam), params["read_length"]
    half = params["umi_length"] // 2
    # ---- duplex_bam's draws, in its order
    kind, a_fam, b_fam = _duplex.molecules(len(sizes), params["duplex_share"],
                                           rng)
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
    d = dict(
        mol_kind=kind, a_fam=a_fam, b_fam=b_fam, mol_of_fam=mol_of_fam,
        strand_of_fam=strand_of_fam, insert=insert, start=start, umi=umi,
        len1=lens, len2=lens,
        codes1=t.mutate(rng, truth[:, :length][mol], params["error_rate"]),
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
                             (2, insert[mol] - lens)):
        d.update(_aligned._align(params, rng, d, truth, mol, mate, first_base,
                                 margin))
    d["tlen"] = d["pos2"] + _aligned.ref_length(d["cigar2"]) - d["pos1"]
    return d


def _records(sl, d, reverse):
    """BAM records of the forward (0) or reverse-flagged (1) read of the
    read pairs in slice ``sl``: ``duplex_bam``'s, with the CIGAR model's
    CIGAR, position, bin, template length and ``MC``."""
    fam, ordinal = d["fam"][sl], d["ordinal"][sl]
    n = len(fam)
    mol, strand = d["mol_of_fam"][fam], d["strand_of_fam"][fam]
    mate, other = (2, 1) if reverse else (1, 2)
    ln, pos = d[f"len{mate}"][sl], d[f"pos{mate}"][sl]
    cigar, ncig = d[f"cigar{mate}"][sl], d[f"ncig{mate}"][sl]
    tlen = -d["tlen"][sl] if reverse else d["tlen"][sl]
    mol_dig, mol_n = t.digits(mol, 8)
    ord_dig, ord_n = t.digits(ordinal, 4)
    letter = (ord("A") + strand).astype(np.uint8)[:, None]
    umi = t.CODE_TO_ASCII[d["umi"][mol]]
    half = umi.shape[1] // 2
    u1, u2 = umi[:, :half], umi[:, half:]
    flip = strand[:, None] == 1
    rx = np.concatenate((np.where(flip, u2, u1), t.const(n, b"-"),
                         np.where(flip, u1, u2)), axis=1)
    name_len = 1 + mol_n + 1 + 1 + ord_n + 1
    body = [
        (t.const(n, b"m"), None), (mol_dig, mol_n), (t.const(n, b":"), None),
        (letter, None), (ord_dig, ord_n), (t.const(n, b"\x00"), None),
        (cigar.astype("<u4").view(np.uint8).reshape(n, -1), 4 * ncig),
        (t.pack_seq(d[f"codes{mate}"][sl], ln), (ln + 1) // 2),
        (d[f"quals{mate}"][sl], ln),
        (t.const(n, b"MCZ"), None),
        *_aligned.cigar_text(d[f"cigar{other}"][sl], d[f"ncig{other}"][sl]),
        (t.const(n, b"\x00RGZA\x00MIZ"), None), (mol_dig, mol_n),
        (t.const(n, b"/"), None), (letter, None),
        (t.const(n, b"\x00RXZ"), None), (rx, None),
        (t.const(n, b"\x00"), None)]
    return t.bam_record(
        body, 0, pos, name_len, 60,
        _grouped._reg2bin(pos, pos + _aligned.ref_length(cigar)), ncig,
        _duplex._FLAGS[strand, int(reverse)], ln, 0, d[f"pos{other}"][sl],
        tlen)


def write(d, prefix, level):
    path = prefix + ".bam"
    chunks = [bamio.bam_header(_grouped.HEADER, [(REF_NAME, REF_LENGTH)])]
    n = len(d["fam"])
    for lo in range(0, n, 65536):
        sl = slice(lo, min(lo + 65536, n))
        # one row per pair: the forward read's record, then the reverse's
        flat, _ = t.pack_rows(_records(sl, d, False) + _records(sl, d, True))
        chunks.append(flat.tobytes())
    bamio.write_bgzf(path, b"".join(chunks), level=level)
    return [path]
