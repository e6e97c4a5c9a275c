"""Layout ``grouped_bam``: MI-grouped mapped read pairs, the input of
``simplex``. R1 reads the molecule's first bases, R2 its last, both stored on
the forward strand (R2 carries the reverse flag)."""

import numpy as np

import bamio
import traffic as t

REF_NAME, REF_LENGTH = "chr1", 10_000_000
HEADER = ("@HD\tVN:1.6\tSO:unsorted\tGO:query\n"
          f"@SQ\tSN:{REF_NAME}\tLN:{REF_LENGTH}\n"
          "@RG\tID:A\tSM:sample\tLB:lib\n")


def generate(params, rng, common):
    sizes, fam = common["sizes"], common["fam"]
    n, length = len(fam), params["read_length"]
    insert = rng.integers(int(length * 1.5), 3 * length, len(sizes))
    start = rng.integers(0, REF_LENGTH - insert - 1)
    truth = rng.integers(0, 4, (len(sizes), 3 * length), dtype=np.uint8)
    len1, len2 = t.lengths(rng, n, params), t.lengths(rng, n, params)
    t1 = truth[:, :length][fam]
    t2 = np.take_along_axis(
        truth, (insert - length)[:, None] + np.arange(length), axis=1)[fam]
    for cut in np.unique(length - len2):  # a shorter R2 starts later
        if cut:
            rows = np.flatnonzero(length - len2 == cut)
            t2[rows, :length - cut] = t2[rows, cut:]
    return dict(
        insert=insert, start=start, len1=len1, len2=len2,
        codes1=t.mutate(rng, t1, params["error_rate"]),
        codes2=t.mutate(rng, t2, params["error_rate"]),
        quals1=t.quals(rng, n, length, params),
        quals2=t.quals(rng, n, length, params),
        n_reads=2 * n)


def _reg2bin(beg, end):
    end = end - 1
    out = np.zeros(len(beg), dtype=np.int64)
    done = np.zeros(len(beg), dtype=bool)
    for shift, base in ((14, 4681), (17, 585), (20, 73), (23, 9), (26, 1)):
        hit = ~done & ((beg >> shift) == (end >> shift))
        out[hit] = base + (beg[hit] >> shift)
        done |= hit
    return out


def _mapped_records(sl, d, mate):
    """BAM records of one mate (1 or 2) for the read pairs in slice ``sl``."""
    fam, ordinal = d["fam"][sl], d["ordinal"][sl]
    n = len(fam)
    len1, len2 = d["len1"][sl], d["len2"][sl]
    start, insert = d["start"][fam], d["insert"][fam]
    r2_pos = start + insert - len2
    if mate == 1:
        ln, mate_ln, pos, mpos, tlen, flag = len1, len2, start, r2_pos, insert, 97
        codes, quals = d["codes1"][sl], d["quals1"][sl]
    else:
        ln, mate_ln, pos, mpos, tlen, flag = len2, len1, r2_pos, start, -insert, 145
        codes, quals = d["codes2"][sl], d["quals2"][sl]
    fam_dig, fam_n = t.digits(fam, 8)
    ord_dig, ord_n = t.digits(ordinal, 4)
    mc_dig, mc_n = t.digits(mate_ln, 4)
    name_len = 3 + fam_n + 2 + ord_n + 1
    body = [
        (t.const(n, b"fam"), None), (fam_dig, fam_n), (t.const(n, b":r"), None),
        (ord_dig, ord_n), (t.const(n, b"\x00"), None),
        (t.ints(("<u4",), (ln << 4)), None),  # one CIGAR op: <ln>M
        (t.pack_seq(codes, ln), (ln + 1) // 2), (quals, ln),
        (t.const(n, b"MCZ"), None), (mc_dig, mc_n),
        (t.const(n, b"M\x00RGZA\x00MIZ"), None), (fam_dig, fam_n),
        (t.const(n, b"\x00"), None)]
    return t.bam_record(body, 0, pos, name_len, 60, _reg2bin(pos, pos + ln),
                        1, flag, ln, 0, mpos, tlen)


def write(d, prefix, level):
    path = prefix + ".bam"
    chunks = [bamio.bam_header(HEADER, [(REF_NAME, REF_LENGTH)])]
    n = len(d["fam"])
    for lo in range(0, n, 65536):
        sl = slice(lo, min(lo + 65536, n))
        # one row per pair: R1's record, then R2's
        flat, _ = t.pack_rows(_mapped_records(sl, d, 1)
                              + _mapped_records(sl, d, 2))
        chunks.append(flat.tobytes())
    bamio.write_bgzf(path, b"".join(chunks), level=level)
    return [path]
