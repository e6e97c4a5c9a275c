"""Layout ``paired_fastq``: gzip FASTQ pairs with an inline UMI in front of
R1, the input of ``pipeline``."""

import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import traffic as t


def generate(params, rng, common):
    sizes, fam = common["sizes"], common["fam"]
    n, length, ulen = len(fam), params["read_length"], params["umi_length"]
    insert = int(length * 1.8)
    umi = rng.integers(0, 4, (len(sizes), ulen), dtype=np.uint8)
    template = rng.integers(0, 4, (len(sizes), insert), dtype=np.uint8)
    body2 = t.COMPLEMENT[template[:, ::-1][:, :length]]
    quals1 = t.quals(rng, n, ulen + length, params)
    quals1[:, :ulen] = 37
    return dict(
        umi=umi,
        codes1=t.mutate(rng, template[fam, :length], params["error_rate"]),
        codes2=t.mutate(rng, body2[fam], params["error_rate"]),
        quals1=quals1, quals2=t.quals(rng, n, length, params),
        n_reads=2 * n)


def _fastq_bytes(d, mate):
    fam, ordinal = d["fam"], d["ordinal"]
    n = len(fam)
    fam_dig, fam_n = t.digits(fam, 8)
    ord_dig, ord_n = t.digits(ordinal, 4)
    codes = d[f"codes{mate}"]
    if mate == 1:
        codes = np.concatenate([d["umi"][fam], codes], axis=1)
    quals = d[f"quals{mate}"] + 33
    flat, _ = t.pack_rows([
        (t.const(n, b"@fam"), None), (fam_dig, fam_n), (t.const(n, b":r"), None),
        (ord_dig, ord_n), (t.const(n, b"/%d\n" % mate), None),
        (t.CODE_TO_ASCII[codes], None), (t.const(n, b"\n+\n"), None),
        (quals, None), (t.const(n, b"\n"), None)])
    return flat


def _write_gzip(path, flat, level):
    comp = zlib.compressobj(level, zlib.DEFLATED, 31)
    with open(path, "wb") as f:
        view = memoryview(flat)
        for lo in range(0, len(view), 1 << 24):
            f.write(comp.compress(view[lo:lo + (1 << 24)]))
        f.write(comp.flush())


def write(d, prefix, level):
    paths = [prefix + ".r1.fq.gz", prefix + ".r2.fq.gz"]
    with ThreadPoolExecutor(2) as pool:
        list(pool.map(lambda m: _write_gzip(paths[m - 1], _fastq_bytes(d, m),
                                            level), (1, 2)))
    return paths
