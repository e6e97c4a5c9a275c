"""Segments of the most-common-alignment filter for tests: CIGARs laid out
as a BAM record holds them, and the filter's answer from ``core/cigar.py`` on
the Python-decoded entries, which the native pass is held to."""

import numpy as np

from fgumi_tpu.core import cigar as cigar_utils

OPS = "MIDNSHP=X"


def cigar_buffer(cigars, rng):
    """CIGARs ([(op, length)] each) as BAM words at odd offsets of one
    buffer that ends on the last one's last byte ->
    (buf, cigar_off, n_cigar)."""
    chunks, offs, at = [], [], 0
    for cig in cigars:
        pad = 2 * int(rng.integers(0, 4)) + 1 - at % 2  # an odd offset
        chunks.append(bytes(rng.integers(0, 256, pad, dtype=np.uint8)))
        at += pad
        offs.append(at)
        words = np.array([n << 4 | OPS.index(op) for op, n in cig],
                         dtype="<u4")
        chunks.append(words.tobytes())
        at += 4 * len(cig)
    buf = np.frombuffer(b"".join(chunks), dtype=np.uint8)
    return (buf, np.array(offs, dtype=np.int64),
            np.array([len(c) for c in cigars], dtype=np.int32))


def filter_oracle(cigars, reverse, lens):
    """``core/cigar.py``'s filter on one segment, as the engine ran it a
    read at a time before the native pass -> keep mask (uint8)."""
    entries = []
    for i, (cig, rev, ln) in enumerate(zip(cigars, reverse, lens)):
        simplified = cigar_utils.simplify(cig)
        if rev:
            simplified = cigar_utils.reverse(simplified)
        entries.append((i, int(ln), cigar_utils.truncate_to_query_length(
            simplified, int(ln))))
    entries.sort(key=lambda t: -t[1])
    kept = set(cigar_utils.select_most_common_alignment_group(entries))
    return np.array([i in kept for i in range(len(cigars))], dtype=np.uint8)


def random_segment(rng):
    """One segment: (cigars, reverse flags, final lengths). Rows are drawn
    round a few template CIGARs so that prefix groups, ties in length and
    ties in group size all come up; some rows are random outright."""
    n = int(rng.integers(1, 61))

    def random_cigar():
        return [(OPS[int(rng.integers(0, 9))], int(rng.integers(1, 40)))
                for _ in range(int(rng.integers(1, 7)))]

    templates = [random_cigar() for _ in range(int(rng.integers(1, 4)))]
    mixed = rng.random() < 0.5
    strand = bool(rng.integers(0, 2))
    few_lens = rng.random() < 0.5
    cigars, reverse, lens = [], [], []
    for _ in range(n):
        u = rng.random()
        if u < 0.15:
            cig = random_cigar()
        else:
            cig = list(templates[int(rng.integers(0, len(templates)))])
            if u < 0.4:  # the same ops, one length moved
                k = int(rng.integers(0, len(cig)))
                cig[k] = (cig[k][0],
                          max(1, cig[k][1] + int(rng.integers(-2, 3))))
            elif u < 0.5:  # an op more, or one fewer
                cig = cig[:-1] or cig if rng.random() < 0.5 \
                    else cig + random_cigar()[:1]
        query = sum(ln for op, ln in cig if op in "MIS=XH")
        cigars.append(cig)
        reverse.append(bool(rng.integers(0, 2)) if mixed else strand)
        hi = max(query, 1)
        lens.append(int(rng.choice([hi, max(hi - 3, 1), max(hi // 2, 1)]))
                    if few_lens else int(rng.integers(1, hi + 1)))
    return cigars, reverse, lens
