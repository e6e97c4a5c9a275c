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
