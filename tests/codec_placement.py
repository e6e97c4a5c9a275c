"""The numpy placement of CODEC strands, kept as the oracle of
`nb.codec_place` (tests/test_fast_codec.py, tests/test_native_asan.py): what
`FastCodecCaller._finish_batch` ran before the native ragged copy. Fill with
the pad, then one gather + scatter a source through two ragged index arrays.
"""

import numpy as np

I16_MAX = 32767
PAD = ord("n")


def _ragged_arange(starts, counts, step=1):
    """``concatenate([arange(s, s + step * c, step) for s, c in zip(starts,
    counts)])`` (``step`` 1 or -1)."""
    excl = np.cumsum(counts) - counts
    return np.repeat(starts - step * excl, counts) \
        + step * np.arange(int(counts.sum()), dtype=np.int64)


def numpy_place(sources, sid, rows, ks, base, offs, table, reverse, cap,
                pad_base):
    """`nb.codec_place`'s contract in numpy."""
    sid, rows, ks, base = (np.asarray(a) for a in (sid, rows, ks, base))
    T = int(offs[-1])
    bt = np.full(T, pad_base, np.uint8)
    qt = np.zeros(T, np.uint8)
    dt = np.zeros(T, np.int32)
    et = np.zeros(T, np.int32)
    for s, mats in enumerate(sources):
        j = np.nonzero(sid == s)[0]
        if not len(j):
            continue
        mats = [np.atleast_2d(m) for m in mats]
        tgt = _ragged_arange(base[j], ks[j])
        first = rows[j] * mats[0].shape[1]
        flat = _ragged_arange(first + ks[j] - 1, ks[j], -1) if reverse \
            else _ragged_arange(first, ks[j])
        b_all, q_all, dmat, emat = (m.reshape(-1) for m in mats)
        bt[tgt] = table[b_all[flat]]
        qt[tgt] = q_all[flat]
        dt[tgt] = np.minimum(dmat[flat], cap)
        et[tgt] = np.minimum(emat[flat], cap)
    return bt, qt, dt, et


def _result_matrices(rng, n_rows, width, dtype, top):
    """Four result matrices as a dispatch leaves them: codes 0..4, quals,
    depths and errors of ``dtype`` up to ``top``."""
    return (rng.integers(0, 5, (n_rows, width)).astype(np.uint8),
            rng.integers(0, 94, (n_rows, width)).astype(np.uint8),
            rng.integers(0, top, (n_rows, width)).astype(dtype),
            rng.integers(0, top, (n_rows, width)).astype(dtype))


#: case -> (which sources exist: the dense batch's int32 matrices, the
#: single-read pass's int64 ones, materialised strands; the sources strands
#: come from; where a shorter strand lies in its molecule; the largest
#: depth; the strands' lengths)
PLACE_CASES = {
    "dense_int32": (("dense",), (0,), "left", 60, (1, 48)),
    "single_int64": (("single",), (1,), "left", 2, (1, 48)),
    "both_sources": (("dense", "single"), (0, 1), "left", 60, (1, 48)),
    "no_strand_of_the_dense_source": (("dense", "single"), (1,), "left", 60,
                                      (1, 48)),
    "left_padded": (("dense", "single"), (0, 1), "right", 60, (1, 48)),
    "capped_over_i16_max": (("dense", "single"), (0, 1), "right",
                            3 * I16_MAX, (1, 48)),
    "molecules_of_length_one": (("dense", "single"), (0, 1), "exact", 60,
                                (1, 2)),
    "one_materialised_strand": (("dense", "single", "arrays"), (0, 1, 2),
                                "right", 60, (1, 48)),
    "only_materialised_strands": (("arrays",), (2, 3, 4), "left", 2,
                                  (0, 48)),
    # the last row's strand ends on its matrix's last element
    "strands_as_wide_as_the_matrices": (("dense", "single", "arrays"),
                                        (0, 1, 2), "exact", 60, (64, 65)),
}


def place_case(name, seed=41):
    """``(sources, sid, rows, ks, base, offs)``: one side of a batch."""
    have, sids, where, top, (k_lo, k_hi) = PLACE_CASES[name]
    rng = np.random.default_rng(seed)
    J, width = 57, 64
    sid = rng.choice(sids, J).astype(np.int32)
    ks = rng.integers(k_lo, k_hi, J)
    sources = [
        _result_matrices(rng, 23, width, np.int32, top)
        if "dense" in have else None,
        _result_matrices(rng, 31, width, np.int64, top)
        if "single" in have else None]
    rows = np.where(sid == 0, rng.integers(0, 23, J), rng.integers(0, 31, J))
    if "arrays" in have:
        # a materialised strand is a source of its own, one row long
        for j in np.nonzero(sid >= 2)[0]:
            sid[j], rows[j] = len(sources), 0
            sources.append(tuple(
                m[0] for m in _result_matrices(rng, 1, ks[j], np.int64,
                                               top)))
    Ls = ks if where == "exact" else ks + rng.integers(0, 9, J)
    offs = np.zeros(J + 1, dtype=np.int64)
    np.cumsum(Ls, out=offs[1:])
    base = offs[:-1] + (Ls - ks if where == "right" else 0)
    return sources, sid, rows, ks, base, offs
