"""The plain reference: what the configuration's command must write, worked
out from the generator's arrays in straightforward numpy, float64.

It imports nothing of the program and takes nothing the program made. The
arithmetic is fgbio's / fgumi's consensus model as the program's own f64
oracle documents it (``fgumi_tpu/ops/oracle.py``, ``ops/phred.py``,
``consensus/vanilla.py``, ``consensus/overlapping.py``): a copy kept here so
that no later PR can move the yardstick. ``dtype=np.float32`` is the control:
the same model one precision lower, which has to come out as *not* correct.

What is assumed of the inputs (true of ``traffic.py``'s): every CIGAR is one
``M`` run, no secondary or supplementary records, R1 forward / R2 reverse on
one contig, and no read extends past its mate's far end.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import traffic
from traffic import N_CODE, pack_rows

MAX_PHRED, MIN_PHRED = 93, 2
I16_MAX = 32767
LN_10 = np.log(10.0)
LN_TWO = np.log(2.0)
LN_FOUR_THIRDS = 0.2876820724517809
PHRED_PRECISION = 0.001


# ------------------------------------------------------------ log-space maths
# (each function follows the operation order of the scalar model exactly; the
# order decides the last ulp and with it the odd integer Phred)

def _log1pexp(x):
    return np.where(
        x <= -37.0, np.exp(np.minimum(x, 0.0)),
        np.where(x <= 18.0, np.log1p(np.exp(np.minimum(x, 18.0))),
                 np.where(x <= 33.3, x + np.exp(-np.maximum(x, 18.0)), x)))


def _ln_one_minus_exp(x):
    with np.errstate(divide="ignore", invalid="ignore"):
        near = np.log(-np.expm1(np.minimum(x, 0.0)))
        far = np.log1p(-np.exp(np.minimum(x, 0.0)))
    return np.where(x >= 0.0, -np.inf, np.where(x >= -LN_TWO, near, far))


def _ln_sum_exp(a, b):
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    with np.errstate(invalid="ignore"):
        combined = lo + _log1pexp(hi - lo)
    return np.where(np.isneginf(a), b, np.where(np.isneginf(b), a, combined))


def _ln_sum_exp4(v):
    """log-sum-exp over the last axis (4 lanes): seeded with the first
    minimum lane, the others folded in lane order."""
    min_idx = np.argmin(v, axis=-1)
    acc = np.take_along_axis(v, min_idx[..., None], axis=-1)[..., 0]
    for lane in range(4):
        acc = np.where(min_idx == lane, acc, _ln_sum_exp(acc, v[..., lane]))
    return np.where(np.all(np.isneginf(v), axis=-1), -np.inf, acc)


def _ln_a_minus_b(a, b):
    eps = np.finfo(a.dtype).eps
    with np.errstate(invalid="ignore"):
        near_equal = np.abs(a - b) < eps
        diff = a + _ln_one_minus_exp(np.minimum(b - a, 0.0))
    return np.where(np.isneginf(b), a, np.where(near_equal, -np.inf, diff))


def _two_trials(p1, p2):
    """ln P(error in either of two trials): X + Y - 4/3 XY in log space."""
    hi, lo = np.maximum(p1, p2), np.minimum(p1, p2)
    with np.errstate(invalid="ignore"):
        quick = (hi - lo) >= 6.0
    term1 = _ln_sum_exp(hi, lo)
    term2 = np.where(quick, -np.inf, hi.dtype.type(LN_FOUR_THIRDS) + hi + lo)
    return np.where(quick, hi, _ln_a_minus_b(term1, term2))


def _to_phred(ln_prob):
    t = ln_prob.dtype.type
    phred = np.floor(t(-10.0) * ln_prob / t(LN_10) + t(PHRED_PRECISION))
    phred = np.clip(phred, MIN_PHRED, MAX_PHRED)
    out = np.where(ln_prob < t(-MAX_PHRED * LN_10 / 10.0), MAX_PHRED, phred)
    return np.where(np.isnan(out), 0, out).astype(np.uint8)


class Tables:
    """Per-quality log-probabilities for one (pre, post) UMI error pair."""

    def __init__(self, pre=45, post=40, dtype=np.float64):
        t = np.dtype(dtype).type
        q = np.arange(MAX_PHRED + 1).astype(dtype)
        ln_err_seq = -q * t(LN_10) / t(10.0)
        ln_post = np.full_like(ln_err_seq, -t(post) * t(LN_10) / t(10.0))
        adjusted = _two_trials(ln_post, ln_err_seq)
        self.correct = _ln_one_minus_exp(adjusted)
        self.error_per_alt = adjusted - t(np.log(3.0))
        self.ln_pre = -t(pre) * t(LN_10) / t(10.0)
        ln_label = np.full_like(ln_err_seq,
                                -t(min(pre, post)) * t(LN_10) / t(10.0))
        self.single = np.minimum(
            _to_phred(_two_trials(ln_err_seq, ln_label)), MAX_PHRED)
        self.dtype = np.dtype(dtype)


# ---------------------------------------------------------------- consensus

def _call_block(codes, quals, start, count, clen, tab):
    """Consensus of the jobs of one block. ``start``/``count`` give each
    job's rows (contiguous, read order), sorted by ``count`` descending;
    ``clen`` is each job's consensus length. Returns (winner, qual, depth,
    errors) as (jobs, L) arrays, before thresholds."""
    m, width = len(start), codes.shape[1]
    dt = tab.dtype
    sums = np.zeros((m, width, 4), dtype=dt)
    comps = np.zeros((m, width, 4), dtype=dt)
    obs = np.zeros((m, width, 4), dtype=np.int32)
    lanes = np.arange(4, dtype=np.uint8)
    in_len = np.arange(width)[None, :] < clen[:, None]
    for r in range(int(count.max(initial=0))):
        k = int(np.searchsorted(-count, -r, side="left"))  # jobs with count > r
        rows = start[:k] + r
        c = codes[rows]
        q = np.minimum(quals[rows], MAX_PHRED)
        valid = ((c != N_CODE) & in_len[:k])[:, :, None]
        one_hot = c[:, :, None] == lanes
        values = np.where(one_hot, tab.correct[q][:, :, None],
                          tab.error_per_alt[q][:, :, None])
        with np.errstate(invalid="ignore"):  # Kahan, in read order
            y = values - comps[:k]
            t = sums[:k] + y
            new_comps = (t - sums[:k]) - y
        sums[:k] = np.where(valid, t, sums[:k])
        comps[:k] = np.where(valid, new_comps, comps[:k])
        obs[:k] += valid & one_hot
    depth = obs.sum(axis=2)
    ll_max = np.where(np.isnan(sums), -np.inf, sums)
    max_ll = ll_max.max(axis=2)
    winner = ll_max.argmax(axis=2)
    with np.errstate(invalid="ignore"):
        close = np.abs(sums - max_ll[:, :, None]) <= np.finfo(dt).eps
    tie = np.any((np.arange(4) > winner[:, :, None]) & close, axis=2)
    tie |= np.isneginf(max_ll)
    with np.errstate(invalid="ignore"):
        ln_posterior = max_ll - _ln_sum_exp4(sums)
        ln_cons_err = _ln_one_minus_exp(ln_posterior)
        qual = _to_phred(_two_trials(np.full_like(ln_cons_err, tab.ln_pre),
                                     ln_cons_err))
    no_call = tie | (depth == 0)
    winner = np.where(no_call, N_CODE, winner).astype(np.uint8)
    qual = np.where(no_call, MIN_PHRED, qual).astype(np.uint8)
    win_obs = np.take_along_axis(obs, np.minimum(winner, 3)[:, :, None],
                                 axis=2)[:, :, 0]
    errors = depth - np.where(no_call, 0, win_obs)
    return winner, qual, depth, errors


def call_jobs(codes, quals, lens, job_start, job_count, opts, dtype,
              block=4096, threads=None):
    """Consensus reads of every job (a job = the reads of one read type of
    one molecule, contiguous rows in read order; positions at or beyond a
    read's length are not observed). Returns per-job (bases, quals, depth,
    errors, consensus length), thresholds applied."""
    tab = Tables(opts["error_rate_pre_umi"], opts["error_rate_post_umi"],
                 dtype)
    min_reads, min_q = opts["min_reads"], opts["min_consensus_base_quality"]
    n_jobs, width = len(job_start), codes.shape[1]
    codes = np.where(np.arange(width)[None, :] < lens[:, None], codes, N_CODE)
    # consensus length: the min_reads-th longest read of the job
    row_job = np.repeat(np.arange(n_jobs), job_count)
    order = np.lexsort((-lens, row_job))
    clen = lens[order][job_start + min_reads - 1]
    bases = np.full((n_jobs, width), N_CODE, dtype=np.uint8)
    out_q = np.zeros((n_jobs, width), dtype=np.uint8)
    depth = np.zeros((n_jobs, width), dtype=np.int32)
    errors = np.zeros((n_jobs, width), dtype=np.int32)
    in_len = np.arange(width)[None, :] < clen[:, None]

    single = np.flatnonzero(job_count == 1)
    rows = job_start[single]
    adj = tab.single[np.minimum(quals[rows], MAX_PHRED)]
    low = adj < min_q
    live = in_len[single]
    bases[single] = np.where(live, np.where(low, N_CODE, codes[rows]), N_CODE)
    out_q[single] = np.where(live, np.where(low, MIN_PHRED, adj), 0)
    depth[single] = live & (codes[rows] != N_CODE)

    multi = np.flatnonzero(job_count > 1)
    multi = multi[np.argsort(-job_count[multi], kind="stable")]
    # interleave so that every block holds a like mix of family sizes
    n_blocks = max(1, -(-len(multi) // block))
    blocks = [multi[i::n_blocks] for i in range(n_blocks)]

    def run(ids):
        w, q, d, e = _call_block(codes, quals, job_start[ids], job_count[ids],
                                 clen[ids], tab)
        low_depth, low_q = d < min_reads, q < min_q
        live = in_len[ids]
        bases[ids] = np.where(live & ~(low_depth | low_q), w, N_CODE)
        out_q[ids] = np.where(live, np.where(
            low_depth, 0, np.where(low_q, MIN_PHRED, q)), 0)
        depth[ids] = np.where(live, d, 0)
        errors[ids] = np.where(live, e, 0)

    with ThreadPoolExecutor(threads or os.cpu_count() or 8) as pool:
        list(pool.map(run, blocks))
    return bases, out_q, depth, errors, clen


# ------------------------------------------------------- read preparation

def overlap_correct(d):
    """Pre-correct the bases a pair's two reads both cover (agreement: sum
    the qualities, cap 93; disagreement: the better base wins with the
    difference, a tie masks both to N/Q2). In place, forward-strand layout."""
    c1, q1, c2, q2 = d["codes1"], d["quals1"], d["codes2"], d["quals2"]
    len1, len2 = d["len1"], d["len2"]
    ov = len1 + len2 - d["insert"][d["fam"]]
    rows = np.flatnonzero(ov > 0)
    if not len(rows):
        return
    width = int(ov.max())
    j = np.arange(width)[None, :]
    live = j < ov[rows, None]
    o2 = np.where(live, j, 0)
    o1 = np.where(live, (len1 - ov)[rows, None] + j, 0)
    r = rows[:, None]
    b1, b2 = c1[r, o1], c2[r, o2]
    qa, qb = q1[r, o1].astype(np.int32), q2[r, o2].astype(np.int32)
    live &= (b1 != N_CODE) & (b2 != N_CODE)
    agree = b1 == b2
    tie = qa == qb
    base = np.where(agree, b1, np.where(tie, N_CODE, np.where(qa > qb, b1, b2)))
    qual = np.where(agree, np.minimum(qa + qb, 93),
                    np.where(tie, MIN_PHRED,
                             np.maximum(np.abs(qa - qb), MIN_PHRED)))
    rr = np.broadcast_to(r, live.shape)[live]
    c1[rr, o1[live]] = base[live]
    c2[rr, o2[live]] = base[live]
    q1[rr, o1[live]] = qual[live]
    q2[rr, o2[live]] = qual[live]


def source_reads(codes, quals, lens, reverse, min_input_q):
    """Orient (reverse-complement a reverse-strand read), mask bases under
    the input quality to N/Q2, trim trailing N. Returns new arrays."""
    width = codes.shape[1]
    cols = np.arange(width)[None, :]
    if reverse:
        idx = np.clip(lens[:, None] - 1 - cols, 0, width - 1)
        codes = traffic.COMPLEMENT[np.take_along_axis(codes, idx, axis=1)]
        quals = np.take_along_axis(quals, idx, axis=1)
    in_len = cols < lens[:, None]
    low = (quals < min_input_q) & in_len
    codes = np.where(low | ~in_len, N_CODE, codes).astype(np.uint8)
    quals = np.where(low, MIN_PHRED, quals).astype(np.uint8)
    called = codes != N_CODE
    final = np.where(called.any(axis=1),
                     width - np.argmax(called[:, ::-1], axis=1), 0)
    return codes, quals, final


# ----------------------------------------------------------- serialisation

def record_segments(name_digits, name_ndig, flag, result, rx=None):
    """BAM records of consensus reads as ``pack_rows`` segments: unmapped,
    named ``fgumi:<MI>``, tags RG cD cM cE cd ce MI (and RX when given)."""
    bases, quals, depth, errors, clen = result
    n = len(clen)
    depth = np.minimum(depth, I16_MAX)
    errors = np.minimum(errors, I16_MAX)
    live = np.arange(bases.shape[1])[None, :] < clen[:, None]
    c_max = depth.max(axis=1)
    c_min = np.where(live, depth, I16_MAX + 1).min(axis=1)
    total_d, total_e = depth.sum(axis=1), errors.sum(axis=1)
    rate = np.where(total_d > 0, total_e.astype(np.float32)
                    / np.maximum(total_d, 1).astype(np.float32),
                    np.float32(0)).astype(np.float32)
    seq = traffic.pack_seq(bases, clen)
    body = [
        (traffic.const(n, b"fgumi:"), None), (name_digits, name_ndig),
        (traffic.const(n, b"\x00"), None),
        (seq, (clen + 1) // 2), (quals, clen),
        (traffic.const(n, b"RGZA\x00cDi"), None),
        (traffic.ints(("<i4",), c_max), None),
        (traffic.const(n, b"cMi"), None),
        (traffic.ints(("<i4",), c_min), None),
        (traffic.const(n, b"cEf"), None),
        (traffic.ints(("<f4",), rate), None),
        (traffic.const(n, b"cdBs"), None),
        (traffic.ints(("<u4",), clen), None),
        (depth.astype("<i2").view(np.uint8).reshape(n, -1), 2 * clen),
        (traffic.const(n, b"ceBs"), None),
        (traffic.ints(("<u4",), clen), None),
        (errors.astype("<i2").view(np.uint8).reshape(n, -1), 2 * clen),
        (traffic.const(n, b"MIZ"), None), (name_digits, name_ndig),
        (traffic.const(n, b"\x00"), None)]
    if rx is not None:
        body += [(traffic.const(n, b"RXZ"), None), (rx, None),
                 (traffic.const(n, b"\x00"), None)]
    return traffic.bam_record(body, -1, -1, 6 + name_ndig + 1, 0, 4680, 0,
                              flag, clen, -1, -1, 0)


SIMPLEX_DEFAULTS = {
    "error_rate_pre_umi": 45, "error_rate_post_umi": 40,
    "min_input_base_quality": 10, "min_reads": 1,
    "min_consensus_base_quality": 40}


def simplex(d, opts, dtype=np.float64):
    """Expected output records of ``simplex`` on a ``grouped_bam`` input:
    for every molecule (MI) one R1 and one R2 consensus read, in input order.
    Returns (flat record bytes, records, input reads accounted for)."""
    opts = {**SIMPLEX_DEFAULTS, **opts}
    d = dict(d)
    for key in ("codes1", "codes2", "quals1", "quals2"):
        d[key] = d[key].copy()
    overlap_correct(d)
    sizes = d["sizes"]
    n_fam = len(sizes)
    fam_start = np.cumsum(sizes) - sizes
    results = []
    for mate, reverse in ((1, False), (2, True)):
        codes, quals, final = source_reads(
            d[f"codes{mate}"], d[f"quals{mate}"], d[f"len{mate}"], reverse,
            opts["min_input_base_quality"])
        if (final == 0).any():
            raise NotImplementedError("a read trimmed to nothing")
        results.append(call_jobs(codes, quals, final, fam_start, sizes, opts,
                                 dtype))
    digits, ndig = traffic.digits(np.arange(n_fam), 8)
    # one row per molecule: R1's record, then R2's
    flat, _ = pack_rows(record_segments(digits, ndig, 77, results[0])
                        + record_segments(digits, ndig, 141, results[1]))
    return flat, 2 * n_fam, int(2 * sizes.sum())


# --------------------------------------------- the FastqToConsensus chain

def adjacency_molecules(umi_ints, ulen, edits=1):
    """UMI-tools directed adjacency over one position group. ``umi_ints`` is
    each template's UMI as a base-4 integer (A<C<G<T, so integer order is
    string order). Unique UMIs are ranked by (-count, string); roots are
    taken in rank order and capture, breadth first, every unassigned UMI
    within ``edits`` mismatches whose count is at most count // 2 + 1.
    Molecule ids are minted in root order. Returns each template's id."""
    if edits != 1:
        raise NotImplementedError("the reference knows one mismatch")
    uniq, inverse, counts = np.unique(umi_ints, return_inverse=True,
                                      return_counts=True)
    rank_order = np.lexsort((uniq, -counts))
    ranked, rcounts = uniq[rank_order], counts[rank_order]
    where = np.full(4 ** ulen, -1, dtype=np.int64)
    where[ranked] = np.arange(len(ranked))
    # every one-mismatch neighbour: replace the base at one position
    place = 4 ** np.arange(ulen, dtype=np.int64)
    digit = (ranked[:, None] // place) % 4
    cand = (ranked[:, None, None]
            + ((np.arange(4)[None, None, :] - digit[:, :, None])
               * place[None, :, None])).reshape(len(ranked), -1)
    nbr = where[cand]
    nbr[nbr == np.arange(len(ranked))[:, None]] = -1
    nbr.sort(axis=1)
    root_of = np.full(len(ranked), -1, dtype=np.int64)
    for root in range(len(ranked)):
        if root_of[root] >= 0:
            continue
        root_of[root] = root
        queue, head = [root], 0
        while head < len(queue):
            idx = queue[head]
            head += 1
            row = nbr[idx]
            row = row[row >= 0]
            for child in row[(root_of[row] < 0)
                             & (rcounts[row] <= rcounts[idx] // 2 + 1)]:
                root_of[child] = root
                queue.append(int(child))
    roots, molecule = np.unique(root_of, return_inverse=True)
    by_unique = np.empty(len(uniq), dtype=np.int64)
    by_unique[rank_order] = molecule
    return by_unique[inverse]


def _consensus_umi(umi_codes, job_start, job_count):
    """The RX tag of each consensus read: the reads' UMI when they agree,
    else the likelihood consensus of the UMIs (flat Q20, Q90/Q90 tables)."""
    first = umi_codes[job_start]
    row_job = np.repeat(np.arange(len(job_start)), job_count)
    mixed = np.zeros(len(job_start), dtype=bool)
    np.logical_or.at(mixed, row_job,
                     (umi_codes != first[row_job]).any(axis=1))
    out = first.copy()
    ids = np.flatnonzero(mixed)
    if len(ids):
        ids = ids[np.argsort(-job_count[ids], kind="stable")]
        quals = np.full(umi_codes.shape, 20, dtype=np.uint8)
        clen = np.full(len(ids), umi_codes.shape[1])
        winner, _q, _d, _e = _call_block(
            umi_codes, quals, job_start[ids], job_count[ids], clen,
            Tables(90, 90, np.float64))
        out[ids] = winner
    return out


CHAIN_DEFAULTS = {
    **SIMPLEX_DEFAULTS, "edits": 1, "filter_min_reads": 3,
    "filter_max_read_error_rate": 0.025, "filter_max_base_error_rate": 0.1,
    "filter_max_no_call_fraction": 0.2}


def chain(d, opts, dtype=np.float64):
    """Expected output records of ``pipeline`` (extract -> sort -> group ->
    simplex -> filter) on a ``paired_fastq`` input whose first read carries
    an inline UMI. Returns (flat record bytes, records, input reads)."""
    opts = {**CHAIN_DEFAULTS, **opts}
    fam, ordinal = d["fam"], d["ordinal"]
    ulen = d["umi"].shape[1]
    # extract + sort: unmapped templates order by raw name bytes (stable)
    names = np.char.add(np.char.add(np.char.add(
        b"fam", fam.astype("S")), b":r"), ordinal.astype("S"))
    order = np.argsort(names, kind="stable")
    umi_codes = d["umi"][fam][order]
    umi_ints = (umi_codes.astype(np.int64)
                * 4 ** np.arange(ulen - 1, -1, -1, dtype=np.int64)).sum(axis=1)
    # group: every unmapped template is in the one position group; the
    # records keep their sorted order and gain MI
    mi = adjacency_molecules(umi_ints, ulen, opts["edits"])
    # simplex: consecutive records of one MI are one molecule
    new_run = np.concatenate(([True], mi[1:] != mi[:-1]))
    job_start = np.flatnonzero(new_run)
    job_count = np.diff(np.concatenate((job_start, [len(mi)])))
    job_mi = mi[job_start]
    rx = traffic.CODE_TO_ASCII[_consensus_umi(umi_codes, job_start, job_count)]
    digits, ndig = traffic.digits(job_mi, 8)
    segs, passes = [], []
    for mate, flag in ((1, 77), (2, 141)):
        codes = d[f"codes{mate}"][order]
        quals = d[f"quals{mate}"][order][:, -codes.shape[1]:]
        lens = np.full(len(codes), codes.shape[1])
        codes, quals, final = source_reads(codes, quals, lens, False,
                                           opts["min_input_base_quality"])
        if (final == 0).any():
            raise NotImplementedError("a read trimmed to nothing")
        bases, out_q, depth, errors, clen = call_jobs(
            codes, quals, final, job_start, job_count, opts, dtype)
        # filter: read-level cD / cE, then per-base masks, then no-calls
        live = np.arange(bases.shape[1])[None, :] < clen[:, None]
        d16, e16 = np.minimum(depth, I16_MAX), np.minimum(errors, I16_MAX)
        total_d, total_e = d16.sum(axis=1), e16.sum(axis=1)
        rate = np.where(total_d > 0, total_e.astype(np.float32)
                        / np.maximum(total_d, 1).astype(np.float32),
                        np.float32(0)).astype(np.float64)
        ok = (rate <= opts["filter_max_read_error_rate"]) \
            & (d16.max(axis=1) >= opts["filter_min_reads"])
        base_rate = np.where(d16 > 0, e16 / np.maximum(d16, 1), 0.0)
        mask = live & ((d16 < opts["filter_min_reads"])
                       | ((d16 > 0)
                          & (base_rate > opts["filter_max_base_error_rate"])))
        bases = np.where(mask, N_CODE, bases)
        out_q = np.where(mask, MIN_PHRED, out_q).astype(np.uint8)
        n_after = (live & (bases == N_CODE)).sum(axis=1)
        ok &= ~((clen > 0) & (n_after / np.maximum(clen, 1)
                              > opts["filter_max_no_call_fraction"]))
        passes.append(ok)
        segs += record_segments(digits, ndig, flag,
                                (bases, out_q, depth, errors, clen), rx=rx)
    keep = passes[0] & passes[1]  # a template passes whole or not at all
    flat, _ = pack_rows([(seg[keep], None if lens is None else lens[keep])
                         for seg, lens in segs])
    return flat, 2 * int(keep.sum()), int(2 * len(fam))
