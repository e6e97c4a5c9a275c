"""Vectorized consensus-filter path over RecordBatch inputs.

The filter-command analog of consensus/fast.py: read-level thresholds,
per-base mask computation (cd/ce and ad/ae/bd/be tag matrices gathered
natively), in-place N/Q2 masking, the no-call check, and template verdicts
all run as whole-batch array passes; kept/rejected records emit as
contiguous slices of the (mutated in place) batch buffer.

Semantics contract: identical output records, statistics, and rejection
reasons to commands/filter.py::run_filter on the same stream (tested in
tests/test_fast_filter.py). Engages only for the configurations the arrays
can express: no reference (unmapped-only input, enforced with the same
error as the classic path), no per-base tag reversal, no single-strand
agreement check. An unexpected per-base tag subtype anywhere in the input
aborts the fast pass and the command re-runs entirely on the classic
per-record engine (cli.py catches _OddSubtype before any output commits
beyond what the rerun rewrites).
"""

import numpy as np

from ..consensus.filter import (EXCESSIVE_ERROR_RATE, INSUFFICIENT_READS,
                                LOW_QUALITY, PASS, TOO_MANY_NO_CALLS,
                                FilterConfig, duplex_base_mask_arrays,
                                simplex_base_mask_arrays)
from ..io.bam import FLAG_SECONDARY, FLAG_SUPPLEMENTARY, FLAG_UNMAPPED
from ..native import batch as nb
from ..observe.trace import spanned
from .filter import FilterStats, _process_one

_R_PASS, _R_INSUF, _R_ERR, _R_LOWQ, _R_NOCALL = 0, 1, 2, 3, 4
_RESULT_STR = {_R_PASS: PASS, _R_INSUF: INSUFFICIENT_READS,
               _R_ERR: EXCESSIVE_ERROR_RATE, _R_LOWQ: LOW_QUALITY,
               _R_NOCALL: TOO_MANY_NO_CALLS}
_INT_TYPES = (("c", 1, True), ("C", 1, False), ("s", 2, True),
              ("S", 2, False), ("i", 4, True), ("I", 4, False))


def int_tag_values(batch, tag: bytes):
    """(values int64[n], present bool[n]) for an integer-typed tag
    (RawRecord.get_int semantics: non-integer types read as absent)."""
    vo, vl, vt = batch.tag_locs(tag)
    buf = batch.buf
    val = np.zeros(batch.n, dtype=np.int64)
    present = np.zeros(batch.n, dtype=bool)
    for code, width, signed in _INT_TYPES:
        m = (vt == ord(code)) & (vo >= 0)
        if not m.any():
            continue
        offs = vo[m]
        v = np.zeros(len(offs), dtype=np.int64)
        for j in range(width):
            v |= buf[offs + j].astype(np.int64) << (8 * j)
        if signed:
            sign_bit = np.int64(1) << (8 * width - 1)
            v = (v ^ sign_bit) - sign_bit
        val[m] = v
        present |= m
    return val, present


def float_tag_values(batch, tag: bytes):
    """(values float64[n], present bool[n]) for an f-typed tag."""
    vo, vl, vt = batch.tag_locs(tag)
    buf = batch.buf
    val = np.zeros(batch.n, dtype=np.float64)
    m = (vt == ord("f")) & (vo >= 0)
    if m.any():
        offs = vo[m]
        raw = np.zeros(len(offs), dtype=np.uint32)
        for j in range(4):
            raw |= buf[offs + j].astype(np.uint32) << (8 * j)
        val[m] = raw.view(np.float32).astype(np.float64)
    return val, m


class FastFilter:
    """Batch filter engine. Feed RecordBatches; collect wire chunks."""

    def __init__(self, config: FilterConfig, *, filter_by_template=True):
        self.config = config
        self.filter_by_template = filter_by_template
        self.stats = FilterStats()
        self._carry = []        # (record bytes,) of the open name group

    @spanned("filter.batch", rusage=True)
    def process_batch(self, batch, emit, emit_reject):
        """Filter one batch; emit(buf_slice_bytes) per kept wire chunk."""
        n = batch.n
        if n == 0:
            return
        buf = batch.buf
        if ((batch.flag & FLAG_UNMAPPED) == 0).any():
            raise ValueError(
                "--ref is required when filtering mapped reads to keep "
                "NM/UQ/MD tags consistent")

        # name-group bounds; the last group may continue into the next batch
        name_off = batch.data_off + 32
        name_len = (batch.l_read_name - 1).astype(np.int32)
        tstarts = nb.group_starts(buf, np.ascontiguousarray(name_off),
                                  name_len)
        tbounds = np.append(tstarts, n)
        nT = len(tbounds) - 1

        # merge a split name group into the carry
        t0 = 0
        if self._carry and buf[name_off[0]:name_off[0] + name_len[0]] \
                .tobytes() == self._carry_name:
            self._carry.extend(
                bytes(buf[batch.data_off[i]:batch.data_end[i]])
                for i in range(tbounds[0], tbounds[1]))
            t0 = 1
        if t0 >= nT:
            return  # the whole batch merged into the (still open) carry
        if self._carry:
            self._emit_carry(emit, emit_reject)

        # hold back the last (possibly split) name group; filter the rest
        lo, hi = int(tbounds[t0]), int(tbounds[nT - 1])
        if hi > lo:
            rows = np.arange(lo, hi)
            self._filter_rows(batch, rows, tbounds[t0:nT].astype(np.int64),
                              emit, emit_reject)
        self._carry = [bytes(buf[batch.data_off[i]:batch.data_end[i]])
                       for i in range(tbounds[nT - 1], tbounds[nT])]
        self._carry_name = buf[
            name_off[tbounds[nT - 1]]:name_off[tbounds[nT - 1]]
            + name_len[tbounds[nT - 1]]].tobytes()

    def _filter_rows(self, batch, rows, tbounds, emit, emit_reject):
        cfg = self.config
        buf = batch.buf
        n = len(rows)
        lo = rows[0]
        # every tag this pass reads, one native aux scan for all of them
        batch.prefetch_tags([b"cD", b"cE", b"aD", b"aM", b"bD", b"bM",
                             b"aE", b"bE", b"cd", b"ce", b"ad", b"ae",
                             b"bd", b"be"])
        l_seq = batch.l_seq[rows].astype(np.int64)
        L = max(int(l_seq.max()), 1) if n else 1

        cD, cD_p = int_tag_values(batch, b"cD")
        cE, cE_p = float_tag_values(batch, b"cE")
        cD, cD_p, cE, cE_p = cD[rows], cD_p[rows], cE[rows], cE_p[rows]
        if not (cD_p.all() and cE_p.all()):
            raise ValueError(
                "read does not appear to have consensus calling tags (cD/cE) "
                "present; filter requires reads produced by consensus calling")
        aD, aD_p = int_tag_values(batch, b"aD")
        aM, aM_p = int_tag_values(batch, b"aM")
        bD, bD_p = int_tag_values(batch, b"bD")
        bM, bM_p = int_tag_values(batch, b"bM")
        aE, aE_p = float_tag_values(batch, b"aE")
        bE, bE_p = float_tag_values(batch, b"bE")
        # duplex detection is by tag PRESENCE of any type
        # (is_duplex_consensus / find_tag), not integer-typedness
        aD_vo = batch.tag_locs(b"aD")[0]
        bD_vo = batch.tag_locs(b"bD")[0]
        duplex = (aD_vo[rows] >= 0) & (bD_vo[rows] >= 0)

        # ---- read-level verdicts (filter_read / filter_duplex_read)
        res = np.full(n, _R_PASS, dtype=np.int8)
        t = cfg.single_strand
        cc = cfg.cc
        thr_min = np.where(duplex, cc.min_reads, t.min_reads)
        thr_err = np.where(duplex, cc.max_read_error_rate,
                           t.max_read_error_rate)
        res[(res == _R_PASS) & (cE > thr_err)] = _R_ERR
        res[cD < thr_min] = _R_INSUF  # depth outranks error rate
        if duplex.any():
            d = np.nonzero(duplex & (res == _R_PASS))[0]
            adp = np.where(aD_p[rows][d], aD[rows][d],
                           np.where(aM_p[rows][d], aM[rows][d], -1))
            bdp = np.where(bD_p[rows][d], bD[rows][d],
                           np.where(bM_p[rows][d], bM[rows][d], -1))
            has_a, has_b = adp >= 0, bdp >= 0
            any_ss = has_a | has_b
            best = np.maximum(np.where(has_a, adp, np.int64(-1 << 40)),
                              np.where(has_b, bdp, np.int64(-1 << 40)))
            worst = np.where(has_a & has_b, np.minimum(adp, bdp), 0)
            ae = np.where(aE_p[rows][d], aE[rows][d], np.nan)
            be = np.where(bE_p[rows][d], bE[rows][d], np.nan)
            errs = np.stack([ae, be])
            with np.errstate(invalid="ignore"):
                best_err = np.where(np.isnan(errs).all(axis=0), 0.0,
                                    np.nanmin(errs, axis=0))
                worst_err = np.where(np.isnan(errs).all(axis=0), 0.0,
                                     np.nanmax(errs, axis=0))
            dres = np.full(len(d), _R_PASS, dtype=np.int8)
            dres[worst_err > cfg.ba.max_read_error_rate] = _R_ERR
            dres[worst < cfg.ba.min_reads] = _R_INSUF
            dres[best_err > cfg.ab.max_read_error_rate] = _R_ERR
            dres[best < cfg.ab.min_reads] = _R_INSUF
            dres[~any_ss] = _R_PASS
            res[d] = dres

        # ---- mean base quality over the full read, pre-mask
        if cfg.min_mean_base_quality is not None:
            sums = nb.qual_scores(batch, 0, 1 << 30).astype(np.float64)[rows]
            mean = np.where(l_seq > 0, sums / np.maximum(l_seq, 1), 0.0)
            res[(res == _R_PASS)
                & (mean < cfg.min_mean_base_quality)] = _R_LOWQ

        # ---- per-base masks
        in_len = np.arange(L)[None, :] < l_seq[:, None]
        quals = self._qual_matrix(batch, rows, L)

        def per_base(tag):
            """(float64 (n, L) matrix, present mask) for a B:s/B:S tag;
            non-B types read as absent (_per_base_padded semantics)."""
            vo, vl, vt = batch.tag_locs(tag)
            vo = np.where(vt == ord("B"), vo, -1)[rows]
            vals, counts = nb.gather_u16_arrays(buf, vo, L)
            if (counts == -2).any():
                raise _OddSubtype()
            present = counts >= 0
            # subtype decides signedness: B:s values are int16, B:S uint16
            f = vals.astype(np.float64)
            signed = present & (buf[np.maximum(vo, 0)] == ord("s"))
            if signed.any():
                f[signed] = vals[signed].view(np.int16)
            return f, present

        cd, cd_p = per_base(b"cd")
        ce, ce_p = per_base(b"ce")
        simplex_pb = ~duplex & cd_p & ce_p
        # one shared numeric core with the device-resident fused filter
        # stage (consensus/filter.py array twins): quality mask everywhere,
        # simplex depth/error masks only where per-base evidence exists
        mask = simplex_base_mask_arrays(
            cd, ce, quals, in_len, cfg.single_strand, cfg.min_base_quality,
            has_per_base=simplex_pb)
        if duplex.any():
            ad, _ = per_base(b"ad")
            ae_b, _ = per_base(b"ae")
            bd, _ = per_base(b"bd")
            be_b, _ = per_base(b"be")
            dmask = duplex_base_mask_arrays(ad, ae_b, bd, be_b, cfg.cc,
                                            cfg.ab, cfg.ba)
            mask |= duplex[:, None] & dmask & in_len

        # EM-Seq/TAPS depth masking (filter.rs:952-1043): cu+ct below the
        # first threshold; duplex rows additionally au+at / bu+bt. Rows
        # without any cu/ct tag are untouched (no-tags no-op).
        mdt = cfg.methylation_depth
        simplex_meth = None
        if mdt is not None:
            cu, cu_p = per_base(b"cu")
            ct, ct_p = per_base(b"ct")
            has_meth = (cu_p | ct_p)[:, None] & in_len
            meth_mask = has_meth & ((cu + ct) < mdt.duplex)
            if duplex.any():
                au, _ = per_base(b"au")
                at, _ = per_base(b"at")
                bu, _ = per_base(b"bu")
                bt, _ = per_base(b"bt")
                meth_mask |= has_meth & duplex[:, None] \
                    & (((au + at) < mdt.ab) | ((bu + bt) < mdt.ba))
            # duplex rows ride the skip-N pass below; simplex rows get a
            # SECOND skip-N pass after the base mask (the reference's
            # methylation masking always skips already-N positions,
            # filter.rs:969-971, while simplex base masking does not)
            mask |= meth_mask & duplex[:, None]
            simplex_meth = meth_mask & ~duplex[:, None]

        skip_n = duplex  # duplex masking skips already-N positions
        newly = np.empty(n, dtype=np.int32)
        n_after = np.empty(n, dtype=np.int32)
        for group, skip in ((np.nonzero(~duplex)[0], False),
                            (np.nonzero(duplex)[0], True)):
            if len(group):
                nw, na = nb.apply_masks(batch, rows[group], mask[group], skip)
                newly[group] = nw
                n_after[group] = na
        if simplex_meth is not None and simplex_meth.any():
            g = np.nonzero(~duplex)[0]
            if len(g):
                nw2, na2 = nb.apply_masks(batch, rows[g], simplex_meth[g],
                                          True)
                newly[g] += nw2
                n_after[g] = na2
        # simplex semantics: only mask when any bit set (mask_bases returns
        # early otherwise) — apply_masks is equivalent since no-bit rows
        # write nothing

        # ---- post-mask no-call check: < 1.0 is a fraction of read
        # length, >= 1.0 an absolute N count (no_call_check semantics)
        if cfg.max_no_call_fraction < 1.0:
            frac = np.where(l_seq > 0, n_after / np.maximum(l_seq, 1), 0.0)
            too_many = (l_seq > 0) & (frac > cfg.max_no_call_fraction)
        else:
            too_many = n_after > cfg.max_no_call_fraction
        res[(res == _R_PASS) & too_many] = _R_NOCALL

        # ---- template verdicts + emit (run_filter.emit_template)
        stats = self.stats
        flag = batch.flag[rows]
        secsup = (flag & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY)) != 0
        ok = res == _R_PASS
        if self.filter_by_template:
            # template passes iff all primaries pass (template_passes)
            t_of = np.repeat(np.arange(len(tbounds) - 1),
                             np.diff(tbounds))
            fail = ~ok & ~secsup
            any_prim = np.zeros(len(tbounds) - 1, dtype=bool)
            np.logical_or.at(any_prim, t_of, ~secsup)
            t_fail = np.zeros(len(tbounds) - 1, dtype=bool)
            np.logical_or.at(t_fail, t_of, fail)
            # a template with no primaries fails (template_passes)
            tpl_pass = ~t_fail & any_prim
            keep = np.where(secsup, tpl_pass[t_of] & ok, tpl_pass[t_of])
        else:
            keep = ok

        stats.total_records += n
        kept = int(keep.sum())
        stats.passed_records += kept
        stats.failed_records += n - kept
        stats.bases_masked += int(newly[keep & ~secsup].sum())
        for i in np.nonzero(~keep)[0]:
            reason = _RESULT_STR[res[i]] if res[i] != _R_PASS \
                else "template_failed"
            stats.rejection_reasons[reason] += 1

        self._emit_runs(batch, rows, keep, emit)
        if emit_reject is not None:
            self._emit_runs(batch, rows, ~keep, emit_reject)

    def _qual_matrix(self, batch, rows, L):
        """Dense (n, L) qualities (zero-padded); per-row gather."""
        buf = batch.buf
        n = len(rows)
        out = np.zeros((n, L), dtype=np.uint8)
        q_off = batch.qual_off[rows]
        l_seq = batch.l_seq[rows]
        # gather via flat fancy indexing: offsets matrix clipped to range
        idx = q_off[:, None] + np.arange(L)[None, :]
        valid = np.arange(L)[None, :] < l_seq[:, None]
        np.copyto(out, buf[np.minimum(idx, len(buf) - 1)], where=valid)
        return out

    def _emit_runs(self, batch, rows, keep, emit):
        """Contiguous kept records emit as single buffer slices (records are
        adjacent on the wire, each preceded by its block_size prefix)."""
        if not keep.any():
            return
        buf = batch.buf
        k = np.nonzero(keep)[0]
        run_starts = np.nonzero(np.concatenate(
            ([True], np.diff(k) > 1)))[0]
        bounds = np.append(run_starts, len(k))
        for ri in range(len(run_starts)):
            a = rows[k[bounds[ri]]]
            b = rows[k[bounds[ri + 1] - 1]]
            emit(bytes(buf[batch.data_off[a] - 4:batch.data_end[b]]))

    # ------------------------------------------------------------------ carry

    def _emit_carry(self, emit, emit_reject):
        """The completed carried name group runs the classic per-record
        path (identical semantics; group sizes are tiny)."""
        from ..io.bam import RawRecord
        from .filter import template_passes

        records = self._carry
        self._carry = []
        processed = [_process_one(data, self.config, False, None, ())
                     for data in records]
        recs = [RawRecord(d) for d, _, _ in processed]
        results = [r for _, r, _ in processed]
        masked = [m for _, _, m in processed]
        stats = self.stats
        pass_flags = [r == PASS for r in results]
        tpl_pass = template_passes(recs, pass_flags) \
            if self.filter_by_template else True
        for rec, okf, result, mk in zip(recs, pass_flags, results, masked):
            stats.total_records += 1
            is_sec = bool(rec.flag & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY))
            if not self.filter_by_template:
                kp = okf
            elif is_sec:
                kp = tpl_pass and okf
            else:
                kp = tpl_pass
            chunk = len(rec.data).to_bytes(4, "little") + rec.data
            if kp:
                stats.passed_records += 1
                stats.bases_masked += 0 if is_sec else mk
                emit(chunk)
            else:
                stats.failed_records += 1
                reason = result if result != PASS else "template_failed"
                stats.rejection_reasons[reason] += 1
                if emit_reject is not None:
                    emit_reject(chunk)

    def flush(self, emit, emit_reject):
        if self._carry:
            self._emit_carry(emit, emit_reject)


class _OddSubtype(Exception):
    """A per-base tag with a non-16-bit subtype: classic fallback needed."""
