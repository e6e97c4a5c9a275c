"""Device-resident consensus→filter fusion (ISSUE 11, ROADMAP §3).

The host-shaped pipeline pays the link twice for every filtered consensus
record: the full winner/qual/depth/errors columns are fetched home
(5.25 B/position), serialized, and then the filter command re-parses the
bytes just to drop most of them on a filter-heavy config. This module fuses
the two stages behind ``--device-filter``:

- stage 1 (ops/kernel._consensus_segments_wire_filter_jit) keeps the
  consensus columns **device-resident**, applies the consensus thresholds
  and the filter library's per-base masks as one fused kernel, and fetches
  only a 28 B/read stats row (max/total depth, total errors, qual sum,
  post-mask N count, newly-masked count, suspect flag);
- the host computes the per-read verdicts from those scalars with the SAME
  array helpers the batch filter engine uses (consensus/filter.py — one
  numeric core, so the fused route cannot drift from ``fgumi-tpu filter``);
- stage 2 gathers only the *surviving* records' masked columns home
  (ops/kernel.filter_gather_device) and the native serializer emits them —
  byte-identical to ``simplex | filter`` by construction.

Exactness contract: every floating-point comparison the host filter makes
is either (a) recomputed on host from exactly-fetched integer sums (cE,
mean quality, no-call fraction), or (b) reformulated as a pure integer
compare on device via :func:`consensus.filter.base_error_rate_table`.
Reads touching an oracle-suspect position fetch their raw columns and run
the ordinary host completion (oracle patch + host filter math). Degraded
device paths (deadline, transient failure, OOM halving) fall back to full
columns + the host filter pass — byte-identical like every other degrade.

The duplex/codec engines route ``--device-filter`` through
:class:`HostFilterTap` — the same in-process fusion (no intermediate BAM,
no re-parse by a second command) with the per-record reference filter;
their column-space device kernels are a follow-up (docs/device-datapath.md
"Device-resident filtering").
"""

import threading

import numpy as np

from ..constants import MIN_PHRED, N_CODE
from ..ops import oracle
from .filter import (PASS, R_PASS, RESULT_NAMES, FilterConfig,
                     base_error_rate_table, simplex_base_mask_arrays,
                     simplex_read_verdicts)

_I16_MAX = 32767


def device_filter_requested(args) -> bool:
    """CLI/env gate for the fused consensus→filter route."""
    import os

    if getattr(args, "device_filter", False):
        return True
    return os.environ.get("FGUMI_TPU_DEVICE_FILTER", "").strip().lower() \
        in ("1", "true", "on", "force")


def device_mask_enabled() -> bool:
    """Whether the fused per-base mask runs ON DEVICE (default yes).
    ``FGUMI_TPU_DEVICE_FILTER=0`` keeps the fused single-process stage but
    computes every mask host-side from fetched full columns — the A/B
    escape hatch for the reduced-fetch kernel."""
    import os

    return os.environ.get("FGUMI_TPU_DEVICE_FILTER", "").strip().lower() \
        not in ("0", "false", "off")


def filter_config_from_args(args) -> FilterConfig:
    """FilterConfig from the consensus commands' ``--filter-*`` options
    (same option grammar as the standalone ``filter`` command)."""
    return FilterConfig.new(
        [int(v) for v in str(args.filter_min_reads).split(",")],
        [float(v) for v in str(args.filter_max_read_error_rate).split(",")],
        [float(v) for v in str(args.filter_max_base_error_rate).split(",")],
        min_base_quality=args.filter_min_base_quality,
        min_mean_base_quality=args.filter_min_mean_base_quality,
        max_no_call_fraction=args.filter_max_no_call_fraction)


class DeviceFilterParams:
    """Device-side constants of the fused simplex mask kernel.

    Built once per run; the error-rate threshold table rides the constant
    cache (content-keyed) so repeated dispatches upload nothing."""

    __slots__ = ("min_reads", "emin_tab", "min_base_q", "per_base")

    def __init__(self, config: FilterConfig, produce_per_base_tags: bool):
        t = config.single_strand
        self.min_reads = np.int32(t.min_reads)
        self.emin_tab = base_error_rate_table(t.max_base_error_rate)
        self.min_base_q = np.int32(-1 if config.min_base_quality is None
                                   else int(config.min_base_quality))
        # mask_bases applies depth/error per-base masks only when the
        # record carries cd+ce tags — i.e. when the engine serializes them
        self.per_base = bool(produce_per_base_tags)


#: columns of the fused kernel's per-read stats fetch (int32 each)
S_MAXD, S_SUMD, S_SUME, S_QSUM, S_NAFTER, S_NEWLY, S_SUSPECT = range(7)
STATS_COLS = 7


def fused_stats_oracle(winner, qual, depth, errors, lens, min_reads_c,
                       min_qual_c, params: DeviceFilterParams):
    """Numpy twin of the fused kernel's threshold+filter epilogue
    (ops/kernel._wire_filter_fn) over PRE-threshold (J, L) columns.

    Built for the sentinel's fused-route audit (ops/sentinel.py): given
    the f64 host oracle's winner/qual/depth/errors, re-derives the masked
    columns and the (J, STATS_COLS) stats rows with exactly the device's
    integer math — consensus thresholds, the emin-table per-base compare,
    the min-base-quality compare — so any device bit flip in the fetched
    stats (or the survivor gather) shows as an exact mismatch. The
    suspect column is device-internal and stays 0 here; callers compare
    it separately. Returns (stats int32, masked_bases u8, masked_quals
    u8)."""
    w = np.asarray(winner, dtype=np.int32)
    q = np.asarray(qual, dtype=np.int32)
    d = np.asarray(depth, dtype=np.int32)
    e = np.asarray(errors, dtype=np.int32)
    n, L = w.shape
    lens = np.asarray(lens, dtype=np.int64)
    low_depth = d < np.int32(min_reads_c)
    low_qual = q < np.int32(min_qual_c)
    tb = np.where(low_depth | low_qual, N_CODE, w)
    tq = np.where(low_depth, 0, np.where(low_qual, MIN_PHRED, q))
    in_len = np.arange(L, dtype=np.int64)[None, :] < lens[:, None]
    d16 = np.minimum(d, _I16_MAX)
    e16 = np.minimum(e, _I16_MAX)
    per_base = bool(params.per_base)
    if per_base:
        fmask = (d16 < params.min_reads) \
            | ((d16 > 0) & (e16 >= params.emin_tab[d16]))
    else:
        fmask = np.zeros((n, L), dtype=bool)
    if int(params.min_base_q) >= 0:
        fmask = fmask | (tq < params.min_base_q)
    fmask = fmask & in_len
    fb = np.where(fmask, N_CODE, tb).astype(np.uint8)
    fq = np.where(fmask, MIN_PHRED, tq).astype(np.uint8)
    stats = np.zeros((n, STATS_COLS), dtype=np.int32)
    if L:
        stats[:, S_MAXD] = np.max(np.where(in_len, d16, 0), axis=1)
    stats[:, S_SUMD] = np.sum(np.where(in_len, d16, 0), axis=1,
                              dtype=np.int32)
    stats[:, S_SUME] = np.sum(np.where(in_len, e16, 0), axis=1,
                              dtype=np.int32)
    stats[:, S_QSUM] = np.sum(np.where(in_len, tq, 0), axis=1,
                              dtype=np.int32)
    stats[:, S_NAFTER] = np.sum(in_len & (fb == N_CODE), axis=1)
    stats[:, S_NEWLY] = np.sum(fmask & (tb != N_CODE), axis=1)
    return stats, fb, fq


class SimplexFilterStage:
    """Fused filter stage for the fast simplex engine (one per run).

    Thread-safe: resolve workers call :meth:`resolve_chunk` concurrently;
    only the stats accumulation is shared."""

    def __init__(self, config: FilterConfig, options,
                 filter_by_template: bool = True):
        from ..commands.filter import FilterStats

        self.config = config
        self.options = options  # VanillaOptions (consensus thresholds)
        self.filter_by_template = filter_by_template
        self.stats = FilterStats()
        self.dev_params = DeviceFilterParams(config,
                                             options.produce_per_base_tags)
        self._lock = threading.Lock()
        self._slow_tap = None

    # ---------------------------------------------------------- host twin

    def host_filter_columns(self, bases, quals, depth, errors, lens):
        """Host twin of the fused kernel's filter math over post-threshold
        (J, L) columns. Returns (masked_bases, masked_quals, stats) with
        ``stats`` shaped (J, STATS_COLS) — the same layout the device
        fetches, so the verdict code downstream is path-blind."""
        cfg = self.config
        n, L = bases.shape
        lens = np.asarray(lens, dtype=np.int64)
        in_len = np.arange(L)[None, :] < lens[:, None]
        d16 = np.minimum(depth, _I16_MAX).astype(np.int64)
        e16 = np.minimum(errors, _I16_MAX).astype(np.int64)
        if self.dev_params.per_base:
            mask = simplex_base_mask_arrays(d16, e16, quals, in_len,
                                            cfg.single_strand,
                                            cfg.min_base_quality)
        else:
            mask = np.zeros((n, L), dtype=bool)
            if cfg.min_base_quality is not None:
                mask = (quals < cfg.min_base_quality) & in_len
        fb = np.where(mask, N_CODE, bases).astype(np.uint8)
        fq = np.where(mask, MIN_PHRED, quals).astype(np.uint8)
        stats = np.zeros((n, STATS_COLS), dtype=np.int64)
        stats[:, S_MAXD] = np.max(np.where(in_len, d16, 0), axis=1) \
            if L else 0
        stats[:, S_SUMD] = np.sum(np.where(in_len, d16, 0), axis=1)
        stats[:, S_SUME] = np.sum(np.where(in_len, e16, 0), axis=1)
        stats[:, S_QSUM] = np.sum(
            np.where(in_len, quals.astype(np.int64), 0), axis=1)
        stats[:, S_NAFTER] = np.sum(in_len & (fb == N_CODE), axis=1)
        stats[:, S_NEWLY] = np.sum(mask & (bases != N_CODE), axis=1)
        return fb, fq, stats

    # ------------------------------------------------------------ verdicts

    def read_verdicts(self, stats, lens):
        """Per-read verdict codes from the stats rows (device or host).

        The cE tag value is float32(tot_e)/float32(tot_d) — exactly the
        native serializer's arithmetic — recomputed here from the exact
        integer sums, then judged by the shared array core."""
        sum_d = stats[:, S_SUMD]
        ce = np.zeros(len(sum_d), dtype=np.float32)
        nz = sum_d > 0
        ce[nz] = stats[nz, S_SUME].astype(np.float32) \
            / sum_d[nz].astype(np.float32)
        cfg = self.config
        return simplex_read_verdicts(
            stats[:, S_MAXD], ce, stats[:, S_QSUM], stats[:, S_NAFTER],
            lens, cfg.single_strand, cfg.min_mean_base_quality,
            cfg.max_no_call_fraction)

    def template_keep(self, verdicts, mi_rec):
        """Keep flags under --filter-by-template: consensus outputs are all
        primary, and jobs of one group (same ``mi_rec``) share a QNAME —
        the template passes iff every member passes."""
        ok = verdicts == R_PASS
        if not self.filter_by_template or not len(ok):
            return ok
        mi_rec = np.asarray(mi_rec)
        t_of = np.concatenate(([0], np.cumsum(mi_rec[1:] != mi_rec[:-1])))
        n_t = int(t_of[-1]) + 1
        t_fail = np.zeros(n_t, dtype=bool)
        np.logical_or.at(t_fail, t_of, ~ok)
        return ~t_fail[t_of]

    def _account(self, verdicts, keep, newly):
        with self._lock:
            st = self.stats
            st.total_records += len(verdicts)
            kept = int(keep.sum())
            st.passed_records += kept
            st.failed_records += len(verdicts) - kept
            st.bases_masked += int(np.asarray(newly)[keep].sum())
            for v in verdicts[~keep]:
                st.rejection_reasons[
                    RESULT_NAMES[int(v)] if v != R_PASS
                    else "template_failed"] += 1

    # ------------------------------------------------------------- resolve

    def resolve_chunk(self, chunk) -> bytes:
        """Fused resolve of one _PendingChunk: complete the device work,
        judge every job, and serialize only the survivors."""
        fast = chunk.fast
        caller = fast.caller
        kernel = caller.kernel
        table = chunk.jobs
        opts = caller.options
        J = len(table)
        blocks = []  # (idxs, fb, fq, d32, e32) — masked survivors' columns
        stats_all = np.zeros((J, STATS_COLS), dtype=np.int64)
        newly = np.zeros(J, dtype=np.int64)

        def add_full_columns(idxs, winner, qual, depth, errors):
            """Full post-oracle columns (host route / degraded device
            route / single-read blocks): thresholds + host filter math."""
            b, q = oracle.apply_consensus_thresholds(
                winner, qual, depth, opts.min_reads,
                opts.min_consensus_base_quality)
            fb, fq, stats = self.host_filter_columns(
                b, q, depth, errors, table.cons_len[idxs])
            stats_all[idxs] = stats
            newly[idxs] = stats[:, S_NEWLY]
            blocks.append((np.asarray(idxs, dtype=np.int64),
                           np.ascontiguousarray(fb),
                           np.ascontiguousarray(fq),
                           np.ascontiguousarray(depth, dtype=np.int32),
                           np.ascontiguousarray(errors, dtype=np.int32)))

        for idxs, b, q, d, e in chunk.blocks:
            # pre-threshold single-read host blocks arrive post-threshold
            # (single_read_consensus already masked); run only the filter
            fb, fq, stats = self.host_filter_columns(
                b, q, d, e, table.cons_len[idxs])
            stats_all[idxs] = stats
            newly[idxs] = stats[:, S_NEWLY]
            blocks.append((np.asarray(idxs, dtype=np.int64),
                           np.ascontiguousarray(fb),
                           np.ascontiguousarray(fq),
                           np.ascontiguousarray(d, dtype=np.int32),
                           np.ascontiguousarray(e, dtype=np.int32)))

        fused = None  # (multi idxs, resident, fused stats rows)
        pending = chunk.pending  # an ops/kernel.PendingSegments, or None
        if pending is not None:
            out = pending.resolve_filtered()
            if out[0] == "columns":
                add_full_columns(chunk.idxs, *out[1:])
            else:
                _, dev_stats, resident = out
                fused = self._fused_rows(kernel, table, chunk.idxs,
                                         pending.starts, pending.codes2d,
                                         pending.quals2d, dev_stats,
                                         resident, stats_all, newly,
                                         add_full_columns)

        verdicts = self.read_verdicts(stats_all, table.cons_len)
        keep = self.template_keep(verdicts, table.mi_rec)
        self._account(verdicts, keep, newly)

        if fused is not None:
            self._gather_fused(kernel, table, fused, keep, blocks,
                               add_full_columns)

        keep_idx = np.nonzero(keep)[0]
        caller.stats.add_consensus_reads(J - len(keep_idx))  # rejected jobs
        sub = _subset_table(table, keep_idx)
        remap = np.full(J, -1, dtype=np.int64)
        remap[keep_idx] = np.arange(len(keep_idx))
        kept_blocks = []
        for idxs, fb, fq, d32, e32 in blocks:
            sel = keep[idxs]
            if not sel.any():
                continue
            kept_blocks.append((remap[idxs[sel]], fb[sel], fq[sel],
                                np.ascontiguousarray(d32[sel]),
                                np.ascontiguousarray(e32[sel])))
        return fast._serialize_jobs(chunk.batch, sub, kept_blocks)

    def _fused_rows(self, kernel, table, idxs, starts, codes_d, quals_d,
                    dev_stats, resident, stats_all, newly,
                    add_full_columns):
        """Fold a fused stats fetch into the per-job arrays; suspect rows
        take the raw-column gather + ordinary host completion."""
        idxs = np.asarray(idxs, dtype=np.int64)
        k = len(idxs)
        st = dev_stats[:k].astype(np.int64)
        sus = st[:, S_SUSPECT] > 0
        clean = ~sus
        stats_all[idxs[clean]] = st[clean]
        newly[idxs[clean]] = st[clean, S_NEWLY]
        if sus.any():
            rows = np.nonzero(sus)[0]
            try:
                w, q, d, e = kernel.filter_resolve_suspect_rows(
                    resident, rows, starts, codes_d, quals_d)
            except BaseException as exc:  # noqa: BLE001 - weather-classified
                if not _is_device_weather(exc):
                    raise
                w, q, d, e = _host_rows(kernel, starts, codes_d, quals_d,
                                        rows)
            add_full_columns(idxs[rows], w, q, d, e)
        return (idxs, resident, clean, starts, codes_d, quals_d)

    def _gather_fused(self, kernel, table, fused, keep, blocks,
                      add_full_columns):
        """Stage-2 gather: fetch only surviving fused rows' masked columns
        (suspect rows already resolved host-side). Device weather on the
        gather degrades to the native f64 host engine for the kept rows —
        byte-identical, like every other degrade path."""
        idxs, resident, clean, starts, codes_d, quals_d = fused
        from ..ops.router import ROUTER

        try:
            want = clean & keep[idxs]
            rows = np.nonzero(want)[0]
            ROUTER.observe_filter_keep(len(rows), int(clean.sum()))
            if len(rows):
                try:
                    fb, fq, d32, e32 = kernel.filter_gather_filtered(
                        resident, rows)
                    blocks.append((idxs[rows], fb, fq, d32, e32))
                except BaseException as exc:  # noqa: BLE001 - classified
                    if not _is_device_weather(exc):
                        raise
                    w, q, d, e = _host_rows(kernel, starts, codes_d,
                                            quals_d, rows)
                    add_full_columns(idxs[rows], w, q, d, e)
        finally:
            resident.release()

    def filter_records_blob(self, blob: bytes) -> bytes:
        """Classic per-record filter over a slow-path blob (complete name
        groups only); stats fold into this stage's counters."""
        with self._lock:
            tap = self._slow_tap
            if tap is None:
                tap = self._slow_tap = HostFilterTap(
                    self.config, self.filter_by_template, stats=self.stats,
                    lock=self._lock)
        return tap.feed(blob) + tap.flush()


def _is_device_weather(exc) -> bool:
    """True for the recoverable device-failure classes (the same set every
    resolve path degrades on): deadline overrun, transient XLA error, OOM."""
    from ..ops.kernel import DeadlineExceeded, _is_oom, _is_transient

    return (isinstance(exc, DeadlineExceeded) or _is_oom(exc)
            or _is_transient(exc))


def _host_rows(kernel, starts, codes2d, quals2d, rows):
    """Native f64 host-engine completion of a subset of a dispatch's
    families (the fused route's gather-failure fallback): post-oracle
    (winner, qual, depth, errors) for ``rows``, byte-identical to the
    device path by the engines' shared exactness contract."""
    starts = np.asarray(starts, dtype=np.int64)
    counts = np.diff(starts)[rows]
    sub_starts = np.concatenate(([0], np.cumsum(counts)))
    sel = np.concatenate([np.arange(starts[r], starts[r + 1])
                          for r in rows])
    return kernel._host_engine_complete(codes2d[sel], quals2d[sel],
                                        sub_starts)


def _subset_table(table, keep_idx):
    """A _JobTable view of the kept jobs (pool arrays are shared — vlo and
    count keep indexing the original row pool)."""
    from .fast import _JobTable

    return _JobTable(table.count[keep_idx], table.vlo[keep_idx],
                     table.read_type[keep_idx], table.cons_len[keep_idx],
                     table.mi_rec[keep_idx], table.pool_rows,
                     table.pool_span)


class HostFilterTap:
    """In-process consensus-output filter over serialized record chunks.

    The fused route for outputs that are not (yet) column-resident: the
    simplex slow path's boundary groups and the duplex/codec engines. Each
    fed blob is a run of block_size-prefixed records; records are judged by
    the per-record reference filter (commands/filter.py::_process_one) with
    template grouping by QNAME, and only survivors are returned. Call
    :meth:`flush` after the last blob (the open name group is held back)."""

    def __init__(self, config: FilterConfig, filter_by_template: bool = True,
                 stats=None, lock=None):
        from ..commands.filter import FilterStats

        self.config = config
        self.filter_by_template = filter_by_template
        self.stats = stats if stats is not None else FilterStats()
        self._group = []       # [(record bytes)] of the open name group
        self._group_name = None
        self._lock = lock if lock is not None else threading.Lock()

    @staticmethod
    def _records(blob):
        off = 0
        view = memoryview(blob)
        while off < len(view):
            size = int.from_bytes(view[off:off + 4], "little")
            yield bytes(view[off + 4:off + 4 + size])
            off += 4 + size

    @staticmethod
    def _name(data: bytes) -> bytes:
        l_read_name = data[8]
        return bytes(data[32:32 + l_read_name - 1])

    def feed(self, blob: bytes) -> bytes:
        """Filter one serialized chunk; returns the kept wire bytes."""
        out = []
        with self._lock:
            for data in self._records(blob):
                name = self._name(data)
                if name != self._group_name and self._group:
                    out.append(self._emit_group_locked())
                self._group_name = name
                self._group.append(data)
        return b"".join(out)

    def flush(self) -> bytes:
        with self._lock:
            if not self._group:
                return b""
            return self._emit_group_locked()

    def _emit_group_locked(self) -> bytes:
        from ..commands.filter import _process_one
        from ..io.bam import (FLAG_SECONDARY, FLAG_SUPPLEMENTARY, RawRecord)
        from .filter import template_passes

        records = self._group
        self._group = []
        self._group_name = None
        processed = [_process_one(data, self.config, False, None, ())
                     for data in records]
        recs = [RawRecord(d) for d, _, _ in processed]
        results = [r for _, r, _ in processed]
        pass_flags = [r == PASS for r in results]
        tpl_pass = template_passes(recs, pass_flags) \
            if self.filter_by_template else True
        st = self.stats
        out = []
        for rec, okf, result, (_, _, mk) in zip(recs, pass_flags, results,
                                                processed):
            st.total_records += 1
            is_sec = bool(rec.flag & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY))
            if not self.filter_by_template:
                kp = okf
            elif is_sec:
                kp = tpl_pass and okf
            else:
                kp = tpl_pass
            if kp:
                st.passed_records += 1
                st.bases_masked += 0 if is_sec else mk
                out.append(len(rec.data).to_bytes(4, "little") + rec.data)
            else:
                st.failed_records += 1
                st.rejection_reasons[
                    result if result != PASS else "template_failed"] += 1
        return b"".join(out)


def make_filter_tap(args):
    """HostFilterTap for a consensus command's ``--device-filter`` request,
    or None when not requested. Raises ValueError on bad thresholds (the
    CLI reports it and exits 2). One constructor for the duplex/codec/
    classic-simplex wiring sites."""
    if not device_filter_requested(args):
        return None
    return HostFilterTap(filter_config_from_args(args),
                         args.filter_by_template)


def wrap_filter_writer(writer, tap):
    """``writer`` unchanged when ``tap`` is None, else the tap-filtering
    wrapper (callers still call ``.finish()`` after the last write)."""
    return writer if tap is None else FilterTapWriter(writer, tap)


class FilterTapWriter:
    """Writer wrapper routing every serialized chunk through a
    :class:`HostFilterTap` (the duplex/codec ``--device-filter`` route)."""

    def __init__(self, writer, tap: HostFilterTap):
        self._writer = writer
        self.tap = tap

    def write_serialized(self, blob):
        kept = self.tap.feed(bytes(blob))
        if kept:
            self._writer.write_serialized(kept)

    def write_record_bytes(self, rec):
        kept = self.tap.feed(len(rec).to_bytes(4, "little") + bytes(rec))
        if kept:
            self._writer.write_serialized(kept)

    def finish(self):
        kept = self.tap.flush()
        if kept:
            self._writer.write_serialized(kept)

    def __getattr__(self, name):
        return getattr(self._writer, name)
