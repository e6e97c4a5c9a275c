"""Vectorized simplex consensus path over RecordBatch inputs.

The host-throughput answer to the reference's raw-byte pipeline discipline
(/root/reference/src/lib/unified_pipeline/bam.rs Decode/Process steps +
crates/fgumi-consensus/src/vanilla_caller.rs:1119-1331): per-record work is
done natively in batch (fgumi_tpu.native.batch), per-family work on numpy
index slices, and the likelihood loop on the device kernel.

Semantics contract: byte-identical output and identical rejection statistics
to VanillaConsensusCaller.call_groups on the same stream (tested in
tests/test_fast_simplex.py). Families whose CIGARs need the
most-common-alignment filter stay on the vectorized path: the filter is one
native pass a span (nb.alignment_filter) whose answer is a keep mask. What
the vectorized path cannot express (methylation mode, quality trimming) falls
back to the slow path per group; a seeded downsample and rejects tracking
take the per-group scan.
"""

import numpy as np

from ..core import cigar as cigar_utils
from ..io.bam import (FLAG_FIRST, FLAG_LAST, FLAG_MATE_UNMAPPED, FLAG_PAIRED,
                      FLAG_REVERSE, FLAG_SECONDARY, FLAG_SUPPLEMENTARY,
                      FLAG_UNMAPPED)
from ..native import batch as nb
from ..observe.metrics import METRICS
from ..observe.trace import span as _span
from ..ops import oracle
from .overlapping import (AGREEMENT_CODES, DISAGREEMENT_CODES,
                          add_native_overlap_stats)
from .simple_umi import consensus_umis_batch
from .vanilla import (FRAGMENT, R1, R2, _TYPE_FLAGS, VanillaConsensusCaller)

# read-type -> record flags as an indexable array (serialize is table-driven)
_TYPE_FLAGS_ARR = np.array([_TYPE_FLAGS[FRAGMENT], _TYPE_FLAGS[R1],
                            _TYPE_FLAGS[R2]], dtype=np.int32)

def resolve_chunk(chunk) -> bytes:
    """Wire bytes of a process_batch output item (resolving deferred device
    work — the fetch+serialize half of a batch runs here, typically on the
    writer stage so transfers overlap the next batch's host prep)."""
    return chunk if isinstance(chunk, bytes) else chunk.resolve()


def pack_shards(codes_d, quals_d, starts, jb, L_max):
    """Pack dense (rows, L) segment data into the (dp, N_max, L) sharded
    layout for device_call_segments_sharded.

    Returns (codes3d, quals3d, seg2d, shard_starts, n_jobs, F_loc). One copy
    of the subtle pad invariants — rows pad with N/Q0, pad rows carry the
    shard's LAST real segment id (so they fold into an existing segment and
    cannot mint phantom families), and N_max/F_loc round up to pow2 for the
    compile cache. Shared by the simplex and duplex sharded dispatches.
    """
    dp = len(jb) - 1
    shard_starts = [starts[jb[d]:jb[d + 1] + 1] - starts[jb[d]]
                    for d in range(dp)]
    n_rows = [int(s[-1]) for s in shard_starts]
    n_jobs = [int(jb[d + 1] - jb[d]) for d in range(dp)]
    from ..ops.kernel import DEVICE_STATS, _pad_rows

    N_max = _pad_rows(max(max(n_rows), 1))
    F_loc = 1 << (max(max(n_jobs), 1) - 1).bit_length()
    DEVICE_STATS.add_pad(sum(n_rows), dp * N_max)

    codes3d = np.full((dp, N_max, L_max), 4, dtype=np.uint8)
    quals3d = np.zeros((dp, N_max, L_max), dtype=np.uint8)
    seg2d = np.zeros((dp, N_max), dtype=np.int32)
    for d in range(dp):
        lo, hi = int(starts[jb[d]]), int(starts[jb[d + 1]])
        n = n_rows[d]
        codes3d[d, :n] = codes_d[lo:hi]
        quals3d[d, :n] = quals_d[lo:hi]
        seg2d[d, :n] = np.repeat(
            np.arange(n_jobs[d], dtype=np.int32),
            np.diff(shard_starts[d]))
        seg2d[d, n:] = max(n_jobs[d] - 1, 0)
    return codes3d, quals3d, seg2d, shard_starts, n_jobs, F_loc


def pack_shards_sp(codes_d, quals_d, starts, jb, L_max, sp):
    """Pack dense segment data into the (dp, sp, N_sp, L) layout for
    device_call_segments_dp_sp.

    Each dp shard's rows split into sp contiguous chunks (segments may span
    chunk boundaries — partial segment sums psum exactly); every chunk pads
    to the common pow2 N_sp with all-N rows carrying the chunk's last real
    segment id (or 0 for empty chunks). Segment ids stay shard-global so the
    psum-combined output is (dp, F_loc, L) exactly like the sp=1 layout."""
    dp = len(jb) - 1
    shard_starts = [starts[jb[d]:jb[d + 1] + 1] - starts[jb[d]]
                    for d in range(dp)]
    n_rows = [int(s[-1]) for s in shard_starts]
    n_jobs = [int(jb[d + 1] - jb[d]) for d in range(dp)]
    chunk = [-(-max(n, 1) // sp) for n in n_rows]
    from ..ops.kernel import DEVICE_STATS, _pad_rows

    N_sp = _pad_rows(max(chunk)) if max(chunk) > 1 else 1
    F_loc = 1 << (max(max(n_jobs), 1) - 1).bit_length()
    DEVICE_STATS.add_pad(sum(n_rows), dp * sp * N_sp)

    codes4 = np.full((dp, sp, N_sp, L_max), 4, dtype=np.uint8)
    quals4 = np.zeros((dp, sp, N_sp, L_max), dtype=np.uint8)
    seg3 = np.zeros((dp, sp, N_sp), dtype=np.int32)
    for d in range(dp):
        base = int(starts[jb[d]])
        n = n_rows[d]
        seg_local = np.repeat(np.arange(n_jobs[d], dtype=np.int32),
                              np.diff(shard_starts[d]))
        for s in range(sp):
            lo = min(s * chunk[d], n)
            hi = min(lo + chunk[d], n)
            m = hi - lo
            if m:
                codes4[d, s, :m] = codes_d[base + lo:base + hi]
                quals4[d, s, :m] = quals_d[base + lo:base + hi]
                seg3[d, s, :m] = seg_local[lo:hi]
                seg3[d, s, m:] = seg_local[hi - 1]
    return codes4, quals4, seg3, shard_starts, n_jobs, F_loc


def _ranges(lo, counts):
    """Concatenated arange(lo_i, lo_i + counts_i) without a Python loop."""
    counts = np.asarray(counts, dtype=np.int64)
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    lo = np.asarray(lo, dtype=np.int64)
    keep = counts > 0
    lo_k = lo[keep]
    c_k = counts[keep]
    step = np.ones(total, dtype=np.int64)
    firsts = np.concatenate(([0], np.cumsum(c_k)[:-1]))
    step[firsts] = lo_k
    # later range-starts jump from the previous range's last value
    step[firsts[1:]] -= lo_k[:-1] + c_k[:-1] - 1
    return np.cumsum(step)


class _JobTable:
    """Array-form job list for one batch span — no per-job Python objects.

    Jobs (consensus outputs) are rows of parallel arrays, in output order:
    per group, fragment first, then the R1/R2 pair (vanilla.py:377-386).
    `vlo`/`count` slice the shared row pool: `pool_rows` holds span-relative
    row indices into the packed code/qual arrays, `pool_span` the same rows
    as absolute batch record indices (for RX lookups). `mi_rec` is the batch
    record whose MI tag value provides the job's UMI bytes (the group's
    first record) — serialization reads it straight out of the batch buffer.
    """

    __slots__ = ("count", "vlo", "read_type", "cons_len", "mi_rec",
                 "pool_rows", "pool_span")

    def __init__(self, count, vlo, read_type, cons_len, mi_rec, pool_rows,
                 pool_span):
        self.count = count
        self.vlo = vlo
        self.read_type = read_type
        self.cons_len = cons_len
        self.mi_rec = mi_rec
        self.pool_rows = pool_rows
        self.pool_span = pool_span

    def __len__(self):
        return len(self.count)


def _table_from_legacy(entries, span):
    """_JobTable from (key, group_start, (read_type, rows, cons_len)) tuples
    already in output order (the rejects-tracking all-scan path)."""
    J = len(entries)
    if J == 0:
        e64 = np.empty(0, dtype=np.int64)
        return _JobTable(e64, e64, np.empty(0, dtype=np.int8),
                         np.empty(0, dtype=np.int32), e64, e64, e64)
    counts = np.fromiter((len(jg[1]) for _, _, jg in entries), np.int64, J)
    vlo = np.concatenate(([0], np.cumsum(counts)[:-1]))
    rt = np.fromiter((jg[0] for _, _, jg in entries), np.int8, J)
    cl = np.fromiter((jg[2] for _, _, jg in entries), np.int32, J)
    mi = np.fromiter((span[s] for _, s, _ in entries), np.int64, J)
    pool = np.concatenate([jg[1] for _, _, jg in entries]).astype(np.int64)
    return _JobTable(counts, vlo, rt, cl, mi, pool, span[pool])


class _FilterTally:
    """What the most-common-alignment filter did in one span: segments it
    ran on, segments that kept every read, reads in, reads rejected, and
    the groups (with their reads) that had such a segment."""

    __slots__ = ("segments", "kept_all", "reads_in", "rejected", "groups",
                 "group_reads")

    def __init__(self):
        self.segments = self.kept_all = self.reads_in = self.rejected = 0
        self.groups = self.group_reads = 0


class _PendingChunk:
    """Deferred half of a batch: fetch packed device results, recompute
    depth/errors on host, apply thresholds, serialize (SURVEY §7 step 4
    double-buffering: dispatch happens in process_batch, this completes it)."""

    __slots__ = ("fast", "batch", "jobs", "idxs", "pending", "blocks")

    def __init__(self, fast, batch, jobs, idxs, pending, blocks0=()):
        self.fast = fast
        self.batch = batch
        self.jobs = jobs  # a _JobTable
        # the multi-read jobs' indices and their submitted segment batch
        # (ops/kernel.PendingSegments); None when every job is single-read
        self.idxs = idxs
        self.pending = pending
        # (job_idxs, bases, quals, depth32, errors32) row blocks; starts with
        # the host-path blocks (single-read jobs) from _dispatch_jobs
        self.blocks = list(blocks0)

    def resolve(self) -> bytes:
        fast = self.fast
        if fast.filter_stage is not None:
            # fused consensus→filter route (ISSUE 11): verdicts from the
            # device stats fetch (or host columns), survivors-only gather,
            # survivors-only serialization — consensus/device_filter.py
            return fast.filter_stage.resolve_chunk(self)
        if self.pending is not None:
            self._assign(self.idxs, *self.pending.resolve())
        with _span("resolve.serialize", rusage=True):
            return fast._serialize_jobs(self.batch, self.jobs, self.blocks)

    def _assign(self, idxs, winner, qual, depth, errors):
        """Thresholds in one vectorized pass; rows are handed to the
        serializer as whole blocks (addresses computed per block, not per
        job — job.result stays None for block-backed jobs)."""
        opts = self.fast.caller.options
        bases_b, quals_b = oracle.apply_consensus_thresholds(
            winner, qual, depth, opts.min_reads,
            opts.min_consensus_base_quality)
        self.blocks.append((np.asarray(idxs, dtype=np.int64),
                            np.ascontiguousarray(bases_b),
                            np.ascontiguousarray(quals_b),
                            np.ascontiguousarray(depth, dtype=np.int32),
                            np.ascontiguousarray(errors, dtype=np.int32)))


class FastSimplexCaller:
    """Batch-vectorized simplex caller wrapping a VanillaConsensusCaller.

    The wrapped caller owns options/tables/kernel/stats/record-builder and
    serves as the per-group fallback, so statistics and output bytes are shared
    across both paths.
    """

    def __init__(self, caller: VanillaConsensusCaller, tag: bytes = b"MI",
                 overlap_caller=None, mesh=None, filter_stage=None):
        """`mesh`: optional jax Mesh with (dp, sp) axes — multi-read jobs
        dispatch through the shard_map-wrapped full-column wire kernels
        (families over dp with no collectives, each shard's read rows over
        sp with one psum combine; ops/kernel._dispatch_wire_mesh). None or
        a 1-device mesh = the legacy single-device path, bit for bit.
        `filter_stage`: a consensus/device_filter.SimplexFilterStage —
        the fused consensus→filter route (--device-filter): outputs are
        filtered before serialization, device-routed batches via the
        fused mask kernel with survivors-only fetch."""
        self.caller = caller
        self.tag = tag
        self.overlap_caller = overlap_caller  # OverlappingBasesConsensusCaller
        self.mesh = mesh if mesh is not None and mesh.size > 1 else None
        self.filter_stage = filter_stage
        # device/host routing is per batch via the adaptive cost model
        # (ops/router.py; FGUMI_TPU_ROUTE forces a side; the explicit
        # FGUMI_TPU_MAX_INFLIGHT escape hatch is honored inside
        # ROUTER.decide)
        opts = caller.options
        # conditions the vectorized conversion cannot express
        self._vector_ok = (not opts.trim and not opts.methylation_mode)
        self._carry = None  # (mi_bytes, [RawRecord]) spanning batch boundary
        self._filter_tally = None  # a _FilterTally while a span is scanned
        self._palin_cache = {}  # cigar bytes -> simplified-CIGAR palindromicity

    # ------------------------------------------------------------------ driver

    def process_batch(self, batch, allow_unmapped: bool = False,
                      final: bool = False):
        """Consume one RecordBatch -> list of consensus record bytes.

        Groups are formed over records passing the consensus pre-group filter
        (core/grouper.py:13-23). The group spanning the batch boundary is
        carried (as RawRecords) until the next batch or `final`; it is
        processed via the slow path, with overlap correction applied there so
        pairs split across batches are still corrected.
        """
        with _span("process.decode", rusage=True):
            flag = batch.flag
            keep = (flag & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY)) == 0
            if not allow_unmapped:
                is_mapped = (flag & FLAG_UNMAPPED) == 0
                mapped_mate = ((flag & FLAG_PAIRED) != 0) \
                    & ((flag & FLAG_MATE_UNMAPPED) == 0)
                keep &= is_mapped | mapped_mate
            idx = np.nonzero(keep)[0]
            if len(idx):
                # every tag this engine reads for the batch, one native aux
                # scan
                batch.prefetch_tags([self.tag, b"MC", b"RX"])
        if len(idx) == 0:
            return self.flush() if final else []

        with _span("process.group", rusage=True):
            mi_off, mi_len, _ = batch.tag_locs(self.tag)
            starts = nb.group_starts(
                batch.buf, np.ascontiguousarray(mi_off[idx]), mi_len[idx])
            bounds = np.append(starts, len(idx))
            n_total = len(bounds) - 1

            # does the first group continue the carried group?
            first_mi = batch.tag_bytes(self.tag, int(idx[bounds[0]]))
            merge_carry = self._carry is not None \
                and self._carry[0] == first_mi
            if merge_carry:
                # materialize before any in-place correction of this batch
                self._carry[1].extend(
                    batch.raw_records(idx[bounds[0]:bounds[1]]))

            # groups [g0, g1) run the vectorized path this call; the last
            # group of a non-final batch is deferred (it may continue into
            # the next batch)
            g0 = 1 if merge_carry else 0
            g1 = n_total if final else max(n_total - 1, g0)
            deferred = None
            if not final and n_total - 1 >= g0:
                last = idx[bounds[n_total - 1]:bounds[n_total]]
                # materialize before in-place correction: the deferred group
                # is corrected exactly once, on the slow path, when it
                # completes
                deferred = (batch.tag_bytes(self.tag, int(last[0])),
                            batch.raw_records(last))

            out = []
            if self._carry is not None:
                # the carry completes unless the merged group is still the
                # open tail of a non-final batch (merge_carry and no group
                # follows)
                if (not merge_carry) or final or n_total >= 2:
                    out.extend(self._call_slow_group(*self._carry))
                    self._carry = None

        if g1 > g0:
            # native in-place overlap correction only for the complete groups
            if self.overlap_caller is not None:
                with _span("process.overlap", rusage=True):
                    self._overlap_correct(batch, idx, bounds, g0, g1)
            out.extend(self._process_groups(batch, idx, bounds, g0, g1))

        if deferred is not None:
            self._carry = deferred
        if final:
            out.extend(self.flush())
        return out

    def flush(self):
        """Emit any carried boundary group (call after the last batch)."""
        if self._carry is None:
            return []
        mi, recs = self._carry
        self._carry = None
        return self._call_slow_group(mi, recs)

    def _call_slow_group(self, mi_bytes, records):
        """Slow-path one group, with Python overlap correction first (the
        carried group's pairs may span batch buffers). Returns wire chunks."""
        if self.overlap_caller is not None:
            from .overlapping import apply_overlapping_consensus

            records = apply_overlapping_consensus(records, self.overlap_caller)
        METRICS.inc("simplex.groups.carried")
        recs = self._call_counted([(mi_bytes.decode(), records)])
        if not recs:
            return []
        return self._post_slow(
            [b"".join(len(r).to_bytes(4, "little") + r for r in recs)])

    def _call_counted(self, groups):
        """The per-group caller on whole groups (one a batch boundary cuts,
        or a mode the vectorized path cannot express), counted like the
        groups of ``_prepare_groups_vec`` so that the run report's
        ``simplex.reads`` is every input read and ``.filter.reads_rejected``
        every ``MinorityAlignment`` reject."""
        stats = self.caller.stats
        before = stats.rejected.get("MinorityAlignment", 0)
        recs = self.caller.call_groups(groups)
        METRICS.inc("simplex.groups", len(groups))
        METRICS.inc("simplex.reads", sum(len(r) for _mi, r in groups))
        rejected = stats.rejected.get("MinorityAlignment", 0) - before
        if rejected:
            METRICS.inc("simplex.filter.reads_rejected", rejected)
        return recs

    def _post_slow(self, chunks):
        """Fused-filter pass over slow-path record blobs (already-serialized
        complete groups); identity when no filter stage is attached."""
        if self.filter_stage is None or not chunks:
            return chunks
        out = [self.filter_stage.filter_records_blob(c) for c in chunks]
        return [c for c in out if c]

    # ------------------------------------------------------------ overlap corr

    def _overlap_correct(self, batch, idx, bounds, g0, g1):
        overlap_correct_span(batch, idx, bounds, g0, g1, self.overlap_caller)

    # ------------------------------------------------------------------ groups

    def _process_groups(self, batch, idx, bounds, g0, g1):
        caller = self.caller
        opts = caller.options

        if not self._vector_ok:
            # trim / methylation modes: whole-group slow path, stream order
            groups = []
            for g in range(g0, g1):
                members = idx[bounds[g]:bounds[g + 1]]
                mi = batch.tag_bytes(self.tag, int(members[0]))
                groups.append((mi.decode(), batch.raw_records(members)))
            recs = self._call_counted(groups)
            if not recs:
                return []
            return self._post_slow(
                [b"".join(len(r).to_bytes(4, "little") + r for r in recs)])

        with _span("process.prep", rusage=True):
            codes, quals, table = self._prepare_jobs(batch, idx, bounds, g0,
                                                     g1)
        if len(table) == 0:
            return []
        multi, pending, blocks0 = self._dispatch_jobs(codes, quals, table)
        return [_PendingChunk(self, batch, table, multi, pending, blocks0)]

    def _prepare_jobs(self, batch, idx, bounds, g0, g1):
        """Native prep of groups [g0, g1): mate clips, packed reads, and the
        job table. Returns (codes, quals, table)."""
        caller = self.caller
        opts = caller.options
        # batch-wide native prep over the kept records of the processed groups
        span = idx[bounds[g0]:bounds[g1]]
        mc_off, mc_len, _ = batch.tag_locs_str(b"MC")
        clips = nb.mate_clips(
            batch.buf, np.ascontiguousarray(batch.cigar_off[span]),
            batch.n_cigar[span], batch.flag[span], batch.ref_id[span],
            batch.pos[span], batch.next_ref_id[span], batch.next_pos[span],
            batch.tlen[span], np.ascontiguousarray(mc_off[span]),
            mc_len[span])
        # stride is a multiple of 32 so every bucket width Lb <= stride
        stride = max(-(-int(batch.l_seq[span].max()) // 32) * 32, 32)
        reverse = ((batch.flag[span] & FLAG_REVERSE) != 0).astype(np.uint8)
        codes, quals, final_len = nb.pack_reads(
            batch.buf, np.ascontiguousarray(batch.seq_off[span]),
            np.ascontiguousarray(batch.qual_off[span]), batch.l_seq[span],
            reverse, clips, opts.min_input_base_quality, stride)

        # span-relative views
        flag_s = batch.flag[span]
        paired = (flag_s & FLAG_PAIRED) != 0
        # read type: fragment / R1 / R2; paired-but-neither drops silently
        # (vanilla.py:296-304 subgroup dict semantics)
        rtype = np.full(len(span), -1, dtype=np.int8)
        rtype[~paired] = FRAGMENT
        rtype[paired & ((flag_s & FLAG_FIRST) != 0)] = R1
        rtype[paired & ((flag_s & FLAG_FIRST) == 0)
              & ((flag_s & FLAG_LAST) != 0)] = R2

        # span-wide CIGAR-equality runs: group g is CIGAR-uniform iff no run
        # boundary falls strictly inside (s, e) — avoids per-subgroup scans
        cig_runs = nb.group_starts(
            batch.buf, np.ascontiguousarray(batch.cigar_off[span]),
            (4 * batch.n_cigar[span]).astype(np.int32))
        rel_bounds = bounds - bounds[g0]
        runs_lo = np.searchsorted(cig_runs, rel_bounds[g0:g1], side="right")
        runs_hi = np.searchsorted(cig_runs, rel_bounds[g0 + 1:g1 + 1],
                                  side="left")
        group_uniform = runs_hi == runs_lo

        # per-group preparation: vectorized common path; the per-group Python
        # scan remains for rejects-tracking mode and for groups needing
        # downsampling
        if caller.track_rejects:
            legacy = []
            self._count_groups(np.diff(rel_bounds[g0:g1 + 1]), "rejects",
                               np.ones(g1 - g0, dtype=bool))
            self._filter_tally = _FilterTally()
            for g in range(g0, g1):
                s, e = rel_bounds[g], rel_bounds[g + 1]
                jobs_g = []
                self._prepare_group_fast(batch, span, s, e, rtype, final_len,
                                         jobs_g,
                                         bool(group_uniform[g - g0]))
                legacy.extend(((g - g0) * 3 + i, int(s), jg)
                              for i, jg in enumerate(jobs_g))
            self._fold_filter_tally()
            table = _table_from_legacy(legacy, span)
        else:
            # rel_bounds is already span-relative (rel_bounds[g0] == 0)
            gb = rel_bounds[g0:g1 + 1]
            table = self._prepare_groups_vec(batch, span, gb, rtype,
                                             final_len, group_uniform)
        return codes, quals, table

    def _prepare_groups_vec(self, batch, span, gb, rtype, final_len,
                            group_uniform):
        """Vectorized _prepare_group_fast over all groups of the span.

        gb: (nG+1,) span-relative group boundaries. Groups that need the
        seeded downsample fall back to the per-group scan (identical
        semantics); everything else — type subgrouping, min-reads/
        zero-length rejection, the most-common-alignment filter (one native
        pass over every segment that needs it, answered as a keep mask),
        consensus length, orphan handling — happens in whole-span array
        passes.

        Returns a _JobTable (jobs in output order, arrays only).
        """
        caller = self.caller
        opts = caller.options
        stats = caller.stats
        min_reads = opts.min_reads
        nG = len(gb) - 1
        sizes = np.diff(gb)
        ord0 = caller._group_ordinal
        caller._group_ordinal += nG

        small = sizes < min_reads
        downs = (np.zeros(nG, dtype=bool) if opts.max_reads is None
                 else sizes > opts.max_reads)

        # candidate rows: valid type, in a group subject to seg analysis
        g_of_row = np.repeat(np.arange(nG), sizes)
        row_ok = (~small & ~downs)[g_of_row] & (rtype >= 0)
        er = np.nonzero(row_ok)[0]
        tally = self._filter_tally = _FilterTally()
        nseg = 0
        if len(er):
            key = g_of_row[er] * 4 + rtype[er]
            order = np.argsort(key, kind="stable")
            srows = er[order]          # seg-grouped; original order within seg
            skey = key[order]
            seg_first = np.concatenate(([True], skey[1:] != skey[:-1]))
            seg_of_row = np.cumsum(seg_first) - 1
            seg_key = skey[seg_first]
            nseg = len(seg_key)
            seg_g = seg_key >> 2
            seg_t = (seg_key & 3).astype(np.int8)
            c0 = np.bincount(seg_of_row, minlength=nseg)

            valid_row = final_len[srows] > 0
            seg_v = seg_of_row[valid_row]  # seg of each compacted valid row
            c1 = np.bincount(seg_v, minlength=nseg)
            alive0 = c0 >= min_reads
            alive = alive0 & (c1 >= min_reads)

            vrows = srows[valid_row]   # compacted valid rows, seg-grouped
            vstarts = np.concatenate(([0], np.cumsum(c1)))
            span_v = span[vrows]
            vlens = final_len[vrows]

            # need-filter analysis (matches _prepare_group_fast): a seg needs
            # the alignment filter when its valid rows' CIGARs differ, or are
            # uniform but mixed-strand with a non-palindromic simplified CIGAR
            guniform_seg = group_uniform[seg_g]
            need = np.zeros(nseg, dtype=bool)
            nonempty = c1 > 0
            first_valid = np.zeros(nseg, dtype=np.int64)
            first_valid[nonempty] = vrows[vstarts[:-1][nonempty]]
            check = alive & ~guniform_seg
            co = batch.cigar_off
            if check.any():
                cl = (4 * batch.n_cigar).astype(np.int32)
                rep_first = np.repeat(span[first_valid], c1)
                eq = nb.ranges_equal(batch.buf, co[span_v], cl[span_v],
                                     co[rep_first], cl[rep_first])
                seg_cig_uniform = np.ones(nseg, dtype=bool)
                seg_cig_uniform[nonempty] = np.minimum.reduceat(
                    eq, vstarts[:-1][nonempty]).astype(bool)
                need = check & ~seg_cig_uniform
                if need.any():
                    # all-single-op-M segs (ragged read lengths, e.g. 80M vs
                    # 100M) are mutually prefix-compatible after simplify:
                    # the most-common-alignment filter provably keeps every
                    # read, so they never reach it (the common case on
                    # length-jittered inputs)
                    row_sm = (batch.n_cigar[span_v] == 1) \
                        & ((batch.buf[co[span_v]] & 0xF) == 0)
                    seg_sm = np.zeros(nseg, dtype=bool)
                    seg_sm[nonempty] = np.minimum.reduceat(
                        row_sm.astype(np.uint8),
                        vstarts[:-1][nonempty]).astype(bool)
                    need &= ~seg_sm
            rev8 = ((batch.flag[span_v] & FLAG_REVERSE) != 0).astype(np.uint8)
            mixed = np.zeros(nseg, dtype=bool)
            if nonempty.any():
                mn = np.minimum.reduceat(rev8, vstarts[:-1][nonempty])
                mx = np.maximum.reduceat(rev8, vstarts[:-1][nonempty])
                mixed[nonempty] = (mn == 0) & (mx == 1)
            strand_check = alive & ~need & mixed & (c1 >= 2)
            if strand_check.any():
                # single-op CIGARs simplify to one run: always palindromic
                n1 = batch.n_cigar[span[first_valid]]
                for s in np.nonzero(strand_check & (n1 != 1))[0]:
                    rec_i = int(span[first_valid[s]])
                    cig_bytes = batch.buf[
                        batch.cigar_off[rec_i]:
                        batch.cigar_off[rec_i]
                        + 4 * batch.n_cigar[rec_i]].tobytes()
                    palin = self._palin_cache.get(cig_bytes)
                    if palin is None:
                        cig = cigar_utils.simplify(
                            self._decode_cigar(batch, rec_i))
                        palin = cig == list(reversed(cig))
                        if len(self._palin_cache) >= 4096:
                            self._palin_cache.clear()
                        self._palin_cache[cig_bytes] = palin
                    if not palin:
                        need[s] = True

            c1_in, alive_in = c1, alive  # as the filter finds them
            n_minor = 0
            if need.any():
                # the filter over every such segment's valid rows at once;
                # a rejected row leaves the compacted arrays, and what hangs
                # off them is made again before anything reads them
                c1_need = c1[need]
                at = _ranges(vstarts[:-1][need], c1_need)
                rows_f = span_v[at]
                cig_f, ncig_f = co[rows_f], batch.n_cigar[rows_f]
                rev_f, len_f = rev8[at], vlens[at]
                starts_f = np.concatenate(([0], np.cumsum(c1_need)))
                with _span("process.prep.align_filter", rusage=True,
                           segments=len(c1_need)):
                    keep = nb.alignment_filter(batch.buf, cig_f, ncig_f,
                                               rev_f, len_f, starts_f)
                drop = at[keep == 0]
                n_minor = len(drop)
                filtered_g = np.unique(seg_g[need])
                tally.segments += len(c1_need)
                tally.reads_in += int(c1_need.sum())
                tally.rejected += n_minor
                tally.kept_all += len(c1_need) - len(np.unique(seg_v[drop]))
                tally.groups += len(filtered_g)
                tally.group_reads += int(sizes[filtered_g].sum())
                if n_minor:
                    kept = np.ones(len(vrows), dtype=bool)
                    kept[drop] = False
                    vrows, span_v = vrows[kept], span_v[kept]
                    vlens, seg_v = vlens[kept], seg_v[kept]
                    c1 = np.bincount(seg_v, minlength=nseg)
                    vstarts = np.concatenate(([0], np.cumsum(c1)))
                    alive = alive_in & (c1 >= min_reads)

        vec_g = ~downs
        stats.input_reads += int(sizes[vec_g].sum())
        n_small = int(sizes[small & vec_g].sum())
        if n_small:
            stats.reject("InsufficientReads", n_small)

        seg_map = None
        if nseg:
            # the tallies of _prepare_group_fast, a segment's steps in its
            # order: too few reads, zero length, too few left, the filter's
            # minority, too few left after it (it never takes a segment's
            # last read)
            few = int(c0[~alive0].sum()) \
                + int(c1_in[alive0 & ~alive_in].sum()) \
                + int(c1[alive_in & ~alive].sum())
            if few:
                stats.reject("InsufficientReads", few)
            zl = int((c0 - c1_in)[alive0].sum())
            if zl:
                stats.reject("ZeroLengthAfterTrimming", zl)
            if n_minor:
                stats.reject("MinorityAlignment", n_minor)

            # consensus length: min_reads-th longest valid len per seg
            ord2 = np.lexsort((-vlens.astype(np.int64), seg_v))
            lens_sorted = vlens[ord2]
            pick = np.minimum(vstarts[:-1] + (min_reads - 1),
                              np.maximum(len(lens_sorted) - 1, 0))
            cons_len = (lens_sorted[pick] if len(lens_sorted)
                        else np.zeros(nseg, dtype=vlens.dtype))

            seg_map = np.full((nG, 3), -1, dtype=np.int64)
            seg_map[seg_g[alive], seg_t[alive]] = np.nonzero(alive)[0]
            # orphan R1/R2 rejection, aggregated (vanilla.py:346-357)
            have_r1 = seg_map[:, R1] >= 0
            have_r2 = seg_map[:, R2] >= 0
            lone_r1 = seg_map[:, R1][have_r1 & ~have_r2]
            lone_r2 = seg_map[:, R2][have_r2 & ~have_r1]
            n_orphan = int(c1[lone_r1].sum() + c1[lone_r2].sum())
            if n_orphan:
                stats.reject("OrphanConsensus", n_orphan)

        # downsampled groups: the per-group scan (it needs the group's own
        # seeded permutation), collected as (order-key, group-start,
        # job-tuple)
        legacy = []
        legacy_ids = np.nonzero(downs)[0]
        self._count_groups(sizes, "downsample", downs)
        if len(legacy_ids):
            # one span a call, never one a group (docs/observability.md)
            with _span("process.prep.legacy", rusage=True,
                       groups=len(legacy_ids)):
                for g in legacy_ids:
                    jobs_g = []
                    self._prepare_group_fast(batch, span, gb[g], gb[g + 1],
                                             rtype, final_len, jobs_g,
                                             bool(group_uniform[g]),
                                             ordinal=ord0 + int(g))
                    legacy.extend((int(g) * 3 + i, int(gb[g]), jg)
                                  for i, jg in enumerate(jobs_g))
        self._fold_filter_tally()

        # vectorized emission: seg_map columns are already in output order
        # (fragment, R1, R2 per group; vanilla.py:377-386), so the row-major
        # flatten index IS the (group, slot) order key
        if nseg:
            flat = seg_map.copy()
            pair = (flat[:, R1] >= 0) & (flat[:, R2] >= 0)
            flat[~pair, R1] = -1
            flat[~pair, R2] = -1
            flat = flat.ravel()
            key_vec = np.nonzero(flat >= 0)[0]
            vseg = flat[key_vec]
        else:
            key_vec = np.empty(0, dtype=np.int64)
            vseg = np.empty(0, dtype=np.int64)
            vrows = np.empty(0, dtype=np.int64)
            span_v = np.empty(0, dtype=np.int64)
            c1 = np.empty(0, dtype=np.int64)
            vstarts = np.zeros(1, dtype=np.int64)
            seg_t = np.empty(0, dtype=np.int8)
            seg_g = np.empty(0, dtype=np.int64)
            cons_len = np.empty(0, dtype=np.int64)

        cnt_v = c1[vseg].astype(np.int64)
        vlo_v = vstarts[:-1][vseg].astype(np.int64)
        typ_v = seg_t[vseg].astype(np.int8)
        len_v = cons_len[vseg].astype(np.int32)
        mi_v = span[gb[seg_g[vseg]]].astype(np.int64)

        if not legacy:
            return _JobTable(cnt_v, vlo_v, typ_v, len_v, mi_v, vrows, span_v)

        nleg = len(legacy)
        cnt_l = np.fromiter((len(jg[1]) for _, _, jg in legacy),
                            np.int64, nleg)
        vlo_l = len(vrows) + np.concatenate(([0], np.cumsum(cnt_l)[:-1]))
        typ_l = np.fromiter((jg[0] for _, _, jg in legacy), np.int8, nleg)
        len_l = np.fromiter((jg[2] for _, _, jg in legacy), np.int32, nleg)
        mi_l = np.fromiter((span[s] for _, s, _ in legacy), np.int64, nleg)
        key_l = np.fromiter((k for k, _, _ in legacy), np.int64, nleg)
        aux = np.concatenate([jg[1] for _, _, jg in legacy])
        order = np.argsort(np.concatenate((key_vec, key_l)), kind="stable")
        return _JobTable(
            np.concatenate((cnt_v, cnt_l))[order],
            np.concatenate((vlo_v, vlo_l))[order],
            np.concatenate((typ_v, typ_l))[order],
            np.concatenate((len_v, len_l))[order],
            np.concatenate((mi_v, mi_l))[order],
            np.concatenate((vrows, aux)),
            np.concatenate((span_v, span[aux])))

    @staticmethod
    def _count_groups(sizes, why, legacy):
        """Run-report counters of one span's groups: how many, and how many
        (the mask ``legacy``) left the whole-array path for the per-group
        scan, and ``why``: a downsample; under ``--rejects`` every group."""
        METRICS.inc("simplex.groups", len(sizes))
        METRICS.inc("simplex.reads", int(sizes.sum()))
        if legacy.any():
            METRICS.inc("simplex.groups.legacy", int(legacy.sum()))
            METRICS.inc("simplex.groups.legacy." + why, int(legacy.sum()))
            METRICS.inc("simplex.reads.legacy", int(sizes[legacy].sum()))

    def _fold_filter_tally(self):
        """Fold the span's ``_FilterTally`` into the run report's counters,
        once a span."""
        tally, self._filter_tally = self._filter_tally, None
        if not tally.segments:
            return
        METRICS.inc("simplex.groups.filtered", tally.groups)
        METRICS.inc("simplex.reads.filtered", tally.group_reads)
        METRICS.inc("simplex.filter.segments", tally.segments)
        METRICS.inc("simplex.filter.segments_kept_all", tally.kept_all)
        METRICS.inc("simplex.filter.reads_in", tally.reads_in)
        METRICS.inc("simplex.filter.reads_rejected", tally.rejected)

    def _prepare_group_fast(self, batch, span, s, e, rtype, final_len, jobs,
                            group_uniform=False, ordinal=None):
        """prepare_group analog on array slices (vanilla.py:274-357).

        `ordinal` is the group's downsample-RNG ordinal; None allocates the
        next one (the vectorized path pre-allocates a span's worth and passes
        each group's explicitly)."""
        caller = self.caller
        opts = caller.options
        stats = caller.stats
        n_records = e - s
        stats.input_reads += int(n_records)
        if ordinal is None:
            ordinal = caller._group_ordinal
            caller._group_ordinal += 1

        def rej(rows_arr):
            # rejects materialize as RawRecords only when tracking is on
            if caller.track_rejects and len(rows_arr):
                caller.rejected_reads.extend(batch.raw_records(span[rows_arr]))

        # secondary/supplementary were pre-filtered from idx; prepare_group's
        # first filter is a no-op here, so `reads` == all group records
        if n_records < opts.min_reads:
            stats.reject("InsufficientReads", int(n_records))
            rej(np.arange(s, e))
            return

        rows = np.arange(s, e)
        if opts.max_reads is not None and n_records > opts.max_reads:
            rng = np.random.Generator(
                np.random.Philox(key=(opts.seed or 0) + ordinal))
            perm = rng.permutation(n_records)[:opts.max_reads]
            rows = rows[perm]  # permuted order, like _downsample

        group_jobs = {}
        filtered = False  # a segment of this group ran the alignment filter
        for read_type in (FRAGMENT, R1, R2):
            t_rows = rows[rtype[rows] == read_type]
            if len(t_rows) == 0:
                continue
            if len(t_rows) < opts.min_reads:
                stats.reject("InsufficientReads", int(len(t_rows)))
                rej(t_rows)
                continue
            lens = final_len[t_rows]
            ok = lens > 0
            zero_len = int((~ok).sum())
            if zero_len:
                stats.reject("ZeroLengthAfterTrimming", zero_len)
                rej(t_rows[~ok])
                t_rows = t_rows[ok]
                lens = lens[ok]
            if len(t_rows) < opts.min_reads:
                if len(t_rows):
                    stats.reject("InsufficientReads", int(len(t_rows)))
                    rej(t_rows)
                continue
            # most-common-alignment filter (vanilla.py:210-222): identical
            # simplified CIGARs always form a single compatibility group ->
            # keep all. Identical raw bytes imply that only when strands agree
            # or the simplified CIGAR is palindromic (reverse-strand reads use
            # the reversed simplified CIGAR, vanilla.py:199-201).
            if group_uniform:
                need_filter = False
            else:
                cig_off = np.ascontiguousarray(batch.cigar_off[span[t_rows]])
                cig_len = (4 * batch.n_cigar[span[t_rows]]).astype(np.int32)
                runs = nb.group_starts(batch.buf, cig_off, cig_len)
                need_filter = len(runs) > 1
                if need_filter \
                        and (batch.n_cigar[span[t_rows]] == 1).all() \
                        and ((batch.buf[cig_off] & 0xF) == 0).all():
                    # all-single-op-M: mutually prefix-compatible, the
                    # filter keeps everything (see _prepare_groups_vec)
                    need_filter = False
            if not need_filter and len(t_rows) >= 2:
                revs = (batch.flag[span[t_rows]] & FLAG_REVERSE) != 0
                if revs.any() and not revs.all():
                    cig = cigar_utils.simplify(
                        self._decode_cigar(batch, int(span[t_rows[0]])))
                    need_filter = cig != list(reversed(cig))
            if need_filter:
                keep = self._alignment_filter(batch, span, t_rows, lens)
                rejected = len(t_rows) - int(keep.sum())
                tally = self._filter_tally
                if not filtered:
                    filtered = True
                    tally.groups += 1
                    tally.group_reads += int(n_records)
                tally.segments += 1
                tally.kept_all += not rejected
                tally.reads_in += len(t_rows)
                tally.rejected += rejected
                if rejected:
                    stats.reject("MinorityAlignment", rejected)
                    rej(t_rows[~keep])
                t_rows = t_rows[keep]
                lens = final_len[t_rows]
                if len(t_rows) < opts.min_reads:
                    if len(t_rows):
                        stats.reject("InsufficientReads", int(len(t_rows)))
                        rej(t_rows)
                    continue
            lens_sorted = np.sort(lens)[::-1]
            consensus_len = int(lens_sorted[opts.min_reads - 1])
            group_jobs[read_type] = (read_type, t_rows, consensus_len)

        # orphan R1/R2 handling (vanilla.py:346-357)
        if FRAGMENT in group_jobs:
            jobs.append(group_jobs[FRAGMENT])
        r1, r2 = group_jobs.get(R1), group_jobs.get(R2)
        if r1 is not None and r2 is not None:
            jobs.extend([r1, r2])
        elif r1 is not None:
            stats.reject("OrphanConsensus", len(r1[1]))
            rej(r1[1])
        elif r2 is not None:
            stats.reject("OrphanConsensus", len(r2[1]))
            rej(r2[1])

    @staticmethod
    def _alignment_filter(batch, span, t_rows, lens):
        """The most-common-alignment filter on one (group, read type): the
        native pass of ``_prepare_groups_vec`` with a single segment, as a
        keep mask over ``t_rows``."""
        recs = span[t_rows]
        keep = nb.alignment_filter(
            batch.buf, batch.cigar_off[recs], batch.n_cigar[recs],
            (batch.flag[recs] & FLAG_REVERSE) != 0, lens,
            np.array([0, len(recs)]))
        return keep.view(bool)

    @staticmethod
    def _decode_cigar(batch, rec_i):
        off = batch.cigar_off[rec_i]
        n = batch.n_cigar[rec_i]
        # tobytes() realigns: a uint32 view of an odd-offset slice would fail
        raw = np.frombuffer(batch.buf[off: off + 4 * n].tobytes(),
                            dtype="<u4")
        return [(_CIGAR_OPS[v & 0xF], int(v) >> 4) for v in raw]

    # ------------------------------------------------------------------ device

    def _dispatch_jobs(self, codes, quals, table):
        """One dense segment-sum kernel dispatch for the whole batch.

        Single-read jobs run vectorized on host (one (S, L) gather + table
        lookup); multi-read jobs concatenate their packed read rows into a
        dense (N, L) layout with sorted segment ids — one device execution
        and one uint16 fetch per record batch, independent of family-size
        mix (the per-execution launch overhead is paid once). The fetch + threshold + serialize half runs
        in _PendingChunk.resolve() (SURVEY §7 step 4: host prep overlaps
        device compute and transfer). Returns (the multi-read jobs' indices,
        their submitted batch or None when there are none, host_blocks).
        """
        caller = self.caller
        opts = caller.options
        kernel = caller.kernel
        count = table.count
        blocks0 = []

        with _span("process.prep", rusage=True):
            # single-read jobs on the host, and the multi-read jobs' rows
            single = np.nonzero(count == 1)[0]
            if len(single):
                rows1 = table.pool_rows[table.vlo[single]]
                Lm = int(table.cons_len[single].max())
                b, q, d, e = oracle.single_read_consensus(
                    codes[rows1, :Lm], quals[rows1, :Lm], caller.tables,
                    opts.min_consensus_base_quality)
                blocks0.append((single, np.ascontiguousarray(b),
                                np.ascontiguousarray(q),
                                np.ascontiguousarray(d.astype(np.int32)),
                                np.ascontiguousarray(e.astype(np.int32))))

            multi = np.nonzero(count > 1)[0]
            if len(multi):
                counts = count[multi]
                rows_all = table.pool_rows[_ranges(table.vlo[multi], counts)]
                # 4-multiple L >= every job's consensus length (<= the pack
                # stride); 4 (not 16) because every padded position is an
                # uploaded wire byte and the 2-bit winner output packs 4
                # positions per byte
                L_max = -(-int(table.cons_len[multi].max()) // 4) * 4
        if len(multi) == 0:
            return multi, None, blocks0

        from ..ops.router import ROUTER

        mesh = self.mesh
        # full-column gate (uint16 depth fetch) decided BEFORE routing so
        # the fused-filter pricing below can never be promised for a batch
        # that would actually dispatch the ordinary full-column kernel
        fused_filter = False
        if self.filter_stage is not None and mesh is None \
                and counts.max() < 65536:
            from .device_filter import device_mask_enabled

            fused_filter = device_mask_enabled()
        if kernel.host_mode():
            side = "host"
        else:
            # adaptive offload: price this batch on both sides from
            # measured EWMAs (ops/router.py decide_batch) — the mesh size
            # selects its own EWMA set, so an N-chip device side is priced
            # as N chips, not as the single-device model. A fused-filter
            # batch is priced with its reduced fetch (stats row + keep-rate
            # scaled survivor columns) instead of the full-column fetch.
            side = ROUTER.decide_batch(
                kernel, len(rows_all), len(multi), L_max,
                devices=mesh.size if mesh is not None else 1,
                filtered=fused_filter)
        return multi, self._pack_and_dispatch(
            codes, quals, table, multi, counts, rows_all, L_max, side,
            fused_filter), blocks0

    def _pack_and_dispatch(self, codes, quals, table, multi, counts, rows_all,
                           L_max, side, fused_filter):
        """Submit one batch's multi-read jobs on the route the router chose:
        the whole batch crosses the link once in the 1 B/position wire
        layout and the device resolves every column, or the native f64
        engine takes the rows at resolve time. One device packs the ragged
        rows in one native pass; a > 1-device mesh runs the same wire
        kernels shard_map-wrapped over (dp, sp) from dense rows —
        byte-identity with the single-device path is the test oracle."""
        kernel = self.caller.kernel
        if self.mesh is not None:
            return kernel.submit_dense(
                lambda: (np.ascontiguousarray(codes[rows_all, :L_max]),
                         np.ascontiguousarray(quals[rows_all, :L_max])),
                counts, side, mesh=self.mesh)
        filter_params = None
        if fused_filter and side != "host":
            # fused consensus→filter dispatch: per-read stats fetch +
            # device-resident masked columns (survivors gathered at
            # resolve time by the filter stage)
            opts = self.caller.options
            filter_params = (np.int32(opts.min_reads),
                             np.int32(opts.min_consensus_base_quality),
                             table.cons_len[multi].astype(np.int32),
                             self.filter_stage.dev_params)
        return kernel.submit_ragged(codes, quals, rows_all, L_max, counts,
                                    side, filter_params=filter_params)

    # ------------------------------------------------------------------ output

    def _serialize_jobs(self, batch, table, blocks=()) -> bytes:
        """Native batch serializer: all jobs -> one block_size-prefixed wire
        blob (fgumi_build_consensus_records; _build_record semantics).
        `blocks` carries every job's result rows as whole blocks (addresses
        computed per block); MI bytes resolve to pointers straight into the
        batch buffer (table.mi_rec), no per-job copies."""
        caller = self.caller
        opts = caller.options
        J = len(table)
        lens = np.ascontiguousarray(table.cons_len, dtype=np.int32)
        flags = _TYPE_FLAGS_ARR[table.read_type]
        code_addr = np.empty(J, dtype=np.int64)
        qual_addr = np.empty(J, dtype=np.int64)
        depth_addr = np.empty(J, dtype=np.int64)
        err_addr = np.empty(J, dtype=np.int64)
        keep_alive = []
        for idxs, b, q, d, e in blocks:
            keep_alive.append((b, q, d, e))
            fi = np.arange(len(idxs), dtype=np.int64)
            code_addr[idxs] = b.ctypes.data + fi * b.shape[1]
            qual_addr[idxs] = q.ctypes.data + fi * q.shape[1]
            depth_addr[idxs] = d.ctypes.data + fi * (4 * d.shape[1])
            err_addr[idxs] = e.ctypes.data + fi * (4 * e.shape[1])

        buf = batch.buf
        buf_base = buf.ctypes.data
        mi_vo, mi_vl, _ = batch.tag_locs(self.tag)
        mi_addr = np.ascontiguousarray(buf_base + mi_vo[table.mi_rec],
                                       dtype=np.int64)
        mi_len = np.ascontiguousarray(mi_vl[table.mi_rec], dtype=np.int32)

        # consensus RX from the surviving reads' RX tags (vanilla.py:460-464):
        # unanimity (the overwhelmingly common case) resolves natively to a
        # pointer into the batch buffer; only divergent families run the
        # Python likelihood consensus
        rx_vo, rx_vl, _ = batch.tag_locs_str(b"RX")
        surv_counts = table.count
        surv_starts = np.concatenate(([0], np.cumsum(surv_counts)))
        surv_all = table.pool_span[_ranges(table.vlo, surv_counts)]
        rxo, rxl = nb.rx_unanimous(buf, rx_vo[surv_all], rx_vl[surv_all],
                                   surv_starts)
        rx_addr = np.where(rxo >= 0, buf_base + rxo, 0)
        rx_len = np.where(rxo >= 0, rxl, 0).astype(np.int32)
        divergent = np.nonzero(rxo == -2)[0]
        if len(divergent):
            fams = []
            for j in divergent:
                lo = int(table.vlo[j])
                hi = lo + int(table.count[j])
                fams.append(
                    [buf[rx_vo[i]: rx_vo[i] + rx_vl[i]].tobytes().decode()
                     for i in table.pool_span[lo:hi] if rx_vo[i] >= 0])
            for j, rx in zip(divergent, consensus_umis_batch(fams)):
                rx_arr = np.frombuffer(rx.encode(), dtype=np.uint8)
                keep_alive.append(rx_arr)
                rx_addr[j] = rx_arr.ctypes.data
                rx_len[j] = len(rx_arr)

        blob, _ = nb.build_consensus_records(
            code_addr, qual_addr, depth_addr, err_addr, lens, flags,
            caller.prefix.encode(), mi_addr, mi_len, rx_addr, rx_len,
            caller.read_group_id.encode(), opts.produce_per_base_tags)
        caller.stats.add_consensus_reads(J)
        del keep_alive
        return blob


_CIGAR_OPS = "MIDNSHP=X"


def overlap_correct_span(batch, idx, bounds, g0, g1, oc):
    """In-place R1/R2 overlap correction over groups [g0, g1) of `idx`.

    Pairs primary R1/R2 by name within each group; one native call. Shared by
    the fast simplex engine (MI groups) and the fast duplex engine
    ((molecule, strand) subgroups).
    """
    flag = batch.flag
    span = idx[bounds[g0]:bounds[g1]]
    # fast path: the grouped-BAM layout keeps each template's primary R1
    # immediately followed by its R2 (group output preserves template
    # adjacency); vectorized detection of (FIRST, LAST) runs with equal
    # names covers it, the per-group dict pairing is the general fallback
    f_span = flag[span]
    # candidate adjacency: FIRST record followed by a LAST-and-not-FIRST
    # one (a FIRST|LAST record sorts into the R1 slot in the dict/
    # reference pairing, overlapping.py:203-206, and never completes a
    # pair — it must not complete one here either)
    is_first = (f_span[:-1] & FLAG_FIRST) != 0
    next_last = ((f_span[1:] & FLAG_LAST) != 0) \
        & ((f_span[1:] & FLAG_FIRST) == 0)
    cand = np.nonzero(is_first & next_last)[0]
    # a pair must not straddle an MI-group boundary: the dict pairing is
    # per group, so a FIRST ending group g adjacent to a LAST opening
    # group g+1 (same-name duplicates across groups in a malformed BAM)
    # must stay two orphans, not become a cross-family correction
    if len(cand) and g1 - g0 > 1:
        boundary = np.zeros(len(span) + 1, dtype=bool)
        boundary[bounds[g0 + 1:g1] - bounds[g0]] = True
        cand = cand[~boundary[cand + 1]]
    adjacent_ok = False
    # flag-level completeness precheck (no name comparisons): every
    # FIRST/LAST-flagged record must sit in some candidate adjacency,
    # else an orphan exists somewhere and the dict scan runs anyway
    first_or_last = (f_span & (FLAG_FIRST | FLAG_LAST)) != 0
    if len(cand):
        # candidates are never adjacent: cand i requires row i+1 to be
        # LAST-and-not-FIRST while cand i+1 would require that same row to
        # be FIRST — so every candidate pair is conflict-free and the
        # greedy keep reduces to the whole candidate set (vectorized; this
        # was a 184k-iteration Python loop per run)
        used = np.zeros(len(span), dtype=bool)
        used[cand] = True
        used[cand + 1] = True
        if bool(used[first_or_last].all()):
            keep = cand
            a, b = span[keep], span[keep + 1]
            name_off = batch.data_off + 32
            name_len = (batch.l_read_name - 1).astype(np.int32)
            same = nb.ranges_equal(batch.buf, name_off[a], name_len[a],
                                   name_off[b], name_len[b])
            # repeated names among kept pairs diverge from the dict
            # pairing (last-writer-wins slots correct only one pair);
            # hash-collision false positives only cause a safe fallback
            hashes = nb.hash_ranges(batch.buf, name_off[a], name_len[a])
            if same.all() and len(np.unique(hashes)) == len(hashes):
                adjacent_ok = True
                r1_offs = batch.data_off[a]
                r2_offs = batch.data_off[b]
    if not adjacent_ok:
        # vectorized (group, name-hash) pairing: keys with exactly one
        # FIRST and one LAST row pair directly (names confirmed by one
        # batched ranges_equal — a hash collision or any odd key shape
        # sends just that group to the per-record dict pairing, whose
        # last-writer-wins semantics stay the reference for weird inputs)
        r1_offs = []
        r2_offs = []
        bad_groups = set()
        rel_bounds = bounds[g0:g1 + 1] - bounds[g0]
        g_of = np.repeat(np.arange(g1 - g0), np.diff(rel_bounds))
        fl_first = (f_span & FLAG_FIRST) != 0
        fl_last = ((f_span & FLAG_LAST) != 0) & ~fl_first
        rid = np.nonzero(fl_first | fl_last)[0]
        if len(rid):
            name_off_s = batch.data_off[span[rid]] + 32
            name_len_s = (batch.l_read_name[span[rid]] - 1).astype(np.int32)
            h = nb.hash_ranges(batch.buf, name_off_s, name_len_s)
            o = np.lexsort((h, g_of[rid]))
            gg, hh = g_of[rid][o], h[o]
            newkey = np.concatenate(
                ([True], (gg[1:] != gg[:-1]) | (hh[1:] != hh[:-1])))
            kb = np.nonzero(np.concatenate((newkey, [True])))[0]
            sizes = np.diff(kb)
            two = np.nonzero(sizes == 2)[0]
            big = np.nonzero(sizes > 2)[0]
            if len(big):
                bad_groups.update(np.unique(gg[kb[big]]).tolist())
            if len(two):
                ra = rid[o[kb[two]]]
                rb = rid[o[kb[two] + 1]]
                one_first = fl_first[ra] ^ fl_first[rb]
                # orient: FIRST -> a slot, LAST -> b slot
                swap = ~fl_first[ra]
                ra2 = np.where(swap, rb, ra)
                rb2 = np.where(swap, ra, rb)
                a_rows = span[ra2]
                b_rows = span[rb2]
                same_name = nb.ranges_equal(
                    batch.buf, batch.data_off[a_rows] + 32,
                    (batch.l_read_name[a_rows] - 1).astype(np.int32),
                    batch.data_off[b_rows] + 32,
                    (batch.l_read_name[b_rows] - 1).astype(np.int32)
                ).astype(bool)
                ok = one_first & same_name
                pair_g = g_of[ra]
                bad_groups.update(np.unique(pair_g[~ok]).tolist())
                # a bad group's rows pair in the dict fallback below —
                # keeping its vectorized pairs would correct them twice
                if bad_groups:
                    bad_arr = np.fromiter(bad_groups, dtype=np.int64,
                                          count=len(bad_groups))
                    ok &= ~np.isin(pair_g, bad_arr)
                r1_offs = batch.data_off[a_rows[ok]]
                r2_offs = batch.data_off[b_rows[ok]]
        if bad_groups:
            extra_a = []
            extra_b = []
            for g_rel in sorted(bad_groups):
                g = g0 + int(g_rel)
                members = idx[bounds[g]:bounds[g + 1]]
                pairs = {}
                for i in members:
                    f = int(flag[i])
                    # secondary/supplementary were already filtered from idx
                    slot = pairs.setdefault(batch.name(int(i)), [None, None])
                    if f & FLAG_FIRST:
                        slot[0] = int(i)
                    elif f & FLAG_LAST:
                        slot[1] = int(i)
                for a, b in pairs.values():
                    if a is not None and b is not None:
                        extra_a.append(batch.data_off[a])
                        extra_b.append(batch.data_off[b])
            r1_offs = np.concatenate(
                [np.asarray(r1_offs, dtype=np.int64),
                 np.asarray(extra_a, dtype=np.int64)])
            r2_offs = np.concatenate(
                [np.asarray(r2_offs, dtype=np.int64),
                 np.asarray(extra_b, dtype=np.int64)])
    if len(r1_offs) == 0:
        return
    stats = nb.overlap_correct_pairs(
        batch.buf, np.asarray(r1_offs, dtype=np.int64),
        np.asarray(r2_offs, dtype=np.int64),
        AGREEMENT_CODES[oc.agreement], DISAGREEMENT_CODES[oc.disagreement])
    add_native_overlap_stats(oc.stats, stats)
