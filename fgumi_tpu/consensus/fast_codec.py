"""Vectorized CODEC host prep over RecordBatch inputs.

Replaces CodecConsensusCaller.prepare()'s record-level work (phases 1-5 of
codec_caller.rs:589-836) with batch arrays. Every group of a batch is paired
by read name in one pass. Where every paired primary is a single-op M CIGAR,
clip amounts, adjusted positions, overlap geometry and the phase checks are
closed-form arithmetic; any other CIGAR shape (soft clips, indels, hard
clips) goes through one native pass a batch (`nb.codec_cigar_prepare`: the
mate clip from the mate's own CIGAR, the clipped CIGAR, codec's per-strand
most-common-alignment filter on it, the longest strands by clipped reference
length and the phase check), and both join one pack layout whose SourceRead
conversion is one native pack. Only a molecule a batch boundary cuts, a
group whose read names collide in the hash and a group that downsamples
leave the arrays, in stream order (sharing the caller's stats and downsample
RNG stream): a group with a CIGAR through the classic prepare() unchanged.

A batch's molecules travel from the prepare to `nb.build_codec_records` as
columns (`_Molecules`: one array a field, one entry a molecule or a strand),
so the verdicts, the strand dispatch, the gates and the names / MI / RX of
the records are whole-array passes, and a strand's bases, qualities, depths
and errors go from the row of the result matrix that holds them to the
record builder through arrays made once: `nb.codec_place` writes them
oriented and padded, depths and errors as int32, and `nb.codec_combine` and
`nb.build_codec_records` read them there. A Python loop runs
only over the molecules the array passes do not cover, one at a time and in
stream order among themselves (`codec.row_molecules`): the one a batch
boundary cut, a group whose read names collide in the hash, a group that
downsamples; and over every emitted molecule under `--cell-tag`, which needs
raw records.

Stage 2 (the SS device pass, geometry finish, combine/masks, record build)
IS the classic caller's `_run_jobs` + `_finish`, so outputs are identical
by construction; tests/test_fast_codec.py asserts byte parity end to end.

`process_batch` returns one pending chunk a batch, straight after the
dispatch (`_CodecPending`, the shape of fast_duplex's `_DuplexPending`); its
`resolve()` does the rest on whichever thread `run_stages` resolves on (the
resolve workers at ``--threads 4``, the writer at 2-3, the caller inline at
0-1). On the thread that calls `process_batch`: the spans `process.decode`,
`.group`, `.prep` (and inside it `process.prep.codec_cigar`, the native
pass), `engine.codec.slow_molecule`, `.single`, `.gather` and the pack and
dispatch; what is a function of the stream and not of the batch (the carry,
the classic ``prepare()`` of a carried or fallback molecule,
``codec.molecules`` / ``.strands``, the prepare-phase rejects and the
filter's tally). Where the chunk resolves: `resolve.wait`, `device.fetch`,
`resolve.unpack` (the thresholds), `engine.codec.place`, `.combine`,
`.gates` and `resolve.serialize`. Stage 2 is a function of its batch but
for two things chunks share: the caller's `CodecStats` (locked) and its
record counter, which names a molecule whose MI value is empty
(`_EmittedOrder`). Run-report counters, by molecule:
`codec.molecules` = `.emitted` + `.rejected` (`.rejected.<reason>`),
`.slow_molecules` = `.slow_molecules.carried` + `.cigar` (why the classic
``prepare()`` took it: the carry, or a group with a CIGAR that collided or
downsampled), `.molecules.cigar` / `.reads.cigar` (the input's molecules
with a paired primary that is not one M run, and their reads),
`.molecules.cigar_vec` (those the native pass prepared),
`.row_molecules`, `.strands`, `.single_strands`,
`.place.strands` / `.place.cells` (what the native placement copied),
`.combine_cells_host`, `.duplex_bases`, `.disagreements`; by
chunk: `.stage2_batches`, `.stage2_off_thread` (docs/observability.md).
"""

import itertools
import struct
import threading

import numpy as np

from ..constants import (CODE_TO_BASE, MIN_PHRED, N_CODE, NO_CALL_BASE,
                         NO_CALL_BASE_LOWER)
from ..io.bam import (FLAG_FIRST, FLAG_MATE_REVERSE, FLAG_MATE_UNMAPPED,
                      FLAG_PAIRED, FLAG_REVERSE, FLAG_SECONDARY,
                      FLAG_SUPPLEMENTARY, FLAG_UNMAPPED)
from ..native import batch as nb
from ..observe.metrics import METRICS
from ..observe.trace import span as _span
from .codec import _ASCII_COMPLEMENT, _SS, combine_arrays
from .simple_umi import _ACGTN_UPPER, consensus_umis_batch

#: a molecule the classic ``prepare()`` returned nothing for is counted under
#: the reason of the latest phase that recorded one (the phases run in order)
_PREPARE_REASONS = ("IndelErrorBetweenStrands", "InsufficientOverlap",
                    "InsufficientReads", "MinorityAlignment",
                    "NotPrimaryFrPair", "FragmentRead")

#: simple_umi's all-equal rule as a byte table: only ``acgtn`` have an
#: uppercase image in ACGTN, every other byte passes
_RX_UPPER = np.arange(256, dtype=np.uint8)
for _a, _b in _ACGTN_UPPER.items():
    _RX_UPPER[_a] = _b

#: a strand's result codes -> the record's bases: the R1 strand as it was
#: called, the R2 strand complemented (it is also reversed; `_finish_batch`)
_BASE_OF_CODE = CODE_TO_BASE[np.minimum(np.arange(256), N_CODE)]
_COMPLEMENT_OF_CODE = _ASCII_COMPLEMENT[_BASE_OF_CODE]

#: where a strand's consensus lies after the dispatch (`_run`'s strand
#: column ``src``, an index into `_finish_batch`'s ``sources``): a row of the
#: dense batch's result matrices, a row of the single-read table pass's, or,
#: from `_SRC_ARRAYS` on, a materialised strand of its own (the single-read
#: strands of a classic-prepared molecule)
_SRC_SLOT, _SRC_SINGLE, _SRC_ARRAYS = 0, 1, 2


def _count_reject(reason, molecules=1):
    """``molecules`` whole molecules rejected for ``reason`` (run-report
    counters; the caller's own stats count reads)."""
    if molecules:
        METRICS.inc("codec.rejected", molecules)
        METRICS.inc("codec.rejected." + reason, molecules)


def _not_one_m_run(rec):
    """A paired primary record whose CIGAR is not one ``M`` run over the
    whole read: `FastCodecCaller._prepare_span`'s ``m_only`` rule, a
    record."""
    flag = rec.flag
    if not flag & FLAG_PAIRED or flag & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY):
        return False
    cigar = rec.cigar()
    return not (len(cigar) == 1 and cigar[0][0] == "M"
                and cigar[0][1] == rec.l_seq > 0)


def _ragged_arange(starts, counts):
    """``concatenate([arange(s, s + c) for s, c in zip(starts, counts)])``
    as one repeat and a running offset."""
    excl = np.cumsum(counts) - counts
    return np.repeat(starts - excl, counts) \
        + np.arange(int(counts.sum()), dtype=np.int64)


class _Molecules:
    """The prepared molecules of one batch as columns, in emission order.

    ``n_r1`` / ``n_r2`` reads a strand, ``len1`` / ``len2`` each strand's
    consensus length (its longest clipped read), ``pk0`` the molecule's
    first row in the span's pack arrays (R1 block, then R2 block),
    ``r1_neg`` / ``r2_neg``, ``length`` the fragment's consensus length,
    ``row_lo`` / ``row_hi`` the group's records in ``batch``. A molecule
    from the classic ``prepare()`` (``classic`` >= 0: its index in
    ``classic_mols``) keeps its SS jobs, MI and records in that dict.
    ``pack_rows``: the batch row of every pack row (``--cell-tag`` only).
    """

    _INT = ("n_r1", "n_r2", "len1", "len2", "pk0", "length", "row_lo",
            "row_hi", "classic")
    _BOOL = ("r1_neg", "r2_neg")
    __slots__ = _INT + _BOOL + ("classic_mols", "batch", "pack_rows")

    def __init__(self, n, lead=(), batch=None):
        """``n`` entries, the first ``len(lead)`` of them the
        classic-prepared molecules ``lead``."""
        for name in self._INT:
            setattr(self, name, np.zeros(n, dtype=np.int64))
        for name in self._BOOL:
            setattr(self, name, np.zeros(n, dtype=bool))
        self.classic[:] = -1
        self.classic_mols = []
        self.batch = batch
        self.pack_rows = None
        for i, mol in enumerate(lead):
            self.set_classic(i, mol)

    def __len__(self):
        return len(self.classic)

    def set_classic(self, i, mol):
        """Entry ``i`` is the classic-prepared ``mol`` (appended)."""
        self.classic[i] = len(self.classic_mols)
        self.classic_mols.append(mol)
        self.set_vec(i, -1, mol["n_r1"], mol["n_r2"],
                     mol["job_r1"].consensus_len, mol["job_r2"].consensus_len,
                     mol["r1_is_negative"], mol["r2_is_negative"],
                     mol["consensus_length"])

    def set_vec(self, i, pk0, n_r1, n_r2, len1, len2, r1_neg, r2_neg,
                length):
        self.pk0[i], self.n_r1[i], self.n_r2[i] = pk0, n_r1, n_r2
        self.len1[i], self.len2[i] = len1, len2
        self.r1_neg[i], self.r2_neg[i] = r1_neg, r2_neg
        self.length[i] = length

    def take(self, idx):
        """The molecules ``idx`` (an index array), same batch."""
        out = _Molecules(0, batch=self.batch)
        for name in self._INT + self._BOOL:
            setattr(out, name, getattr(self, name)[idx])
        out.classic_mols, out.pack_rows = self.classic_mols, self.pack_rows
        return out


class _EmittedOrder:
    """The caller's record counter over chunks that resolve in any order.

    The counter names a molecule whose MI value is empty by its place in
    the output, and a chunk knows how many molecules it emits only after
    its gates. So chunks are numbered as they are made, each publishes its
    emitted count when its gates are done, and a chunk that has to name a
    molecule by the counter waits until every earlier chunk has published.
    Earlier chunks never wait on later ones and every resolver takes
    chunks in the order they were made, so the earliest unpublished chunk
    always runs; a chunk that fails or is dropped publishes too
    (`_CodecPending`), so a failed run drains instead of hanging a waiter.
    """

    def __init__(self, caller):
        self._caller = caller
        self._cond = threading.Condition()
        self._ahead = {}  # chunk -> its count, published before an earlier one
        self._next = 0    # every chunk below it is in caller._counter
        #: the number of the chunk being made (the processing thread's)
        self.issue = itertools.count().__next__

    def publish(self, serial, emitted, wait=False):
        """Chunk ``serial`` emits ``emitted`` molecules. ``wait``: only
        after every earlier chunk has published, returning the counter
        before this chunk's first molecule."""
        with self._cond:
            try:
                if wait:
                    self._cond.wait_for(lambda: self._next == serial)
            finally:  # an interrupted wait publishes too
                before = self._caller._counter
                self._ahead[serial] = emitted
                while self._next in self._ahead:
                    self._caller._counter += self._ahead.pop(self._next)
                    self._next += 1
                self._cond.notify_all()
            return before


class _CodecPending:
    """One batch between its dispatch and its bytes.

    process_batch returns it as soon as the multi-read strands are packed
    and handed to the feeder (or kept for the host engine; a batch with
    none dispatches nothing and resolves the same way); resolve() does the
    rest on whichever thread run_stages resolves on: the fetch and unpack,
    the thresholds, stage 2 and the serialization. A chunk dropped
    unresolved (a failed run) hands its dispatch back, so the feeder slot
    is not leaked, and publishes no molecules, so a later chunk does not
    wait for it."""

    __slots__ = ("_finish", "_discard", "_made_on", "_order", "_serial",
                 "_published")

    def __init__(self, finish, discard, order):
        self._finish = finish
        self._discard = discard  # None: nothing in flight on the device
        self._made_on = threading.get_ident()
        self._order = order
        self._serial = order.issue()
        self._published = False

    def publish(self, emitted, wait=False):
        """`_EmittedOrder.publish` for this chunk (once: `resolve` and
        `__del__` publish 0 for a chunk that has not)."""
        self._published = True
        return self._order.publish(self._serial, emitted, wait)

    def resolve(self) -> bytes:
        finish, self._finish, self._discard = self._finish, None, None
        METRICS.inc("codec.stage2_batches")
        if threading.get_ident() != self._made_on:
            METRICS.inc("codec.stage2_off_thread")
        try:
            return b"".join(finish(self))
        finally:
            if not self._published:
                self.publish(0)

    def __del__(self):
        if self._discard is not None:
            self._discard()
        if not self._published:
            self.publish(0)


class FastCodecCaller:
    """Batch CODEC engine wrapping a CodecConsensusCaller."""

    def __init__(self, caller, tag: bytes = b"MI", mesh=None):
        """`mesh`: optional jax Mesh with (dp, sp) axes — the SS device
        pass routes through the shard_map-wrapped wire kernels. None or a
        1-device mesh = the legacy single-device path, bit for bit."""
        self.caller = caller
        # device/host routing is per batch via the adaptive cost model
        # (ops/router.py; FGUMI_TPU_ROUTE / FGUMI_TPU_MAX_INFLIGHT handled
        # inside ROUTER.decide)
        self.tag = tag
        self.mesh = mesh if mesh is not None and mesh.size > 1 else None
        self._carry = None  # (mi string, [RawRecord])
        self._order = _EmittedOrder(caller)
        self._records_lock = threading.Lock()  # --cell-tag's builder

    # ----------------------------------------------------------------- driver

    def process_batch(self, batch, final: bool = False):
        """Consume one RecordBatch -> pending chunks (`_CodecPending`: at
        most one, and the flush's when ``final``), to be resolved in the
        order they were made (`fast.resolve_chunk`).

        Each chunk's bytes carry its records' block_size prefixes
        (BamWriter.write_serialized framing)."""
        n = batch.n
        if n == 0:
            return self.flush() if final else []
        buf = batch.buf
        with _span("process.decode", rusage=True):
            # Z/H-typed presence gate matches the classic get_str-based
            # grouping
            mi_off, mi_len, _ = batch.tag_locs_str(self.tag)
            if (mi_off < 0).any():
                bad = int(np.nonzero(mi_off < 0)[0][0])
                raise ValueError(
                    f"record {batch.name(bad)!r} missing "
                    f"{self.tag.decode()} tag")
        with _span("process.group", rusage=True):
            starts = nb.group_starts(buf, np.ascontiguousarray(mi_off),
                                     mi_len)
            bounds = np.append(starts, n)
            n_total = len(bounds) - 1

            first_mi = batch.tag_bytes(self.tag, int(bounds[0])).decode()
            merge_carry = self._carry is not None \
                and self._carry[0] == first_mi
            if merge_carry:
                self._carry[1].extend(
                    batch.raw_records(np.arange(bounds[0], bounds[1])))

            g0 = 1 if merge_carry else 0
            g1 = n_total if final else max(n_total - 1, g0)
            deferred = None
            if not final and n_total - 1 >= g0:
                lo, hi = bounds[n_total - 1], bounds[n_total]
                deferred = (batch.tag_bytes(self.tag, int(lo)).decode(),
                            batch.raw_records(np.arange(lo, hi)))

        lead = []  # the carried molecule leaves first
        if self._carry is not None:
            if (not merge_carry) or final or n_total >= 2:
                mi, recs = self._carry
                self._carry = None
                mol = self._prepare_slow(recs, mi, "carried")
                if mol is not None:
                    lead.append(mol)

        codes_pk = quals_pk = None
        if g1 > g0:
            METRICS.inc("codec.molecules", g1 - g0)
            with _span("process.prep", rusage=True):
                mols, codes_pk, quals_pk = self._prepare_span(
                    batch, bounds, g0, g1, lead)
        else:
            mols = _Molecules(len(lead), lead)

        if deferred is not None:
            self._carry = deferred

        out = self._run(mols, codes_pk, quals_pk)
        if final:
            out.extend(self.flush())
        return out

    def flush(self):
        """The carried molecule's chunk (pending, as `process_batch`'s)."""
        if self._carry is None:
            return []
        mi, recs = self._carry
        self._carry = None
        mol = self._prepare_slow(recs, mi, "carried")
        return self._run(_Molecules(1, [mol]) if mol is not None
                         else _Molecules(0))

    def _prepare_slow(self, records, mi, cause):
        """One molecule through the classic ``prepare()`` (the semantic
        reference), counted under ``codec.slow_molecules.<cause>``:
        ``carried``, the one a batch boundary cut, or ``cigar``, a group of
        the vectorized span (already in ``codec.molecules`` and
        ``.molecules.cigar``) with a CIGAR other than one M run whose names
        collide in the hash or whose pairs downsample."""
        METRICS.inc("codec.slow_molecules")
        METRICS.inc("codec.slow_molecules." + cause)
        METRICS.inc("codec.row_molecules")
        if cause == "carried":
            METRICS.inc("codec.molecules")
            if any(_not_one_m_run(rec) for rec in records):
                METRICS.inc("codec.molecules.cigar")
                METRICS.inc("codec.reads.cigar", len(records))
        before = self._prepare_rejects()
        with _span("engine.codec.slow_molecule", rusage=True):
            mol = self.caller.prepare(records, umi=mi)
        if mol is None:
            self._count_prepare_reject(before)
        return mol

    def _prepare_rejects(self):
        """The caller's reads rejected so far under each prepare-phase
        reason. Only the processing thread records those; stage 2's own
        reasons, which a resolving thread may be adding meanwhile, are not
        read."""
        reasons = self.caller.stats.rejection_reasons
        return [reasons.get(r, 0) for r in _PREPARE_REASONS]

    def _count_prepare_reject(self, before):
        """Count one molecule a per-molecule prepare returned nothing for,
        under the latest phase's reason among those that grew since
        ``before`` (`_prepare_rejects` then)."""
        grew = [r for r, was, now in zip(_PREPARE_REASONS, before,
                                         self._prepare_rejects())
                if now > was]
        _count_reject(grew[0] if grew else "NoUsableReads")

    def _run(self, mols, codes_pk=None, quals_pk=None):
        """One SS device dispatch -> the batch's pending chunk (a list of
        one; none for no molecules), whose resolve() is the batched finish.

        Vec-prepared molecules (strand rows resident in the pack arrays)
        land in the dense layout via ONE gather from codes_pk/quals_pk —
        the same decide/submit_dense/thresholds sequence as
        VanillaConsensusCaller._run_jobs, minus the per-read row repack.
        Classic-prepared molecules (carry/fallback ConsensusJobs) repack
        their few rows into the same layout, so every batch costs exactly
        one device execution.

        The strands are columns of 2M entries, strand ``2 * i + side`` of
        molecule ``i``: ``cnt`` reads, ``slen`` consensus length, ``b0``
        first pack row; the dispatch fills ``src`` (a ``_SRC_*``) and
        ``srow`` (the row in that source).
        """
        from ..ops import oracle

        caller = self.caller
        ss = caller.ss
        M = len(mols)
        if M == 0:
            return []

        def strands(r1_col, r2_col):
            col = np.empty(2 * M, dtype=np.int64)
            col[0::2] = r1_col
            col[1::2] = r2_col
            return col

        cnt = strands(mols.n_r1, mols.n_r2)
        slen = strands(mols.len1, mols.len2)
        b0 = strands(mols.pk0, mols.pk0 + mols.n_r1)
        src = np.full(2 * M, _SRC_SLOT, dtype=np.int32)
        srow = np.zeros(2 * M, dtype=np.int64)
        vec = np.repeat(mols.classic < 0, 2)
        sv = np.nonzero(vec & (cnt == 1))[0]  # single-read strands
        mv = np.nonzero(vec & (cnt > 1))[0]   # multi-read strands
        # carry/fallback molecules: the same dispatch, rows repacked below
        # (a separate _run_jobs call would cost a second device execution
        # on essentially every streamed batch)
        classic_multi = []   # (strand, job)
        classic_single = []  # (strand, job)
        for i in np.nonzero(mols.classic >= 0)[0]:
            m = mols.classic_mols[mols.classic[i]]
            for s, job in enumerate((m["job_r1"], m["job_r2"])):
                (classic_single if len(job.codes) == 1
                 else classic_multi).append((2 * int(i) + s, job))
        METRICS.inc("codec.strands", 2 * M)
        METRICS.inc("codec.single_strands", len(sv) + len(classic_single))

        single_mats = None
        arrays = []  # materialised strands: (bases, quals, depths, errors)
        if len(sv) or classic_single:
            # the single-read strands never leave the host: one table pass
            # over their pack rows (elementwise, so each strand's row is
            # what a call of its own would give), a second slot source
            with _span("engine.codec.single", rusage=True):
                min_q = ss.options.min_consensus_base_quality
                if len(sv):
                    rows = b0[sv]
                    single_mats = oracle.single_read_consensus(
                        codes_pk[rows], quals_pk[rows], ss.tables, min_q)
                    src[sv] = _SRC_SINGLE
                    srow[sv] = np.arange(len(sv))
                for s, job in classic_single:
                    cl = job.consensus_len
                    res = oracle.single_read_consensus(
                        job.codes[0][:cl], job.quals[0][:cl], ss.tables,
                        min_q)
                    src[s], slen[s] = _SRC_ARRAYS + len(arrays), len(res[0])
                    arrays.append(res)

        pending = None
        if len(mv) or classic_multi:
            with _span("engine.codec.gather", rusage=True):
                cnt_v = cnt[mv]
                counts = np.concatenate(
                    [cnt_v, np.array([len(job.codes)
                                      for _, job in classic_multi],
                                     dtype=np.int64)])
                max_cl = max(([int(slen[mv].max())] if len(mv) else [])
                             + [job.consensus_len
                                for _, job in classic_multi])
                L_max = max(-(-max_cl // 16) * 16, 16)
                n_vec_rows = int(cnt_v.sum())
                N = int(counts.sum())
                codes2d = np.full((N, L_max), N_CODE, dtype=np.uint8)
                quals2d = np.zeros((N, L_max), dtype=np.uint8)
                if len(mv):
                    rows_idx = _ragged_arange(b0[mv], cnt_v)
                    # pack rows are N/Q0-padded past each read's final
                    # length, so a single fancy-index gather IS the dense job
                    # layout. A carry molecule's longer reads can push L_max
                    # past the span's pack stride; vec flens never exceed the
                    # stride, so clamping the gather width keeps the tail at
                    # N/Q0.
                    wv = min(L_max, codes_pk.shape[1])
                    codes2d[:n_vec_rows, :wv] = codes_pk[rows_idx, :wv]
                    quals2d[:n_vec_rows, :wv] = quals_pk[rows_idx, :wv]
                    srow[mv] = np.arange(len(mv))
                row = n_vec_rows
                for k, (s, job) in enumerate(classic_multi):
                    srow[s] = len(mv) + k
                    for c, q in zip(job.codes, job.quals):
                        w = min(len(c), L_max)
                        codes2d[row, :w] = c[:w]
                        quals2d[row, :w] = q[:w]
                        row += 1
            # adaptive offload: host f64 engine or full-column wire,
            # decided per batch (ops/kernel.py route_and_call_segments'
            # decision; the batch is resolved later, where its chunk is)
            from ..ops.router import ROUTER

            kernel = ss.kernel
            route = "host"
            if not kernel.host_mode():
                route = ROUTER.decide_batch(
                    kernel, N, len(counts), L_max,
                    devices=self.mesh.size if self.mesh is not None else 1)
            pending = kernel.submit_dense(lambda: (codes2d, quals2d), counts,
                                          route, mesh=self.mesh)

        def finish(chunk):
            slot_mats = None
            if pending is not None:
                w, q_, d, e = pending.resolve()
                # thresholds are elementwise: one vectorized pass over the
                # whole (F, L) batch, then per-slot length slicing (positions
                # past a slot's consensus length are computed and discarded)
                with _span("resolve.unpack", rusage=True):
                    b_all, q_all = oracle.apply_consensus_thresholds(
                        w, q_, d, ss.options.min_reads,
                        ss.options.min_consensus_base_quality)
                slot_mats = (b_all, q_all, d, e)
            return self._finish_batch(mols, src, srow, slen,
                                      [slot_mats, single_mats] + arrays,
                                      chunk)

        return [_CodecPending(
            finish, pending.discard if pending is not None else None,
            self._order)]

    def _finish_batch(self, mols, src, srow, slen, sources, chunk):
        """Batched `_finish` (codec.py:527-568): strand geometry lands in
        concatenated position arrays, the duplex combine + quality-mask math
        of codec.py:360-456 runs once over all molecules (each molecule's
        slice is element-identical to the per-molecule version), and the
        records serialize in one native pass. Stats totals match the
        sequential path.

        Runs on whichever thread resolves ``chunk``, several batches at
        once on a resolve pool: everything it writes is its own batch's but
        the tallies (`CodecStats`' locked methods, METRICS) and the record
        counter, which ``chunk``
        publishes to once the gates have said how many molecules it emits.

        A strand's result is row ``srow`` of source ``src`` in ``sources``:
        the dense batch's (F, L) result matrices, the single-read table
        pass's (n_single, stride), or one materialised strand of a carry or
        fallback molecule (one or two a batch). `nb.codec_place` copies a
        side's strands from there to the padded per-molecule arrays."""
        from .vanilla import I16_MAX

        caller = self.caller
        st, opts = caller.stats, caller.options
        with _span("engine.codec.place", rusage=True):
            failed = (mols.length < slen[0::2]) | (mols.length < slen[1::2])
            n_failed = int(failed.sum())
            if n_failed:
                st.reject("ClipOverlapFailed",
                          int((mols.n_r1 + mols.n_r2)[failed].sum()))
                _count_reject("ClipOverlapFailed", n_failed)
                keep = np.nonzero(~failed)[0]
                if not len(keep):
                    return []
                mols = mols.take(keep)
                sidx = np.repeat(2 * keep, 2)
                sidx[1::2] += 1
                src, srow, slen = src[sidx], srow[sidx], slen[sidx]
            J = len(mols)
            Ls, r1n, r2n = mols.length, mols.r1_neg, mols.r2_neg
            offs = np.zeros(J + 1, dtype=np.int64)
            np.cumsum(Ls, out=offs[1:])
            T = int(offs[-1])

            # The strands land in the RECORD's orientation, not the
            # fragment's (codec.py _finish orients both strands onto the
            # forward fragment, combines, and reverse-complements the result
            # of an R1-negative molecule): the R1 strand as it was called,
            # from the record's first base; the R2 strand reverse-
            # complemented, from the end its overlap geometry gives. The
            # combine, the gates' sums and the quality masks are positionwise
            # or symmetric, so every base is what the two steps would give
            # and the serializer copies nothing around.
            #
            # One native ragged copy a side, both matrix sets and the
            # materialised strands in the one call: it writes the pad too
            # (lowercase n / Q0 / depth 0 / errors 0), and depths and errors
            # come out capped at I16_MAX as the int32 the combine and the
            # record builder read.
            def place(side, table):
                base = offs[:-1]
                if side:
                    base = base + np.where(r1n ^ r2n, Ls - slen[1::2], 0)
                return nb.codec_place(
                    sources, src[side::2], srow[side::2], slen[side::2],
                    base, offs, table, bool(side), I16_MAX,
                    NO_CALL_BASE_LOWER)

            b1, q1, d1, e1 = place(0, _BASE_OF_CODE)
            b2, q2, d2, e2 = place(1, _COMPLEMENT_OF_CODE)
            METRICS.inc("codec.place.strands", 2 * J)
            METRICS.inc("codec.place.cells", int(slen.sum()))

        # ---- duplex combine, one pass over the concatenated strands: the
        # native C pass, or numpy without the library — byte-identical (the
        # classic combine_arrays stays the oracle)
        with _span("engine.codec.combine", rusage=True):
            if nb.available():
                res = nb.codec_combine(
                    b1, b2, q1, q2, d1, d2, e1, e2, MIN_PHRED, NO_CALL_BASE,
                    NO_CALL_BASE_LOWER, I16_MAX)
            else:
                res = combine_arrays(b1, b2, q1, q2, d1, d2, e1, e2)
            METRICS.inc("codec.combine_cells_host", T)
        cb, cq, cd, ce, both, disag = res

        with _span("engine.codec.gates", rusage=True):
            # per-molecule disagreement thresholds (recoverable rejects)
            def seg_sum(x):
                # one pass and no T-long running sum (a molecule with no
                # position has no segment to reduce)
                some = Ls > 0
                sums = np.zeros(J, np.int64)
                sums[some] = np.add.reduceat(x, offs[:-1][some],
                                             dtype=np.int64)
                return sums

            duplex_bases = seg_sum(both)
            disagreements = seg_sum(disag)
            n_duplex, n_disag = int(duplex_bases.sum()), \
                int(disagreements.sum())
            st.add(consensus_duplex_bases_emitted=n_duplex,
                   duplex_disagreement_base_count=n_disag)
            METRICS.inc("codec.duplex_bases", n_duplex)
            METRICS.inc("codec.disagreements", n_disag)
            nz = duplex_bases > 0
            bad = np.zeros(J, dtype=bool)
            if opts.max_duplex_disagreements is not None:
                bad |= nz & (disagreements > opts.max_duplex_disagreements)
            rate = np.divide(disagreements.astype(np.float64), duplex_bases,
                             out=np.zeros(J, np.float64), where=nz)
            bad |= nz & (rate > opts.max_duplex_disagreement_rate)

            # ---- quality masks (codec.py _mask_quals: outer bands, then SS)
            if (opts.outer_bases_length > 0
                    and opts.outer_bases_qual is not None) \
                    or opts.single_strand_qual is not None:
                if opts.outer_bases_length > 0 \
                        and opts.outer_bases_qual is not None:
                    pos = np.arange(T, dtype=np.int64) \
                        - np.repeat(offs[:-1], Ls)
                    l_rep = np.repeat(Ls, Ls)
                    n_rep = np.minimum(opts.outer_bases_length, l_rep)
                    cq[(pos < n_rep) | (pos >= l_rep - n_rep)] = \
                        opts.outer_bases_qual
                if opts.single_strand_qual is not None:
                    is_n = lambda x: ((x == NO_CALL_BASE)
                                      | (x == NO_CALL_BASE_LOWER))
                    cq[is_n(b1) | is_n(b2)] = opts.single_strand_qual

            n_bad = int(bad.sum())
            if n_bad:
                st.reject("HighDuplexDisagreement",
                          int((mols.n_r1 + mols.n_r2)[bad].sum()))
                st.add(consensus_reads_rejected_hdd=n_bad)
                _count_reject("HighDuplexDisagreement", n_bad)
            good = np.nonzero(~bad)[0]
        # ---- record serialization
        if not len(good):
            return []
        METRICS.inc("codec.emitted", len(good))
        with _span("resolve.serialize", rusage=True):
            serialize = self._serialize_native if opts.cell_tag is None \
                else self._serialize_records
            return serialize(chunk, mols, good, offs, Ls, (cb, cq, cd, ce),
                             (b1, q1, d1, e1), (b2, q2, d2, e2))

    def _serialize_records(self, chunk, mols, good, offs, Ls, cons, side_a,
                           side_b):
        """``--cell-tag`` (rare): the cell tag needs each molecule's raw
        source records, so every emitted molecule builds through the classic
        RecordBuilder path, one at a time, numbered from the records of
        every earlier chunk."""
        caller = self.caller
        batch = mols.batch
        out = []
        counter0 = chunk.publish(len(good), wait=True)
        for number, j in enumerate(good, counter0 + 1):
            sl = slice(int(offs[j]), int(offs[j] + Ls[j]))
            n_r1, n_r2 = int(mols.n_r1[j]), int(mols.n_r2[j])
            if mols.classic[j] >= 0:
                m = mols.classic_mols[mols.classic[j]]
                umi, records, source_raws = m["umi"], m["records"], \
                    m["source_raws"]
            else:
                rows = range(int(mols.row_lo[j]), int(mols.row_hi[j]))
                umi = batch.tag_bytes(self.tag, rows.start).decode()
                records = batch.raw_records(rows)
                p0 = int(mols.pk0[j])
                source_raws = [records[int(r) - rows.start] for r in
                               mols.pack_rows[p0:p0 + n_r1 + n_r2]]

            def ss_of(arrs, count):
                return _SS(*(a[sl] for a in arrs), count)

            # rx_umis=None: the RX consensus scans the group's records
            with self._records_lock:  # the caller has one RecordBuilder
                rec = caller._build_record(
                    ss_of(cons, n_r1 + n_r2), ss_of(side_a, n_r1),
                    ss_of(side_b, n_r2), umi, source_raws, records,
                    number=number)
            out.append(struct.pack("<I", len(rec)) + rec)
        return out

    def _serialize_native(self, chunk, mols, good, offs, Ls, cons, side_a,
                          side_b):
        """One native serialization pass (codec.py _build_record byte-exact).

        The arrays lie in the records' orientation already (`_finish_batch`
        placed them so), so the rows pass to C as raw addresses into them
        (the consensus depths are not a record field; its errors only feed
        the cE sum); names, MI and RX go into one blob by ragged copies from
        the batch's bytes (`_name_rx_blob`).
        """
        caller = self.caller
        st, opts = caller.stats, caller.options
        # the names first: they publish the chunk's count, which a later
        # chunk may be waiting for
        G = len(good)
        name_addr, name_len, mi_addr, mi_len, rx_addr, rx_len, keep_alive = \
            self._name_rx_blob(mols.take(good) if G < len(mols) else mols,
                               chunk)

        # the native builder reads the rows where `nb.codec_place` and the
        # combine left them, depths and errors as the int32 they were made
        # in (a copy only of what a route handed over in another form)
        u8 = lambda x: np.ascontiguousarray(x, dtype=np.uint8)
        i32 = lambda x: np.ascontiguousarray(x, dtype=np.int32)
        cb, cq, ce = u8(cons[0]), u8(cons[1]), i32(cons[3])
        b1, q1, a_d, a_e = u8(side_a[0]), u8(side_a[1]), i32(side_a[2]), \
            i32(side_a[3])
        b2, q2, b_d, b_e = u8(side_b[0]), u8(side_b[1]), i32(side_b[2]), \
            i32(side_b[3])

        og = offs[:-1][good]
        wire, rec_end = nb.build_codec_records(
            cb.ctypes.data + og, cq.ctypes.data + og,
            ce.ctypes.data + 4 * og,
            b1.ctypes.data + og, q1.ctypes.data + og,
            a_d.ctypes.data + 4 * og, a_e.ctypes.data + 4 * og,
            b2.ctypes.data + og, q2.ctypes.data + og,
            b_d.ctypes.data + 4 * og, b_e.ctypes.data + 4 * og,
            Ls[good], name_addr, name_len, mi_addr, mi_len, rx_addr, rx_len,
            caller.read_group_id.encode(), FLAG_UNMAPPED,
            opts.produce_per_base_tags)
        del keep_alive
        st.add(consensus_reads_generated=G)
        return [wire]  # records carry their block_size prefixes

    def _name_rx_blob(self, mols, chunk):
        """Names, MI and RX of the emitted molecules ``mols`` as addresses
        for `nb.build_codec_records`: ``(name_addr, name_len, mi_addr,
        mi_len, rx_addr, rx_len, keep_alive)``; ``mi_len`` -1 = no MI tag,
        ``rx_addr`` 0 = no RX tag.

        One ragged copy (`nb.concat_spans`) lays down ``prefix:MI`` a
        molecule from the batch's bytes (the MI tag's value is the name's
        tail: no second copy; the counter's digits where the MI is empty)
        and each molecule's first RX. The RX consensus follows
        `consensus_umis_batch`'s own trivial cases on bytes: one RX is taken
        as it is; rows whose RX byte ranges all equal the first take it
        through the ACGTN uppercase table. Only a molecule whose RX values
        differ, or hold a byte past ASCII (``decode(errors="replace")``
        rewrites it), or that came from the classic prepare with its own
        records, goes to `consensus_umis_batch` as strings."""
        caller = self.caller
        G = len(mols)
        batch = mols.batch
        vec = mols.classic < 0
        kv = np.nonzero(vec)[0]
        head = (caller.prefix + ":").encode()
        texts = bytearray()  # made in Python: counter names, a carry's MI
        # name tail: (source, offset, length) a molecule; source 1 = the
        # batch's bytes, 2 = ``texts``
        t_src = np.ones(G, dtype=np.int32)
        t_off = np.zeros(G, dtype=np.int64)
        t_len = np.zeros(G, dtype=np.int32)
        if len(kv):
            mo, ml, _ = batch.tag_locs_str(self.tag)
            first_rows = mols.row_lo[kv]
            t_off[kv] = mo[first_rows]
            t_len[kv] = ml[first_rows]
        strings = {}  # molecule -> its RX values as the classic path reads
        for k in np.nonzero(~vec)[0]:
            m = mols.classic_mols[mols.classic[k]]
            mi = (m["umi"] or "").encode()
            t_src[k], t_off[k], t_len[k] = 2, len(texts), len(mi)
            texts += mi
            strings[int(k)] = [u for u in (r.get_str(b"RX")
                                           for r in m["records"]) if u]
        mi_len = np.where(t_len > 0, t_len, -1).astype(np.int32)
        # the counter's names need every earlier chunk's count: wait for
        # them only where this chunk has such a name
        unnamed = np.nonzero(t_len == 0)[0]
        counter0 = chunk.publish(G, wait=len(unnamed) > 0)
        for k in unnamed:
            digits = str(counter0 + int(k) + 1).encode()
            t_src[k], t_off[k], t_len[k] = 2, len(texts), len(digits)
            texts += digits

        # RX: every vec molecule's present rows are consecutive entries of
        # ``present_rows`` (a group's records are consecutive)
        first_rx = np.zeros(0, dtype=np.int64)  # a batch row a molecule
        kv_rx = kv[:0]       # vec molecules with an RX taken from bytes
        upper = np.zeros(0, dtype=bool)
        if len(kv):
            ro, rl, _ = batch.tag_locs_str(b"RX")
            present = (ro >= 0) & (rl > 0)
            present_rows = np.nonzero(present)[0]
            rank = np.zeros(len(present) + 1, dtype=np.int64)
            np.cumsum(present, out=rank[1:])
            r0 = rank[mols.row_lo[kv]]
            n_rx = rank[mols.row_hi[kv]] - r0
            some = n_rx > 0
            kv_rx, r0, n_rx = kv[some], r0[some], n_rx[some]
            first_rx = present_rows[r0]
            upper = n_rx > 1
            same = np.ones(len(kv_rx), dtype=bool)
            if upper.any():
                rows = present_rows[_ragged_arange(r0[upper], n_rx[upper])]
                heads = np.repeat(first_rx[upper], n_rx[upper])
                same[upper] = np.minimum.reduceat(
                    nb.ranges_equal(batch.buf, ro[rows], rl[rows], ro[heads],
                                    rl[heads]),
                    np.cumsum(n_rx[upper]) - n_rx[upper])
            for k in kv_rx[~same]:
                strings[int(k)] = self._rx_strings(batch, mols, int(k))
            # the verbatim values first, then those for the uppercase table
            order = np.argsort(upper[same], kind="stable")
            kv_rx, first_rx, upper = (a[same][order]
                                      for a in (kv_rx, first_rx, upper))

        # one ragged copy: [head, tail] a molecule, then the RX values
        n_rx_spans = len(kv_rx)
        sid = np.empty(2 * G + n_rx_spans, dtype=np.int32)
        off = np.empty(len(sid), dtype=np.int64)
        ln = np.empty(len(sid), dtype=np.int32)
        sid[0:2 * G:2], off[0:2 * G:2], ln[0:2 * G:2] = 0, 0, len(head)
        sid[1:2 * G:2], off[1:2 * G:2], ln[1:2 * G:2] = t_src, t_off, t_len
        if n_rx_spans:
            sid[2 * G:], off[2 * G:], ln[2 * G:] = 1, ro[first_rx], \
                rl[first_rx]
        nothing = np.zeros(1, dtype=np.uint8)
        blob, at = nb.concat_spans(
            [np.frombuffer(head, dtype=np.uint8),
             batch.buf if batch is not None else nothing,
             np.frombuffer(bytes(texts), dtype=np.uint8) if texts
             else nothing], sid, off, ln)
        base = blob.ctypes.data
        name_addr = base + at[0:2 * G:2]
        name_len = (at[2:2 * G + 2:2] - at[0:2 * G:2]).astype(np.int32)
        mi_addr = np.where(mi_len >= 0, name_addr + len(head), 0)
        rx_addr = np.zeros(G, dtype=np.int64)
        rx_len = np.zeros(G, dtype=np.int32)
        if n_rx_spans:
            rx_at = at[2 * G:]
            n_verbatim = n_rx_spans - int(upper.sum())
            region = blob[rx_at[0]:rx_at[-1]]
            high = np.nonzero(region >= 0x80)[0]
            if len(high):
                # bytes past ASCII: those molecules' values as strings
                hit = np.unique(np.searchsorted(rx_at[1:] - rx_at[0], high,
                                                side="right"))
                for h in hit:
                    strings[int(kv_rx[h])] = self._rx_strings(
                        batch, mols, int(kv_rx[h]))
            tail = blob[rx_at[n_verbatim]:rx_at[-1]]
            tail[:] = _RX_UPPER[tail]
            rx_addr[kv_rx] = base + rx_at[:-1]
            rx_len[kv_rx] = np.diff(rx_at)

        keep_alive = [blob]
        if strings:
            ks = sorted(strings)
            texts = bytearray()
            cut = [0]
            for cu in consensus_umis_batch([strings[k] for k in ks]):
                texts += (cu or "").encode()
                cut.append(len(texts))
            consensi = np.frombuffer(bytes(texts) or b"\0", dtype=np.uint8)
            keep_alive.append(consensi)
            ks = np.asarray(ks, dtype=np.int64)
            rx_len[ks] = np.diff(cut)
            rx_addr[ks] = np.where(rx_len[ks] > 0, consensi.ctypes.data
                                   + np.asarray(cut[:-1], dtype=np.int64), 0)
        return name_addr, name_len, mi_addr, mi_len, rx_addr, rx_len, \
            keep_alive

    @staticmethod
    def _rx_strings(batch, mols, k):
        """The RX values of vec molecule ``k``'s records, as
        ``RawRecord.get_str`` reads them (Z/H typed, lenient decode)."""
        ro, rl, _ = batch.tag_locs_str(b"RX")
        buf = batch.buf
        return [buf[ro[r]:ro[r] + rl[r]].tobytes().decode(errors="replace")
                for r in range(int(mols.row_lo[k]), int(mols.row_hi[k]))
                if ro[r] >= 0 and rl[r] > 0]

    # ---------------------------------------------------------------- prepare

    def _prepare_span(self, batch, bounds, g0, g1, lead=()):
        """Vectorized prepare for complete groups [g0, g1), after the
        classic-prepared molecules ``lead``: the molecules as columns
        (`_Molecules`) and their pack arrays. Every group is paired by name
        in one pass (`_pair_span`); a group whose paired primaries are each
        one M run takes the closed forms (`_geometry_vec`), any other one
        native pass (`_prepare_cigar`: the mate clip, the clipped CIGAR, the
        per-strand filter, the geometry and the phase check), and their
        verdicts reject by mask. The rest (names that collide in the hash, a
        downsample) are prepared one at a time, in stream order among
        themselves: a group with a CIGAR through the classic
        ``prepare()``."""
        caller = self.caller
        st = caller.stats
        buf = batch.buf
        lo, hi = int(bounds[g0]), int(bounds[g1])
        span = np.arange(lo, hi)
        flag = batch.flag
        l_seq = batch.l_seq[lo:hi]
        nG = g1 - g0
        gb = bounds[g0:g1 + 1] - lo  # the groups' bounds within the span

        # single-op all-M CIGAR covering the whole read
        co = batch.cigar_off[lo:hi]
        v = np.zeros(len(span), dtype=np.uint32)
        for j in range(4):
            v |= buf[co + j].astype(np.uint32) << (8 * j)
        m_only = ((batch.n_cigar[lo:hi] == 1) & ((v & 0xF) == 0)
                  & ((v >> 4) == l_seq) & (l_seq > 0))
        fl = flag[lo:hi]
        paired_primary = ((fl & FLAG_PAIRED) != 0) \
            & ((fl & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY)) == 0)
        g_of_row = np.repeat(np.arange(nG), np.diff(gb))
        grp_ok = np.logical_and.reduceat(m_only | ~paired_primary, gb[:-1])
        n_cigar = nG - int(grp_ok.sum())
        if n_cigar:  # the input's molecules with a CIGAR, and their reads
            METRICS.inc("codec.molecules.cigar", n_cigar)
            METRICS.inc("codec.reads.cigar", int(np.diff(gb)[~grp_ok].sum()))

        # phases 1-2 (primary-pair formation by name) run once over the
        # whole span, then phases 3-4 as one array pass for the all-M groups
        # (_geometry_vec) and one native pass for the others
        # (_prepare_cigar); all-M groups whose names collide in the hash
        # fall back to the per-molecule pairing, downsampled ones to the
        # per-molecule geometry, and such groups with a CIGAR to the classic
        # prepare()
        pair_of_group, py_groups, by_row, geom, cig = self._pair_span(
            batch, span, g_of_row, grp_ok, m_only, fl, paired_primary)
        n_cigar_vec = n_cigar - int((by_row & ~grp_ok).sum())
        if n_cigar_vec:
            METRICS.inc("codec.molecules.cigar_vec", n_cigar_vec)

        nL = len(lead)
        mols = _Molecules(nL + nG, lead, batch)
        mols.row_lo[nL:] = bounds[g0:g1]
        mols.row_hi[nL:] = bounds[g0 + 1:g1 + 1]
        alive = np.zeros(nL + nG, dtype=bool)
        alive[:nL] = True
        # a group with no verdict yet: not in the geometry, not an exception
        unpaired = ~by_row

        # bulk pack layout of the geometry-ok groups, then of the CIGAR
        # groups', occupies [0, pk_base); per-molecule fallbacks append
        # after it
        pk_base = 0
        if geom is not None:
            pk_base = len(geom["pack0"])
            gid, n_g = geom["gid"], geom["n_g"]
            unpaired[gid] = False
            ok = geom["okg"]
            k = nL + gid[ok]
            alive[k] = True
            mols.pk0[k] = geom["pk0_seg"][ok]
            mols.n_r1[k] = mols.n_r2[k] = n_g[ok]
            mols.len1[k], mols.len2[k] = geom["m1"][ok], geom["m2"][ok]
            mols.r1_neg[k], mols.r2_neg[k] = geom["r1_neg"][ok], \
                geom["r2_neg"][ok]
            mols.length[k] = geom["consensus_length"][ok]
            # the verdicts in the phases' order; a group that downsamples
            # consumes the shared RNG stream and gets its own from its own
            # prepare, below
            by_row[gid[geom["downs"]]] = True
            open_ = ~geom["downs"]
            for reason, verdict in (
                    ("InsufficientReads", geom["small"]),
                    ("InsufficientOverlap", geom["short"]),
                    ("IndelErrorBetweenStrands", geom["indel"])):
                hit = open_ & verdict
                open_ &= ~verdict
                n_hit = int(hit.sum())
                if n_hit:
                    st.reject(reason, 2 * int(n_g[hit].sum()))
                    _count_reject(reason, n_hit)
        if cig is not None:
            unpaired[cig["gid"]] = False
            k = nL + cig["gid"][cig["ok"]]
            alive[k] = True
            n_r1, n_r2 = cig["n_r1"], cig["n_r2"]
            mols.pk0[k] = pk_base + np.cumsum(n_r1 + n_r2) - (n_r1 + n_r2)
            mols.n_r1[k], mols.n_r2[k] = n_r1, n_r2
            mols.len1[k], mols.len2[k] = cig["len1"], cig["len2"]
            mols.r1_neg[k], mols.r2_neg[k] = cig["r1_neg"], cig["r2_neg"]
            mols.length[k] = cig["length"]
            pk_base += len(cig["rows"])
        n_unpaired = int(unpaired.sum())
        if n_unpaired:
            # no surviving FR pair (the reads were counted in _pair_span)
            n_pp = int((unpaired & np.logical_or.reduceat(
                paired_primary, gb[:-1])).sum())
            _count_reject("NotPrimaryFrPair", n_pp)
            _count_reject("FragmentRead", n_unpaired - n_pp)

        pack_rows = []     # per-molecule fallback rows, after the bulk block
        pack_clips = []
        for g in np.nonzero(by_row)[0]:
            rows = np.arange(int(bounds[g0 + g]), int(bounds[g0 + g + 1]))
            if not grp_ok[g]:
                # classic prepare runs HERE, in stream order — the shared
                # downsample RNG stream must see molecules in input order
                mi = batch.tag_bytes(self.tag, int(rows[0])).decode()
                mol = self._prepare_slow(batch.raw_records(rows), mi,
                                         "cigar")
                if mol is not None:
                    mols.set_classic(nL + g, mol)
                    alive[nL + g] = True
                continue
            METRICS.inc("codec.row_molecules")
            before = self._prepare_rejects()
            if int(g) in py_groups:
                prep = self._prepare_molecule_vec(batch, rows, pack_rows,
                                                  pack_clips, pk_base)
            else:
                prep = self._finish_molecule_vec(
                    pair_of_group[int(g)], pack_rows, pack_clips, pk_base)
            if prep is None:
                self._count_prepare_reject(before)
            else:
                mols.set_vec(nL + g, *prep)
                alive[nL + g] = True

        codes_pk = quals_pk = None
        if pk_base or pack_rows:
            parts = [(np.asarray(pack_rows, dtype=np.int64),
                      np.asarray(pack_clips, dtype=np.int64))]
            if cig is not None:
                parts.insert(0, (cig["rows"], cig["clips"]))
            if geom is not None:
                parts.insert(0, (geom["pack0"], geom["clips0"]))
            rows_arr = np.concatenate([rows for rows, _clips in parts])
            clips_arr = np.concatenate([clips for _rows, clips in parts])
            row_len = batch.l_seq[rows_arr]
            stride = max(-(-int(row_len.max()) // 32) * 32, 32)
            rev = ((flag[rows_arr] & FLAG_REVERSE) != 0).astype(np.uint8)
            codes_pk, quals_pk, _ = nb.pack_reads(
                buf, np.ascontiguousarray(batch.seq_off[rows_arr]),
                np.ascontiguousarray(batch.qual_off[rows_arr]),
                row_len, rev, clips_arr.astype(np.int32), 0, stride, mode=3)
            mols.pack_rows = rows_arr

        if not alive.all():
            mols = mols.take(np.nonzero(alive)[0])
        return mols, codes_pk, quals_pk

    def _pair_span(self, batch, span, g_of_row, grp_ok, m_only, fl_span,
                   pp_span):
        """Phases 1-2 for every group of the span in one pass: primary FR
        pairing by read name (FNV hash buckets, byte-verified), then, for
        the groups whose paired primaries are each one M run (``grp_ok``),
        the clip/adjusted-position closed forms and `_geometry_vec`, and for
        the others `_prepare_cigar`, all as span-wide passes. m_only /
        fl_span / pp_span are the caller's per-span one-M-run mask, flag
        values and paired-primary mask (shared, not recomputed).

        Returns ({local_g: per-pair arrays}, {local_g needing the python
        pairing}, by_row, geometry, CIGAR groups). The second set holds
        groups where two distinct names share a hash (byte-verify failed);
        ``by_row`` marks them and the groups with a CIGAR that downsample
        (the classic ``prepare()`` takes those whole, in stream order, for
        the shared RNG stream): their stats are untouched here so the
        per-molecule path recounts them exactly.
        """
        st = self.caller.stats
        opts = self.caller.options
        flag = batch.flag
        l_seq = batch.l_seq
        pos = batch.pos
        buf = batch.buf
        nG = len(grp_ok)
        paired = (fl_span & FLAG_PAIRED) != 0
        pr = span[pp_span]
        pg = g_of_row[pp_span]

        # name buckets within each group (classic by_name first-appearance
        # dict, fast_codec _prepare_molecule_vec phase 2)
        noff = (batch.data_off[pr] + 32).astype(np.int64)
        nlen = batch.l_read_name[pr].astype(np.int32) - 1
        h = nb.hash_ranges(buf, noff, nlen)
        order = np.lexsort((np.arange(len(pr)), h, pg))
        sp, sg, sh = pr[order], pg[order], h[order]
        so, sno, snl = order, noff[order], nlen[order]
        new_b = np.ones(len(sp), dtype=bool)
        if len(sp) > 1:
            new_b[1:] = (sg[1:] != sg[:-1]) | (sh[1:] != sh[:-1])
        b_start = np.nonzero(new_b)[0]
        b_size = np.diff(np.append(b_start, len(sp)))
        # collision guard: every bucket member must byte-match its head
        head = np.repeat(b_start, b_size)
        same = nb.ranges_equal(buf, sno, snl, sno[head], snl[head])
        py_groups = set(int(g) for g in np.unique(sg[same == 0]))
        by_row = np.zeros(nG, dtype=bool)
        by_row[list(py_groups)] = True
        ok_mask = ~by_row[sg[b_start]]

        two = ok_mask & (b_size == 2)
        ia = sp[b_start[two]]
        ib = sp[b_start[two] + 1]
        bg = sg[b_start[two]]
        first_orig = so[b_start[two]]  # classic bucket order: name appearance

        # is_primary_fr_pair, vectorized (overlap.py:96-156)
        fa, fb = flag[ia], flag[ib]
        ok = ((fa | fb) & (FLAG_UNMAPPED | FLAG_MATE_UNMAPPED)) == 0
        ok &= batch.ref_id[ia] == batch.ref_id[ib]
        a_rev = (fa & FLAG_REVERSE) != 0
        ok &= a_rev != ((fb & FLAG_REVERSE) != 0)
        r = np.where(a_rev, ia, ib)
        rf = flag[r]
        ok &= batch.ref_id[r] == batch.next_ref_id[r]
        ok &= ((rf & FLAG_REVERSE) != 0) != ((rf & FLAG_MATE_REVERSE) != 0)
        start = pos[r].astype(np.int64) + 1
        mate_start = batch.next_pos[r].astype(np.int64) + 1
        rrev = (rf & FLAG_REVERSE) != 0
        # the reverse record's reference length: one M run's is its length,
        # any other CIGAR's is read from its words (`nb.ref_spans`' end is
        # pos + max(length, 1), which gives the same end below)
        ref_len = l_seq[r].astype(np.int64)
        walk = ~m_only[r - span[0]]
        if walk.any():
            rw = r[walk]
            ref_len[walk] = nb.ref_spans(buf, batch.cigar_off[rw],
                                         batch.n_cigar[rw], pos[rw]) \
                - pos[rw].astype(np.int64)
        end = start + np.maximum(ref_len - 1, 0)
        pos5 = np.where(rrev, mate_start, start)
        neg5 = np.where(rrev, end, start + batch.tlen[r].astype(np.int64))
        ok &= pos5 < neg5

        max_pairs = opts.max_reads_per_strand
        if max_pairs is not None:
            n_pairs = np.bincount(bg[ok], minlength=nG)
            by_row |= ~grp_ok & (n_pairs > max_pairs) \
                & (n_pairs >= opts.min_reads_per_strand)
        # stats for the groups resolved here (by_row groups excluded)
        counted = ~by_row[g_of_row]
        st.total_input_reads += int(counted.sum())
        frag = int((~paired & counted).sum())
        if frag:
            st.reject("FragmentRead", frag)
        odd_total = int(b_size[ok_mask & ~two & ~by_row[sg[b_start]]].sum())
        n_failed = int((~ok & ~by_row[bg]).sum())
        if odd_total or n_failed:
            st.reject("NotPrimaryFrPair", odd_total + 2 * n_failed)

        take = ok & ~by_row[bg]
        ia, ib, bg, first_orig = ia[take], ib[take], bg[take], \
            first_orig[take]
        a_first = (flag[ia] & FLAG_FIRST) != 0
        r1 = np.where(a_first, ia, ib)
        r2 = np.where(a_first, ib, ia)
        # classic pair order within a group = first appearance of the name
        po = np.lexsort((first_orig, bg))
        r1, r2, bg = r1[po], r2[po], bg[po]
        mo = grp_ok[bg]
        cig = self._prepare_cigar(batch, r1[~mo], r2[~mo], bg[~mo])
        r1, r2, bg = r1[mo], r2[mo], bg[mo]

        # clip_vs closed forms, both directions (all-M geometry)
        def clips(ra, rb):
            ms = pos[rb].astype(np.int64) + 1
            me = pos[rb].astype(np.int64) + l_seq[rb]
            p1 = pos[ra].astype(np.int64) + 1
            L = l_seq[ra].astype(np.int64)
            d = ms - p1
            c_rev = np.where((p1 <= ms) & (d < L), d, 0)
            end1 = p1 - 1 + L
            bp = np.where((me < p1) | (me >= p1 + L), 0, me - p1 + 1)
            c_fwd = np.where(end1 >= me, np.maximum(L - bp, 0), 0)
            return np.where((flag[ra] & FLAG_REVERSE) != 0, c_rev, c_fwd)

        def info(rr, clip):
            rev = (flag[rr] & FLAG_REVERSE) != 0
            L = l_seq[rr].astype(np.int64)
            flen = np.maximum(L - clip, 0)
            adj = pos[rr].astype(np.int64) + 1 \
                + np.where(rev, np.minimum(clip, L), 0)
            return clip.astype(np.int64), rev, flen, adj

        c1, rev1, flen1, adj1 = info(r1, clips(r1, r2))
        c2, rev2, flen2, adj2 = info(r2, clips(r2, r1))

        arrs = (r1, c1, rev1, flen1, adj1, r2, c2, rev2, flen2, adj2)
        geom = self._geometry_vec(arrs, bg)
        # per-group pair tuples only for the groups that still take the
        # per-molecule path (downsampling consumes the shared RNG stream);
        # slicing them for every group was a measurable per-group loop
        out = {}
        if geom is not None and geom["downs"].any():
            starts, ends, gid = geom["starts"], geom["ends"], geom["gid"]
            for k in np.nonzero(geom["downs"])[0]:
                out[int(gid[k])] = tuple(a[starts[k]:ends[k]] for a in arrs)
        return out, py_groups, by_row, geom, cig

    def _prepare_cigar(self, batch, r1, r2, bg):
        """Phases 2-4 of the groups with a CIGAR other than one M run, from
        their FR pairs ``r1`` / ``r2`` (batch rows, in prepare()'s order
        within each group ``bg``): one native pass
        (`nb.codec_cigar_prepare`: the mate clip from the mate's own CIGAR,
        the clipped CIGAR and position, the per-strand filter, the longest
        strands by clipped reference length, the overlap and the phase
        check), then the verdicts' rejects, in prepare()'s reasons, and the
        filter's tally. None without pairs; else the groups ``gid``, which
        kept (``ok``), the kept ones' columns, and their kept reads as pack
        ``rows`` with their ``clips``: a molecule's R1s, then its R2s, in
        pair order."""
        P = len(bg)
        if P == 0:
            return None
        caller = self.caller
        st, opts = caller.stats, caller.options
        starts = np.nonzero(np.concatenate(([True], bg[1:] != bg[:-1])))[0]
        bounds = np.append(starts, P)
        reads = np.concatenate([r1, r2])
        with _span("process.prep.codec_cigar", rusage=True,
                   molecules=len(starts)):
            res = nb.codec_cigar_prepare(
                batch.buf, batch.cigar_off[reads], batch.n_cigar[reads],
                batch.flag[reads], batch.pos[reads], batch.l_seq[reads],
                bounds, opts.min_reads_per_strand, opts.min_duplex_length)
        tally = res["tally"]
        for i, count in enumerate(tally.tolist()):
            st.filter_tally[i] += count
        if tally[3]:
            st.reject("MinorityAlignment", int(tally[3]))
        verdict = res["verdict"]
        n_r1 = res["n_kept1"].astype(np.int64)
        n_r2 = res["n_kept2"].astype(np.int64)
        for code, reason in ((1, "InsufficientReads"),
                             (2, "InsufficientOverlap"),
                             (3, "IndelErrorBetweenStrands")):
            hit = verdict == code
            n_hit = int(hit.sum())
            if n_hit:
                st.reject(reason, int((n_r1 + n_r2)[hit].sum()))
                _count_reject(reason, n_hit)
        ok = verdict == 0
        seg = np.repeat(np.arange(len(starts)), np.diff(bounds))
        keep = (res["keep"] != 0) & np.tile(ok[seg], 2)
        order = np.argsort(np.concatenate([2 * seg, 2 * seg + 1])[keep],
                           kind="stable")
        rev = (batch.flag[reads] & FLAG_REVERSE) != 0
        return {"gid": bg[starts], "ok": ok, "n_r1": n_r1[ok],
                "n_r2": n_r2[ok], "len1": res["len1"][ok],
                "len2": res["len2"][ok], "r1_neg": rev[res["longest1"][ok]],
                "r2_neg": rev[res["longest2"][ok]],
                "length": res["length"][ok], "rows": reads[keep][order],
                "clips": res["clip"][keep][order].astype(np.int64)}

    def _geometry_vec(self, arrs, bg):
        """Phases 3-4 for EVERY paired group in one array pass: the
        per-group verdict (ok / too-small / short-overlap / indel /
        needs-per-molecule-downsample), the overlap geometry of the ok
        groups, and their bulk pack layout (r1 block then r2 block per
        group, group order) — semantically identical to running
        _finish_molecule_vec per group, which remains the reference
        implementation used by the downsample fallback."""
        P = len(bg)
        if P == 0:
            return None
        opts = self.caller.options
        (r1, c1, rev1, flen1, adj1, r2, c2, rev2, flen2, adj2) = arrs
        starts = np.nonzero(np.concatenate(([True], bg[1:] != bg[:-1])))[0]
        ends = np.append(starts[1:], P)
        gid = bg[starts]
        nseg = len(gid)
        n_g = ends - starts
        seg_of_pair = np.repeat(np.arange(nseg), n_g)

        # first-occurrence argmax of each strand's clipped lengths
        pidx = np.arange(P)
        m1 = np.maximum.reduceat(flen1, starts)
        i1 = np.minimum.reduceat(
            np.where(flen1 == m1[seg_of_pair], pidx, P), starts)
        m2 = np.maximum.reduceat(flen2, starts)
        i2 = np.minimum.reduceat(
            np.where(flen2 == m2[seg_of_pair], pidx, P), starts)

        r1_neg = rev1[i1]
        r2_neg = rev2[i2]
        L1f, L1a = flen1[i1], adj1[i1]
        L2f, L2a = flen2[i2], adj2[i2]
        Lpf = np.where(r1_neg, L2f, L1f)
        Lpa = np.where(r1_neg, L2a, L1a)
        Lnf = np.where(r1_neg, L1f, L2f)
        Lna = np.where(r1_neg, L1a, L2a)
        overlap_start = Lna
        pos_end = Lpa + np.maximum(Lpf - 1, 0)
        duplex_length = pos_end - overlap_start + 1

        def rp(adj, cl, p):
            return p - adj + 1, (adj <= p) & (p <= adj + cl - 1)

        r1s, ok1s = rp(L1a, L1f, overlap_start)
        r2s, ok2s = rp(L2a, L2f, overlap_start)
        r1e, ok1e = rp(L1a, L1f, pos_end)
        r2e, ok2e = rp(L2a, L2f, pos_end)
        pv, okp = rp(Lpa, Lpf, pos_end)
        nv, okn = rp(Lna, Lnf, pos_end)
        indel = ~(ok1s & ok2s & ok1e & ok2e) \
            | ((r1s - r2s) != (r1e - r2e)) | ~okp | ~okn
        consensus_length = pv + Lnf - nv

        small = n_g < opts.min_reads_per_strand
        max_pairs = opts.max_reads_per_strand
        downs = (n_g > max_pairs) & ~small if max_pairs is not None \
            else np.zeros(nseg, dtype=bool)
        short = duplex_length < opts.min_duplex_length
        okg = ~small & ~downs & ~short & ~indel

        # bulk pack layout for ok groups: [r1 block, r2 block] per group
        n_s = n_g[okg]
        excl = (np.concatenate(([0], np.cumsum(n_s)[:-1]))
                if len(n_s) else np.zeros(0, np.int64)).astype(np.int64)
        off = 2 * excl
        pk0_seg = np.full(nseg, -1, dtype=np.int64)
        pk0_seg[okg] = off
        total = int(2 * n_s.sum())
        sel = okg[seg_of_pair]
        within = np.arange(int(n_s.sum()), dtype=np.int64) \
            - np.repeat(excl, n_s)
        r1_t = np.repeat(off, n_s) + within
        r2_t = np.repeat(off + n_s, n_s) + within
        pack0 = np.empty(total, dtype=np.int64)
        clips0 = np.empty(total, dtype=np.int64)
        pack0[r1_t] = r1[sel]
        pack0[r2_t] = r2[sel]
        clips0[r1_t] = c1[sel]
        clips0[r2_t] = c2[sel]

        return {"gid": gid, "starts": starts, "ends": ends,
                "n_g": n_g, "small": small, "downs": downs, "short": short,
                "indel": indel, "okg": okg, "r1_neg": r1_neg,
                "r2_neg": r2_neg, "consensus_length": consensus_length,
                "pk0_seg": pk0_seg, "pack0": pack0, "clips0": clips0,
                "m1": m1, "m2": m2}

    def _finish_molecule_vec(self, pairs, pack_rows, pack_clips, pk_base=0):
        """Phases 3-5 for one group given its span-paired arrays; returns
        the molecule's columns (`_Molecules.set_vec`'s arguments, pack rows
        staged) or None with classic reject stats."""
        caller = self.caller
        st = caller.stats
        opts = caller.options
        (r1, c1, rev1, flen1, adj1, r2, c2, rev2, flen2, adj2) = pairs
        n = len(r1)
        if n < opts.min_reads_per_strand:
            st.reject("InsufficientReads", 2 * n)
            return None
        max_pairs = opts.max_reads_per_strand
        if max_pairs is not None and n > max_pairs:
            idxs = np.sort(caller._rng.permutation(n)[:max_pairs])
            (r1, c1, rev1, flen1, adj1, r2, c2, rev2, flen2, adj2) = (
                a[idxs] for a in pairs)
            n = max_pairs
        n_filtered = 2 * n

        # phase 4: overlap geometry on the longest strands (first max)
        i1, i2 = int(np.argmax(flen1)), int(np.argmax(flen2))
        r1_neg, r2_neg = bool(rev1[i1]), bool(rev2[i2])
        L1 = (int(flen1[i1]), int(adj1[i1]))
        L2 = (int(flen2[i2]), int(adj2[i2]))
        Lpos, Lneg = (L2, L1) if r1_neg else (L1, L2)
        overlap_start = Lneg[1]
        pos_end = Lpos[1] + max(Lpos[0] - 1, 0)
        duplex_length = pos_end - overlap_start + 1
        if duplex_length < opts.min_duplex_length:
            st.reject("InsufficientOverlap", n_filtered)
            return None

        def rp(i, p):
            flen, adj = i
            if adj <= p <= adj + flen - 1:
                return p - adj + 1
            return None

        r1s, r2s = rp(L1, overlap_start), rp(L2, overlap_start)
        r1e, r2e = rp(L1, pos_end), rp(L2, pos_end)
        if None in (r1s, r2s, r1e, r2e) or (r1s - r2s) != (r1e - r2e):
            st.reject("IndelErrorBetweenStrands", n_filtered)
            return None
        p = rp(Lpos, pos_end)
        n_ = rp(Lneg, pos_end)
        if p is None or n_ is None:
            st.reject("IndelErrorBetweenStrands", n_filtered)
            return None
        consensus_length = p + Lneg[0] - n_

        pk0 = pk_base + len(pack_rows)
        pack_rows.extend(r1.tolist())
        pack_clips.extend(c1.tolist())
        pack_rows.extend(r2.tolist())
        pack_clips.extend(c2.tolist())
        return (pk0, n, n, int(flen1[i1]), int(flen2[i2]), r1_neg, r2_neg,
                consensus_length)

    def _prepare_molecule_vec(self, batch, rows, pack_rows, pack_clips,
                              pk_base=0):
        """Phases 1-4 on arrays; returns the molecule's columns
        (`_Molecules.set_vec`'s arguments, pack rows staged) or None
        (rejected, reasons recorded like classic prepare)."""
        caller = self.caller
        st = caller.stats
        opts = caller.options
        flag = batch.flag
        l_seq = batch.l_seq
        pos = batch.pos
        st.total_input_reads += len(rows)

        fl = flag[rows]
        frag = int(((fl & FLAG_PAIRED) == 0).sum())
        if frag:
            st.reject("FragmentRead", frag)
        pp = rows[((fl & FLAG_PAIRED) != 0)
                  & ((fl & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY)) == 0)]
        if len(pp) == 0:
            return None

        # phase 2: first-appearance name buckets, one FR pair per template
        by_name = {}
        for k in range(len(pp)):
            by_name.setdefault(batch.name(int(pp[k])), []).append(k)
        pairs = []  # (r1_row, r2_row)
        for name, bucket in by_name.items():
            if len(bucket) != 2 or not self._is_primary_fr_pair(
                    batch, int(pp[bucket[0]]), int(pp[bucket[1]])):
                st.reject("NotPrimaryFrPair", len(bucket))
                continue
            ra, rb = int(pp[bucket[0]]), int(pp[bucket[1]])
            pairs.append((ra, rb) if flag[ra] & FLAG_FIRST else (rb, ra))
        if not pairs:
            return None
        if len(pairs) < opts.min_reads_per_strand:
            st.reject("InsufficientReads", 2 * len(pairs))
            return None

        max_pairs = opts.max_reads_per_strand
        if max_pairs is not None and len(pairs) > max_pairs:
            idxs = sorted(caller._rng.permutation(len(pairs))[:max_pairs])
            pairs = [pairs[i] for i in idxs]

        # clip + adjusted position + clipped length (all-M closed forms)
        def clip_vs(ra, rb):
            ms = pos[rb] + 1
            me = pos[rb] + l_seq[rb]
            p1 = pos[ra] + 1
            L = int(l_seq[ra])
            if flag[ra] & FLAG_REVERSE:
                if p1 <= ms:
                    d = int(ms - p1)
                    return d if d < L else 0
                return 0
            end1 = p1 - 1 + L
            if end1 >= me:
                if me < p1 or me >= p1 + L:
                    bp = 0
                else:
                    bp = int(me - p1 + 1)
                return max(L - bp, 0)
            return 0

        def info(r, clip):
            rev = bool(flag[r] & FLAG_REVERSE)
            ref_consumed = min(clip, int(l_seq[r]))
            adj = int(pos[r]) + 1 + (ref_consumed if rev else 0)
            return (r, clip, rev, max(int(l_seq[r]) - clip, 0), adj)

        r1i = []
        r2i = []
        for ra, rb in pairs:
            r1i.append(info(ra, clip_vs(ra, rb)))
            r2i.append(info(rb, clip_vs(rb, ra)))
        # phase 3 (most-common-alignment filter): single-op M CIGARs always
        # form one prefix-compatible group -> keep all, no rejects
        n_filtered = len(r1i) + len(r2i)

        # phase 4: overlap geometry on the longest strands (first max)
        cl1 = np.array([i[3] for i in r1i])
        cl2 = np.array([i[3] for i in r2i])
        L1 = r1i[int(np.argmax(cl1))]
        L2 = r2i[int(np.argmax(cl2))]
        r1_neg, r2_neg = L1[2], L2[2]
        Lpos, Lneg = (L2, L1) if r1_neg else (L1, L2)
        overlap_start = Lneg[4]
        pos_end = Lpos[4] + max(Lpos[3] - 1, 0)
        duplex_length = pos_end - overlap_start + 1
        if duplex_length < opts.min_duplex_length:
            st.reject("InsufficientOverlap", n_filtered)
            return None

        def rp(i, p):
            adj, cl = i[4], i[3]
            if adj <= p <= adj + cl - 1:
                return p - adj + 1
            return None

        r1s, r2s = rp(L1, overlap_start), rp(L2, overlap_start)
        r1e, r2e = rp(L1, pos_end), rp(L2, pos_end)
        if None in (r1s, r2s, r1e, r2e) or (r1s - r2s) != (r1e - r2e):
            st.reject("IndelErrorBetweenStrands", n_filtered)
            return None
        p = rp(Lpos, pos_end)
        n_ = rp(Lneg, pos_end)
        if p is None or n_ is None:
            st.reject("IndelErrorBetweenStrands", n_filtered)
            return None
        consensus_length = p + Lneg[3] - n_

        # stage the pack rows (r1 strand then r2 strand, pair order)
        pk0 = pk_base + len(pack_rows)
        for i in r1i:
            pack_rows.append(i[0])
            pack_clips.append(i[1])
        for i in r2i:
            pack_rows.append(i[0])
            pack_clips.append(i[1])
        return (pk0, len(r1i), len(r2i), L1[3], L2[3], r1_neg, r2_neg,
                consensus_length)

    @staticmethod
    def _is_primary_fr_pair(batch, ia, ib):
        """is_primary_fr_pair + is_fr_pair for all-M records (overlap.py:96-156)."""
        flag = batch.flag
        fa, fb = int(flag[ia]), int(flag[ib])
        if (fa | fb) & (FLAG_UNMAPPED | FLAG_MATE_UNMAPPED):
            return False
        if batch.ref_id[ia] != batch.ref_id[ib]:
            return False
        a_rev = bool(fa & FLAG_REVERSE)
        if a_rev == bool(fb & FLAG_REVERSE):
            return False
        r = ia if a_rev else ib
        rf = int(flag[r])
        if batch.ref_id[r] != batch.next_ref_id[r]:
            return False
        if bool(rf & FLAG_REVERSE) == bool(rf & FLAG_MATE_REVERSE):
            return False
        # is_fr_pair on the reverse-strand record (M-only: ref_len == l_seq)
        start = int(batch.pos[r]) + 1
        mate_start = int(batch.next_pos[r]) + 1
        if rf & FLAG_REVERSE:
            end = start + max(int(batch.l_seq[r]) - 1, 0)
            positive_5p, negative_5p = mate_start, end
        else:
            positive_5p, negative_5p = start, start + int(batch.tlen[r])
        return positive_5p < negative_5p
