"""Vectorized duplex consensus path over RecordBatch inputs.

The duplex analog of consensus/fast.py: per-record work happens natively in
batch (fgumi_tpu.native.batch), per-molecule work on numpy index slices, the
single-strand likelihood loop on the device kernel, stage-2 strand
combination as whole-batch array math, and record serialization in one
native call (fgumi_build_duplex_records).

Semantics contract: byte-identical output and identical rejection statistics
to DuplexConsensusCaller.call_groups on the same stream (reference
duplex_caller.rs:1755-2268; tested in tests/test_fast_duplex.py). Molecules
the vectorized path cannot express (FIRST|LAST-flagged reads, per-strand
downsampling, most-common-alignment filtering) fall back to the slow caller
per molecule, in stream order.
"""

import threading
from typing import NamedTuple

import numpy as np

from ..constants import MAX_PHRED, MIN_PHRED, N_CODE
from ..io.bam import (FLAG_FIRST, FLAG_LAST, FLAG_MATE_UNMAPPED, FLAG_PAIRED,
                      FLAG_REVERSE, FLAG_SECONDARY, FLAG_SUPPLEMENTARY,
                      FLAG_UNMAPPED)
from ..native import batch as nb
from ..observe.metrics import METRICS
from ..observe.trace import span as _span
from ..ops import oracle
from .fast import overlap_correct_span
from .simple_umi import _ACGTN_UPPER, consensus_umis_batch
from .vanilla import I16_MAX, R1, R2, _TYPE_FLAGS

# seg types within a molecule: (strand, read-type) -> 0..3
AB_R1, AB_R2, BA_R1, BA_R2 = 0, 1, 2, 3


def _flip_umi(value: str) -> str:
    """Dual-UMI strand reorientation (duplex_caller.rs:1226-1231)."""
    return "-".join(reversed(value.split("-")))


class OutputReads(NamedTuple):
    """A span's K output reads as columns, in output order: molecule
    order, R1 then R2 of each emitted molecule."""

    mols: np.ndarray   # int64: the read's molecule within the span
    flags: np.ndarray  # int32: _TYPE_FLAGS of R1 / R2
    kinds: np.ndarray  # int8: 2 = combined, 1 = a passed through, 0 = b
    aseg: np.ndarray   # int64: the first (or only) seg it is made of
    bseg: np.ndarray   # int64: the second seg of a combined read, else -1
    lens: np.ndarray   # int32: the read's length
    rx_a: np.ndarray   # int64: AB seg whose reads' RX values count, or -1
    rx_b: np.ndarray   # int64: BA seg (values strand-flipped), or -1


def output_read_columns(seg_map, seg_len, d16, live_mol, min_total, min_xy,
                        min_yx):
    """Which output reads a span's molecules give, as whole-array numpy
    over the ``(nG, 4)`` molecule -> seg map (duplex.py _combine_molecule
    and _has_min_reads, a molecule a row).

    Returns ``(reads, emitted, full, ab_only, ba_only)``: the columns and
    bool masks over the span's molecules. Output read R1 is made of
    (AB_R1, BA_R2) and R2 of (AB_R2, BA_R1); a molecule with one strand
    keeps that strand's two segs and is a candidate only where YX may be
    0. Within a read a seg is alive if it has a positive depth before the
    read's length (the shorter seg's where both are present): both alive
    combine, one alive passes through at its own length, and a molecule
    is emitted only if both its reads are alive and, with both strands,
    pass the min-reads gate on their output depths."""
    nG = len(seg_map)
    p = seg_map >= 0
    full = p.all(axis=1) & live_mol
    one_ok = live_mol & (min_yx == 0)
    ab_only = p[:, AB_R1] & p[:, AB_R2] & ~p[:, BA_R1] & ~p[:, BA_R2] & one_ok
    ba_only = ~p[:, AB_R1] & ~p[:, AB_R2] & p[:, BA_R1] & p[:, BA_R2] & one_ok
    cand = np.nonzero(full | ab_only | ba_only)[0]

    # the sides as taken, (n, 2) with R1 then R2: they are the RX sides
    # too, since the reference folds in the raws of BOTH segs even when
    # one strand's consensus is depth-dead (duplex.py:421-434)
    sm = seg_map[cand]
    a = np.where(ba_only[cand, None], -1, sm[:, [AB_R1, AB_R2]])
    b = np.where(ab_only[cand, None], -1, sm[:, [BA_R2, BA_R1]])
    has_a, has_b = a >= 0, b >= 0
    a0, b0 = np.maximum(a, 0), np.maximum(b, 0)

    # per-seg aliveness: one pass finds each seg's first positive-depth
    # column, and a read's check (lengths differ by pairing) is a compare
    pos_depth = d16 > 0
    first_nz = np.where(pos_depth.any(axis=1), np.argmax(pos_depth, axis=1),
                        1 << 30)
    La = np.where(has_a, seg_len[a0], 0)
    Lb = np.where(has_b, seg_len[b0], 0)
    both = has_a & has_b
    shorter = np.minimum(La, Lb)
    alive_a = has_a & (first_nz[a0] < np.where(both, shorter, La))
    alive_b = has_b & (first_nz[b0] < np.where(both, shorter, Lb))
    comb = alive_a & alive_b
    kinds = np.where(comb, 2, np.where(alive_a, 1, 0)).astype(np.int8)
    aseg = np.where(alive_a, a, b)
    bseg = np.where(comb, b, -1)
    lens = np.where(comb, shorter, np.where(alive_a, La, Lb))
    ok = (alive_a | alive_b).all(axis=1)

    # _has_min_reads on both output reads of a both-strand molecule
    # (duplex.py:304-308): the largest depth within the read's length on
    # each side, 0 for the side a passed-through read lacks
    gated = np.nonzero(ok & full[cand])[0]
    if len(gated):
        in_len = np.arange(d16.shape[1]) < lens[gated, :, None]
        na = np.where(in_len, d16[aseg[gated]], 0).max(axis=2)
        nb_ = np.where(in_len & comb[gated, :, None],
                       d16[np.maximum(bseg[gated], 0)], 0).max(axis=2)
        xy, yx = np.maximum(na, nb_), np.minimum(na, nb_)
        passes = (min_total <= xy + yx) & (min_xy <= xy) & (min_yx <= yx)
        ok[gated] = passes.all(axis=1)

    emitted = np.zeros(nG, dtype=bool)
    emitted[cand[ok]] = True
    n_out = int(ok.sum())
    reads = OutputReads(
        mols=np.repeat(cand[ok], 2),
        flags=np.tile(np.array([_TYPE_FLAGS[R1], _TYPE_FLAGS[R2]],
                               dtype=np.int32), n_out),
        kinds=kinds[ok].ravel(), aseg=aseg[ok].ravel(),
        bseg=bseg[ok].ravel(), lens=lens[ok].ravel().astype(np.int32),
        rx_a=a[ok].ravel(), rx_b=b[ok].ravel())
    return reads, emitted, full, ab_only, ba_only


class _DuplexPending:
    """One span of a duplex batch between its dispatch and its bytes.

    process_batch returns it as soon as the batch is packed and handed to
    the feeder (or kept for the host engine); resolve() does the rest on
    whichever thread run_stages resolves on: the SS fetch and unpack, the
    thresholds, stage 2 and the serialization. Stage 2 is a function of
    its batch: ordinals were reserved and the fallback molecules called at
    process time, and its tallies go through CallerStats' locked methods.
    A chunk dropped unresolved (a failed run) hands its dispatch back, so
    the feeder slot and the resident-byte accounting are not leaked."""

    __slots__ = ("_finish", "_discard", "_made_on")

    def __init__(self, finish, discard=None):
        self._finish = finish
        self._discard = discard  # None: nothing in flight on the device
        self._made_on = threading.get_ident()

    def resolve(self) -> bytes:
        finish, self._finish, self._discard = self._finish, None, None
        METRICS.inc("duplex.stage2_batches")
        if threading.get_ident() != self._made_on:
            METRICS.inc("duplex.stage2_off_thread")
        return finish()

    def __del__(self):
        if self._discard is not None:
            self._discard()


class FastDuplexCaller:
    """Batch-vectorized duplex caller wrapping a DuplexConsensusCaller.

    The wrapped caller owns options/stats/kernel and serves as the
    per-molecule fallback, so statistics and output bytes are shared across
    both paths.
    """

    def __init__(self, caller, tag: bytes = b"MI", overlap_caller=None,
                 mesh=None):
        """`mesh`: optional jax Mesh with (dp, sp) axes — multi-read SS
        segments dispatch through the shard_map-wrapped full-column wire
        kernels (same mesh compile path as the simplex caller, including
        the resident fused strand combine). None or a 1-device mesh = the
        legacy single-device path, bit for bit."""
        self.caller = caller
        self.ss = caller.ss
        self.kernel = caller.ss.kernel
        self.tag = tag
        self.overlap_caller = overlap_caller
        self.mesh = mesh if mesh is not None and mesh.size > 1 else None
        # device/host routing is per batch via the adaptive cost model
        # (ops/router.py; FGUMI_TPU_ROUTE / FGUMI_TPU_MAX_INFLIGHT handled
        # inside ROUTER.decide)
        self._carry = None  # (base_mi, [RawRecord] a, [RawRecord] b)

    # ------------------------------------------------------------------ driver

    def process_batch(self, batch, allow_unmapped: bool = False,
                      final: bool = False):
        """Consume one RecordBatch -> list of output items for
        fast.resolve_chunk: wire bytes (block_size-prefixed record runs) of
        a carried molecule, and the span's pending chunk. The molecule
        spanning the batch boundary is carried as RawRecords and processed
        via the slow path when it completes."""
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
                mo, ml = self._parse_mi(batch, idx)
        if len(idx) == 0:
            return self.flush() if final else []

        with _span("process.group", rusage=True):
            buf = batch.buf
            starts = nb.group_starts(buf, np.ascontiguousarray(mo),
                                     (ml - 2).astype(np.int32))
            bounds = np.append(starts, len(idx))
            n_total = len(bounds) - 1
            strand_b = buf[mo + ml - 1] == ord("B")  # per kept row

            def materialize(lo, hi):
                rows = idx[lo:hi]
                a = batch.raw_records(rows[~strand_b[lo:hi]])
                b = batch.raw_records(rows[strand_b[lo:hi]])
                return a, b

            first_base = self._base_mi(batch, int(idx[bounds[0]]))
            merge_carry = self._carry is not None \
                and self._carry[0] == first_base
            if merge_carry:
                a, b = materialize(bounds[0], bounds[1])
                self._carry[1].extend(a)
                self._carry[2].extend(b)

            g0 = 1 if merge_carry else 0
            g1 = n_total if final else max(n_total - 1, g0)
            deferred = None
            if not final and n_total - 1 >= g0:
                a, b = materialize(bounds[n_total - 1], bounds[n_total])
                deferred = (
                    self._base_mi(batch, int(idx[bounds[n_total - 1]])),
                    a, b)

            out = []
            if self._carry is not None:
                if (not merge_carry) or final or n_total >= 2:
                    out.extend(self._call_slow_molecule(*self._carry))
                    self._carry = None

        if g1 > g0:
            if self.overlap_caller is not None:
                with _span("process.overlap", rusage=True):
                    self._overlap_correct(batch, idx, bounds, strand_b, g0,
                                          g1)
            out.extend(self._process_molecules(batch, idx, bounds, strand_b,
                                               g0, g1))

        if deferred is not None:
            self._carry = deferred
        if final:
            out.extend(self.flush())
        return out

    def _parse_mi(self, batch, idx):
        """One native aux scan for the tags this engine reads, then the MI
        value's place in the buffer for every kept row, each checked for its
        ``/A`` or ``/B`` suffix."""
        batch.prefetch_tags([self.tag, b"MC", b"RX"])
        mi_off, mi_len, _ = batch.tag_locs(self.tag)
        mo, ml = mi_off[idx], mi_len[idx]
        if (mo < 0).any():
            bad = int(idx[np.nonzero(mo < 0)[0][0]])
            raise ValueError(
                f"record {batch.name(bad)!r} missing {self.tag.decode()} tag")
        buf = batch.buf
        ok = (ml >= 3) & (buf[mo + ml - 2] == ord("/")) \
            & ((buf[mo + ml - 1] == ord("A")) | (buf[mo + ml - 1] == ord("B")))
        if not ok.all():
            bad = int(idx[np.nonzero(~ok)[0][0]])
            mi = batch.tag_bytes(self.tag, bad).decode()
            raise ValueError(
                f"Read has MI tag {mi!r} without /A or /B suffix. Duplex "
                "consensus requires input from `group --strategy paired`, "
                "which marks the source strand.")
        return mo, ml

    def flush(self):
        if self._carry is None:
            return []
        base_mi, a, b = self._carry
        self._carry = None
        return self._call_slow_molecule(base_mi, a, b)

    def _base_mi(self, batch, i: int) -> str:
        return batch.tag_bytes(self.tag, i)[:-2].decode()

    # ------------------------------------------------------------ slow interop

    def _call_slow_molecule(self, base_mi, a_records, b_records,
                            corrected=False):
        """One molecule through DuplexConsensusCaller (the semantic
        reference). Overlap correction applies here unless the records were
        already corrected in place natively."""
        # corrected=True marks a molecule of the vectorized span (stage 2's
        # fallback set, already counted there); the others were carried
        # across a batch boundary and are seen here for the first time
        METRICS.inc("duplex.slow_molecules")
        if not corrected:
            METRICS.inc("duplex.molecules")
        with _span("engine.duplex.slow_molecule", rusage=True):
            if self.overlap_caller is not None and not corrected \
                    and a_records and b_records:
                from .overlapping import apply_overlapping_consensus

                a_records = apply_overlapping_consensus(a_records,
                                                        self.overlap_caller)
                b_records = apply_overlapping_consensus(b_records,
                                                        self.overlap_caller)
            recs = self.caller.call_groups([(base_mi, a_records, b_records)])
        if not recs:
            return []
        return [b"".join(len(r).to_bytes(4, "little") + r for r in recs)]

    # ------------------------------------------------------------ overlap corr

    def _overlap_correct(self, batch, idx, bounds, strand_b, g0, g1):
        """Per (molecule, strand) correction for molecules with both strands
        (the cmd-level `a_recs and b_recs` gate, duplex.rs has_both_strands)."""
        nG = g1 - g0
        lo, hi = bounds[g0], bounds[g1]
        g_of_row = np.repeat(np.arange(nG), np.diff(bounds[g0:g1 + 1]))
        sb = strand_b[lo:hi]
        n_b = np.bincount(g_of_row, weights=sb, minlength=nG)
        n_a = np.bincount(g_of_row, weights=~sb, minlength=nG)
        both = (n_a > 0) & (n_b > 0)
        if not both.any():
            return
        rows_ok = both[g_of_row]
        er = np.nonzero(rows_ok)[0]
        key = g_of_row[er] * 2 + sb[er]
        order = np.argsort(key, kind="stable")
        idx2 = idx[lo:hi][er[order]]
        skey = key[order]
        seg_first = np.concatenate(([True], skey[1:] != skey[:-1]))
        bounds2 = np.append(np.nonzero(seg_first)[0], len(idx2))
        overlap_correct_span(batch, idx2, bounds2, 0, len(bounds2) - 1,
                             self.overlap_caller)

    # ------------------------------------------------------------- stage 1 + 2

    def _process_molecules(self, batch, idx, bounds, strand_b, g0, g1):
        with _span("process.prep", rusage=True):
            caller = self.caller
            stats = caller.stats
            span = idx[bounds[g0]:bounds[g1]]
            nG = g1 - g0
            gb = bounds[g0:g1 + 1] - bounds[g0]
            sizes = np.diff(gb)
            g_of_row = np.repeat(np.arange(nG), sizes)
            sb = strand_b[bounds[g0]:bounds[g1]]

            flag_s = batch.flag[span]
            paired = (flag_s & FLAG_PAIRED) != 0
            first = (flag_s & FLAG_FIRST) != 0
            last = (flag_s & FLAG_LAST) != 0

            # molecule-level fallback: FIRST|LAST reads (belong to both X and Y
            # sets) and per-strand downsampling
            fallback = np.zeros(nG, dtype=bool)
            fl_both = paired & first & last
            fallback[g_of_row[fl_both]] = True
            max_rs = self.ss.options.max_reads
            if self.caller.track_rejects or self.ss.options.methylation_mode:
                # methylation needs each read's CIGAR/position context for the
                # reference annotation — the packed batch path strips it, so
                # every molecule runs the classic per-molecule path (the same
                # engineering choice as the simplex engine's _vector_ok gate)
                fallback[:] = True

            # per-row seg type (AB_R1..BA_R2); fragments and paired-but-neither
            # get -1
            t = np.full(len(span), -1, dtype=np.int8)
            r1 = paired & first
            r2 = paired & last & ~first
            t[~sb & r1] = AB_R1
            t[~sb & r2] = AB_R2
            t[sb & r1] = BA_R1
            t[sb & r2] = BA_R2

            frag = ~paired
            n_frag = np.bincount(g_of_row[frag], minlength=nG)
            n_paired = sizes - n_frag
            num_a_r1 = np.bincount(g_of_row[~sb & r1], minlength=nG)
            num_b_r1 = np.bincount(g_of_row[sb & r1], minlength=nG)
            num_xy = np.maximum(num_a_r1, num_b_r1)
            num_yx = np.minimum(num_a_r1, num_b_r1)
            gate_ok = (caller.min_total <= num_xy + num_yx) \
                & (caller.min_xy <= num_xy) & (caller.min_yx <= num_yx)

            # strand-orientation validation (duplex_caller.rs:1830-1860):
            # only for molecules with paired rows on both strands; X = AB-R1 +
            # BA-R2 and Y = AB-R2 + BA-R1 must each be strand-uniform
            n_pa = np.bincount(g_of_row[~sb & paired], minlength=nG)
            n_pb = np.bincount(g_of_row[sb & paired], minlength=nG)
            both_strands = (n_pa > 0) & (n_pb > 0)
            rev = (flag_s & FLAG_REVERSE) != 0
            is_x = (t == AB_R1) | (t == BA_R2)
            is_y = (t == AB_R2) | (t == BA_R1)
            coll = np.zeros(nG, dtype=bool)
            for setm in (is_x, is_y):
                gr = g_of_row[setm]
                rv = rev[setm]
                mn = np.full(nG, 2, dtype=np.int8)
                mx = np.full(nG, -1, dtype=np.int8)
                np.minimum.at(mn, gr, rv.astype(np.int8))
                np.maximum.at(mx, gr, rv.astype(np.int8))
                coll |= (mx - mn) > 0
            coll &= both_strands

            # native pack over all rows (clip/trim/RC/mask; fast.py discipline)
            mc_off, mc_len, _ = batch.tag_locs_str(b"MC")
            clips = nb.mate_clips(
                batch.buf, np.ascontiguousarray(batch.cigar_off[span]),
                batch.n_cigar[span], batch.flag[span], batch.ref_id[span],
                batch.pos[span], batch.next_ref_id[span], batch.next_pos[span],
                batch.tlen[span], np.ascontiguousarray(mc_off[span]),
                mc_len[span])
            stride = max(-(-int(batch.l_seq[span].max()) // 32) * 32, 32)
            codes, quals, final_len = nb.pack_reads(
                batch.buf, np.ascontiguousarray(batch.seq_off[span]),
                np.ascontiguousarray(batch.qual_off[span]), batch.l_seq[span],
                rev.astype(np.uint8), clips,
                self.ss.options.min_input_base_quality, stride)

            # seg construction over valid rows of live molecules (dead
            # molecules -- failed gates/validation -- need no conversion)
            live_mol = gate_ok & ~coll & (n_paired > 0) & ~fallback
            valid = (final_len > 0) & (t >= 0) & live_mol[g_of_row]
            er = np.nonzero(valid)[0]
            key = g_of_row[er] * 4 + t[er]
            order = np.argsort(key, kind="stable")
            vrows = er[order]
            skey = key[order]
            seg_first = np.concatenate(([True], skey[1:] != skey[:-1])) \
                if len(skey) else np.empty(0, dtype=bool)
            seg_of_row = (np.cumsum(seg_first) - 1) if len(skey) else skey
            seg_key = skey[seg_first] if len(skey) else skey
            nseg = len(seg_key)
            seg_g = seg_key >> 2
            seg_t = (seg_key & 3).astype(np.int8)
            c1 = np.bincount(seg_of_row, minlength=nseg).astype(np.int64)
            vstarts = np.concatenate(([0], np.cumsum(c1))).astype(np.int64)
            if max_rs is not None and nseg and (c1 > max_rs).any():
                fallback[seg_g[c1 > max_rs]] = True

            # alignment-filter analysis per X/Y set of each live molecule:
            # uniform CIGARs over the set's valid rows, with the mixed-strand
            # palindrome rule (fast.py _prepare_groups_vec)
            if nseg:
                self._need_filter_fallback(batch, span, vrows, g_of_row, t,
                                           fallback, nG)
            live_mol &= ~fallback

            # rejection tallies for non-fallback molecules
            vec = ~fallback
            stats.input_reads += int(sizes[vec].sum())
            n_fr = int(n_frag[vec].sum())
            if n_fr:
                stats.reject("FragmentRead", n_fr)
            gate_dead = vec & ~gate_ok & (n_paired > 0)
            if gate_dead.any():
                stats.reject("InsufficientReads",
                             int(n_paired[gate_dead].sum()))
            coll_dead = vec & gate_ok & coll
            if coll_dead.any():
                stats.reject("PotentialCollision",
                             int(n_paired[coll_dead].sum()))
            # the same tallies by molecule (run report `duplex.*`); stage 2
            # adds what it emits and what it drops
            METRICS.inc("duplex.molecules", nG)
            for reason, sel in (("fragments_only", vec & (n_paired == 0)),
                                ("insufficient_reads", gate_dead),
                                ("collision", coll_dead)):
                if sel.any():
                    METRICS.inc("duplex.rejected." + reason, int(sel.sum()))
                    METRICS.inc("duplex.rejected", int(sel.sum()))

            # molecule -> seg map for live molecules
            seg_map = np.full((nG, 4), -1, dtype=np.int64)
            if nseg:
                lm = live_mol[seg_g]
                seg_map[seg_g[lm], seg_t[lm]] = np.nonzero(lm)[0]

            # this span's ordinal range, one a molecule in stream order (the
            # simplex engine's _group_ordinal discipline): the fallback
            # calls below number themselves from it
            ord0 = caller._ordinal

            seg_len = np.zeros(nseg, dtype=np.int64)
            if nseg:
                fl = final_len[vrows]
                np.maximum.at(seg_len, seg_of_row, fl)

        # SS consensus for every seg: one kernel dispatch for multi-read
        # segs, one vectorized host pass for single-read segs
        L_max = stride
        finish_ss, discard = self._ss_consensus(codes, quals, vrows, c1,
                                                vstarts, nseg, L_max)
        slow = {}  # fallback molecule -> its wire bytes, filled below

        def finish():
            tb, tq, d16, e16, ctx = finish_ss()
            try:
                return self._stage2(
                    batch, span, gb, n_paired, slow, live_mol, seg_map,
                    seg_len, tb, tq, d16, e16, codes, vrows, vstarts, L_max,
                    ctx)
            finally:
                if ctx is not None:
                    ctx["resident"].release()

        chunk = _DuplexPending(finish, discard)
        # the per-molecule caller carries stats, the ordinal and the slow
        # path's kernel calls, none of it safe on two threads: the fallback
        # set (final before the dispatch) goes through it here, in stream
        # order, while the device works on the rest
        for g in np.nonzero(fallback)[0].tolist():
            rows = span[gb[g]:gb[g + 1]]
            sb_g = sb[gb[g]:gb[g + 1]]
            caller._ordinal = ord0 + g
            slow[g] = b"".join(self._call_slow_molecule(
                self._base_mi(batch, int(rows[0])),
                batch.raw_records(rows[~sb_g]), batch.raw_records(rows[sb_g]),
                corrected=True))
        caller._ordinal = ord0 + nG
        return [chunk]

    def _need_filter_fallback(self, batch, span, vrows, g_of_row, t, fallback,
                              nG):
        """Mark molecules whose X or Y set would engage the alignment filter."""
        tt = t[vrows]
        setid = np.where((tt == AB_R1) | (tt == BA_R2), 0, 1)
        key = g_of_row[vrows] * 2 + setid
        order = np.argsort(key, kind="stable")
        srows = vrows[order]
        skey = key[order]
        if not len(skey):
            return
        sfirst = np.concatenate(([True], skey[1:] != skey[:-1]))
        sstarts = np.append(np.nonzero(sfirst)[0], len(skey))
        set_g = skey[sfirst] >> 1
        co = batch.cigar_off
        cl = (4 * batch.n_cigar).astype(np.int32)
        firsts = srows[sstarts[:-1]]
        counts = np.diff(sstarts)
        rep_first = np.repeat(firsts, counts)
        eq = nb.ranges_equal(batch.buf, co[span[srows]], cl[span[srows]],
                             co[span[rep_first]], cl[span[rep_first]])
        uniform = np.minimum.reduceat(eq, sstarts[:-1]).astype(bool)
        rev8 = ((batch.flag[span[srows]] & FLAG_REVERSE) != 0).astype(np.uint8)
        mn = np.minimum.reduceat(rev8, sstarts[:-1])
        mx = np.maximum.reduceat(rev8, sstarts[:-1])
        mixed = (mn == 0) & (mx == 1) & (counts >= 2)
        need = ~uniform
        if need.any():
            # all-single-op-M sets (ragged read lengths) are mutually
            # prefix-compatible after simplify: the alignment filter
            # provably keeps every read, so non-uniform bytes alone do not
            # require the fallback (fast.py _prepare_groups_vec twin)
            row_sm = (batch.n_cigar[span[srows]] == 1) \
                & ((batch.buf[co[span[srows]]] & 0xF) == 0)
            set_sm = np.minimum.reduceat(
                row_sm.astype(np.uint8), sstarts[:-1]).astype(bool)
            need &= ~set_sm
        for s in np.nonzero(uniform & mixed)[0]:
            rec_i = int(span[firsts[s]])
            if batch.n_cigar[rec_i] == 1:
                continue  # single-op simplified CIGARs are palindromic
            from ..core import cigar as cigar_utils
            from .fast import FastSimplexCaller

            cig = FastSimplexCaller._decode_cigar(batch, rec_i)
            simplified = cigar_utils.simplify(cig)
            if simplified != list(reversed(simplified)):
                need[s] = True
        fallback[set_g[need]] = True

    def _ss_consensus(self, codes, quals, vrows, c1, vstarts, nseg, L_max):
        """Start all segs' single-strand consensus: the single-read host
        pass and the multi-read segs' dispatch, packed from their rows
        where they lie in ``codes`` / ``quals`` (handed to the feeder, or
        kept for the native host engine when there is no device or the
        router prices the batch host-side).

        Returns (finish, discard). finish() completes it on the resolving
        thread: thresholded bases/quals and i16-clamped depth/error arrays,
        (nseg, L_max) each, and the fused strand-combine context (None
        unless the full-column device route kept stage-1 outputs
        resident). discard() hands a wire dispatch back when finish() will
        never run (None, or a no-op on the host route, when there is
        none)."""
        opts = self.ss.options
        tb = np.zeros((nseg, L_max), dtype=np.uint8)
        tq = np.zeros((nseg, L_max), dtype=np.uint8)
        d16 = np.zeros((nseg, L_max), dtype=np.int32)
        e16 = np.zeros((nseg, L_max), dtype=np.int32)
        if not nseg:
            return (lambda: (tb, tq, d16, e16, None)), None

        single = c1 == 1
        multi = np.nonzero(~single)[0]
        METRICS.inc("duplex.single_segments", nseg - len(multi))
        METRICS.inc("duplex.multi_segments", len(multi))
        if single.any():
            with _span("engine.duplex.single", rusage=True):
                rows = vrows[vstarts[:-1][single]]
                b, q, d, e = oracle.single_read_consensus(
                    codes[rows], quals[rows], self.ss.tables,
                    opts.min_consensus_base_quality)
                tb[single] = b
                tq[single] = q
                d16[single] = np.minimum(d, I16_MAX).astype(np.int32)
                # errors are zero for single-read consensus
        if not len(multi):
            return (lambda: (tb, tq, d16, e16, None)), None
        counts_m = c1[multi]
        # vrows is in seg order: the multi-read segs' rows are a mask of it
        rows_m = vrows[np.repeat(~single, c1)]

        def finish_with(w, q_, d, e, ctx):
            with _span("resolve.unpack", rusage=True):
                b_m, q_m = oracle.apply_consensus_thresholds(
                    w, q_, d, opts.min_reads, opts.min_consensus_base_quality)
                tb[multi] = b_m
                tq[multi] = q_m
                d16[multi] = np.minimum(d, I16_MAX).astype(np.int32)
                e16[multi] = np.minimum(e, I16_MAX).astype(np.int32)
            return tb, tq, d16, e16, ctx

        route = "host"
        if not self.kernel.host_mode():
            # adaptive offload: same pricing as the simplex engine (the
            # mesh size selects its own cost-model EWMA set)
            from ..ops.router import ROUTER

            route = ROUTER.decide_batch(
                self.kernel, int(counts_m.sum()), len(multi), L_max,
                devices=self.mesh.size if self.mesh is not None else 1)
        # host: the native f64 engine computes the batch at resolve time.
        # Device: the whole multi-seg pileup crosses the link once in the
        # full-column wire layout; with the resident variant the
        # thresholded outputs stay on device for the fused strand combine.
        # One device packs the ragged rows in one native pass (fast.py
        # _pack_and_dispatch's choice). A > 1-device mesh runs the same
        # kernels shard_map-wrapped (families over dp, read rows over sp
        # with one psum) from dense rows; the resident arrays then live
        # sharded along dp and the combine's indices are mapped through
        # the shard-order gather below.
        import os

        comb_env = os.environ.get("FGUMI_TPU_DUPLEX_COMBINE",
                                  "auto").strip().lower()
        resident = None if comb_env == "host" else \
            (opts.min_reads, opts.min_consensus_base_quality)
        if self.mesh is not None:
            pending = self.kernel.submit_dense(
                lambda: (np.ascontiguousarray(codes[rows_m]),
                         np.ascontiguousarray(quals[rows_m])),
                counts_m, route, mesh=self.mesh, resident_thresholds=resident)
        else:
            pending = self.kernel.submit_ragged(
                codes, quals, rows_m, L_max, counts_m, route,
                resident_thresholds=resident)

        def resolve():
            w, q_, d, e, extras = pending.resolve(want_extras=True)
            ctx = None
            if extras["resident"] is not None:
                seg_to_multi = np.full(nseg, -1, dtype=np.int64)
                seg_to_multi[multi] = np.arange(len(multi))
                ctx = {"resident": extras["resident"],
                       "suspect": extras["suspect"],
                       "seg_to_multi": seg_to_multi,
                       "override": comb_env,
                       # mesh dispatches: multi index -> row of the
                       # shard-ordered resident arrays
                       "gather": extras["gather"]}
            return finish_with(w, q_, d, e, ctx)

        return resolve, pending.discard

    # ---------------------------------------------------------------- stage 2

    def _stage2(self, batch, span, gb, n_paired, slow, live_mol, seg_map,
                seg_len, tb, tq, d16, e16, codes, vrows, vstarts, L_max,
                combine_ctx=None) -> bytes:
        """Strand combination + serialization, molecule order preserved.

        Runs on whichever thread resolves the span's chunk, several spans
        at once on a resolve pool: everything it writes is its own batch's,
        but the tallies (CallerStats' locked methods, METRICS) and the
        locked DUPLEX_COMBINE chooser. ``slow``: the wire bytes of the
        span's fallback molecules by molecule index, called at process
        time; they are only interleaved here."""
        caller = self.caller
        stats = caller.stats

        with _span("engine.duplex.classify", rusage=True):
            reads, emitted, full, ab_only, ba_only = output_read_columns(
                seg_map, seg_len, d16, live_mol, caller.min_total,
                caller.min_xy, caller.min_yx)

            # InsufficientReads for live-but-unemitted molecules (the
            # fallthrough reject in _combine_molecule, duplex.py:361-363)
            dead = live_mol & ~emitted
            if dead.any():
                stats.reject("InsufficientReads", int(n_paired[dead].sum()))

            # what became of this span's molecules (run report `duplex.*`;
            # the fallback set is counted where the slow caller takes it)
            for name, sel in (("full", full), ("ab_only", ab_only),
                              ("ba_only", ba_only)):
                METRICS.inc("duplex." + name, int((emitted & sel).sum()))
            if dead.any():
                METRICS.inc("duplex.rejected.no_consensus", int(dead.sum()))
                METRICS.inc("duplex.rejected", int(dead.sum()))

        K = len(reads.mols)
        fast_blob = b""
        rec_end = np.zeros(0, dtype=np.int64)
        if K:
            fast_blob, rec_end = self._serialize_outputs(
                batch, span, gb, reads, tb, tq, d16, e16, codes, vrows,
                vstarts, L_max, combine_ctx)
            stats.add_consensus_reads(K)
        if not slow:
            return fast_blob
        # molecule order: each fallback molecule's bytes go in after the
        # fast records of the molecules before it (ascending by
        # construction, and none of them among the columns' molecules)
        before = np.searchsorted(reads.mols,
                                 np.fromiter(slow, np.int64, len(slow)))
        ends = np.concatenate(([0], rec_end))[before].tolist()
        parts = []
        start = 0
        for end, blob in zip(ends, slow.values()):
            parts += (fast_blob[start:end], blob)
            start = end
        parts.append(fast_blob[start:])
        return b"".join(parts)

    def _serialize_outputs(self, batch, span, gb, reads, tb, tq, d16, e16,
                           codes, vrows, vstarts, L_max, combine_ctx=None):
        """Combine + native-serialize the K fast output reads (order kept).

        The strand combine runs either as numpy (the semantic reference) or
        as the fused device stage over the stage-1 resident SS arrays
        (``combine_ctx``; ops/kernel._duplex_combine_jit) — integer-exact
        twins, chosen per batch by the adaptive cost model. Output rows
        whose inputs carry an oracle patch (suspect positions) always take
        the host combine: the resident arrays are pre-patch."""
        caller = self.caller
        kinds, aseg, lens = reads.kinds, reads.aseg, reads.lens
        K = len(kinds)
        with _span("engine.duplex.combine", rusage=True) as sp:
            out_b, out_q, out_e = self._combine_outputs(
                reads, tb, tq, e16, codes, vrows, vstarts, L_max,
                combine_ctx, sp)

        # serializer strand inputs: 'a' side = dup.ab_consensus (aseg: the
        # alive / AB side, truncated to the combined length), 'b' side =
        # ba_consensus (combined case only)
        b_present = (kinds == 2).astype(np.uint8)
        b_rows = np.where(kinds == 2, reads.bseg, 0)
        b_len = np.where(kinds == 2, lens, 0).astype(np.int32)

        def row_addrs(arr, rows):
            return arr.ctypes.data + rows * arr.shape[1] * arr.itemsize

        # RX per output read (strand-reoriented consensus, duplex.py:421-434)
        with _span("engine.duplex.rx", rusage=True):
            rx_addr, rx_len, keep_alive = self._output_rx(
                batch, span, reads.rx_a, reads.rx_b, vrows, vstarts)

        with _span("resolve.serialize", rusage=True):
            mi_off, mi_len, _ = batch.tag_locs(self.tag)
            first_rows = span[gb[reads.mols]]
            mi_addr = batch.buf.ctypes.data + mi_off[first_rows]
            # base MI, no /A|/B
            mi_l = (mi_len[first_rows] - 2).astype(np.int32)

            blob, rec_end = nb.build_duplex_records(
                row_addrs(out_b, np.arange(K)), row_addrs(out_q, np.arange(K)),
                row_addrs(out_e, np.arange(K)), lens, reads.flags,
                caller.prefix.encode(), mi_addr, mi_l,
                row_addrs(tb, aseg), row_addrs(tq, aseg),
                row_addrs(d16, aseg), row_addrs(e16, aseg), lens,
                row_addrs(tb, b_rows), row_addrs(tq, b_rows),
                row_addrs(d16, b_rows), row_addrs(e16, b_rows), b_len,
                b_present, rx_addr, rx_len, caller.read_group_id.encode(),
                caller.produce_per_base_tags)
        del keep_alive
        return blob, rec_end

    def _combine_outputs(self, reads, tb, tq, e16, codes, vrows, vstarts,
                         L_max, combine_ctx, sp):
        """The K output reads' bases, quals and errors, strand-combined
        or passed through. ``codes`` is the batch's packed rows and
        ``vrows`` the valid ones in seg order (seg s is
        ``vrows[vstarts[s]:vstarts[s + 1]]``): the error recount reads
        them in place. ``sp`` is the ``engine.duplex.combine`` span this
        runs in."""
        kinds, aseg, bseg, lens = reads.kinds, reads.aseg, reads.bseg, \
            reads.lens
        K = len(kinds)
        col = np.arange(L_max)

        out_b = np.zeros((K, L_max), dtype=np.uint8)
        out_q = np.zeros((K, L_max), dtype=np.uint8)
        out_e = np.zeros((K, L_max), dtype=np.int32)

        comb = np.nonzero(kinds == 2)[0]

        def combine_host(sel):
            """Numpy strand combine for output rows `sel` (the semantic
            reference the device stage must match bit-for-bit)."""
            ca, cb = aseg[sel], bseg[sel]
            a_b = tb[ca].astype(np.int32)
            b_b = tb[cb].astype(np.int32)
            a_q = tq[ca].astype(np.int32)
            b_q = tq[cb].astype(np.int32)
            agree = a_b == b_b
            a_wins = (~agree) & (a_q > b_q)
            b_wins = (~agree) & (b_q > a_q)
            tie = (~agree) & (a_q == b_q)
            raw_base = np.where(agree | a_wins, a_b, b_b)
            raw_qual = np.where(
                agree, np.clip(a_q + b_q, MIN_PHRED, MAX_PHRED),
                np.where(a_wins, np.clip(a_q - b_q, MIN_PHRED, MAX_PHRED),
                         np.where(b_wins, np.clip(b_q - a_q, MIN_PHRED,
                                                  MAX_PHRED), MIN_PHRED)))
            either_n = (a_b == N_CODE) | (b_b == N_CODE)
            mask = either_n | (raw_qual == MIN_PHRED) | tie
            in_len = col[None, :] < lens[sel, None]
            out_b[sel] = np.where(in_len & ~mask, raw_base, N_CODE)
            out_q[sel] = np.where(in_len & ~mask, raw_qual, MIN_PHRED)
            out_b[sel] = np.where(in_len, out_b[sel], 0)
            out_q[sel] = np.where(in_len, out_q[sel], 0)
            # exact per-base errors vs the pre-mask raw duplex base over both
            # segs' packed source rows (duplex.py:118-126), with positions at
            # or beyond the combined length excluded per source read
            rb8 = np.ascontiguousarray(raw_base.astype(np.uint8))
            errs = np.zeros((len(sel), L_max), dtype=np.int32)
            for side in (ca, cb):
                # one native pass per side over each output's seg row range
                _, e_side = nb.segment_depth_errors_ranges(
                    codes, vrows, rb8, vstarts[:-1][side], vstarts[1:][side])
                errs += e_side
            errs[rb8 == N_CODE] = 0
            errs[~in_len] = 0
            out_e[sel] = np.minimum(errs, I16_MAX)

        done_rows = np.empty(0, dtype=np.int64)
        side = "host"
        if len(comb) and combine_ctx is not None:
            s2m = combine_ctx["seg_to_multi"]
            ma = s2m[aseg[comb]]
            mb = s2m[bseg[comb]]
            eligible = (ma >= 0) & (mb >= 0)  # single-read segs: host-only
            sus = combine_ctx["suspect"]
            if sus is not None and eligible.any():
                # any oracle-patched position on either strand sends the
                # whole output row to the host combine (resident arrays
                # are pre-patch; conservative over the full row width)
                sus_row = sus.any(axis=1)
                touched = eligible & (sus_row[np.maximum(ma, 0)]
                                      | sus_row[np.maximum(mb, 0)])
                METRICS.inc("duplex.suspect_rows", int(touched.sum()))
                eligible &= ~touched
            cand = comb[eligible]
            if len(cand):
                from ..ops.kernel import duplex_combine_device
                from ..ops.router import DUPLEX_COMBINE, run_adaptive_stage

                # mesh dispatches keep the resident arrays shard-ordered
                # on device: remap multi indices through the gather instead
                # of paying a device-side re-shuffle (single-device: rows
                # ARE multi order, gather is None)
                gather = combine_ctx.get("gather")
                a_rows = s2m[aseg[cand]]
                b_rows = s2m[bseg[cand]]
                if gather is not None:
                    a_rows = gather[a_rows]
                    b_rows = gather[b_rows]

                def _device_combine():
                    ob, oq, oe = duplex_combine_device(
                        combine_ctx["resident"], a_rows, b_rows,
                        lens[cand])
                    out_b[cand] = ob
                    out_q[cand] = oq
                    out_e[cand] = oe

                _, side = run_adaptive_stage(
                    DUPLEX_COMBINE, len(cand) * L_max,
                    combine_ctx.get("override", "auto"),
                    _device_combine, lambda: combine_host(cand))
                done_rows = cand
        on_device = len(done_rows) if side == "device" else 0
        sp.set(side=side)
        METRICS.inc("duplex.combine_rows_device", on_device)
        METRICS.inc("duplex.combine_rows_host", len(comb) - on_device)
        rest = np.setdiff1d(comb, done_rows)
        if len(rest):
            # suspect-touched / single-seg / no-resident rows: always the
            # host combine (not a chooser sample — the cand subset is the
            # measured apples-to-apples comparison)
            combine_host(rest)
        if combine_ctx is not None:
            # the fused combine is done with the stage-1 resident arrays:
            # release their device-byte accounting (ISSUE 11 satellite)
            combine_ctx["resident"].release()

        passthrough = np.nonzero(kinds != 2)[0]
        if len(passthrough):
            src = aseg[passthrough]
            in_len = col[None, :] < lens[passthrough, None]
            out_b[passthrough] = np.where(in_len, tb[src], 0)
            out_q[passthrough] = np.where(in_len, tq[src], 0)
            out_e[passthrough] = np.where(in_len, e16[src], 0)
        return out_b, out_q, out_e

    def _output_rx(self, batch, span, rx_a, rx_b, vrows, vstarts):
        """RX tag per output read: the values of seg ``rx_a`` verbatim,
        those of seg ``rx_b`` strand-flipped, then the UMI consensus
        (unanimous fast path)."""
        rx_vo, rx_vl, _ = batch.tag_locs_str(b"RX")
        buf = batch.buf

        span_v = span[vrows]
        una_off, una_len = nb.rx_unanimous(buf, rx_vo[span_v], rx_vl[span_v],
                                           vstarts)
        present = (rx_vo[span_v] >= 0).astype(np.int64)
        cnt = np.add.reduceat(present, vstarts[:-1]) \
            if len(span_v) else np.zeros(0, dtype=np.int64)

        # native fast path: every output whose contributing segs are
        # unanimous/absent resolves in one C pass (single-read verbatim /
        # all-equal uppercased, b-side flip on bytes); only the divergent or
        # disagreeing outputs it hands back go through the Python
        # likelihood loop
        n_off, n_len, n_blob, fallback = nb.duplex_rx_fast(
            buf, una_off, una_len, cnt, rx_a, rx_b)
        n_blob_arr = n_blob if len(n_blob) else np.zeros(1, dtype=np.uint8)
        rx_addr = np.where(n_len > 0, n_blob_arr.ctypes.data + n_off, 0)
        if len(fallback) == 0:
            return rx_addr, n_len, [n_blob_arr]

        rx_off_in_blob = np.zeros(len(rx_a), dtype=np.int64)
        rx_len = np.zeros(len(rx_a), dtype=np.int32)
        blob = bytearray()  # one allocation for all values, not one per emit

        def seg_values(s):
            """Ordered present RX strings of seg s."""
            rows = span_v[vstarts[s]:vstarts[s + 1]]
            vals = []
            for i in rows:
                if rx_vo[i] >= 0:
                    vals.append(buf[rx_vo[i]:rx_vo[i] + rx_vl[i]]
                                .tobytes().decode())
            return vals

        def emit(k, rx):
            rx_off_in_blob[k] = len(blob)
            blob.extend(rx.encode())
            rx_len[k] = len(rx)

        fams = []
        fam_ks = []
        for k in fallback.tolist():
            # AB-seg values verbatim, BA-seg values flipped — BOTH segs of
            # the branch contribute, independent of consensus aliveness
            a_s, b_s = int(rx_a[k]), int(rx_b[k])
            # fast path: when every contributing seg is unanimous, the
            # family holds at most two distinct values — if they agree, the
            # consensus is that value (simple_umi's all-equal rule: verbatim
            # for a single read, ACGTN-uppercased otherwise), with no
            # per-read list or likelihood call. This is ~every real duplex
            # molecule (a-strand RX == flip(b-strand RX)).
            svals = []
            simple = True
            for s, flip in ((a_s, False), (b_s, True)):
                if s < 0 or una_off[s] == -1:
                    continue
                if una_off[s] == -2:
                    simple = False
                    break
                v = buf[una_off[s]:una_off[s] + una_len[s]].tobytes().decode()
                if flip:
                    v = _flip_umi(v)
                svals.append((v, int(cnt[s])))
            if simple:
                if not svals:
                    continue
                total = sum(c for _, c in svals)
                if total == 1:
                    emit(k, svals[0][0])
                    continue
                if all(v == svals[0][0] for v, _ in svals):
                    emit(k, svals[0][0].translate(_ACGTN_UPPER))
                    continue
            vals = []
            for s, flip in ((a_s, False), (b_s, True)):
                if s < 0:
                    continue
                if una_off[s] == -2:  # divergent: materialize + flip each
                    vs = seg_values(s)
                    if flip:
                        vs = [_flip_umi(v) for v in vs]
                elif una_off[s] >= 0:  # unanimous: decode (and flip) ONCE
                    v = buf[una_off[s]:una_off[s] + una_len[s]] \
                        .tobytes().decode()
                    if flip:
                        v = _flip_umi(v)
                    vs = [v] * int(cnt[s])
                else:
                    continue
                vals.extend(vs)
            if not vals:
                continue
            fams.append(vals)
            fam_ks.append(k)
        for k, rx in zip(fam_ks, consensus_umis_batch(fams)):
            emit(k, rx)
        # the Python-resolved outputs override the native arena's entries;
        # both arenas stay alive through the returned list
        blob_arr = np.frombuffer(bytes(blob) or b"\x00", dtype=np.uint8)
        py_mask = rx_len > 0
        rx_addr = np.where(py_mask, blob_arr.ctypes.data + rx_off_in_blob,
                           rx_addr)
        return (rx_addr, np.where(py_mask, rx_len, n_len),
                [blob_arr, n_blob_arr])
