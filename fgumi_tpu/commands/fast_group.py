"""Vectorized GroupReadsByUmi host path over RecordBatch inputs.

The group-command analog of consensus/fast.py: template formation, position
keys, filtering, and MI-tag record rewriting happen in whole-batch array
passes (native ops from fgumi_tpu.native.batch); only the per-position-group
UMI assignment (the strategy assigner) remains Python, matching the
reference's split where assigners are the algorithmic core
(/root/reference/src/lib/commands/group.rs:505-560) and everything around
them is raw-byte plumbing. An orientation sub-group the adjacency assigner
can take as 2-bit integer codes (``AdjacencyUmiAssigner.assign_codes``)
goes from the batch's bytes to int64 molecule ids and their MI values with
no Python string; every other one hands the assigner its strings.

Semantics contract: byte-identical output records, identical filter metrics
and family-size histograms to commands/group.py::run_group on the same
stream (tested in tests/test_fast_group.py). The position group spanning a
batch boundary is carried as Python Templates and runs the per-template
reference path, sharing the assigner (and so the global molecule counter).
"""

import numpy as np

from ..core.template import (UNKNOWN_POS, UNKNOWN_REF, UNKNOWN_STRAND,
                             classify, library_lookup_from_header,
                             read_info_key)
from ..io.bam import (FLAG_FIRST, FLAG_LAST, FLAG_MATE_UNMAPPED, FLAG_PAIRED,
                      FLAG_QC_FAIL, FLAG_REVERSE, FLAG_SECONDARY,
                      FLAG_SUPPLEMENTARY, FLAG_UNMAPPED)
from ..native import batch as nb
from ..observe.metrics import METRICS
from ..observe.trace import spanned
from ..umi.assigners import molecule_ids, molecule_kinds, render_mis, umi_codes
from .group import (FilterMetrics, append_mi_tag, assign_group, extract_umi,
                    filter_template, pair_orientation)

_ACCEPT, _POOR, _NONPF, _NS, _SHORT = 0, 1, 2, 3, 4


class _PySeg:
    """Carried-group segment of python Templates (tail merges, weird UMIs);
    filtered and tallied at group closure."""

    __slots__ = ("templates",)

    def __init__(self, templates):
        self.templates = templates


class _ArrSeg:
    """Carried-group segment backed by a retained RecordBatch: templates
    were filtered/tallied at batch time; closure only assigns + rewrites."""

    __slots__ = ("batch", "umis", "out_rows")

    def __init__(self, batch, umis, out_rows):
        self.batch = batch
        self.umis = umis          # _Umis of the kept templates, in order
        self.out_rows = out_rows  # (rows_flat int64[], counts int64[])


class _Umis:
    """Kept templates' UMIs in stream order, as the assigner takes them.

    ``parts`` hold them in order: each a (buf, offs, lens) triple of the
    representative reads' UMI bytes in a batch buffer, or a list of the
    strings assign_group extracts (a carried python Template's, and the
    ``paired`` strategy's prefixed ones). ``ori`` is the orientation
    sub-group key a template, r1 forward * 2 + r2 forward (None for
    ``paired``). Strings and 2-bit codes are made on first use, so a
    sub-group the codes take makes no string."""

    __slots__ = ("parts", "ori", "n", "_strings", "_codes")

    def __init__(self, parts, ori, n):
        self.parts = parts
        self.ori = ori
        self.n = n
        self._strings = None
        self._codes = None

    @classmethod
    def join(cls, runs):
        ori = (None if runs[0].ori is None
               else np.concatenate([u.ori for u in runs]))
        return cls([p for u in runs for p in u.parts], ori,
                   sum(u.n for u in runs))

    def strings(self):
        """The upper-cased strings, a list."""
        if self._strings is None:
            out = [p if isinstance(p, list) else _decode_umis(*p)
                   for p in self.parts]
            self._strings = (out[0] if len(out) == 1
                             else [u for part in out for u in part])
        return self._strings

    def codes(self, min_len):
        """(codes uint64, length int64) a template: the 2-bit code of the
        UMI the strings' ``--min-umi-length`` truncation keeps, and its
        length, -1 where it has no code (see ``_span_codes``)."""
        if self._codes is None:
            pieces = [_span_codes(*(_string_spans(p) if isinstance(p, list)
                                    else p), min_len) for p in self.parts]
            self._codes = (pieces[0] if len(pieces) == 1 else
                           tuple(map(np.concatenate, zip(*pieces))))
        return self._codes


def _decode_umis(buf, offs, lens):
    """Upper-cased strings of ASCII UMI byte spans: one gather, one decode."""
    blob, boff = nb.concat_spans([buf], np.zeros(len(offs), np.int32), offs,
                                 lens)
    s = blob.tobytes().upper().decode()
    bo = boff.tolist()
    return [s[bo[i]:bo[i + 1]] for i in range(len(offs))]


def _string_spans(strings):
    """(buf, offs, lens) of the UTF-8 bytes of strings."""
    raw = [u.encode() for u in strings]
    lens = np.fromiter(map(len, raw), np.int64, len(raw))
    offs = np.zeros(len(raw), dtype=np.int64)
    np.cumsum(lens[:-1], out=offs[1:])
    return np.frombuffer(b"".join(raw) or b"\0", dtype=np.uint8), offs, lens


def _span_codes(buf, offs, lens, min_len):
    """(codes uint64, length int64) of UMI byte spans, truncated to
    ``min_len`` bases where that is set: length -1 where a span has no
    code, which is where its bytes leave ACGTacgt or ``CODE_MAX_LEN``, where
    the spans differ in length, or where one is shorter than ``min_len``
    (the strings' truncation then raises)."""
    n = len(offs)
    length = int(lens[0]) if min_len is None else min_len
    uncoded = lens != length if min_len is None else lens < length
    if length < 1 or uncoded.any():
        return np.zeros(n, dtype=np.uint64), np.full(n, -1, dtype=np.int64)
    blob, _ = nb.concat_spans([buf], np.zeros(n, np.int32), offs,
                              np.full(n, length, dtype=np.int32))
    codes, valid = umi_codes(blob.reshape(n, length))
    return codes, np.where(valid, length, -1)


class FastGrouper:
    """Batch GroupReadsByUmi engine. Feed RecordBatches; collect wire chunks."""

    # per-batch tags beyond umi_tag fetched in ONE fused aux scan;
    # subclasses extend with their own lookups
    _PREFETCH_TAGS = [b"RG", b"MQ"]

    def __init__(self, header, assigner, *, umi_tag=b"RX", assigned_tag=b"MI",
                 min_mapq=1, include_non_pf=False, min_umi_length=None,
                 no_umi=False, allow_unmapped=False):
        self.assigner = assigner
        self.umi_tag = umi_tag
        self.assigned_tag = assigned_tag
        self.min_mapq = min_mapq
        self.include_non_pf = include_non_pf
        self.min_umi_length = min_umi_length
        self.no_umi = no_umi
        self.allow_unmapped = allow_unmapped
        self.library_of = library_lookup_from_header(header.text)
        libs = sorted(set(self.library_of.values()) | {"unknown"})
        self._lib_ord = {lib: i for i, lib in enumerate(libs)}
        self._rg_to_ord = {rg: self._lib_ord[lib]
                           for rg, lib in self.library_of.items()}
        self.metrics = FilterMetrics()
        self.family_sizes = {}
        self.position_group_sizes = {}
        self.records_out = 0
        # run-report counters, folded into METRICS once, by flush()
        self._counts = {"position_groups": 0, "subgroups": 0, "templates": 0,
                        "subgroups.coded": 0, "templates.coded": 0}
        self._ids_folded = 0
        self._carry = []        # python Templates of the open position group
        self._carry_key = None  # their read_info_key
        self._tail = None       # the held-back, possibly-split last template

    # ------------------------------------------------------------------ slow

    def _template_key(self, t):
        r = t.primary_r1 or t.r2
        rg = r.get_str(b"RG") if r is not None else None
        return read_info_key(t, self.library_of.get(rg, "unknown"))

    def _emit_slow_group(self, templates):
        """One position group through the reference per-template path."""
        m = self.metrics
        kept = [t for t in templates
                if filter_template(t, umi_tag=self.umi_tag,
                                   min_mapq=self.min_mapq,
                                   include_non_pf=self.include_non_pf,
                                   min_umi_length=self.min_umi_length,
                                   no_umi=self.no_umi,
                                   allow_unmapped=self.allow_unmapped,
                                   metrics=m)]
        if not kept:
            return []
        m.accepted += sum(len(t.primary_records()) for t in kept)
        assign_group(kept, self.assigner, self.umi_tag, self.min_umi_length,
                     self.no_umi)
        self._count_group(len(kept),
                          len({pair_orientation(t) for t in kept})
                          if self.assigner.split_by_orientation() else 1)
        self._tally(kept)
        out = bytearray()
        for t in kept:
            mi = t.mi.render()
            for rec in t.primary_records():
                data = append_mi_tag(rec, mi, self.assigned_tag)
                out += len(data).to_bytes(4, "little") + data
                self.records_out += 1
        return [bytes(out)] if out else []

    def _tally(self, kept):
        sizes = {}
        for t in kept:
            key = t.mi.render()
            sizes[key] = sizes.get(key, 0) + 1
        for size in sizes.values():
            self.family_sizes[size] = self.family_sizes.get(size, 0) + 1
        pg = sum(sizes.values())
        self.position_group_sizes[pg] = \
            self.position_group_sizes.get(pg, 0) + 1

    def _resolve_tail(self):
        """The held-back template is now known complete: join the open group
        or close it and start a new one."""
        if self._tail is None:
            return []
        tail, self._tail = self._tail, None
        tk = self._template_key(tail)
        if self._carry and tk == self._carry_key:
            self._carry.append(_PySeg([tail]))
            return []
        out = self._flush_carry()
        self._carry = [_PySeg([tail])]
        self._carry_key = tk
        return out

    def _flush_carry(self):
        """Close the open position group: one assignment over every carried
        segment's templates, then per-segment emission (native rewrite for
        array segments). Groups spanning many batches — the degenerate
        all-unmapped single-group input the reference's parallel assigners
        exist for (group.rs:366-498) — stay vectorized end to end."""
        segs, self._carry, self._carry_key = self._carry, [], None
        if not segs:
            return []
        runs = []       # the kept templates' _Umis, a segment each
        emit_plan = []  # per seg: ("arr", seg) | ("py", kept templates)
        m = self.metrics
        for seg in segs:
            if isinstance(seg, _PySeg):
                kept = [t for t in seg.templates
                        if filter_template(
                            t, umi_tag=self.umi_tag, min_mapq=self.min_mapq,
                            include_non_pf=self.include_non_pf,
                            min_umi_length=self.min_umi_length,
                            no_umi=self.no_umi,
                            allow_unmapped=self.allow_unmapped, metrics=m)]
                m.accepted += sum(len(t.primary_records()) for t in kept)
                if kept:
                    runs.append(self._python_umis(kept))
                emit_plan.append(("py", kept))
            else:
                runs.append(seg.umis)
                emit_plan.append(("arr", seg))
        if not runs:
            return []
        total = sum(u.n for u in runs)

        # orientation subgrouping + truncation + assignment (assign_group)
        ids, kinds = self._assign_umis(_Umis.join(runs))
        self._tally_family_sizes(ids, kinds)
        rendered = render_mis(ids, kinds)
        self.position_group_sizes[total] = \
            self.position_group_sizes.get(total, 0) + 1

        out = []
        pos = 0
        for plan in emit_plan:
            if plan[0] == "py":
                blob = bytearray()
                for t in plan[1]:
                    mi = rendered[pos].decode()
                    pos += 1
                    for rec in t.primary_records():
                        data = append_mi_tag(rec, mi, self.assigned_tag)
                        blob += len(data).to_bytes(4, "little") + data
                        self.records_out += 1
                if blob:
                    out.append(bytes(blob))
            else:
                seg = plan[1]
                rows_flat, counts = seg.out_rows
                k = seg.umis.n
                # one repeat expands template values to record values
                values = np.repeat(rendered[pos:pos + k],
                                   np.asarray(counts, dtype=np.int64))
                pos += k
                out.extend(self._flush_pending(seg.batch, rows_flat,
                                               values))
        return out

    def _python_umis(self, kept):
        """_Umis of carried python Templates: assign_group's strings."""
        strings = ([""] * len(kept) if self.no_umi else
                   [extract_umi(t, self.umi_tag, self.assigner) for t in kept])
        ori = None
        if self.assigner.split_by_orientation():
            ori = np.array([2 * r1 + r2 for r1, r2 in map(pair_orientation,
                                                          kept)], np.int8)
        return _Umis([strings], ori, len(kept))

    def flush(self):
        """End of stream: resolve the held template and close the open group."""
        out = self._resolve_tail()
        out.extend(self._flush_carry())
        self._fold_counts()
        return out

    def _count_group(self, templates, subgroups):
        c = self._counts
        c["position_groups"] += 1
        c["subgroups"] += subgroups
        c["templates"] += templates

    def _fold_counts(self):
        """The stream's tallies into the metrics registry, once (a counter a
        position group would cost a capture panel's million groups a
        second)."""
        for key, n in self._counts.items():
            METRICS.inc("group." + key, n)
        tally = self.assigner.counter
        METRICS.inc("group.molecules", tally.value - self._ids_folded)
        METRICS.inc("group.unique_umis", tally.uniques)
        self._counts = dict.fromkeys(self._counts, 0)
        self._ids_folded, tally.uniques = tally.value, 0

    # ----------------------------------------------------------------- driver

    def process_batch(self, batch):
        """The last template of a batch may be SPLIT across the batch
        boundary, making its position key unreliable; it is held back
        (`_tail`) until the next batch proves it complete, and the last
        complete position group stays open (`_carry`) since the tail may
        belong to it. Both run the reference per-template path; call
        flush() after the last batch."""
        n = batch.n
        if n == 0:
            return []
        buf = batch.buf
        # one native aux scan covers every tag the phases of this engine
        # read (FastDedup extends the list with its tc/CB lookups)
        batch.prefetch_tags([self.umi_tag] + self._PREFETCH_TAGS)
        name_off = batch.data_off + 32
        name_len = (batch.l_read_name - 1).astype(np.int32)
        tstarts = nb.group_starts(buf, np.ascontiguousarray(name_off),
                                  name_len)
        tbounds = np.append(tstarts, n)
        nT = len(tbounds) - 1

        # merge a template split across the batch boundary into the tail
        t0 = 0
        if self._tail is not None and buf[
                name_off[0]:name_off[0] + name_len[0]] \
                .tobytes() == self._tail.name:
            merged = classify(self._tail.all_records()
                              + [batch.raw_record(int(i))
                                 for i in range(tbounds[0], tbounds[1])])
            self._tail = merged
            t0 = 1
        if t0 >= nT:
            return []  # the whole batch merged into the (still open) tail

        # the tail is complete now (a later template exists in this batch)
        out = self._resolve_tail()

        keys = self._template_keys(batch, tbounds, nT)
        nC = nT - 1  # complete templates; the last may continue

        # absorb batch-leading templates continuing the open group
        if self._carry and t0 < nC \
                and self._python_key(batch, tbounds, keys, t0) \
                == self._carry_key:
            diffs = np.nonzero(
                (keys[t0 + 1:nC] != keys[t0:nC - 1]).any(axis=1))[0]
            run_end = (t0 + 1 + int(diffs[0])) if len(diffs) else nC
            self._defer_templates(batch, tbounds,
                                  np.arange(t0, run_end, dtype=np.int64))
            t0 = run_end
        if self._carry and t0 < nC:
            out.extend(self._flush_carry())  # a differing template follows

        if t0 < nC:
            # position-group boundaries among complete templates [t0, nC)
            diff = (keys[t0 + 1:nC] != keys[t0:nC - 1]).any(axis=1)
            gb = [t0] + (np.nonzero(diff)[0] + t0 + 1).tolist() + [nC]
            # the last complete group becomes the new open group
            if len(gb) > 2:
                out.extend(self._process_groups(batch, tbounds, keys,
                                                gb[:-1]))
            last_start = gb[-2]
            assert not self._carry
            self._defer_templates(batch, tbounds,
                                  np.arange(last_start, nC, dtype=np.int64))
            self._carry_key = self._python_key(batch, tbounds, keys,
                                               last_start)

        self._tail = self._materialize(batch, tbounds, nT - 1)
        return out

    @spanned("group.defer", rusage=True)
    def _defer_templates(self, batch, tbounds, ts):
        """Append templates of the open group to the carry: filter + tally
        now (vectorized), carry only the kept templates' UMIs and output
        rows; non-ASCII-UMI templates carry as python Templates,
        interleaved in stream order (MI numbering is order-sensitive)."""
        if not len(ts):
            return
        cat, weird = self._filter_codes_cached(batch, tbounds)
        cat, weird = cat[ts], weird[ts]
        m = self.metrics
        n_prim = np.zeros(len(ts), dtype=np.int64)
        for sel in (self._r1_of, self._r2_of, self._fr_of):
            n_prim += sel[ts] >= 0
        ok = ~weird
        m.total_templates += int(n_prim[ok].sum())
        for code, attr in ((_POOR, "poor_alignment"), (_NONPF, "non_pf"),
                           (_NS, "ns_in_umi"), (_SHORT, "umi_too_short")):
            c = int(n_prim[ok & (cat == code)].sum())
            if c:
                setattr(m, attr, getattr(m, attr) + c)
        keep = ok & (cat == _ACCEPT)
        m.accepted += int(n_prim[keep].sum())

        def flush_run(run):
            if not run:
                return
            kept_t = np.asarray(run, dtype=np.int64)
            picks = np.stack([self._fr_of[kept_t], self._r1_of[kept_t],
                              self._r2_of[kept_t]], axis=1)
            rows_flat = picks.ravel()
            rows_flat = rows_flat[rows_flat >= 0]
            counts = (picks >= 0).sum(axis=1)
            self._carry.append(_ArrSeg(batch,
                                       self._template_umis(batch, kept_t),
                                       (rows_flat, counts)))

        run = []
        for li, t in enumerate(ts):
            if weird[li]:
                flush_run(run)
                run = []
                self._carry.append(
                    _PySeg([self._materialize(batch, tbounds, int(t))]))
            elif keep[li]:
                run.append(int(t))
        flush_run(run)

    def _filter_codes_cached(self, batch, tbounds):
        """Full-batch filter categories, computed once per batch (both the
        group processor and the defer path consume slices)."""
        if getattr(self, "_fc_batch", None) is not batch:
            nT = len(tbounds) - 1
            self._fc = self._filter_codes(batch, tbounds, nT, 0, nT)
            self._fc_batch = batch
        return self._fc

    def _template_umis(self, batch, kept_t):
        """_Umis of kept templates, read from each one's representative row
        (r1 > fragment > r2): the spans of their UMI bytes in the batch
        buffer and the orientation keys; for ``paired`` the prefixed
        strings assign_group would hand the assigner."""
        kt = np.asarray(kept_t, dtype=np.int64)
        r1s, r2s, frs = self._r1_of[kt], self._r2_of[kt], self._fr_of[kt]
        rep = np.where(r1s >= 0, r1s, np.where(frs >= 0, frs, r2s))
        uo, ul, _ = batch.tag_locs_str(self.umi_tag)
        offs = uo[rep]
        lens = np.where(offs >= 0, ul[rep], 0).astype(np.int64)
        part = [""] * len(kt) if self.no_umi else (batch.buf, offs, lens)
        if self.assigner.split_by_orientation():
            flag = batch.flag
            fwd1 = (r1s < 0) | ((flag[np.maximum(r1s, 0)] & FLAG_REVERSE) == 0)
            fwd2 = (r2s < 0) | ((flag[np.maximum(r2s, 0)] & FLAG_REVERSE) == 0)
            return _Umis([part], (fwd1.astype(np.int8) << 1) | fwd2, len(kt))
        return _Umis([self._paired_umis(batch, kt, _decode_umis(*part))],
                     None, len(kt))

    def _paired_umis(self, batch, kept_t, all_umis):
        """The ``paired`` strategy's strings: each template's two UMI halves
        with the prefixes of its reads' genomic order."""
        flag = batch.flag
        umis = []
        u5 = self._u5_cache(batch)
        lo_p, hi_p = self.assigner.lower_prefix, self.assigner.higher_prefix
        for i, t in enumerate(kept_t):
            umi = all_umis[i]
            parts = umi.split("-")
            if len(parts) != 2:
                raise ValueError(
                    "Paired strategy used but UMI did not contain 2 segments "
                    f"delimited by '-': {umi}")
            r1, r2 = self._r1_of[t], self._r2_of[t]
            if r1 >= 0 and r2 >= 0:
                if batch.ref_id[r1] != batch.ref_id[r2]:
                    r1_earlier = batch.ref_id[r1] < batch.ref_id[r2]
                elif u5[r1] != u5[r2]:
                    r1_earlier = u5[r1] < u5[r2]
                else:
                    r1_earlier = not flag[r1] & FLAG_REVERSE
            else:
                r1_earlier = True
            if r1_earlier:
                umis.append(f"{lo_p}:{parts[0]}-{hi_p}:{parts[1]}")
            else:
                umis.append(f"{hi_p}:{parts[0]}-{lo_p}:{parts[1]}")
        return umis

    def _materialize(self, batch, tbounds, t):
        return classify(batch.raw_records(
            np.arange(tbounds[t], tbounds[t + 1])))

    # ------------------------------------------------------------------- keys

    @spanned("group.keys", rusage=True)
    def _template_keys(self, batch, tbounds, nT):
        """Per-template position-key fields, (nT, 7) int64:
        lib_ord, a_tid, a_pos, a_strand, b_tid, b_pos, b_strand."""
        n = batch.n
        flag = batch.flag
        secsup = (flag & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY)) != 0
        paired = (flag & FLAG_PAIRED) != 0
        first = (flag & FLAG_FIRST) != 0
        last = (flag & FLAG_LAST) != 0
        role_r1 = ~secsup & paired & first            # classify() elif order
        role_r2 = ~secsup & paired & ~first & last
        role_fr = ~secsup & ~paired
        t_of = np.repeat(np.arange(nT), np.diff(tbounds))

        # last-wins role selection (classify overwrites on duplicates)
        def pick(mask):
            sel = np.full(nT, -1, dtype=np.int64)
            rows = np.nonzero(mask)[0]
            sel[t_of[rows]] = rows  # ascending rows: later assignment wins
            return sel

        self._r1_of = pick(role_r1)
        self._r2_of = pick(role_r2)
        self._fr_of = pick(role_fr)
        self._t_of = t_of

        u5 = self._u5_cache(batch)
        unmapped = (flag & FLAG_UNMAPPED) != 0
        rev = ((flag & FLAG_REVERSE) != 0).astype(np.int64)

        def end_of(sel):
            """(tid, pos, strand) per template for one role; sentinel when
            the role is absent or the read unmapped."""
            has = sel >= 0
            idx = np.where(has, sel, 0)
            ok = has & ~unmapped[idx]
            tid = np.where(ok, batch.ref_id[idx], UNKNOWN_REF)
            pos = np.where(ok, u5[idx], UNKNOWN_POS)
            strand = np.where(ok, rev[idx], UNKNOWN_STRAND)
            return np.stack([tid, pos, strand], axis=1).astype(np.int64), has

        e1, has1 = end_of(self._r1_of)
        e2, has2 = end_of(self._r2_of)
        ef, _ = end_of(self._fr_of)
        # read_info_key: r1/r2 when either exists, else the fragment
        use_frag = ~has1 & ~has2
        e1 = np.where(use_frag[:, None], ef, e1)
        unknown = np.array([UNKNOWN_REF, UNKNOWN_POS, UNKNOWN_STRAND],
                           dtype=np.int64)
        e2 = np.where(use_frag[:, None], unknown[None, :], e2)
        # order ends: lower tuple first (sentinels already sort last)
        swap = ((e1[:, 0] > e2[:, 0])
                | ((e1[:, 0] == e2[:, 0]) & (e1[:, 1] > e2[:, 1]))
                | ((e1[:, 0] == e2[:, 0]) & (e1[:, 1] == e2[:, 1])
                   & (e1[:, 2] > e2[:, 2])))
        a = np.where(swap[:, None], e2, e1)
        b = np.where(swap[:, None], e1, e2)

        # library ordinal from the primary r1 (or fragment, or r2)'s RG
        key_read = np.where(self._r1_of >= 0, self._r1_of,
                            np.where(self._fr_of >= 0, self._fr_of,
                                     self._r2_of))
        lib = np.full(nT, self._lib_ord["unknown"], dtype=np.int64)
        rg_off, rg_len, _ = batch.tag_locs_str(b"RG")
        kr = np.where(key_read >= 0, key_read, 0)
        ro = np.where(key_read >= 0, rg_off[kr], -1)
        rl = rg_len[kr]
        present = ro >= 0
        if present.any():
            hashes = nb.hash_ranges(batch.buf, ro, rl)
            uniq, first_idx, inv = np.unique(hashes, return_index=True,
                                             return_inverse=True)
            reps = first_idx[inv]
            eq = nb.ranges_equal(batch.buf, ro, rl, ro[reps], rl[reps])
            if eq[present].all():
                ords = np.empty(len(uniq), dtype=np.int64)
                for u, fi in enumerate(first_idx):
                    if ro[fi] < 0:
                        ords[u] = self._lib_ord["unknown"]
                        continue
                    rg = batch.buf[ro[fi]:ro[fi] + rl[fi]].tobytes() \
                        .decode(errors="replace")
                    ords[u] = self._rg_to_ord.get(rg,
                                                  self._lib_ord["unknown"])
                lib = ords[inv].copy()
                lib[~present] = self._lib_ord["unknown"]
            else:
                for t in np.nonzero(present)[0]:
                    rg = batch.buf[ro[t]:ro[t] + rl[t]].tobytes() \
                        .decode(errors="replace")
                    lib[t] = self._rg_to_ord.get(rg,
                                                 self._lib_ord["unknown"])
        return np.concatenate([lib[:, None], a, b], axis=1)

    def _python_key(self, batch, tbounds, keys, t):
        """The canonical python read_info_key of template t (for cross-batch
        carry comparisons; within-batch equality uses the int key rows)."""
        return self._template_key(self._materialize(batch, tbounds, t))

    # ----------------------------------------------------------------- filter

    def _filter_codes(self, batch, tbounds, nT, t_lo, t_hi):
        """Per-template accept/reject category, replicating the reference's
        first-failing-check attribution (filter_template evaluation order)."""
        flag = batch.flag
        m = self.min_mapq

        def arr(sel, field, default):
            idx = np.where(sel >= 0, sel, 0)
            return np.where(sel >= 0, field[idx], default)

        roles = [self._r1_of, self._r2_of, self._fr_of]
        # reads order in filter_template: r1, r2, fragment
        unmapped = (flag & FLAG_UNMAPPED) != 0
        qcfail = (flag & FLAG_QC_FAIL) != 0
        paired = (flag & FLAG_PAIRED) != 0
        mate_unmapped = (flag & FLAG_MATE_UNMAPPED) != 0

        mq_val = self._mq_values(batch)
        uo, ul, _ = batch.tag_locs_str(self.umi_tag)
        has_n, bases, ascii_ok = nb.umi_scan(batch.buf, uo, ul)

        conds = []
        codes = []

        def add(cond, code):
            conds.append(cond)
            codes.append(code)

        # primaries empty -> poor (no primary records at all)
        n_prim = np.zeros(nT, dtype=np.int64)
        for sel in roles:
            n_prim += sel >= 0
        add(n_prim == 0, _POOR)

        # both_unmapped (over present reads) and not allow_unmapped
        if not self.allow_unmapped:
            all_unmapped = np.ones(nT, dtype=bool)
            for sel in roles:
                r_unmapped = arr(sel, unmapped, True)
                all_unmapped &= np.where(sel >= 0, r_unmapped, True)
            add((n_prim > 0) & all_unmapped, _POOR)

        # loop 1 per read: qc-fail then mapq
        for sel in roles:
            present = sel >= 0
            if not self.include_non_pf:
                add(present & arr(sel, qcfail, False), _NONPF)
            r_unmapped = arr(sel, unmapped, True)
            mapq = arr(sel, batch.mapq.astype(np.int64), m)
            add(present & ~r_unmapped & (mapq < m), _POOR)

        # loop 2 per read: MQ tag, then UMI checks
        for sel in roles:
            present = sel >= 0
            r_paired = arr(sel, paired, False)
            r_mu = arr(sel, mate_unmapped, True)
            mq = arr(sel, mq_val, np.int64(1 << 40))
            add(present & r_paired & ~r_mu & (mq < m), _POOR)
            if not self.no_umi:
                u_off = arr(sel, uo, -1)
                add(present & (u_off < 0), _POOR)
                add(present & arr(sel, has_n.astype(bool), False), _NS)
                if self.min_umi_length is not None:
                    add(present
                        & (arr(sel, bases.astype(np.int64), 1 << 40)
                           < self.min_umi_length), _SHORT)

        cat = np.select(conds, codes, default=_ACCEPT)[t_lo:t_hi]

        # non-ASCII UMI bytes route the group through the python path (their
        # decoded character count can differ from the byte count)
        weird = np.zeros(nT, dtype=bool)
        if not self.no_umi:
            for sel in roles:
                weird |= (sel >= 0) & ~arr(sel, ascii_ok.astype(bool), True)
        return cat, weird[t_lo:t_hi]

    def _mq_values(self, batch):
        """Per-record MQ tag as int64 (absent/non-integer -> huge sentinel,
        which never fails the < min_mapq check — get_int None semantics)."""
        vo, vl, vt = batch.tag_locs(b"MQ")
        buf = batch.buf
        val = np.full(batch.n, 1 << 40, dtype=np.int64)
        for code, width, signed in (("c", 1, True), ("C", 1, False),
                                    ("s", 2, True), ("S", 2, False),
                                    ("i", 4, True), ("I", 4, False)):
            mask = (vt == ord(code)) & (vo >= 0)
            if not mask.any():
                continue
            offs = vo[mask]
            v = np.zeros(len(offs), dtype=np.int64)
            for j in range(width):
                v |= buf[offs + j].astype(np.int64) << (8 * j)
            if signed:
                sign_bit = np.int64(1) << (8 * width - 1)
                v = (v ^ sign_bit) - sign_bit
            val[mask] = v
        return val

    # ----------------------------------------------------------------- groups

    def _process_groups(self, batch, tbounds, keys, gb):
        """Vectorized filter + python assignment + native MI rewrite for
        complete groups gb[0]..gb[-1]."""
        m = self.metrics
        t_lo, t_hi = gb[0], gb[-1]
        cat, weird = self._filter_codes_cached(batch, tbounds)
        cat, weird = cat[t_lo:t_hi], weird[t_lo:t_hi]
        sizes_prim = np.zeros(t_hi - t_lo, dtype=np.int64)
        for sel in (self._r1_of, self._r2_of, self._fr_of):
            sizes_prim += sel[t_lo:t_hi] >= 0

        out = []
        # accumulated fast-group output, emitted in one vectorized pass:
        # assignment stays per group (the algorithm is per position group)
        # but rendering, family tallies, and row/value expansion run ONCE
        # over the whole accumulation
        acc_ids = []    # int64 molecule ids, template order across groups
        acc_kinds = []  # their kind bytes (None: every id Single)
        acc_kept = []   # kept template-index arrays

        def flush_fast():
            if not acc_ids:
                return []
            ids = np.concatenate(acc_ids)
            kinds = (None if acc_kinds[0] is None
                     else np.concatenate(acc_kinds))
            kept_all = np.concatenate(acc_kept)
            acc_ids.clear()
            acc_kinds.clear()
            acc_kept.clear()
            # MI values are globally unique per family (the deterministic
            # counter), so one tally covers every group in the accumulation
            self._tally_family_sizes(ids, kinds)
            sels = np.stack([self._fr_of[kept_all], self._r1_of[kept_all],
                             self._r2_of[kept_all]], axis=1)
            valid = sels >= 0
            rows = sels[valid]
            values = np.repeat(render_mis(ids, kinds), valid.sum(axis=1))
            return self._flush_pending(batch, rows, values)

        for gi in range(len(gb) - 1):
            lo, hi = gb[gi] - t_lo, gb[gi + 1] - t_lo
            g_cat = cat[lo:hi]
            if weird[lo:hi].any():
                # rare: python path for the whole group, after flushing the
                # pending fast output to preserve stream order
                out.extend(flush_fast())
                out.extend(self._emit_slow_group(
                    [self._materialize(batch, tbounds, t)
                     for t in range(gb[gi], gb[gi + 1])]))
                continue
            # metrics: total per template; category counters
            g_sizes = sizes_prim[lo:hi]
            m.total_templates += int(g_sizes.sum())
            for code, attr in ((_POOR, "poor_alignment"), (_NONPF, "non_pf"),
                               (_NS, "ns_in_umi"), (_SHORT, "umi_too_short")):
                c = int(g_sizes[g_cat == code].sum())
                if c:
                    setattr(m, attr, getattr(m, attr) + c)
            kept_t = np.nonzero(g_cat == _ACCEPT)[0] + gb[gi]
            if not len(kept_t):
                continue
            m.accepted += int(g_sizes[g_cat == _ACCEPT].sum())

            ids, kinds = self._assign_light(batch, kept_t)
            self.position_group_sizes[len(ids)] = \
                self.position_group_sizes.get(len(ids), 0) + 1
            acc_ids.append(ids)
            acc_kinds.append(kinds)
            acc_kept.append(kept_t)

        out.extend(flush_fast())
        return out

    def _tally_family_sizes(self, ids, kinds):
        """Family multiplicities from molecule ids (and kinds: a paired
        id's /A and /B are two families) with two bincounts. Safe across
        position groups — the ids are globally unique per family, and those
        of one flush span few values."""
        key = ids - ids.min()
        if kinds is not None:
            key = 2 * key + (kinds == ord("B"))
        hist = np.bincount(np.bincount(key))
        for size in np.nonzero(hist[1:])[0] + 1:
            self.family_sizes[int(size)] = \
                self.family_sizes.get(int(size), 0) + int(hist[size])

    @spanned("group.rewrite", rusage=True)
    def _flush_pending(self, batch, rows, values):
        if len(rows) == 0:
            return []
        try:
            blob = nb.rewrite_tag_records(
                batch, np.asarray(rows, dtype=np.int64), self.assigned_tag,
                values)
        except ValueError:
            # malformed aux region somewhere in the run: per-record python
            # editor (identical output, tolerant TLV walk)
            parts = []
            for r, v in zip(rows, values):
                data = append_mi_tag(batch.raw_record(int(r)),
                                     v.decode(), self.assigned_tag)
                parts.append(len(data).to_bytes(4, "little") + data)
            blob = b"".join(parts)
        self.records_out += len(rows)
        return [blob]

    def _assign_light(self, batch, kept_t):
        """UMI extraction + strategy assignment for one group's kept
        templates: (ids, kinds) in template order, as _assign_umis."""
        return self._assign_umis(self._template_umis(batch, kept_t))

    @spanned("group.assign", rusage=True)
    def _assign_umis(self, umis):
        """assign_group's subgroup/truncate/assign tail over one position
        group's _Umis: molecule ids, int64 in entry order, and their kinds
        (None where every id is Single, as every split strategy's are; the
        ``paired`` strategy's kind bytes otherwise)."""
        assigner = self.assigner
        if umis.ori is None:
            self._count_group(umis.n, 1)
            mols = assigner.assign(self._truncate(umis.strings()))
            return molecule_ids(mols), molecule_kinds(mols)
        # the orientation key r1*2+r2 sorts as the (r1, r2) bool tuples do
        # (False < True), so np.unique's order is the scalar path's
        # sorted(subgroups.items()) order
        uniq_ok, inv_ok = np.unique(umis.ori, return_inverse=True)
        self._count_group(umis.n, len(uniq_ok))
        if len(uniq_ok) == 1:
            return self._assign_subgroup(umis, None), None
        ids = np.empty(umis.n, dtype=np.int64)
        order = np.argsort(inv_ok, kind="stable")
        bounds = np.searchsorted(inv_ok[order], np.arange(len(uniq_ok) + 1))
        for g in range(len(uniq_ok)):
            idxs = order[bounds[g]:bounds[g + 1]]
            ids[idxs] = self._assign_subgroup(umis, idxs)
        return ids, None

    def _assign_subgroup(self, umis, idxs):
        """One orientation sub-group's molecule ids (``idxs`` None: the
        whole group): over the 2-bit codes where the assigner takes them,
        else over the strings, whose checks raise as they always have."""
        whole = slice(None) if idxs is None else idxs
        n = umis.n if idxs is None else len(idxs)
        ids = self.assigner.assign_codes(
            n, lambda: [a[whole] for a in umis.codes(self.min_umi_length)])
        if ids is not None:
            self._counts["subgroups.coded"] += 1
            self._counts["templates.coded"] += n
            return ids
        sub = umis.strings()
        if idxs is not None:
            sub = [sub[i] for i in idxs]
        if not self.no_umi:
            sub = self._truncate(sub)
        return molecule_ids(self.assigner.assign(sub))

    def _truncate(self, umis):
        if self.min_umi_length is None:
            return umis
        shortest = min((len(u) for u in umis), default=0)
        if shortest < self.min_umi_length:
            raise ValueError(
                f"UMI found that had shorter length than expected "
                f"({shortest} < {self.min_umi_length})")
        return [u[:self.min_umi_length] for u in umis]

    def _u5_cache(self, batch):
        if getattr(self, "_u5_batch", None) is not batch:
            self._u5_arr = nb.unclipped_5prime(batch)
            self._u5_batch = batch
        return self._u5_arr

    def result(self):
        return {
            "records_out": self.records_out,
            "filter": self.metrics.as_dict(),
            "family_sizes": dict(sorted(self.family_sizes.items())),
            "position_group_sizes": dict(
                sorted(self.position_group_sizes.items())),
        }


class FastDedup(FastGrouper):
    """Batch dedup engine (commands/dedup.py semantics over RecordBatches).

    Reuses the grouper's template/key/filter machinery; differs in
    per-template metric counting, the unmapped pass-through split, Picard
    best-template selection, duplicate-flag + MI record rewriting over ALL
    records (incl. secondary/supplementary), and per-read output metrics.
    Groups with CB cell barcodes or --no-umi run the reference per-template
    path (rare); so does the batch-boundary carry.
    """

    # the dedup phases additionally read tc (template-coordinate keys from
    # zipper) and CB (cell partitions) — same fused scan
    _PREFETCH_TAGS = FastGrouper._PREFETCH_TAGS + [b"tc", b"CB"]

    def __init__(self, header, assigner, *, umi_tag=b"RX", assigned_tag=b"MI",
                 min_mapq=0, include_non_pf=False, min_umi_length=None,
                 no_umi=False, include_unmapped=False,
                 remove_duplicates=False):
        from .dedup import DedupMetrics

        super().__init__(header, assigner, umi_tag=umi_tag,
                         assigned_tag=assigned_tag, min_mapq=min_mapq,
                         include_non_pf=include_non_pf,
                         min_umi_length=min_umi_length, no_umi=no_umi,
                         allow_unmapped=False)
        self.include_unmapped = include_unmapped
        self.remove_duplicates = remove_duplicates
        self.dmetrics = DedupMetrics()
        self.metrics = self.dmetrics.filter  # FilterMetrics slot

    # ------------------------------------------------------------------ slow

    def _defer_templates(self, batch, tbounds, ts):
        for t in ts:
            self._carry.append(
                _PySeg([self._materialize(batch, tbounds, int(t))]))

    def _flush_carry(self):
        segs, self._carry, self._carry_key = self._carry, [], None
        templates = [t for seg in segs for t in seg.templates]
        return self._emit_slow_group(templates) if templates else []

    def _emit_slow_group(self, templates):
        from .dedup import (_record_with_flag_and_mi, filter_dedup_template,
                            is_unmapped_passthrough, process_group)

        dm = self.dmetrics
        passthrough, candidates = [], templates
        if self.include_unmapped:
            passthrough, candidates = [], []
            for t in templates:
                (passthrough if is_unmapped_passthrough(t)
                 else candidates).append(t)
        kept = [t for t in candidates
                if filter_dedup_template(t, umi_tag=self.umi_tag,
                                         min_mapq=self.min_mapq,
                                         include_non_pf=self.include_non_pf,
                                         min_umi_length=self.min_umi_length,
                                         no_umi=self.no_umi,
                                         metrics=dm.filter)]
        if kept:
            sizes = process_group(kept, self.assigner, umi_tag=self.umi_tag,
                                  min_umi_length=self.min_umi_length,
                                  no_umi=self.no_umi, metrics=dm)
            for size, count in sizes.items():
                self.family_sizes[size] = \
                    self.family_sizes.get(size, 0) + count
        out = bytearray()

        def emit(data):
            out.extend(len(data).to_bytes(4, "little") + data)
            self.records_out += 1

        for t in kept:
            mi_str = t.mi.render() if t.mi is not None else None
            for rec in t.all_records():
                self._count_read_slow(rec, t.is_duplicate)
                if self.remove_duplicates and t.is_duplicate:
                    continue
                emit(_record_with_flag_and_mi(rec, t.is_duplicate, mi_str,
                                              self.assigned_tag))
        for t in passthrough:
            dm.total_templates += 1
            dm.unique_templates += 1
            for rec in t.all_records():
                self._count_read_slow(rec, False)
                emit(rec.data)
        return [bytes(out)] if out else []

    def _count_read_slow(self, rec, is_dup):
        dm = self.dmetrics
        dm.total_reads += 1
        if is_dup:
            dm.duplicate_reads += 1
        sec = rec.flag & FLAG_SECONDARY
        sup = rec.flag & FLAG_SUPPLEMENTARY
        if sec:
            dm.secondary_reads += 1
        if sup:
            dm.supplementary_reads += 1
        if (sec or sup) and rec.find_tag(b"tc") is None:
            dm.missing_tc_tag += 1

    # ----------------------------------------------------------------- groups

    def _process_groups(self, batch, tbounds, keys, gb):
        from .dedup import (PICARD_MAX_SCORE_PER_READ, PICARD_MIN_BASE_QUALITY,
                            PICARD_QC_FAIL_DISCOUNT)
        from ..io.bam import FLAG_DUPLICATE

        dm = self.dmetrics
        m = dm.filter
        t_lo, t_hi = gb[0], gb[-1]
        cat, weird = self._filter_codes_cached(batch, tbounds)
        cat, weird = cat[t_lo:t_hi], weird[t_lo:t_hi]
        flag = batch.flag
        unmapped = (flag & FLAG_UNMAPPED) != 0
        qcfail = (flag & FLAG_QC_FAIL) != 0
        tc_off, _tc_len, _ = batch.tag_locs(b"tc")
        cb_off, _cb_len, _ = batch.tag_locs_str(b"CB")

        # per-template passthrough mask: has primaries and all unmapped
        nT = len(tbounds) - 1
        n_prim = np.zeros(nT, dtype=np.int64)
        all_unm = np.ones(nT, dtype=bool)
        for sel in (self._r1_of, self._r2_of, self._fr_of):
            has = sel >= 0
            n_prim += has
            idx = np.where(has, sel, 0)
            all_unm &= np.where(has, unmapped[idx], True)
        passthrough_t = (n_prim > 0) & all_unm if self.include_unmapped \
            else np.zeros(nT, dtype=bool)

        scores = None  # computed lazily: slow-routed batches never need it
        name_off = batch.data_off + 32
        name_len = batch.l_read_name - 1

        out = []
        pending_rows = []
        pending_flags = []
        pending_values = []

        def flush_pending():
            if not pending_rows:
                return
            blob = self._rewrite(batch, pending_rows, pending_values,
                                 pending_flags)
            out.append(blob)
            pending_rows.clear()
            pending_flags.clear()
            pending_values.clear()

        for gi in range(len(gb) - 1):
            g_ts = np.arange(gb[gi], gb[gi + 1])
            cand = g_ts[~passthrough_t[g_ts]]
            # CB barcodes present -> reference path for the whole group
            cb_present = False
            for t in cand:
                r = self._r1_of[t] if self._r1_of[t] >= 0 else (
                    self._fr_of[t] if self._fr_of[t] >= 0 else self._r2_of[t])
                if r >= 0 and cb_off[r] >= 0:
                    cb_present = True
                    break
            if cb_present or self.no_umi \
                    or weird[gb[gi] - t_lo:gb[gi + 1] - t_lo].any():
                flush_pending()
                out.extend(self._emit_slow_group(
                    [self._materialize(batch, tbounds, t) for t in g_ts]))
                continue

            g_cat = cat[gb[gi] - t_lo:gb[gi + 1] - t_lo].copy()
            g_cat[passthrough_t[g_ts]] = -1  # split off before filtering
            n_cand = int((g_cat >= 0).sum())
            m.total_templates += n_cand
            for code, attr in ((_POOR, "poor_alignment"), (_NONPF, "non_pf"),
                               (_NS, "ns_in_umi"), (_SHORT, "umi_too_short")):
                c = int((g_cat == code).sum())
                if c:
                    setattr(m, attr, getattr(m, attr) + c)
            kept_t = g_ts[g_cat == _ACCEPT]
            m.accepted += len(kept_t)

            is_dup = {}
            if len(kept_t):
                # MI values, one a template: a family is one value (an id,
                # or a paired id's /A or /B), name-ordered within
                mis = render_mis(*self._assign_light(batch, kept_t)).tolist()
                fams = {}
                for k, t in enumerate(kept_t):
                    fams.setdefault(mis[k], []).append((k, t))
                for fam in fams.values():
                    fam.sort(key=lambda kt: batch.buf[
                        name_off[tbounds[kt[1]]]:
                        name_off[tbounds[kt[1]]]
                        + name_len[tbounds[kt[1]]]].tobytes())
                    self.family_sizes[len(fam)] = \
                        self.family_sizes.get(len(fam), 0) + 1
                    if len(fam) == 1:
                        best = 0
                    else:
                        if scores is None:
                            scores = nb.qual_scores(
                                batch, PICARD_MIN_BASE_QUALITY,
                                PICARD_MAX_SCORE_PER_READ)
                        best_score = None
                        best = 0
                        for j, (k, t) in enumerate(fam):
                            s = 0
                            for sel in (self._r1_of, self._r2_of,
                                        self._fr_of):
                                r = sel[t]
                                if r >= 0:
                                    rs = int(scores[r])
                                    if qcfail[r]:
                                        rs += PICARD_QC_FAIL_DISCOUNT
                                    s += rs
                            if best_score is None or s > best_score:
                                best_score = s
                                best = j
                    for j, (k, t) in enumerate(fam):
                        dup = j != best
                        is_dup[int(t)] = dup
                        dm.total_templates += 1
                        if dup:
                            dm.duplicate_templates += 1
                        else:
                            dm.unique_templates += 1

                for k, t in enumerate(kept_t):
                    t = int(t)
                    dup = is_dup[t]
                    mi_b = mis[k]
                    rows = self._template_rows(batch, tbounds, t)
                    self._count_rows(rows, dup, flag, tc_off)
                    if self.remove_duplicates and dup:
                        continue
                    for r in rows:
                        pending_rows.append(r)
                        pending_values.append(mi_b)
                        f = (int(flag[r]) & ~FLAG_DUPLICATE) \
                            | (FLAG_DUPLICATE if dup else 0)
                        pending_flags.append(f)

            # pass-through templates: verbatim records after the kept ones
            pts = g_ts[passthrough_t[g_ts]]
            if len(pts):
                flush_pending()
                blob = bytearray()
                for t in pts:
                    dm.total_templates += 1
                    dm.unique_templates += 1
                    rows = self._template_rows(batch, tbounds, int(t))
                    self._count_rows(rows, False, flag, tc_off)
                    for r in rows:
                        data = batch.buf[batch.data_off[r]:
                                         batch.data_end[r]].tobytes()
                        blob += len(data).to_bytes(4, "little") + data
                        self.records_out += 1
                if blob:
                    out.append(bytes(blob))

        flush_pending()
        return out

    def _template_rows(self, batch, tbounds, t):
        """Record rows of template t in all_records() order: picked primaries
        (fragment, r1, r2) then the remaining rows in file order."""
        picks = [int(sel[t]) for sel in (self._fr_of, self._r1_of,
                                         self._r2_of) if sel[t] >= 0]
        pick_set = set(picks)
        rows = picks[:]
        flag = batch.flag
        for r in range(int(tbounds[t]), int(tbounds[t + 1])):
            if r in pick_set:
                continue
            f = int(flag[r])
            if f & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY) \
                    or (f & FLAG_PAIRED and not f & FLAG_FIRST
                        and not f & FLAG_LAST):
                rows.append(r)
            # overwritten duplicate-role primaries are dropped (classify
            # last-wins keeps only the pick)
        return rows

    def _count_rows(self, rows, is_dup, flag, tc_off):
        dm = self.dmetrics
        dm.total_reads += len(rows)
        if is_dup:
            dm.duplicate_reads += len(rows)
        for r in rows:
            f = int(flag[r])
            if f & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY):
                if f & FLAG_SECONDARY:
                    dm.secondary_reads += 1
                if f & FLAG_SUPPLEMENTARY:
                    dm.supplementary_reads += 1
                if tc_off[r] < 0:
                    dm.missing_tc_tag += 1

    def _rewrite(self, batch, rows, values, flags):
        from ..io.bam import FLAG_DUPLICATE

        try:
            blob = nb.rewrite_tag_records(
                batch, np.asarray(rows, dtype=np.int64), self.assigned_tag,
                values, new_flags=np.asarray(flags, dtype=np.int32))
        except ValueError:
            from .dedup import _record_with_flag_and_mi

            parts = []
            for r, v, f in zip(rows, values, flags):
                data = _record_with_flag_and_mi(
                    batch.raw_record(int(r)), bool(f & FLAG_DUPLICATE),
                    v.decode(), self.assigned_tag)
                parts.append(len(data).to_bytes(4, "little") + data)
            blob = b"".join(parts)
        self.records_out += len(rows)
        return blob

    def result(self):
        dm = self.dmetrics
        dm.unique_reads = dm.total_reads - dm.duplicate_reads
        return dm, dict(sorted(self.family_sizes.items()))
