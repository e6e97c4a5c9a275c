"""Duplex consensus caller (two-stage: single-strand then strand combination).

Mirrors /root/reference/crates/fgumi-consensus/src/duplex_caller.rs:
- reads grouped by base MI with /A (AB strand) and /B (BA strand) suffixes
  (duplex_caller.rs:477-527);
- min_reads = [total, XY, YX] with padTo(3, last) and high-to-low validation
  (duplex_caller.rs:361-400);
- SS consensus via the vanilla caller with min_reads=1 / min_consensus_qual=Q2
  (duplex_caller.rs:400-420), X/Y alignment filtering across strands
  (duplex_caller.rs:1871-1933), strand-orientation validation (1830-1860);
- stage-2 combine (duplex_consensus, 844-1021): truncate to min length, agreement
  sums quality (cap Q93), disagreement takes the higher-quality base with the
  difference, equal-disagreement and N propagate (N, Q2); exact per-base errors
  counted against source reads;
- output tags MI, RG, aD/aE/aM [+ac/ad/ae/aq], bD/bE/bM [+bc/bd/be/bq], cD/cE/cM,
  RX (strand-reoriented UMI consensus) (duplex_read_into, 1056-1249).

Stage 1 (the hot loop) executes on the batched TPU kernel via the shared vanilla
job machinery; stage 2 is cheap vectorized host math.
"""

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..constants import MAX_PHRED, MIN_PHRED, N_CODE
from ..core.overlap import num_bases_extending_past_mate
from ..io.bam import (FLAG_FIRST, FLAG_LAST, FLAG_PAIRED, FLAG_REVERSE,
                      RawRecord, RecordBuilder)
from ..observe.metrics import METRICS
from .simple_umi import consensus_umis
from .rejects import RejectTracking
from .vanilla import (CallerStats, I16_MAX, R1, R2, VanillaConsensusCaller,
                      VanillaConsensusRead, VanillaOptions, _TYPE_FLAGS)


@dataclass
class DuplexConsensusRead:
    """Stage-2 result (DuplexConsensusRead, duplex_caller.rs:225-256)."""

    id: str
    bases: np.ndarray
    quals: np.ndarray
    errors: np.ndarray
    ab_consensus: VanillaConsensusRead
    ba_consensus: Optional[VanillaConsensusRead]
    is_ba_only: bool = False
    methylation: object = None  # combined MethylationAnnotation when enabled


def parse_min_reads(values) -> tuple:
    """[total] / [total, ss] / [total, xy, yx] -> (total, xy, yx); high-to-low
    validation (duplex_caller.rs:374-400)."""
    values = list(values)
    if not values or len(values) > 3:
        raise ValueError("min_reads must have 1-3 values: total, [XY, [YX]]")
    last = values[-1]
    total = values[0]
    xy = values[1] if len(values) > 1 else last
    yx = values[2] if len(values) > 2 else last
    if xy > total or yx > xy:
        raise ValueError("min-reads values must be specified high to low (total >= XY >= YX)")
    return total, xy, yx


def split_mi(mi: str):
    """MI -> (base, strand) where strand is 'A'/'B'; raises without suffix."""
    if mi.endswith("/A"):
        return mi[:-2], "A"
    if mi.endswith("/B"):
        return mi[:-2], "B"
    raise ValueError(
        f"Read has MI tag {mi!r} without /A or /B suffix. Duplex consensus requires "
        "input from `group --strategy paired`, which marks the source strand.")


def duplex_combine(ab: Optional[VanillaConsensusRead], ba: Optional[VanillaConsensusRead],
                   source_reads=None) -> Optional[DuplexConsensusRead]:
    """Stage-2 combination (duplex_consensus, duplex_caller.rs:844-1021), vectorized."""
    length = min(len(ab.bases) if ab is not None else np.inf,
                 len(ba.bases) if ba is not None else np.inf)
    length = int(length)
    if ab is not None and not (ab.depths[:length] > 0).any():
        ab = None
    if ba is not None and not (ba.depths[:length] > 0).any():
        ba = None

    def strand_ann(c):
        return c.methylation[0] if c is not None and c.methylation else None

    if ab is None and ba is None:
        return None
    if ba is None:
        return DuplexConsensusRead(id=ab.id, bases=ab.bases, quals=ab.quals,
                                   errors=ab.errors, ab_consensus=ab, ba_consensus=None,
                                   methylation=strand_ann(ab))
    if ab is None:
        return DuplexConsensusRead(id=ba.id, bases=ba.bases, quals=ba.quals,
                                   errors=ba.errors, ab_consensus=ba, ba_consensus=None,
                                   is_ba_only=True, methylation=strand_ann(ba))

    a_b = ab.bases[:length].astype(np.int32)
    b_b = ba.bases[:length].astype(np.int32)
    a_q = ab.quals[:length].astype(np.int32)
    b_q = ba.quals[:length].astype(np.int32)

    agree = a_b == b_b
    a_wins = (~agree) & (a_q > b_q)
    b_wins = (~agree) & (b_q > a_q)
    tie = (~agree) & (a_q == b_q)

    # EM-Seq/TAPS conversion artifact (duplex_caller.rs:897-925): a C/T (or
    # G/A) cross-strand pair at a ref-C position is expected conversion, not
    # a disagreement — call the unconverted base with summed quality and no
    # error contribution
    is_conv = np.zeros(length, dtype=bool)
    unconv = np.zeros(length, dtype=np.int32)
    if ab.methylation is not None or ba.methylation is not None:
        from .methylation import A as _A, C as _C, G as _G, T as _T

        is_ref_c = np.zeros(length, dtype=bool)
        for strand in (ab, ba):
            if strand.methylation is not None:
                ann = strand.methylation[0]
                n = min(length, len(ann.is_ref_c))
                is_ref_c[:n] |= ann.is_ref_c[:n]
        ct_pair = ((a_b == _C) & (b_b == _T)) | ((a_b == _T) & (b_b == _C))
        ga_pair = ((a_b == _G) & (b_b == _A)) | ((a_b == _A) & (b_b == _G))
        is_conv = (~agree) & is_ref_c & (ct_pair | ga_pair)
        unconv = np.where(ct_pair, _C, _G).astype(np.int32)

    raw_base = np.where(is_conv, unconv,
                        np.where(agree | a_wins, a_b, b_b))
    raw_qual = np.where(
        (agree | is_conv), np.clip(a_q + b_q, MIN_PHRED, MAX_PHRED),
        np.where(a_wins, np.clip(a_q - b_q, MIN_PHRED, MAX_PHRED),
                 np.where(b_wins, np.clip(b_q - a_q, MIN_PHRED, MAX_PHRED), MIN_PHRED)))

    either_n = (a_b == N_CODE) | (b_b == N_CODE)
    mask = either_n | (raw_qual == MIN_PHRED) | (tie & ~is_conv)
    bases = np.where(mask, N_CODE, raw_base).astype(np.uint8)
    quals = np.where(mask, MIN_PHRED, raw_qual).astype(np.uint8)

    if source_reads:
        # exact errors: disagreements of each source read base with the raw duplex base
        errors = np.zeros(length, dtype=np.int64)
        for sr in source_reads:
            n = min(len(sr.codes), length)
            src = sr.codes[:n].astype(np.int32)
            err = (src != N_CODE) & (raw_base[:n] != N_CODE) & (src != raw_base[:n])
            errors[:n] += err
        errors = np.minimum(errors, I16_MAX)
    else:
        # approximate from per-strand counts (duplex_caller.rs:958-972)
        a_e = ab.errors[:length]
        b_e = ba.errors[:length]
        a_d = ab.depths[:length]
        b_d = ba.depths[:length]
        errors = np.where(agree, a_e + b_e,
                          np.where(raw_base == a_b, a_e + (b_d - b_e),
                                   b_e + (a_d - a_e)))
        errors = np.minimum(errors, I16_MAX)
    # conversion artifacts count as agreement: no errors (rs:948-951)
    if is_conv.any():
        errors = np.where(is_conv, 0, errors)

    def truncate(c):
        meth = c.methylation
        if meth is not None:
            meth = (meth[0].truncate(length), meth[1])
        return VanillaConsensusRead(
            id=c.id, bases=c.bases[:length], quals=c.quals[:length],
            depths=c.depths[:length], errors=c.errors[:length],
            methylation=meth)

    combined = None
    if ab.methylation is not None or ba.methylation is not None:
        from . import methylation as meth_mod

        combined = meth_mod.combine_annotations(strand_ann(ab), strand_ann(ba),
                                                length)
    return DuplexConsensusRead(id=ab.id, bases=bases, quals=quals, errors=errors,
                               ab_consensus=truncate(ab), ba_consensus=truncate(ba),
                               methylation=combined)


class DuplexConsensusCaller(RejectTracking):
    """Duplex caller over base-MI groups carrying /A and /B strand reads."""

    def __init__(self, read_name_prefix: str, read_group_id: str, min_reads=(1,),
                 min_input_base_quality: int = 10, produce_per_base_tags: bool = True,
                 trim: bool = False, max_reads_per_strand: Optional[int] = None,
                 error_rate_pre_umi: int = 45, error_rate_post_umi: int = 40,
                 seed: Optional[int] = 42, kernel=None,
                 track_rejects: bool = False, methylation_mode=None,
                 reference=None, ref_names=None):
        self.prefix = read_name_prefix
        self.read_group_id = read_group_id
        self.min_total, self.min_xy, self.min_yx = parse_min_reads(min_reads)
        self.produce_per_base_tags = produce_per_base_tags
        # SS caller: min_reads=1, min_consensus_qual=Q2 (duplex_caller.rs:400-420)
        # methylation rides the SS caller's options/reference, exactly like
        # the reference's with_methylation (duplex_caller.rs:437-448)
        ss_opts = VanillaOptions(
            error_rate_pre_umi=error_rate_pre_umi,
            error_rate_post_umi=error_rate_post_umi,
            min_input_base_quality=min_input_base_quality,
            min_reads=1, max_reads=max_reads_per_strand,
            produce_per_base_tags=produce_per_base_tags, seed=seed, trim=trim,
            min_consensus_base_quality=MIN_PHRED,
            methylation_mode=methylation_mode)
        self.ss = VanillaConsensusCaller(read_name_prefix, read_group_id, ss_opts,
                                         kernel=kernel, reference=reference,
                                         ref_names=ref_names)
        self.kernel = self.ss.kernel
        self.stats = CallerStats()
        self._init_rejects(track_rejects)
        self._builder = RecordBuilder()
        self._ordinal = 0
        # the X/Y filter's runs on sets of two reads or more: sets, sets
        # that kept every read, reads in, reads rejected (plain integers a
        # molecule, folded into the run report once a job)
        self.filter_tally = [0, 0, 0, 0]

    def fold_filter_tally(self):
        """The X/Y filter's tally into the run report's ``duplex.filter.*``
        counters; nothing when the filter never ran."""
        tally, self.filter_tally = self.filter_tally, [0, 0, 0, 0]
        if tally[0]:
            for name, count in zip(("sets", "sets_kept_all", "reads_in",
                                    "reads_rejected"), tally):
                METRICS.inc("duplex.filter." + name, count)

    def merged_stats(self) -> CallerStats:
        """Duplex-level stats plus SS-level rejections (e.g. MinorityAlignment
        recorded by the inner vanilla caller's alignment filter)."""
        merged = CallerStats(input_reads=self.stats.input_reads,
                             consensus_reads=self.stats.consensus_reads,
                             rejected=dict(self.stats.rejected))
        for k, v in self.ss.stats.rejected.items():
            merged.reject(k, v)
        return merged

    # ---------------------------------------------------------------- stage 1 prep

    def _prepare_molecule(self, base_mi: str, a_records, b_records):
        """Host prep for one molecule: validation + the four SS jobs
        (process_group, duplex_caller.rs:1755-1983). Returns a dict or None."""
        self.stats.input_reads += len(a_records) + len(b_records)
        ordinal = self._ordinal
        self._ordinal += 1

        # fragments are rejected as NonPairedReads (duplex_caller.rs:2256-2268)
        frags = sum(1 for r in a_records + b_records if not r.flag & FLAG_PAIRED)
        if frags:
            self.stats.reject("FragmentRead", frags)
            self._reject_records(r for r in a_records + b_records
                                 if not r.flag & FLAG_PAIRED)
            a_records = [r for r in a_records if r.flag & FLAG_PAIRED]
            b_records = [r for r in b_records if r.flag & FLAG_PAIRED]

        if not a_records and not b_records:
            return None

        def is_r1(r):
            return (r.flag & FLAG_PAIRED) and (r.flag & FLAG_FIRST)

        def is_r2(r):
            return (r.flag & FLAG_PAIRED) and (r.flag & FLAG_LAST)

        num_a = sum(1 for r in a_records if is_r1(r))
        num_b = sum(1 for r in b_records if is_r1(r))
        num_xy, num_yx = max(num_a, num_b), min(num_a, num_b)
        if not (self.min_total <= num_xy + num_yx and self.min_xy <= num_xy
                and self.min_yx <= num_yx):
            self.stats.reject("InsufficientReads", len(a_records) + len(b_records))
            self._reject_records(a_records)
            self._reject_records(b_records)
            return None

        ab_r1 = [r for r in a_records if is_r1(r)]
        ab_r2 = [r for r in a_records if is_r2(r)]
        ba_r1 = [r for r in b_records if is_r1(r)]
        ba_r2 = [r for r in b_records if is_r2(r)]

        # strand-orientation validation (duplex_caller.rs:1830-1860)
        def same_strand(recs):
            strands = {bool(r.flag & FLAG_REVERSE) for r in recs}
            return len(strands) <= 1

        if a_records and b_records:
            if not same_strand(ab_r1 + ba_r2) or not same_strand(ab_r2 + ba_r1):
                self.stats.reject("PotentialCollision",
                                  len(a_records) + len(b_records))
                self._reject_records(a_records)
                self._reject_records(b_records)
                return None

        # X = AB-R1 + BA-R2, Y = AB-R2 + BA-R1: convert + filter together.
        # Reads dropped here contribute to no consensus even when the
        # molecule succeeds, so they are rejected immediately; prep_ids keeps
        # a later molecule-level failure from double-rejecting them.
        prep_ids = set()

        def prep_reject(recs):
            if self.track_rejects:
                recs = list(recs)
                prep_ids.update(map(id, recs))
                self._reject_records(recs)

        def to_sources(recs):
            out = []
            for i, r in enumerate(recs):
                sr = self.ss._create_source_read(r, i, num_bases_extending_past_mate(r))
                if sr is not None:
                    out.append(sr)
                else:  # unconvertible: 0xFF quals / zero length
                    prep_reject([r])
            return out

        minority = 0  # reads the filter set aside: tallied there

        def filter_alignment(sources, raws_list):
            nonlocal minority
            kept = self.ss._filter_by_alignment(sources)
            if len(sources) >= 2:
                tally = self.filter_tally
                tally[0] += 1
                tally[1] += len(kept) == len(sources)
                tally[2] += len(sources)
                tally[3] += len(sources) - len(kept)
            minority += len(sources) - len(kept)
            if len(kept) < len(sources):
                kept_idx = {sr.original_idx for sr in kept}
                prep_reject(raws_list[sr.original_idx] for sr in sources
                            if sr.original_idx not in kept_idx)
            return kept

        x_raws = ab_r1 + ba_r2
        y_raws = ab_r2 + ba_r1
        filtered_x = filter_alignment(to_sources(x_raws), x_raws)
        filtered_y = filter_alignment(to_sources(y_raws), y_raws)

        f_ab_r1 = [sr for sr in filtered_x if sr.flags & FLAG_FIRST]
        f_ba_r2 = [sr for sr in filtered_x if not sr.flags & FLAG_FIRST]
        f_ab_r2 = [sr for sr in filtered_y if not sr.flags & FLAG_FIRST]
        f_ba_r1 = [sr for sr in filtered_y if sr.flags & FLAG_FIRST]

        ab_umi, ba_umi = f"{base_mi}/A", f"{base_mi}/B"
        jobs = {}
        for key, umi, srs in (("ab_r1", ab_umi, f_ab_r1), ("ab_r2", ab_umi, f_ab_r2),
                              ("ba_r1", ba_umi, f_ba_r1), ("ba_r2", ba_umi, f_ba_r2)):
            job = self.ss.job_from_source_reads(umi, R1, srs, ordinal=ordinal,
                                               keep_source_reads=True)
            if job is not None:
                jobs[key] = job

        raws = {
            "ab_r1": [x_raws[sr.original_idx] for sr in f_ab_r1],
            "ba_r2": [x_raws[sr.original_idx] for sr in f_ba_r2],
            "ab_r2": [y_raws[sr.original_idx] for sr in f_ab_r2],
            "ba_r1": [y_raws[sr.original_idx] for sr in f_ba_r1],
        }
        return {"base_mi": base_mi, "jobs": jobs, "raws": raws,
                # what a molecule-level reject counts: its reads but those
                # already tallied as MinorityAlignment, as the rejects
                # stream leaves them out (prep_ids)
                "n_records": len(a_records) + len(b_records) - minority,
                # molecule-failure rejects: only reads not already rejected
                # during prep (built only when tracking)
                "all_records": [r for r in list(a_records) + list(b_records)
                                if id(r) not in prep_ids]
                if self.track_rejects else ()}

    # ---------------------------------------------------------------- stage 2

    def _has_min_reads(self, dup: DuplexConsensusRead) -> bool:
        num_a = dup.ab_consensus.max_depth()
        num_b = dup.ba_consensus.max_depth() if dup.ba_consensus is not None else 0
        xy, yx = max(num_a, num_b), min(num_a, num_b)
        return (self.min_total <= xy + yx and self.min_xy <= xy and self.min_yx <= yx)

    def _combine_molecule(self, mol, consensus):
        """Stage-2 for one molecule given its SS consensus dict. Returns record
        bytes list (R1 then R2) or None (match arms, duplex_caller.rs:2017-2237)."""
        c = consensus
        ab_r1, ab_r2 = c.get("ab_r1"), c.get("ab_r2")
        ba_r1, ba_r2 = c.get("ba_r1"), c.get("ba_r2")
        raws = mol["raws"]
        base_mi = mol["base_mi"]

        if ab_r1 is not None and ab_r2 is not None and ba_r1 is not None \
                and ba_r2 is not None:
            r1_sources = list(ab_r1.source_reads or []) + list(ba_r2.source_reads or [])
            r2_sources = list(ab_r2.source_reads or []) + list(ba_r1.source_reads or [])
            dr1 = duplex_combine(ab_r1, ba_r2, r1_sources or None)
            dr2 = duplex_combine(ab_r2, ba_r1, r2_sources or None)
            if dr1 is not None and dr2 is not None:
                if self._has_min_reads(dr1) and self._has_min_reads(dr2):
                    recs = [
                        self._build_record(dr1, R1, base_mi, raws["ab_r1"], raws["ba_r2"]),
                        self._build_record(dr2, R2, base_mi, raws["ab_r2"], raws["ba_r1"]),
                    ]
                    self.stats.add_consensus_reads(2)
                    return recs
                self.stats.reject("InsufficientReads", mol["n_records"])
                self._reject_records(mol.get("all_records", ()))
                return None
        elif ab_r1 is not None and ab_r2 is not None and ba_r1 is None \
                and ba_r2 is None:
            if self.min_yx == 0:
                dr1 = duplex_combine(ab_r1, None)
                dr2 = duplex_combine(ab_r2, None)
                if dr1 is not None and dr2 is not None:
                    recs = [
                        self._build_record(dr1, R1, base_mi, raws["ab_r1"], []),
                        self._build_record(dr2, R2, base_mi, raws["ab_r2"], []),
                    ]
                    self.stats.add_consensus_reads(2)
                    return recs
        elif ab_r1 is None and ab_r2 is None and ba_r1 is not None \
                and ba_r2 is not None:
            # BA-only: output R1 derives from BA-R2, R2 from BA-R1 (rs:2179-2231)
            if self.min_yx == 0:
                dr1 = duplex_combine(None, ba_r2)
                dr2 = duplex_combine(None, ba_r1)
                if dr1 is not None and dr2 is not None:
                    recs = [
                        self._build_record(dr1, R1, base_mi, [], raws["ba_r2"]),
                        self._build_record(dr2, R2, base_mi, [], raws["ba_r1"]),
                    ]
                    self.stats.add_consensus_reads(2)
                    return recs
        self.stats.reject("InsufficientReads", mol["n_records"])
        self._reject_records(mol.get("all_records", ()))
        return None

    # ---------------------------------------------------------------- output

    def _build_record(self, dup: DuplexConsensusRead, read_type: int, base_mi: str,
                      raws_a, raws_b) -> bytes:
        """duplex_read_into (duplex_caller.rs:1056-1249); tag order preserved."""
        from ..constants import CODE_TO_BASE

        b = self._builder
        name = f"{self.prefix}:{base_mi}".encode()
        seq = CODE_TO_BASE[np.minimum(dup.bases, N_CODE)].tobytes()
        b.start_unmapped(name, _TYPE_FLAGS[read_type], seq, dup.quals)
        b.tag_str(b"MI", base_mi.encode())
        b.tag_str(b"RG", self.read_group_id.encode())

        def strand_metrics(c: Optional[VanillaConsensusRead]):
            if c is None or not len(c.depths):
                return 0, 0, np.float32(0)
            d = np.minimum(c.depths, I16_MAX)
            e = np.minimum(c.errors, I16_MAX)
            total_d = int(d.sum())
            rate = np.float32(int(e.sum())) / np.float32(total_d) if total_d else np.float32(0)
            return int(d.max()), int(d.min()), rate

        ab, ba = dup.ab_consensus, dup.ba_consensus
        a_max, a_min, a_rate = strand_metrics(ab)
        b.tag_int(b"aD", a_max)
        b.tag_float(b"aE", float(a_rate))
        b.tag_int(b"aM", a_min)
        if self.produce_per_base_tags:
            b.tag_str(b"ac", CODE_TO_BASE[np.minimum(ab.bases, N_CODE)].tobytes())
            b.tag_array_i16(b"ad", np.minimum(ab.depths, I16_MAX))
            b.tag_array_i16(b"ae", np.minimum(ab.errors, I16_MAX))
            b.tag_str(b"aq", (ab.quals + 33).astype(np.uint8).tobytes())

        b_max, b_min, b_rate = strand_metrics(ba)
        b.tag_int(b"bD", b_max)
        b.tag_float(b"bE", float(b_rate))
        b.tag_int(b"bM", b_min)
        if self.produce_per_base_tags and ba is not None:
            b.tag_str(b"bc", CODE_TO_BASE[np.minimum(ba.bases, N_CODE)].tobytes())
            b.tag_array_i16(b"bd", np.minimum(ba.depths, I16_MAX))
            b.tag_array_i16(b"be", np.minimum(ba.errors, I16_MAX))
            b.tag_str(b"bq", (ba.quals + 33).astype(np.uint8).tobytes())

        # combined cD/cE/cM: per-strand per-base clamp before summing (rs:1188-1215)
        length = len(dup.bases)
        comb = np.minimum(ab.depths[:length], I16_MAX).astype(np.int64)
        if ba is not None:
            comb = comb + np.minimum(ba.depths[:length], I16_MAX)
        total_d = int(comb.sum())
        total_e = int(np.minimum(dup.errors, I16_MAX).sum())
        rate = np.float32(total_e) / np.float32(total_d) if total_d else np.float32(0)
        b.tag_int(b"cD", int(comb.max()) if length else 0)
        b.tag_float(b"cE", float(rate))
        b.tag_int(b"cM", int(comb.min()) if length else 0)

        # RX: strand-reoriented UMI consensus (rs:1217-1249)
        first_of_pair = read_type == R1
        all_umis = []
        for raw in list(raws_a) + list(raws_b):
            rx = raw.get_str(b"RX")
            if rx is None:
                continue
            is_first = bool(raw.flag & FLAG_FIRST)
            if is_first == first_of_pair:
                all_umis.append(rx)
            else:
                all_umis.append("-".join(reversed(rx.split("-"))))
        if all_umis:
            b.tag_str(b"RX", consensus_umis(all_umis).encode())

        # methylation tags (EM-Seq/TAPS; duplex_caller.rs:1251-1312): per
        # strand am/au/at (top) / bm/bu/bt (bottom), then combined MM/ML +
        # cu/ct. BA-only molecules store their strand in ab_consensus, so
        # per-strand tags switch to bottom orientation.
        if dup.methylation is not None:
            from . import methylation as meth_mod

            mode = self.ss.options.methylation_mode
            is_top = not dup.is_ba_only
            ab_meth = ab.methylation
            if ab_meth is not None:
                mm_tag, u_tag, t_tag = (b"am", b"au", b"at") if is_top \
                    else (b"bm", b"bu", b"bt")
                got = meth_mod.build_mm_ml(ab.bases, ab_meth[0], is_top, mode)
                if got is not None:
                    b.tag_str(mm_tag, got[0].encode())
                b.tag_array_i16(u_tag, ab_meth[0].cu())
                b.tag_array_i16(t_tag, ab_meth[0].ct())
            if ba is not None and ba.methylation is not None:
                ba_ann = ba.methylation[0]
                got = meth_mod.build_mm_ml(ba.bases, ba_ann, False, mode)
                if got is not None:
                    b.tag_str(b"bm", got[0].encode())
                b.tag_array_i16(b"bu", ba_ann.cu())
                b.tag_array_i16(b"bt", ba_ann.ct())
            got = meth_mod.build_mm_ml(dup.bases, dup.methylation, is_top,
                                       mode)
            if got is not None:
                b.tag_str(b"MM", got[0].encode())
                b.tag_array_u8(b"ML", np.frombuffer(got[1], dtype=np.uint8))
            b.tag_array_i16(b"cu", dup.methylation.cu())
            b.tag_array_i16(b"ct", dup.methylation.ct())
        return b.finish()

    # ---------------------------------------------------------------- driver

    def call_groups(self, groups) -> list:
        """Process [(base_mi, a_records, b_records)] -> consensus record bytes.

        All molecules' SS jobs run as one batched device pass; stage 2 follows on
        host. Output order: molecule order, R1 then R2.
        """
        molecules = []
        for base_mi, a_records, b_records in groups:
            mol = self._prepare_molecule(base_mi, a_records, b_records)
            if mol is not None:
                molecules.append(mol)
        all_jobs = []
        for mol in molecules:
            for job in mol["jobs"].values():
                all_jobs.append(job)
        results = self.ss._run_jobs(all_jobs) if all_jobs else []
        it = iter(results)
        out = []
        for mol in molecules:
            consensus = {}
            for key, job in mol["jobs"].items():
                consensus[key] = self.ss.result_to_consensus_read(job, next(it))
            recs = self._combine_molecule(mol, consensus)
            if recs:
                out.extend(recs)
        return out


def iter_duplex_groups(records, tag: bytes = b"MI", record_filter=None):
    """Group consecutive records by base MI -> (base_mi, a_records, b_records).

    Input must be grouped by base MI (the paired-strategy group output keeps /A and
    /B of a molecule adjacent, mi_group.rs contract)."""
    current_base = None
    a_recs, b_recs = [], []
    for rec in records:
        if record_filter is not None and not record_filter(rec):
            continue
        mi = rec.get_str(tag)
        if mi is None:
            raise ValueError(f"record {rec.name!r} missing {tag.decode()} tag")
        base, strand = split_mi(mi)
        if base != current_base:
            if current_base is not None and (a_recs or b_recs):
                yield current_base, a_recs, b_recs
            current_base = base
            a_recs, b_recs = [], []
        (a_recs if strand == "A" else b_recs).append(rec)
    if current_base is not None and (a_recs or b_recs):
        yield current_base, a_recs, b_recs
