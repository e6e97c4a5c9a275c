"""Vanilla (simplex) UMI consensus caller.

Host-side orchestration mirroring the reference pipeline
(/root/reference/crates/fgumi-consensus/src/vanilla_caller.rs:1119-1331: filter
secondary/supplementary -> min_reads -> downsample -> subgroup fragment/R1/R2 ->
SourceRead conversion (RC, quality mask, mate-overlap trim, trailing-N trim) ->
most-common-alignment filter -> consensus -> raw BAM record with cD/cM/cE/cd/ce/MI),
with the per-position likelihood loop replaced by the batched TPU kernel
(fgumi_tpu.ops.kernel) over padded (family, read, position) tensors.

Determinism contract: downsampling uses a NumPy Philox generator seeded per group
from (seed, group ordinal); the reference documents its own selection as
deterministic-per-seed but not byte-identical to fgbio (vanilla_caller.rs:829-835) —
this build makes the same promise with its own pinned stream.
"""

import threading
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..constants import (BASE_TO_CODE, CODE_TO_BASE, MAX_PHRED, MIN_PHRED,
                         N_CODE, reverse_complement_codes)
from ..core import cigar as cigar_utils
from ..core.overlap import num_bases_extending_past_mate
from ..io.bam import (FLAG_FIRST, FLAG_LAST, FLAG_MATE_UNMAPPED, FLAG_PAIRED,
                      FLAG_REVERSE, FLAG_SECONDARY, FLAG_SUPPLEMENTARY,
                      FLAG_UNMAPPED, RawRecord, RecordBuilder)
from ..ops import oracle
from ..ops.kernel import ConsensusKernel
from ..ops.tables import quality_tables
from .rejects import RejectTracking
from .simple_umi import consensus_umis

I16_MAX = 32767

# Read types (order matters for output: fragment, then R1, then R2).
FRAGMENT, R1, R2 = 0, 1, 2
_TYPE_FLAGS = {
    FRAGMENT: FLAG_UNMAPPED,
    R1: FLAG_UNMAPPED | FLAG_PAIRED | FLAG_FIRST | FLAG_MATE_UNMAPPED,
    R2: FLAG_UNMAPPED | FLAG_PAIRED | FLAG_LAST | FLAG_MATE_UNMAPPED,
}


@dataclass
class VanillaOptions:
    """Mirrors VanillaUmiConsensusOptions defaults (vanilla_caller.rs:327-344)."""

    tag: str = "MI"
    error_rate_pre_umi: int = 45
    error_rate_post_umi: int = 40
    min_input_base_quality: int = 10
    min_reads: int = 2
    max_reads: Optional[int] = None
    produce_per_base_tags: bool = True
    seed: Optional[int] = 42
    trim: bool = False
    min_consensus_base_quality: int = 40
    # None | "em-seq" | "taps" (methylation.rs MethylationMode)
    methylation_mode: Optional[str] = None


@dataclass
class CallerStats:
    """Aggregate statistics (ConsensusCallingStats analog).

    `add_consensus_reads` and `reject` take the lock because whichever
    thread resolves a deferred batch (a resolve worker, the writer stage)
    bumps them while the processing thread does too; input_reads stays on
    the processing thread.
    """

    input_reads: int = 0
    consensus_reads: int = 0
    rejected: dict = field(default_factory=dict)
    lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def add_consensus_reads(self, count: int):
        with self.lock:
            self.consensus_reads += count

    def reject(self, reason: str, count: int):
        with self.lock:
            self.rejected[reason] = self.rejected.get(reason, 0) + count

    def merge(self, other: "CallerStats"):
        self.input_reads += other.input_reads
        self.consensus_reads += other.consensus_reads
        for k, v in other.rejected.items():
            self.reject(k, v)


@dataclass
class SourceRead:
    """Transformed read (vanilla_caller.rs:125-150): oriented, masked, trimmed."""

    original_idx: int
    codes: np.ndarray  # uint8 base codes 0..4
    quals: np.ndarray  # uint8
    simplified_cigar: list
    flags: int
    ref_id: int = -1
    alignment_start: int = -1  # 0-based
    original_cigar: list = None  # simplified, un-reversed (methylation anchor)


@dataclass
class ConsensusJob:
    """One subgroup's device work unit."""

    umi: str
    read_type: int
    codes: list  # list of per-read code arrays (variable length)
    quals: list
    consensus_len: int
    original_raws: list  # RawRecords surviving filtering (for tag extraction)
    source_reads: list = None  # SourceReads (kept when the caller needs them, e.g. duplex)
    methylation: object = None  # (MethylationAnnotation, is_top) when enabled


@dataclass
class VanillaConsensusRead:
    """Intermediate single-strand consensus (VanillaConsensusRead, vanilla_caller.rs:153-180)."""

    id: str
    bases: np.ndarray  # uint8 codes 0..4
    quals: np.ndarray  # uint8
    depths: np.ndarray  # int64, already clamped to I16_MAX per base
    errors: np.ndarray  # int64, already clamped to I16_MAX per base
    source_reads: list = None
    methylation: object = None  # (MethylationAnnotation, is_top) when enabled

    def max_depth(self) -> int:
        return int(self.depths.max()) if len(self.depths) else 0


def find_quality_trim_point(quals: np.ndarray, trim_qual: int) -> int:
    """htsjdk TrimmingUtil.findQualityTrimPoint (vanilla_caller.rs:857-881)."""
    length = len(quals)
    if trim_qual < 1 or length == 0:
        return 0
    score = 0
    max_score = 0
    trim_point = length
    for i in range(length - 1, -1, -1):
        score += trim_qual - int(quals[i])
        if score < 0:
            break
        if score > max_score:
            max_score = score
            trim_point = i
    return trim_point


class VanillaConsensusCaller(RejectTracking):
    """Simplex consensus caller over MI groups, batched onto the TPU kernel."""

    def __init__(self, read_name_prefix: str, read_group_id: str,
                 options: VanillaOptions = None, kernel: ConsensusKernel = None,
                 reference=None, ref_names=None, track_rejects: bool = False):
        """`reference`: chrom -> bytes mapping (or any .get-able) and
        `ref_names`: BAM ref_id -> name list; both required only for
        methylation-aware calling. With `track_rejects`, raw records that do
        not contribute to any consensus accumulate in `rejected_reads` (the
        reference's secondary rejects stream, base.rs:1838)."""
        self.options = options or VanillaOptions()
        self.reference = reference
        self.ref_names = ref_names or []
        self.prefix = read_name_prefix
        self.read_group_id = read_group_id
        self.tables = quality_tables(self.options.error_rate_pre_umi,
                                     self.options.error_rate_post_umi)
        self.kernel = kernel or ConsensusKernel(self.tables)
        self.stats = CallerStats()
        self._init_rejects(track_rejects)
        self._builder = RecordBuilder()
        self._group_ordinal = 0

    # ------------------------------------------------------------------ host prep

    def _create_source_read(self, rec: RawRecord, idx: int, mate_clip: int):
        """SourceRead conversion (create_source_read, vanilla_caller.rs:940-1032)."""
        opts = self.options
        quals = rec.quals()
        read_len = rec.l_seq
        if read_len == 0 or len(quals) != read_len:
            return None
        # BAM spec: absent quals are 0xFF-filled; reject (vanilla_caller.rs:962-967)
        if (quals == 0xFF).all():
            return None
        codes = BASE_TO_CODE[np.frombuffer(rec.seq_bytes(), dtype=np.uint8)]

        is_negative = bool(rec.flag & FLAG_REVERSE)
        if is_negative:
            codes = reverse_complement_codes(codes)
            quals = quals[::-1].copy()
        else:
            codes = codes.copy()

        trim_to = find_quality_trim_point(quals, opts.min_input_base_quality) \
            if opts.trim else read_len

        # mask low-quality bases to N/Q2 up to the trim point
        mask = quals[:trim_to] < opts.min_input_base_quality
        codes[:trim_to][mask] = N_CODE
        quals[:trim_to][mask] = MIN_PHRED

        final_len = min(max(read_len - mate_clip, 0), trim_to)
        while final_len > 0 and codes[final_len - 1] == N_CODE:
            final_len -= 1
        if final_len == 0:
            return None

        original_simplified = cigar_utils.simplify(rec.cigar())
        simplified = original_simplified
        if is_negative:
            simplified = cigar_utils.reverse(simplified)
        simplified = cigar_utils.truncate_to_query_length(simplified, final_len)

        return SourceRead(original_idx=idx, codes=codes[:final_len],
                          quals=quals[:final_len], simplified_cigar=simplified,
                          flags=rec.flag, ref_id=rec.ref_id,
                          alignment_start=rec.pos,
                          original_cigar=original_simplified)

    def _filter_by_alignment(self, source_reads):
        """Most-common-alignment filter (vanilla_caller.rs:1038-1089)."""
        if len(source_reads) < 2:
            return source_reads
        indexed = sorted(
            ((i, len(sr.codes), sr.simplified_cigar) for i, sr in enumerate(source_reads)),
            key=lambda t: -t[1],
        )
        keep = set(cigar_utils.select_most_common_alignment_group(indexed))
        rejected = len(source_reads) - len(keep)
        if rejected:
            self.stats.reject("MinorityAlignment", rejected)
        return [sr for i, sr in enumerate(source_reads) if i in keep]

    def _annotate_methylation(self, source_reads):
        """EM-Seq/TAPS annotate + normalize (vanilla_caller.rs
        annotate_and_normalize): maps the longest read's query positions to the
        reference, counts conversion evidence at ref-C positions, and rewrites
        converted bases so scoring treats conversion as agreement.

        Returns (annotation, is_top) or None when disabled/unmappable.
        """
        if not self.options.methylation_mode or self.reference is None:
            return None
        if not source_reads:
            return None
        from . import methylation

        anchor = max(source_reads, key=lambda sr: len(sr.codes))
        if anchor.ref_id < 0 or anchor.alignment_start < 0 \
                or anchor.ref_id >= len(self.ref_names):
            return None
        ref_name = self.ref_names[anchor.ref_id]
        ref_seq = self.reference.get(ref_name) \
            if hasattr(self.reference, "get") else None
        if ref_seq is None:
            # warn once: a BAM/FASTA contig-name mismatch (chr1 vs 1) would
            # otherwise silently disable methylation for the whole run
            if not getattr(self, "_warned_missing_contig", False):
                self._warned_missing_contig = True
                import logging

                logging.getLogger("fgumi_tpu").warning(
                    "contig %r not found in the reference FASTA; methylation "
                    "annotation is skipped for reads on missing contigs",
                    ref_name)
            return None
        is_top = methylation.is_top_strand(anchor.flags)
        ref_positions = methylation.query_to_ref_positions(
            anchor.simplified_cigar, anchor.alignment_start,
            bool(anchor.flags & FLAG_REVERSE), anchor.original_cigar or [])
        ref_codes = methylation.ref_codes_at_positions(ref_positions, ref_seq)
        annotation = methylation.annotate(source_reads, ref_codes, is_top)
        methylation.normalize_source_reads(source_reads, annotation, is_top)
        return annotation, is_top

    def _downsample(self, items: list, rng) -> list:
        """Seeded shuffle-take-max_reads (vanilla_caller.rs:799-845)."""
        max_reads = self.options.max_reads
        if max_reads is None or len(items) <= max_reads:
            return items
        perm = rng.permutation(len(items))[:max_reads]
        return [items[i] for i in perm]

    def prepare_group(self, umi: str, records: list):
        """Host prep for one MI group -> list of ConsensusJob (process_group)."""
        self.stats.input_reads += len(records)
        opts = self.options
        ordinal = self._group_ordinal
        self._group_ordinal += 1

        reads = [r for r in records
                 if not r.flag & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY)]
        if len(reads) < len(records):
            self.stats.reject("SecondaryOrSupplementary", len(records) - len(reads))
            self._reject_records(
                r for r in records
                if r.flag & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY))
        if not reads:
            return []
        if len(reads) < opts.min_reads:
            self.stats.reject("InsufficientReads", len(reads))
            self._reject_records(reads)
            return []

        if opts.max_reads is not None and len(reads) > opts.max_reads:
            rng = np.random.Generator(np.random.Philox(key=(opts.seed or 0) + ordinal))
            reads = self._downsample(reads, rng)

        # subgroup by read type (vanilla_caller.rs:1096-1116)
        subgroups = {FRAGMENT: [], R1: [], R2: []}
        for r in reads:
            flg = r.flag
            if not flg & FLAG_PAIRED:
                subgroups[FRAGMENT].append(r)
            elif flg & FLAG_FIRST:
                subgroups[R1].append(r)
            elif flg & FLAG_LAST:
                subgroups[R2].append(r)

        jobs = {}
        for read_type in (FRAGMENT, R1, R2):
            group_reads = subgroups[read_type]
            if not group_reads:
                continue
            if len(group_reads) < opts.min_reads:
                self.stats.reject("InsufficientReads", len(group_reads))
                self._reject_records(group_reads)
                continue
            source_reads = []
            zero_len = 0
            for idx, rec in enumerate(group_reads):
                clip = num_bases_extending_past_mate(rec)
                sr = self._create_source_read(rec, idx, clip)
                if sr is None:
                    zero_len += 1
                    self._reject_records([rec])
                else:
                    source_reads.append(sr)
            if zero_len:
                self.stats.reject("ZeroLengthAfterTrimming", zero_len)
            if len(source_reads) < opts.min_reads:
                if source_reads:
                    self.stats.reject("InsufficientReads", len(source_reads))
                    self._reject_records(
                        group_reads[sr.original_idx] for sr in source_reads)
                continue
            before = source_reads
            source_reads = self._filter_by_alignment(source_reads)
            if len(source_reads) < len(before):
                kept_idx = {sr.original_idx for sr in source_reads}
                self._reject_records(group_reads[sr.original_idx]
                                     for sr in before
                                     if sr.original_idx not in kept_idx)
            if len(source_reads) < opts.min_reads:
                if source_reads:
                    self.stats.reject("InsufficientReads", len(source_reads))
                    self._reject_records(
                        group_reads[sr.original_idx] for sr in source_reads)
                continue
            meth = self._annotate_methylation(source_reads)
            lengths = sorted((len(sr.codes) for sr in source_reads), reverse=True)
            consensus_len = lengths[opts.min_reads - 1]
            jobs[read_type] = ConsensusJob(
                umi=umi, read_type=read_type,
                codes=[sr.codes for sr in source_reads],
                quals=[sr.quals for sr in source_reads],
                consensus_len=consensus_len,
                original_raws=[group_reads[sr.original_idx] for sr in source_reads],
                methylation=meth,
            )

        # orphan R1/R2 handling (vanilla_caller.rs:1166-1185): both or neither
        out = []
        if FRAGMENT in jobs:
            out.append(jobs[FRAGMENT])
        r1, r2 = jobs.get(R1), jobs.get(R2)
        if r1 is not None and r2 is not None:
            out.extend([r1, r2])
        elif r1 is not None:
            self.stats.reject("OrphanConsensus", len(r1.codes))
            self._reject_records(r1.original_raws)
        elif r2 is not None:
            self.stats.reject("OrphanConsensus", len(r2.codes))
            self._reject_records(r2.original_raws)
        return out

    def job_from_source_reads(self, umi: str, read_type: int, source_reads,
                              ordinal: int = 0, keep_source_reads: bool = False):
        """consensus_call analog (vanilla_caller.rs:635-706): build a ConsensusJob
        from pre-filtered SourceReads. The max_reads cap shapes only the consensus
        scoring set; the full set is retained on the job when requested (fgbio passes
        the pre-cap reads to duplexConsensus)."""
        opts = self.options
        if not source_reads or len(source_reads) < opts.min_reads:
            return None
        capped = source_reads
        if opts.max_reads is not None and len(source_reads) > opts.max_reads:
            rng = np.random.Generator(np.random.Philox(key=(opts.seed or 0) + ordinal))
            capped = self._downsample(source_reads, rng)
        if len(capped) < opts.min_reads:
            return None
        # methylation annotate + normalize on the scoring set (the duplex
        # SS stage's analog of prepare_group's annotation; duplex_caller.rs
        # routes methylation through ss_caller.options)
        meth = self._annotate_methylation(capped)
        lengths = sorted((len(sr.codes) for sr in capped), reverse=True)
        consensus_len = lengths[opts.min_reads - 1]
        return ConsensusJob(
            umi=umi, read_type=read_type,
            codes=[sr.codes for sr in capped], quals=[sr.quals for sr in capped],
            consensus_len=consensus_len, original_raws=[],
            source_reads=source_reads if keep_source_reads else None,
            methylation=meth)

    def result_to_consensus_read(self, job: ConsensusJob, result) -> VanillaConsensusRead:
        """Wrap a job's (already thresholded) _run_jobs outputs as a
        VanillaConsensusRead; per-base depths/errors clamp to fgbio's Short ceiling
        (vanilla_caller.rs:1414-1424)."""
        bases, quals, depth, errors = result
        return VanillaConsensusRead(
            id=job.umi, bases=np.asarray(bases), quals=np.asarray(quals),
            depths=np.minimum(depth, I16_MAX), errors=np.minimum(errors, I16_MAX),
            source_reads=job.source_reads, methylation=job.methylation)

    # ------------------------------------------------------------------ device

    def _run_jobs(self, jobs):
        """Execute jobs: single-read on host, multi-read via ONE ragged
        segment-sum dispatch (ops/kernel.route_and_call_segments) per call.

        One device execution per job batch regardless of family-size mix —
        the same dense layout the fast simplex engine uses (consensus/fast.py
        _dispatch_jobs), so duplex/CODEC/classic callers share its economics.
        Returns per-job (bases_codes, quals, depths, errors) pre-threshold
        clamped arrays trimmed to consensus_len.
        """
        results = [None] * len(jobs)
        multi = []
        for j, job in enumerate(jobs):
            if len(job.codes) == 1:
                b, q, d, e = oracle.single_read_consensus(
                    job.codes[0][: job.consensus_len],
                    job.quals[0][: job.consensus_len],
                    self.tables, self.options.min_consensus_base_quality)
                results[j] = (b, q, d, e)
            else:
                multi.append(j)
        if not multi:
            return results

        L_max = -(-max(jobs[j].consensus_len for j in multi) // 16) * 16
        counts = np.array([len(jobs[j].codes) for j in multi], dtype=np.int64)
        N = int(counts.sum())

        if N <= 64:
            # Tiny workload (typically a batch-boundary carry group): call the
            # f64 oracle on host. The device result is defined as oracle-
            # integer-exact (guard band + suspect patch), so this is the same
            # bytes — without a micro dispatch that would serialize behind the
            # in-flight big batch on the device queue (round-4 profile: 0.6s
            # of queue wait per boundary group, ~10% of simplex wall).
            for j in multi:
                job = jobs[j]
                L = job.consensus_len
                R = len(job.codes)
                codes = np.full((R, L), N_CODE, dtype=np.uint8)
                quals = np.zeros((R, L), dtype=np.uint8)
                for r, (c, q) in enumerate(zip(job.codes, job.quals)):
                    n = min(len(c), L)
                    codes[r, :n] = c[:n]
                    quals[r, :n] = q[:n]
                w, q_, d, e = oracle.call_family(codes, quals, self.tables)
                b_j, q_j = oracle.apply_consensus_thresholds(
                    w, q_, d, self.options.min_reads,
                    self.options.min_consensus_base_quality)
                results[j] = (b_j, q_j, d, e)
            return results
        codes2d = np.full((N, L_max), N_CODE, dtype=np.uint8)
        quals2d = np.zeros((N, L_max), dtype=np.uint8)
        row = 0
        for j in multi:
            job = jobs[j]
            for c, q in zip(job.codes, job.quals):
                n = min(len(c), L_max)
                codes2d[row, :n] = c[:n]
                quals2d[row, :n] = q[:n]
                row += 1
        # same adaptive routing as the fast engines (ops/router.py) —
        # classic/--classic runs share their link economics
        from ..ops.kernel import route_and_call_segments

        w, q_, d, e = route_and_call_segments(self.kernel, codes2d, quals2d,
                                              counts)
        for fi, j in enumerate(multi):
            L = jobs[j].consensus_len
            b_j, q_j = oracle.apply_consensus_thresholds(
                w[fi, :L], q_[fi, :L], d[fi, :L],
                self.options.min_reads, self.options.min_consensus_base_quality)
            results[j] = (b_j, q_j, d[fi, :L], e[fi, :L])
        return results

    # ------------------------------------------------------------------ output

    def _build_record(self, job: ConsensusJob, bases_codes, quals, depths, errors) -> bytes:
        """Serialize a consensus record (build_consensus_record_into,
        vanilla_caller.rs:1452-1540). Per-base depths/errors clamp to i16::MAX
        (fgbio Short semantics, vanilla_caller.rs:1414-1424)."""
        depths16 = np.minimum(depths, I16_MAX).astype(np.int32)
        errors16 = np.minimum(errors, I16_MAX).astype(np.int32)
        name = f"{self.prefix}:{job.umi}".encode()
        seq = CODE_TO_BASE[np.minimum(bases_codes, N_CODE)].tobytes()
        b = self._builder
        b.start_unmapped(name, _TYPE_FLAGS[job.read_type], seq, quals)
        b.tag_str(b"RG", self.read_group_id.encode())
        b.tag_int(b"cD", int(depths16.max()) if len(depths16) else 0)
        b.tag_int(b"cM", int(depths16.min()) if len(depths16) else 0)
        total_depth = int(depths16.sum())
        total_errors = int(errors16.sum())
        rate = np.float32(total_errors) / np.float32(total_depth) if total_depth else np.float32(0)
        b.tag_float(b"cE", float(rate))
        if self.options.produce_per_base_tags:
            b.tag_array_i16(b"cd", depths16)
            b.tag_array_i16(b"ce", errors16)
        b.tag_str(b"MI", job.umi.encode())
        # consensus RX from the surviving input reads' RX tags (vanilla_caller.rs:1522-1536)
        rx_umis = [u for u in (rec.get_str(b"RX") for rec in job.original_raws)
                   if u is not None]
        if rx_umis:
            b.tag_str(b"RX", consensus_umis(rx_umis).encode())
        # methylation tags (EM-Seq/TAPS; vanilla_caller.rs:1538-1560)
        if job.methylation is not None:
            from . import methylation as meth_mod

            annotation, anchor_is_top = job.methylation
            annotation = annotation.truncate(len(bases_codes))
            is_top = anchor_is_top
            if job.original_raws:
                is_top = meth_mod.is_top_strand(job.original_raws[0].flag)
            got = meth_mod.build_mm_ml(np.asarray(bases_codes), annotation,
                                       is_top, self.options.methylation_mode)
            if got is not None:
                mm, ml = got
                b.tag_str(b"MM", mm.encode())
                b.tag_array_u8(b"ML", np.frombuffer(ml, dtype=np.uint8))
            b.tag_array_i16(b"cu", annotation.cu())
            b.tag_array_i16(b"ct", annotation.ct())
        self.stats.add_consensus_reads(1)
        return b.finish()

    def call_groups(self, groups) -> list:
        """Process [(umi, [RawRecord])] -> list of consensus record bytes.

        Output order: group order, fragment/R1/R2 within a group (process_group).
        """
        jobs = []
        for umi, records in groups:
            jobs.extend(self.prepare_group(umi, records))
        if not jobs:
            return []
        results = self._run_jobs(jobs)
        return [self._build_record(job, *res) for job, res in zip(jobs, results)]
