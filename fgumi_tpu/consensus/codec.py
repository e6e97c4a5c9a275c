"""CODEC consensus caller: one read-pair sequences both strands.

Mirrors /root/reference/crates/fgumi-consensus/src/codec_caller.rs:
- phase 1: keep paired primary reads; fragments rejected (codec_caller.rs:609-631);
- phase 2: pair R1/R2 by name; a template must be exactly one primary FR pair
  (symmetric per-pair test, codec_caller.rs:647-686); overlap clip amounts come
  from the mate record in hand, soft-only boundary (overlap.rs:156-165);
- phase 3: per-strand most-common-alignment filtering on clipped CIGARs
  (codec_caller.rs:722-738, 961-1002);
- phase 4: genomic-overlap geometry on the longest R1/R2 by reference length,
  min_duplex_length check, phase (indel) check, consensus length
  (codec_caller.rs:740-794, 1005-1062);
- phase 5: single-strand consensus per strand via the vanilla caller
  (min_reads=1, per-base tags, no masking/trim in SourceRead conversion,
  codec_caller.rs:378-402, 467-532, 796-847), RC one side, lowercase-'n' pad
  (codec_caller.rs:849-857, 1064-1116);
- duplex combine per position: agreement sums quality (cap Q93), disagreement
  takes the higher-quality base with the difference, ties keep base A at Q2;
  either-N masks; exact fgbio error accounting (codec_caller.rs:1118-1296);
- high-duplex-disagreement count/rate rejects are recoverable group drops
  (codec_caller.rs:99-141, 858-875);
- quality masks: outer bases assigned first, then single-strand regions
  (codec_caller.rs:1298-1345);
- output: single unmapped fragment with RG/MI/cD/cM/cE/aD/aM/aE/bD/bM/bE
  [+ad/bd/ae/be/ac/bc/aq/bq] [+CB] [+RX] (codec_caller.rs:1364-1539).

The single-strand hot loop runs on the batched TPU kernel through the shared
vanilla job machinery; geometry and the pairwise combine are vectorized host math.
"""

import threading
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..constants import (CODE_TO_BASE, MAX_PHRED, MIN_PHRED, N_CODE,
                         NO_CALL_BASE, NO_CALL_BASE_LOWER)
from ..core import cigar as cigar_utils
from ..core.overlap import (is_primary_fr_pair,
                            num_bases_extending_past_mate_vs_mate)
from ..io.bam import (FLAG_FIRST, FLAG_PAIRED, FLAG_REVERSE, FLAG_SECONDARY,
                      FLAG_SUPPLEMENTARY, FLAG_UNMAPPED, RawRecord,
                      RecordBuilder)
from ..observe.metrics import METRICS
from ..ops.kernel import ConsensusKernel
from .simple_umi import consensus_umis
from .vanilla import (I16_MAX, R1, SourceRead, VanillaConsensusCaller,
                      VanillaOptions)

# ASCII complement preserving case ('n' pads survive RC, codec_caller.rs:1064-1073).
_ASCII_COMPLEMENT = np.arange(256, dtype=np.uint8)
for _a, _b in zip(b"ACGTacgt", b"TGCAtgca"):
    _ASCII_COMPLEMENT[_a] = _b


class DuplexDisagreementError(Exception):
    """Recoverable reject: the molecule exceeded duplex-disagreement limits."""

    def __init__(self, kind: str, value):
        self.kind = kind  # "count" | "rate"
        self.value = value
        super().__init__(f"High duplex disagreement {kind}: {value}")


@dataclass
class CodecOptions:
    """Mirrors CodecConsensusOptions defaults (codec_caller.rs:192-212)."""

    min_input_base_quality: int = 10
    error_rate_pre_umi: int = 45
    error_rate_post_umi: int = 40
    min_reads_per_strand: int = 1
    max_reads_per_strand: Optional[int] = None
    min_duplex_length: int = 1
    single_strand_qual: Optional[int] = None
    outer_bases_qual: Optional[int] = None
    outer_bases_length: int = 5
    max_duplex_disagreements: Optional[int] = None  # None = unlimited
    max_duplex_disagreement_rate: float = 1.0
    cell_tag: Optional[str] = None
    produce_per_base_tags: bool = False
    trim: bool = False
    min_consensus_base_quality: int = 0
    seed: int = 42


#: what `CodecStats.filter_tally` counts: sets, sets that kept every read,
#: reads in, reads rejected (``MinorityAlignment``)
FILTER_TALLY = ("sets", "sets_kept_all", "reads_in", "reads_rejected")


@dataclass
class CodecStats:
    """CodecConsensusStats analog (codec_caller.rs:214-259).

    `reject` and `add` take the lock because the batch engine's stage 2
    bumps them from whichever thread resolves a chunk (fast_codec.py) while
    the processing thread records the prepare phases' rejects;
    total_input_reads stays on the processing thread.
    """

    total_input_reads: int = 0
    consensus_reads_generated: int = 0
    reads_filtered: int = 0
    consensus_reads_rejected_hdd: int = 0
    consensus_duplex_bases_emitted: int = 0
    duplex_disagreement_base_count: int = 0
    rejection_reasons: dict = field(default_factory=dict)
    #: the most-common-alignment filter's runs on sets of two reads or more
    #: (`FILTER_TALLY`), plain integers on the processing thread; where it
    #: runs is the engine's choice (the batch engine runs it on the
    #: molecules with a CIGAR other than one M run and in the per-molecule
    #: ``prepare()``), so engines compare equal without it
    filter_tally: list = field(default_factory=lambda: [0, 0, 0, 0],
                               compare=False)
    lock: threading.Lock = field(default_factory=threading.Lock, repr=False,
                                 compare=False)

    def reject(self, reason: str, count: int):
        with self.lock:
            self.rejection_reasons[reason] = \
                self.rejection_reasons.get(reason, 0) + count
            self.reads_filtered += count

    def add(self, **counts):
        """Add to the named tallies (the fields above but the dict)."""
        with self.lock:
            for name, count in counts.items():
                setattr(self, name, getattr(self, name) + count)

    def fold_filter_tally(self):
        """The filter's tally into the run report's ``codec.filter.*``
        counters; nothing when the filter never ran."""
        tally, self.filter_tally = self.filter_tally, [0, 0, 0, 0]
        if tally[0]:
            for name, count in zip(FILTER_TALLY, tally):
                METRICS.inc("codec.filter." + name, count)

    def duplex_disagreement_rate(self) -> float:
        if self.consensus_duplex_bases_emitted:
            return self.duplex_disagreement_base_count / self.consensus_duplex_bases_emitted
        return 0.0


@dataclass
class _SS:
    """Single-strand consensus in ASCII byte space (codec_caller.rs:261-284)."""

    bases: np.ndarray  # uint8 ASCII, 'n' = pad
    quals: np.ndarray  # uint8
    depths: np.ndarray  # int64
    errors: np.ndarray  # int64
    raw_read_count: int


def _rc_ss(ss: _SS) -> _SS:
    """Reverse-complement; depths/errors reverse with the bases (rs:557-578)."""
    return _SS(bases=_ASCII_COMPLEMENT[ss.bases[::-1]],
               quals=ss.quals[::-1].copy(), depths=ss.depths[::-1].copy(),
               errors=ss.errors[::-1].copy(), raw_read_count=ss.raw_read_count)


def combine_arrays(bases_a, bases_b, quals_a, quals_b, da, db, ea, eb):
    """Elementwise duplex-combine (rs:1127-1296), shared by the classic
    per-molecule `_combine` and the batch engine's concatenated pass
    (fast_codec.py `_finish_batch`) so the rules live in one place.

    Inputs are ASCII-base uint8 / qual uint8 / integer depth+error arrays of
    equal length (int64 on the classic path; the batch engine passes int32
    with values pre-capped at I16_MAX — sums here stay ~2x I16_MAX, so any
    int dtype >= int32 is safe); returns (base u8, qual u8, depth, errors,
    both, disag) with the either-strand N mask and the I16 caps applied.
    """
    ba, bb = bases_a.astype(np.int32), bases_b.astype(np.int32)
    qa, qb = quals_a.astype(np.int32), quals_b.astype(np.int32)

    a_has = (ba != NO_CALL_BASE) & (ba != NO_CALL_BASE_LOWER)
    b_has = (bb != NO_CALL_BASE) & (bb != NO_CALL_BASE_LOWER)
    both = a_has & b_has
    agree = both & (ba == bb)
    a_wins = both & ~agree & (qa > qb)
    b_wins = both & ~agree & (qb > qa)
    tie = both & ~agree & (qa == qb)

    raw_base = np.where(b_wins, bb, ba)  # agree/a_wins/tie keep base A
    # np.where chains, not np.select: select's broadcast machinery
    # dominated the per-molecule combine cost
    raw_qual = np.where(
        agree, np.minimum(93, qa + qb),
        np.where(a_wins, np.maximum(MIN_PHRED, qa - qb),
                 np.where(b_wins, np.maximum(MIN_PHRED, qb - qa),
                          np.where(tie, np.int32(MIN_PHRED),
                                   np.int32(0)))))
    # min-quality masking inside the duplex region (rs:1185-1190)
    q_masked = both & (raw_qual == MIN_PHRED)
    dup_base = np.where(q_masked, NO_CALL_BASE, raw_base)
    dup_qual = np.where(q_masked, MIN_PHRED, raw_qual)

    cap = lambda x: np.minimum(x, I16_MAX)
    dup_depth = cap(da) + cap(db)
    chose_a = agree | a_wins | tie
    dup_err = np.where(agree, ea + eb,
                       np.where(chose_a, ea + np.maximum(db - eb, 0),
                                eb + np.maximum(da - ea, 0)))

    only_a = a_has & ~b_has
    only_b = b_has & ~a_has
    a_q2 = qa == MIN_PHRED
    b_q2 = qb == MIN_PHRED

    base = np.where(
        both, dup_base,
        np.where(only_a, np.where(a_q2, NO_CALL_BASE, ba),
                 np.where(only_b, np.where(b_q2, NO_CALL_BASE, bb),
                          NO_CALL_BASE)))
    qual = np.where(
        both, dup_qual,
        np.where(only_a & ~a_q2, qa,
                 np.where(only_b & ~b_q2, qb, MIN_PHRED)))
    depth = np.where(both, dup_depth,
                     np.where(only_a, da, np.where(only_b, db, 0)))
    errors = np.where(both, dup_err,
                      np.where(only_a, ea,
                               np.where(only_b, eb, cap(ea + eb))))

    # either-strand uppercase-N mask, applied after rawBase math (rs:1253-1260)
    n_mask = (ba == NO_CALL_BASE) | (bb == NO_CALL_BASE)
    base = np.where(n_mask, NO_CALL_BASE, base).astype(np.uint8)
    qual = np.where(n_mask, MIN_PHRED, qual).astype(np.uint8)
    return (base, qual, np.minimum(depth, 2 * I16_MAX),
            np.minimum(errors, I16_MAX), both, a_wins | b_wins | tie)


def _pad_ss(ss: _SS, new_length: int, pad_left: bool) -> _SS:
    """Pad with lowercase 'n' / Q0 / depth 0 (rs:1064-1116)."""
    cur = len(ss.bases)
    if new_length <= cur:
        return ss
    n = new_length - cur
    pads = (np.full(n, NO_CALL_BASE_LOWER, dtype=np.uint8), np.zeros(n, np.uint8),
            np.zeros(n, np.int64), np.zeros(n, np.int64))
    arrays = (ss.bases, ss.quals, ss.depths, ss.errors)
    joined = [np.concatenate([p, a] if pad_left else [a, p])
              for p, a in zip(pads, arrays)]
    return _SS(*joined, raw_read_count=ss.raw_read_count)


@dataclass
class _ClippedInfo:
    """Per-record clip metadata (ClippedRecordInfo, codec_caller.rs:294-313)."""

    raw_idx: int
    clip_amount: int
    clip_from_start: bool
    clipped_seq_len: int
    clipped_cigar: list
    adjusted_pos: int  # 1-based, start-clip adjusted
    flags: int


class CodecConsensusCaller:
    """CODEC caller over MI groups; SS stage batched onto the TPU kernel."""

    def __init__(self, read_name_prefix: str, read_group_id: str,
                 options: CodecOptions = None, kernel: ConsensusKernel = None,
                 track_rejects: bool = False):
        self.options = options or CodecOptions()
        self.prefix = read_name_prefix
        self.read_group_id = read_group_id
        # SS delegation mirrors fgbio's ssCaller init (codec_caller.rs:378-402):
        # min_reads=1, per-base tags on, min consensus quality 0 (codec masks itself).
        ss_opts = VanillaOptions(
            error_rate_pre_umi=self.options.error_rate_pre_umi,
            error_rate_post_umi=self.options.error_rate_post_umi,
            min_input_base_quality=self.options.min_input_base_quality,
            min_reads=1, max_reads=None, produce_per_base_tags=True,
            seed=None, trim=False, min_consensus_base_quality=0)
        self.ss = VanillaConsensusCaller(read_name_prefix, read_group_id, ss_opts,
                                         kernel=kernel)
        self.kernel = self.ss.kernel
        self.stats = CodecStats()
        self._builder = RecordBuilder()
        self._counter = 0
        # Deterministic downsampling stream; the reference pins StdRng seed 42
        # (codec_caller.rs:376) — this build pins its own Philox stream.
        self._rng = np.random.Generator(np.random.Philox(key=self.options.seed))
        self.track_rejects = track_rejects
        self.rejected_reads = []

    # ------------------------------------------------------------ geometry prep

    def _build_clipped_info(self, rec: RawRecord, raw_idx: int,
                            clip_amount: int) -> _ClippedInfo:
        """build_clipped_info (codec_caller.rs:910-945)."""
        flg = rec.flag
        clip_from_start = bool(flg & FLAG_REVERSE)
        clipped_cigar, ref_consumed = cigar_utils.clip_cigar(
            rec.cigar(), clip_amount, clip_from_start)
        adjusted = rec.pos + 1 + (ref_consumed if clip_from_start else 0)
        return _ClippedInfo(
            raw_idx=raw_idx, clip_amount=clip_amount,
            clip_from_start=clip_from_start,
            clipped_seq_len=max(rec.l_seq - clip_amount, 0),
            clipped_cigar=clipped_cigar, adjusted_pos=adjusted, flags=flg)

    def _filter_most_common_alignment(self, infos: list) -> list:
        """Most-common-alignment filter on clipped CIGARs (rs:961-1002)."""
        if len(infos) < 2:
            return infos
        indexed = []
        for i, info in enumerate(infos):
            cig = cigar_utils.simplify(info.clipped_cigar)
            if info.flags & FLAG_REVERSE:
                cig = cigar_utils.reverse(cig)
            indexed.append((i, info.clipped_seq_len, cig))
        indexed.sort(key=lambda t: -t[1])
        keep = set(cigar_utils.select_most_common_alignment_group(indexed))
        rejected = len(infos) - len(keep)
        tally = self.stats.filter_tally
        tally[0] += 1
        tally[1] += not rejected
        tally[2] += len(infos)
        tally[3] += rejected
        if rejected:
            self.stats.reject("MinorityAlignment", rejected)
        return [info for i, info in enumerate(infos) if i in keep]

    def _to_source_read(self, rec: RawRecord, idx: int,
                        info: _ClippedInfo) -> SourceRead:
        """to_source_read_for_codec_raw (rs:467-532): clip, RC if negative;
        no quality masking / trailing-N trim / quality trimming."""
        from ..constants import BASE_TO_CODE, reverse_complement_codes

        codes = BASE_TO_CODE[np.frombuffer(rec.seq_bytes(), dtype=np.uint8)]
        quals = rec.quals()
        clip = min(info.clip_amount, len(codes))
        if clip:
            if info.clip_from_start:
                codes, quals = codes[clip:], quals[clip:]
            else:
                codes, quals = codes[:-clip], quals[:-clip]
        simplified = cigar_utils.simplify(info.clipped_cigar)
        if info.flags & FLAG_REVERSE:
            codes = reverse_complement_codes(codes)
            quals = quals[::-1]
            simplified = cigar_utils.reverse(simplified)
        else:
            codes = codes.copy()
        return SourceRead(original_idx=idx, codes=codes, quals=quals.copy(),
                          simplified_cigar=simplified, flags=rec.flag)

    def prepare(self, records: list, umi: Optional[str] = None):
        """Phases 1-5 host prep for one MI group (consensus_reads_raw,
        codec_caller.rs:589-836). Returns a molecule dict with the two SS jobs,
        or None (rejected; reasons recorded). `umi` is the group key (from the
        grouping tag); falls back to the first record's MI tag."""
        self.stats.total_input_reads += len(records)
        if not records:
            return None
        if umi is None:
            umi = records[0].get_str(b"MI")

        # Phase 1: paired primary reads only.
        paired = []
        frag_count = 0
        for i, rec in enumerate(records):
            flg = rec.flag
            if not flg & FLAG_PAIRED:
                frag_count += 1
                continue
            if flg & (FLAG_SECONDARY | FLAG_SUPPLEMENTARY):
                continue
            paired.append((i, rec))
        if frag_count:
            self.stats.reject("FragmentRead", frag_count)
        if not paired:
            return None

        # Phase 2: bucket by name (first-appearance order), require one FR pair.
        by_name = {}
        for i, rec in paired:
            by_name.setdefault(rec.name, []).append((i, rec))
        r1_infos, r2_infos = [], []
        for name, bucket in by_name.items():
            if len(bucket) != 2 or not is_primary_fr_pair(bucket[0][1], bucket[1][1]):
                self.stats.reject("NotPrimaryFrPair", len(bucket))
                continue
            (ia, a), (ib, b) = bucket
            (i1, rec1), (i2, rec2) = ((ia, a), (ib, b)) if a.flag & FLAG_FIRST \
                else ((ib, b), (ia, a))
            clip1 = num_bases_extending_past_mate_vs_mate(rec1, rec2)
            clip2 = num_bases_extending_past_mate_vs_mate(rec2, rec1)
            r1_infos.append(self._build_clipped_info(rec1, i1, clip1))
            r2_infos.append(self._build_clipped_info(rec2, i2, clip2))
        if not r1_infos:
            return None
        if len(r1_infos) < self.options.min_reads_per_strand:
            self.stats.reject("InsufficientReads", len(r1_infos) + len(r2_infos))
            return None

        # Downsample pairs (rs:701-720).
        max_pairs = self.options.max_reads_per_strand
        if max_pairs is not None and len(r1_infos) > max_pairs:
            idxs = sorted(self._rng.permutation(len(r1_infos))[:max_pairs])
            r1_infos = [r1_infos[i] for i in idxs]
            r2_infos = [r2_infos[i] for i in idxs]

        # Phase 3: per-strand alignment filtering.
        r1_infos = self._filter_most_common_alignment(r1_infos)
        r2_infos = self._filter_most_common_alignment(r2_infos)
        if not r1_infos or not r2_infos:
            return None
        if (len(r1_infos) < self.options.min_reads_per_strand
                or len(r2_infos) < self.options.min_reads_per_strand):
            self.stats.reject("InsufficientReads", len(r1_infos) + len(r2_infos))
            return None
        n_filtered = len(r1_infos) + len(r2_infos)

        # Phase 4: overlap geometry on the longest strands by reference length.
        ref_len = lambda info: cigar_utils.reference_length(info.clipped_cigar)
        longest_r1 = max(r1_infos, key=ref_len)  # first max (rs:742-751 rev-iter)
        longest_r2 = max(r2_infos, key=ref_len)
        r1_is_negative = bool(longest_r1.flags & FLAG_REVERSE)
        r2_is_negative = bool(longest_r2.flags & FLAG_REVERSE)
        longest_pos, longest_neg = ((longest_r2, longest_r1) if r1_is_negative
                                    else (longest_r1, longest_r2))
        overlap_start = longest_neg.adjusted_pos
        pos_end = longest_pos.adjusted_pos + max(ref_len(longest_pos) - 1, 0)
        duplex_length = pos_end - overlap_start + 1
        if duplex_length < self.options.min_duplex_length:
            self.stats.reject("InsufficientOverlap", n_filtered)
            return None

        # Phase check (rs:1005-1040): equal read-pos offsets at both ends.
        rp = lambda info, pos, last: cigar_utils.read_pos_at_ref_pos(
            info.clipped_cigar, info.adjusted_pos, pos, last)
        r1s, r2s = rp(longest_r1, overlap_start, True), rp(longest_r2, overlap_start, True)
        r1e, r2e = rp(longest_r1, pos_end, True), rp(longest_r2, pos_end, True)
        if None in (r1s, r2s, r1e, r2e) or (r1s - r2s) != (r1e - r2e):
            self.stats.reject("IndelErrorBetweenStrands", n_filtered)
            return None

        # Consensus length (rs:1042-1062).
        p = rp(longest_pos, pos_end, False)
        n_ = rp(longest_neg, pos_end, False)
        if p is None or n_ is None:
            self.stats.reject("IndelErrorBetweenStrands", n_filtered)
            return None
        consensus_length = p + longest_neg.clipped_seq_len - n_

        # Phase 5: SourceReads + SS jobs through the vanilla machinery.
        umi_str = umi or ""
        r1_sources = [self._to_source_read(records[info.raw_idx], i, info)
                      for i, info in enumerate(r1_infos)]
        r2_sources = [self._to_source_read(records[info.raw_idx], i, info)
                      for i, info in enumerate(r2_infos)]
        job_r1 = self.ss.job_from_source_reads(umi_str, R1, r1_sources)
        job_r2 = self.ss.job_from_source_reads(umi_str, R1, r2_sources)
        if job_r1 is None or job_r2 is None:
            return None

        return {
            "umi": umi, "records": records,
            "job_r1": job_r1, "job_r2": job_r2,
            "n_r1": len(r1_infos), "n_r2": len(r2_infos),
            "r1_is_negative": r1_is_negative, "r2_is_negative": r2_is_negative,
            "consensus_length": consensus_length,
            "source_raws": [records[info.raw_idx] for info in r1_infos + r2_infos],
        }

    # ------------------------------------------------------------ duplex combine

    def _combine(self, a: _SS, b: _SS):
        """Per-position duplex combine, vectorized (rs:1127-1296).

        Returns _SS; raises DuplexDisagreementError on threshold breach.
        """
        base, qual, depth, errors, both, disag = combine_arrays(
            a.bases, b.bases, a.quals, b.quals, a.depths, b.depths,
            a.errors, b.errors)

        duplex_bases = int(both.sum())
        disagreements = int(disag.sum())
        if duplex_bases:
            self.stats.consensus_duplex_bases_emitted += duplex_bases
            self.stats.duplex_disagreement_base_count += disagreements
            max_dd = self.options.max_duplex_disagreements
            if max_dd is not None and disagreements > max_dd:
                raise DuplexDisagreementError("count", disagreements)
            rate = disagreements / duplex_bases
            if rate > self.options.max_duplex_disagreement_rate:
                raise DuplexDisagreementError("rate", rate)

        return _SS(bases=base, quals=qual, depths=depth, errors=errors,
                   raw_read_count=a.raw_read_count + b.raw_read_count)

    def _mask_quals(self, consensus: _SS, padded_r1: _SS, padded_r2: _SS) -> _SS:
        """Outer-bases mask first, then single-strand regions (rs:1298-1345)."""
        opts = self.options
        length = len(consensus.quals)
        quals = consensus.quals.copy()
        if opts.outer_bases_length > 0 and opts.outer_bases_qual is not None:
            n = min(opts.outer_bases_length, length)
            quals[:n] = opts.outer_bases_qual
            quals[length - n:] = opts.outer_bases_qual
        if opts.single_strand_qual is not None:
            is_n = lambda x: (x == NO_CALL_BASE) | (x == NO_CALL_BASE_LOWER)
            ss_region = is_n(padded_r1.bases) | is_n(padded_r2.bases)
            quals[ss_region] = opts.single_strand_qual
        consensus.quals = quals
        return consensus

    # ------------------------------------------------------------ output

    def _build_record(self, consensus: _SS, ss_a: _SS, ss_b: _SS,
                      umi: Optional[str], source_raws: list,
                      all_records: list, rx_umis=None,
                      number: int = None) -> bytes:
        """build_output_record_into (rs:1374-1539); tag order preserved.

        rx_umis: precomputed per-record RX strings (batch engine); None means
        scan all_records here. number: the record's place in the output (1 =
        first) where the caller keeps the count itself (the batch engine's
        chunks, fast_codec.py `_EmittedOrder`); None takes the next.
        """
        if number is None:
            self._counter += 1
            number = self._counter
        name = f"{self.prefix}:{umi or number}".encode()
        b = self._builder
        b.start_unmapped(name, FLAG_UNMAPPED, consensus.bases.tobytes(),
                         consensus.quals)
        b.tag_str(b"RG", self.read_group_id.encode())
        if umi:
            b.tag_str(b"MI", umi.encode())

        cap = lambda x: np.minimum(x, I16_MAX).astype(np.int64)
        total_depths = cap(ss_a.depths) + cap(ss_b.depths)
        total_errors = int(cap(consensus.errors).sum())
        total_bases = int(total_depths.sum())
        rate = (np.float32(total_errors) / np.float32(total_bases)
                if total_bases else np.float32(0))
        b.tag_int(b"cD", int(total_depths.max()) if len(total_depths) else 0)
        b.tag_int(b"cM", int(total_depths.min()) if len(total_depths) else 0)
        b.tag_float(b"cE", float(rate))

        for tag_d, tag_m, tag_e, ss in ((b"aD", b"aM", b"aE", ss_a),
                                        (b"bD", b"bM", b"bE", ss_b)):
            d = cap(ss.depths)
            errs = int(cap(ss.errors).sum())
            total = int(d.sum())
            srate = np.float32(errs) / np.float32(total) if total else np.float32(0)
            b.tag_int(tag_d, int(d.max()) if len(d) else 0)
            b.tag_int(tag_m, int(d.min()) if len(d) else 0)
            b.tag_float(tag_e, float(srate))

        if self.options.produce_per_base_tags:
            b.tag_array_i16(b"ad", cap(ss_a.depths))
            b.tag_array_i16(b"bd", cap(ss_b.depths))
            b.tag_array_i16(b"ae", cap(ss_a.errors))
            b.tag_array_i16(b"be", cap(ss_b.errors))
            b.tag_str(b"ac", ss_a.bases.tobytes())
            b.tag_str(b"bc", ss_b.bases.tobytes())
            b.tag_str(b"aq", (ss_a.quals + 33).astype(np.uint8).tobytes())
            b.tag_str(b"bq", (ss_b.quals + 33).astype(np.uint8).tobytes())

        if self.options.cell_tag:
            ct = self.options.cell_tag.encode()
            for raw in source_raws:
                cb = raw.get_str(ct)
                if cb:
                    b.tag_str(ct, cb.encode())
                    break

        # RX consensus over ALL records in the MI group (rs:1513-1532).
        umis = (rx_umis if rx_umis is not None else
                [u for u in (r.get_str(b"RX") for r in all_records) if u])
        if umis:
            cu = consensus_umis(umis)
            if cu:
                b.tag_str(b"RX", cu.encode())

        self.stats.add(consensus_reads_generated=1)
        return b.finish()

    def _finish(self, mol, vcr_r1, vcr_r2) -> Optional[bytes]:
        """Geometry + combine + masking after the SS device pass (rs:838-908)."""
        consensus_length = mol["consensus_length"]
        to_ascii = lambda vcr: _SS(
            bases=CODE_TO_BASE[np.minimum(vcr.bases, N_CODE)].copy(),
            quals=np.asarray(vcr.quals, dtype=np.uint8).copy(),
            depths=np.asarray(vcr.depths, dtype=np.int64),
            errors=np.asarray(vcr.errors, dtype=np.int64),
            raw_read_count=0)
        ss_r1, ss_r2 = to_ascii(vcr_r1), to_ascii(vcr_r2)
        ss_r1.raw_read_count = mol["n_r1"]
        ss_r2.raw_read_count = mol["n_r2"]
        n_filtered = mol["n_r1"] + mol["n_r2"]

        if consensus_length < len(ss_r1.bases) or consensus_length < len(ss_r2.bases):
            self.stats.reject("ClipOverlapFailed", n_filtered)
            return None

        r1_neg, r2_neg = mol["r1_is_negative"], mol["r2_is_negative"]
        if r1_neg:
            oriented_r1, oriented_r2 = _rc_ss(ss_r1), ss_r2
        else:
            oriented_r1, oriented_r2 = ss_r1, _rc_ss(ss_r2)
        padded_r1 = _pad_ss(oriented_r1, consensus_length, r1_neg)
        padded_r2 = _pad_ss(oriented_r2, consensus_length, r2_neg)

        try:
            consensus = self._combine(padded_r1, padded_r2)
        except DuplexDisagreementError:
            self.stats.reject("HighDuplexDisagreement", n_filtered)
            self.stats.consensus_reads_rejected_hdd += 1
            raise
        consensus = self._mask_quals(consensus, padded_r1, padded_r2)
        if r1_neg:
            consensus = _rc_ss(consensus)
            ss_for_ac, ss_for_bc = _rc_ss(padded_r1), _rc_ss(padded_r2)
        else:
            ss_for_ac, ss_for_bc = padded_r1, padded_r2

        return self._build_record(consensus, ss_for_ac, ss_for_bc, mol["umi"],
                                  mol["source_raws"], mol["records"],
                                  rx_umis=mol.get("rx_umis"))

    # ------------------------------------------------------------ driver

    def call_groups(self, groups) -> list:
        """Process [(mi, [RawRecord])] -> consensus record bytes (batched).

        All molecules' SS jobs run as one device pass. Rejected groups
        (including recoverable duplex-disagreement drops) go to
        self.rejected_reads when track_rejects is on.
        """
        molecules = []
        for mi, records in groups:
            mol = self.prepare(records, umi=mi)
            if mol is None:
                if self.track_rejects:
                    self.rejected_reads.extend(records)
                continue
            molecules.append(mol)
        if not molecules:
            return []
        jobs = []
        for mol in molecules:
            jobs.extend([mol["job_r1"], mol["job_r2"]])
        results = self.ss._run_jobs(jobs)
        out = []
        for i, mol in enumerate(molecules):
            vcr_r1 = self.ss.result_to_consensus_read(mol["job_r1"], results[2 * i])
            vcr_r2 = self.ss.result_to_consensus_read(mol["job_r2"], results[2 * i + 1])
            try:
                rec = self._finish(mol, vcr_r1, vcr_r2)
            except DuplexDisagreementError:
                rec = None
            if rec is not None:
                out.append(rec)
            elif self.track_rejects:
                self.rejected_reads.extend(mol["records"])
        return out
