"""Batched BAM reading: SoA record batches over contiguous chunk buffers.

The per-batch analog of the reference's Decode step
(/root/reference/src/lib/unified_pipeline/bam.rs:180,329: FindBoundaries +
parallel Decode into cached GroupKeys): decompressed bytes are scanned for
record boundaries and field-decoded natively (fgumi_tpu.native.batch), so the
Python layer holds numpy arrays per batch instead of objects per record.
"""

import numpy as np

from ..native import batch as nb
from .bam import BamHeader, RawRecord
from .bgzf import BgzfReader

# Smallest possible BAM record on the wire: 4-byte block_size + 32 fixed +
# 1-byte name (NUL only); guards the boundary-array allocation.
_MIN_RECORD_WIRE = 37


class RecordBatch:
    """A contiguous run of BAM records decoded struct-of-arrays.

    `buf` is a writable uint8 view of the chunk (overlap correction mutates
    seq/qual bytes in place, consensus/overlapping.py semantics). All offset
    arrays index into `buf`.
    """

    __slots__ = ("buf", "rec_off", "n", "ref_id", "pos", "mapq", "flag",
                 "l_seq", "n_cigar", "l_read_name", "next_ref_id", "next_pos",
                 "tlen", "data_off", "data_end", "cigar_off", "seq_off",
                 "qual_off", "aux_off", "_tag_locs")

    def __init__(self, chunk: bytearray, rec_off: np.ndarray):
        self.buf = np.frombuffer(chunk, dtype=np.uint8)
        self.rec_off = rec_off
        self.n = len(rec_off)
        f = nb.decode_fields(self.buf, rec_off)
        for k, v in f.items():
            setattr(self, k, v)
        self.cigar_off = self.data_off + 32 + self.l_read_name
        self.seq_off = self.cigar_off + 4 * self.n_cigar.astype(np.int64)
        self.qual_off = self.seq_off + (self.l_seq + 1) // 2
        self.aux_off = self.qual_off + self.l_seq
        self._tag_locs = {}

    def prefetch_tags(self, tags):
        """Seed the per-batch tag cache with ONE native aux scan for every
        not-yet-cached tag (the C scan takes k tags per pass; commands that
        read many tags were paying one full-batch scan per tag)."""
        need = [t for t in tags if t not in self._tag_locs]
        if not need:
            return
        # the fused scan packs tags at 2-byte stride; a stray non-2-byte
        # tag would silently misalign every LATER tag's column
        bad = [t for t in need if len(t) != 2]
        if bad:
            raise ValueError(f"SAM tags must be exactly 2 bytes: {bad!r}")
        vo, vl, vt = nb.scan_tags(self.buf, self.aux_off, self.data_end,
                                  need)
        for j, t in enumerate(need):
            self._tag_locs[t] = (np.ascontiguousarray(vo[:, j]),
                                 np.ascontiguousarray(vl[:, j]),
                                 np.ascontiguousarray(vt[:, j]))

    def tag_locs(self, tag: bytes):
        """(val_off int64[n], val_len int32[n], val_type uint8[n]) for one tag;
        val_off -1 where absent. Cached per batch."""
        got = self._tag_locs.get(tag)
        if got is None:
            self.prefetch_tags([tag])
            got = self._tag_locs[tag]
        return got

    def tag_locs_str(self, tag: bytes):
        """tag_locs with non-string-typed (not Z/H) tags masked to absent,
        matching RawRecord.get_str's type gate. Cached per batch."""
        got = self._tag_locs.get((tag, "str"))
        if got is None:
            vo, vl, vt = self.tag_locs(tag)
            ok = (vt == ord("Z")) | (vt == ord("H"))
            got = (np.where(ok, vo, -1), vl, vt)
            self._tag_locs[(tag, "str")] = got
        return got

    def tag_bytes(self, tag: bytes, i: int):
        """One record's tag value bytes (Z/H string, no NUL), or None."""
        vo, vl, _ = self.tag_locs(tag)
        if vo[i] < 0:
            return None
        return self.buf[vo[i]: vo[i] + vl[i]].tobytes()

    def name(self, i: int) -> bytes:
        off = self.data_off[i] + 32
        return self.buf[off: off + self.l_read_name[i] - 1].tobytes()

    def raw_record(self, i: int) -> RawRecord:
        """Materialize one record as a RawRecord (slow-path interop)."""
        return RawRecord(self.buf[self.data_off[i]: self.data_end[i]].tobytes())

    def raw_records(self, indices) -> list:
        return [self.raw_record(int(i)) for i in indices]


class BatchedRecordReader:
    """BamReader-compatible record iterator backed by BamBatchReader.

    Yields RawRecords, but the decompress/boundary-scan path runs natively
    per batch instead of per record — a drop-in accelerator for streaming
    commands that still consume records one at a time (zipper, merge, ...).
    """

    def __init__(self, path_or_obj, target_bytes: int = 8 << 20):
        self._r = BamBatchReader(path_or_obj, target_bytes=target_bytes)
        self.header = self._r.header

    def __iter__(self):
        for batch in self._r:
            for i in range(batch.n):
                yield batch.raw_record(i)

    def close(self):
        self._r.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class _BatchAssembler:
    """Accumulate → boundary-scan → tail-carry loop shared by every batch
    source: ``read_chunk()`` returns the next decoded uint8 array (empty at
    end of stream) — the BGZF reader for file-backed batches, a fused-chain
    channel for in-memory handoff (``pipeline_chain.ChannelBatchReader``) —
    and iteration yields :class:`RecordBatch` objects of ~``target_bytes``
    payload. Factoring it here keeps the re-chunking behavior (single-part
    no-copy wrap, concatenate-once, partial-record tail carry, oversized-
    record target growth) identical across sources."""

    def __init__(self, read_chunk, target_bytes: int):
        self._read_chunk = read_chunk
        # a non-positive target would make _fill yield nothing and the
        # command silently write an empty output; clamp to "one chunk"
        self._target = max(int(target_bytes), 1)
        # decoded chunks accumulate as arrays and concatenate ONCE per
        # batch: appending into a bytearray and re-wrapping cost several
        # full copies of every decompressed byte (chain profiles)
        self._parts = []
        self._parts_len = 0
        self._eof = False

    def _fill(self):
        while self._parts_len < self._target and not self._eof:
            arr = self._read_chunk()
            if not len(arr):
                self._eof = True
                break
            self._parts.append(arr)
            self._parts_len += len(arr)

    def __iter__(self):
        while True:
            batch = self._next_batch()
            if batch is None:
                return
            yield batch

    def _next_batch(self):
        """The next RecordBatch, or None at end of stream (one
        ``reader.decode`` span: chunk reads, concatenation, boundary scan)."""
        from ..observe.trace import span

        with span("reader.decode", rusage=True):
            while True:
                self._fill()
                if not self._parts_len:
                    return None
                buf = (self._parts[0] if len(self._parts) == 1
                       else np.concatenate(self._parts))
                max_records = len(buf) // _MIN_RECORD_WIRE + 1
                offsets, scanned = nb.find_boundaries(buf, max_records)
                if len(offsets) == 0:
                    if self._eof:
                        raise EOFError(
                            "truncated BAM record at end of stream")
                    # a single record larger than the accumulated bytes:
                    # grow
                    self._target *= 2
                    self._parts = [buf]
                    self._parts_len = len(buf)
                    continue
                # tail: copy the (at most one partial record) remainder so
                # the next batch doesn't pin this batch's full buffer
                tail = buf[scanned:].copy()
                self._parts = [tail] if len(tail) else []
                self._parts_len = len(tail)
                # a trailing partial record at EOF surfaces as an empty scan
                # on the next call and raises there, after this chunk is
                # consumed
                return RecordBatch(buf[:scanned], offsets.copy())


class BamBatchReader:
    """Yields RecordBatch objects of ~target_bytes decompressed payload."""

    def __init__(self, path_or_obj, target_bytes: int = 16 << 20):
        owns = isinstance(path_or_obj, str)
        fileobj = open(path_or_obj, "rb") if owns else path_or_obj
        if owns:
            from .prefetch import PrefetchFile, prefetch_enabled

            if prefetch_enabled():
                # async read-ahead + POSIX_FADV_SEQUENTIAL (reference
                # PrefetchReader, prefetch_reader.rs:93 + os_hints.rs):
                # overlaps disk latency with decompress/decode even when
                # the command runs without a reader stage thread
                fileobj = PrefetchFile(fileobj)
        self._r = BgzfReader(fileobj, owns_fileobj=owns,
                             name=path_or_obj if owns else None)
        try:
            self.header = BamHeader.decode_from(self._r.read)
        except BaseException:
            # stop the prefetch thread + close the fd even when the header
            # is corrupt — an unreferenced running thread never gets GC'd
            self._r.close()
            raise
        self._asm = _BatchAssembler(self._r.read_decoded, target_bytes)

    def __iter__(self):
        return iter(self._asm)

    def close(self):
        self._r.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
