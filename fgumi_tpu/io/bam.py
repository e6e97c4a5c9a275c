"""Raw-byte BAM record layer.

Record accessors work directly on BAM wire bytes at fixed offsets, mirroring the
reference's raw-record design (/root/reference/crates/fgumi-raw-bam/src/fields.rs:7-24:
refID/pos/l_read_name/mapq/bin/n_cigar_op/flag/l_seq/next_refID/next_pos/tlen then
name, cigar, packed seq, qual, aux TLV) — decoding only what each consumer touches,
which is what keeps host-side feeding cheap (raw_bam_record.rs:6-13 rationale).
"""

import contextvars
import struct
import zlib
from dataclasses import dataclass, field

import numpy as np

from .bgzf import BgzfReader, BgzfWriter

BAM_MAGIC = b"BAM\x01"
# SAM spec reg2bin(-1, 0) — the unmapped record bin (builder.rs:1-3).
UNMAPPED_BIN = 4680

# BAM flags (SAM spec).
FLAG_PAIRED = 0x1
FLAG_PROPER_PAIR = 0x2
FLAG_UNMAPPED = 0x4
FLAG_MATE_UNMAPPED = 0x8
FLAG_REVERSE = 0x10
FLAG_MATE_REVERSE = 0x20
FLAG_FIRST = 0x40
FLAG_LAST = 0x80
FLAG_SECONDARY = 0x100
FLAG_QC_FAIL = 0x200
FLAG_DUPLICATE = 0x400
FLAG_SUPPLEMENTARY = 0x800

# 4-bit seq nibble -> ASCII (=ACMGRSVTWYHKDBN).
NIBBLE_TO_BASE = np.frombuffer(b"=ACMGRSVTWYHKDBN", dtype=np.uint8)
BASE_TO_NIBBLE = np.full(256, 15, dtype=np.uint8)  # default N
for _i, _b in enumerate(b"=ACMGRSVTWYHKDBN"):
    BASE_TO_NIBBLE[_b] = _i
for _i, _b in enumerate(b"=acmgrsvtwyhkdbn"):
    BASE_TO_NIBBLE[_b] = _i

CIGAR_OPS = "MIDNSHP=X"
_CONSUMES_QUERY = frozenset("MIS=X")
_CONSUMES_REF = frozenset("MDN=X")


# canonical SAM-spec binning lives in io/bai.py (index writer/reader)
from .bai import reg2bin as _reg2bin  # noqa: E402


@dataclass
class BamHeader:
    text: str
    ref_names: list
    ref_lengths: list
    _name_to_id: dict = field(default_factory=dict, repr=False)

    def __post_init__(self):
        if not self._name_to_id:
            self._name_to_id = {n: i for i, n in enumerate(self.ref_names)}

    def ref_id(self, name: str) -> int:
        return self._name_to_id.get(name, -1)

    def encode(self) -> bytes:
        text_b = self.text.encode()
        out = bytearray(BAM_MAGIC)
        out += struct.pack("<i", len(text_b))
        out += text_b
        out += struct.pack("<i", len(self.ref_names))
        for name, length in zip(self.ref_names, self.ref_lengths):
            nb = name.encode() + b"\x00"
            out += struct.pack("<i", len(nb)) + nb + struct.pack("<i", length)
        return bytes(out)

    @classmethod
    def decode_from(cls, read):
        """Parse from a `read(n)` callable positioned at the stream start."""
        magic = read(4)
        if magic != BAM_MAGIC:
            raise ValueError(f"not a BAM stream (magic {magic!r})")
        (l_text,) = struct.unpack("<i", read(4))
        text = read(l_text).decode(errors="replace").rstrip("\x00")
        (n_ref,) = struct.unpack("<i", read(4))
        names, lengths = [], []
        for _ in range(n_ref):
            (l_name,) = struct.unpack("<i", read(4))
            names.append(read(l_name)[:-1].decode())
            (l_ref,) = struct.unpack("<i", read(4))
            lengths.append(l_ref)
        return cls(text=text, ref_names=names, ref_lengths=lengths)


def header_roundtrip(header: BamHeader) -> BamHeader:
    """The header exactly as a file round trip would deliver it.

    The fused pipeline chain (``pipeline_chain``) hands headers between
    stages in memory; downstream stages derive provenance from the header
    *text* (@HD rewrites in sort, @PG chaining in filter), so the handoff
    must replicate what ``encode()`` → ``decode_from()`` produces — byte
    for byte — or the fused run's headers could drift from the staged
    run's (e.g. trailing-NUL stripping)."""
    import io as _io

    return BamHeader.decode_from(_io.BytesIO(header.encode()).read)


class RawRecord:
    """A single BAM record's wire bytes (without the leading block_size)."""

    __slots__ = ("data", "_tag_idx", "_aux", "_cigar")

    def __init__(self, data: bytes):
        self.data = data
        self._tag_idx = None  # lazy {tag: (typ, value_off)} built on first lookup
        self._aux = None      # lazy cached aux-region offset
        self._cigar = None    # lazy cached decoded CIGAR

    # --- fixed-offset fields (fields.rs:7-24) ---
    @property
    def ref_id(self) -> int:
        return int.from_bytes(self.data[0:4], "little", signed=True)

    @property
    def pos(self) -> int:
        return int.from_bytes(self.data[4:8], "little", signed=True)

    @property
    def l_read_name(self) -> int:
        return self.data[8]

    @property
    def mapq(self) -> int:
        return self.data[9]

    @property
    def n_cigar_op(self) -> int:
        return int.from_bytes(self.data[12:14], "little")

    @property
    def flag(self) -> int:
        return int.from_bytes(self.data[14:16], "little")

    @property
    def l_seq(self) -> int:
        return int.from_bytes(self.data[16:20], "little")

    @property
    def next_ref_id(self) -> int:
        return int.from_bytes(self.data[20:24], "little", signed=True)

    @property
    def next_pos(self) -> int:
        return int.from_bytes(self.data[24:28], "little", signed=True)

    @property
    def tlen(self) -> int:
        return int.from_bytes(self.data[28:32], "little", signed=True)

    @property
    def name(self) -> bytes:
        return self.data[32 : 32 + self.l_read_name - 1]

    # --- variable sections ---
    def _cigar_off(self) -> int:
        return 32 + self.l_read_name

    def _seq_off(self) -> int:
        return self._cigar_off() + 4 * self.n_cigar_op

    def _qual_off(self) -> int:
        return self._seq_off() + (self.l_seq + 1) // 2

    def _aux_off(self) -> int:
        # cached: tag scans and record edits probe this repeatedly, and the
        # record's bytes are immutable
        aux = self._aux
        if aux is None:
            aux = self._aux = self._qual_off() + self.l_seq
        return aux

    def cigar(self):
        """[(op_char, length)] decoded CIGAR (cached; the record's bytes are
        immutable and consumers probe the CIGAR several times per record)."""
        out = self._cigar
        if out is None:
            off = self._cigar_off()
            data = self.data
            out = []
            for i in range(self.n_cigar_op):
                v = int.from_bytes(data[off + 4 * i: off + 4 * i + 4],
                                   "little")
                out.append((CIGAR_OPS[v & 0xF], v >> 4))
            self._cigar = out
        return out

    def seq_bytes(self) -> bytes:
        """ASCII sequence (unpacked from 4-bit codes)."""
        n = self.l_seq
        packed = np.frombuffer(self.data, dtype=np.uint8, count=(n + 1) // 2,
                               offset=self._seq_off())
        nibbles = np.empty(2 * len(packed), dtype=np.uint8)
        nibbles[0::2] = packed >> 4
        nibbles[1::2] = packed & 0xF
        return NIBBLE_TO_BASE[nibbles[:n]].tobytes()

    def quals(self) -> np.ndarray:
        return np.frombuffer(self.data, dtype=np.uint8, count=self.l_seq,
                             offset=self._qual_off()).copy()

    # --- aux tag TLV scan (tags.rs:8-40) ---
    def _iter_tags(self):
        data = self.data
        off = self._aux_off()
        end = len(data)
        while off + 3 <= end:
            tag = data[off : off + 2]
            typ = data[off + 2]
            off += 3
            yield tag, typ, off
            off = _skip_tag_value(data, typ, off)

    def find_tag(self, tag: bytes):
        """Return (type_char, python value) or None.

        The TLV scan runs once per record and caches {tag: (typ, off)} —
        commands typically probe several tags per record (filter reads 5+),
        and rescanning the aux region per probe dominated their profiles.
        """
        idx = self._tag_idx
        if idx is None:
            idx = {}
            for t, typ, off in self._iter_tags():
                if t not in idx:  # first occurrence wins, like the linear scan
                    idx[t] = (typ, off)
            self._tag_idx = idx
        got = idx.get(tag)
        if got is None:
            return None
        typ, off = got
        return chr(typ), _read_tag_value(self.data, typ, off)

    def get_str(self, tag: bytes):
        got = self.find_tag(tag)
        if got is None:
            return None
        typ, val = got
        return val if typ in ("Z", "H") else None

    def get_int(self, tag: bytes):
        got = self.find_tag(tag)
        if got is None:
            return None
        typ, val = got
        return int(val) if typ in "cCsSiI" else None

    def aux_bytes(self) -> bytes:
        return self.data[self._aux_off():]

    def data_without_tag(self, tag: bytes) -> bytes:
        """Record bytes with every occurrence of `tag` removed (aux TLV edit)."""
        spans = []
        for t, typ, off in self._iter_tags():
            if t == tag:
                spans.append((off - 3, _skip_tag_value(self.data, typ, off)))
        if not spans:
            return self.data
        out = bytearray()
        prev = 0
        for start, end in spans:
            out += self.data[prev:start]
            prev = end
        out += self.data[prev:]
        return bytes(out)

    def read_length_from_cigar(self) -> int:
        return sum(n for op, n in self.cigar() if op in _CONSUMES_QUERY)

    def reference_length(self) -> int:
        return sum(n for op, n in self.cigar() if op in _CONSUMES_REF)

    def unclipped_start(self) -> int:
        """0-based alignment start minus leading clips."""
        pos = self.pos
        for op, n in self.cigar():
            if op in "SH":
                pos -= n
            else:
                break
        return pos

    def unclipped_end(self) -> int:
        """0-based inclusive alignment end plus trailing clips."""
        end = self.pos + self.reference_length() - 1
        for op, n in reversed(self.cigar()):
            if op in "SH":
                end += n
            else:
                break
        return end


_TAG_SIZES = {ord("c"): 1, ord("C"): 1, ord("s"): 2, ord("S"): 2, ord("i"): 4,
              ord("I"): 4, ord("f"): 4, ord("A"): 1}
_ARRAY_DTYPES = {ord("c"): np.int8, ord("C"): np.uint8, ord("s"): np.int16,
                 ord("S"): np.uint16, ord("i"): np.int32, ord("I"): np.uint32,
                 ord("f"): np.float32}


def _skip_tag_value(data: bytes, typ: int, off: int) -> int:
    size = _TAG_SIZES.get(typ)
    if size is not None:
        return off + size
    if typ in (ord("Z"), ord("H")):
        return data.index(b"\x00", off) + 1
    if typ == ord("B"):
        sub = data[off]
        (count,) = struct.unpack_from("<I", data, off + 1)
        return off + 5 + count * _TAG_SIZES[sub]
    raise ValueError(f"unknown aux tag type {typ!r}")


def _read_tag_value(data: bytes, typ: int, off: int):
    c = chr(typ)
    if c == "A":
        return chr(data[off])
    if c in "cCsSiI":
        fmt = {"c": "<b", "C": "<B", "s": "<h", "S": "<H", "i": "<i", "I": "<I"}[c]
        return struct.unpack_from(fmt, data, off)[0]
    if c == "f":
        return struct.unpack_from("<f", data, off)[0]
    if c in "ZH":
        end = data.index(b"\x00", off)
        return data[off:end].decode(errors="replace")
    if c == "B":
        sub = data[off]
        (count,) = struct.unpack_from("<I", data, off + 1)
        dt = _ARRAY_DTYPES[sub]
        return np.frombuffer(data, dtype=dt, count=count, offset=off + 5).copy()
    raise ValueError(f"unknown aux tag type {c!r}")


def pack_seq(seq) -> bytes:
    """ASCII sequence (bytes or uint8 array) -> BAM 4-bit packed bytes."""
    codes = BASE_TO_NIBBLE[np.frombuffer(seq, dtype=np.uint8)
                           if isinstance(seq, (bytes, bytearray))
                           else np.asarray(seq, dtype=np.uint8)]
    if len(codes) % 2:
        codes = np.append(codes, 0)
    return ((codes[0::2] << 4) | codes[1::2]).astype(np.uint8).tobytes()


class RecordBuilder:
    """Builds raw BAM record bytes (mirrors UnmappedSamBuilder, builder.rs:69-200)."""

    def __init__(self):
        self._buf = bytearray()

    def start_unmapped(self, name: bytes, flag: int, seq: bytes, quals) -> "RecordBuilder":
        """Begin an unmapped record: ref_id=-1, pos=-1, mapq=0, bin=4680, no CIGAR."""
        buf = self._buf
        buf.clear()
        l_name = len(name) + 1
        if l_name > 255:
            raise ValueError(f"read name too long ({len(name)} bytes): {name[:40]!r}...")
        n = len(seq)
        buf += struct.pack("<iiBBHHHiiii", -1, -1, l_name, 0, UNMAPPED_BIN, 0,
                           flag, n, -1, -1, 0)
        buf += name
        buf += b"\x00"
        buf += pack_seq(seq)
        buf += np.asarray(quals, dtype=np.uint8).tobytes()
        return self

    def start_mapped(self, name: bytes, flag: int, ref_id: int, pos: int,
                     mapq: int, cigar, seq: bytes, quals,
                     next_ref_id: int = -1, next_pos: int = -1,
                     tlen: int = 0) -> "RecordBuilder":
        """Begin a mapped record. `cigar` is [(op_char, length)] (builder.rs:356)."""
        buf = self._buf
        buf.clear()
        l_name = len(name) + 1
        if l_name > 255:
            raise ValueError(f"read name too long ({len(name)} bytes)")
        n = len(seq)
        ref_len = sum(ln for op, ln in cigar if op in _CONSUMES_REF) or 1
        bin_ = _reg2bin(pos, pos + ref_len) if pos >= 0 else UNMAPPED_BIN
        buf += struct.pack("<iiBBHHHiiii", ref_id, pos, l_name, mapq, bin_,
                           len(cigar), flag, n, next_ref_id, next_pos, tlen)
        buf += name
        buf += b"\x00"
        for op, length in cigar:
            buf += struct.pack("<I", (length << 4) | CIGAR_OPS.index(op))
        buf += pack_seq(seq)
        buf += np.asarray(quals, dtype=np.uint8).tobytes()
        return self

    def tag_str(self, tag: bytes, value: bytes) -> "RecordBuilder":
        self._buf += tag + b"Z" + value + b"\x00"
        return self

    def tag_int(self, tag: bytes, value: int) -> "RecordBuilder":
        self._buf += tag + b"i" + struct.pack("<i", value)
        return self

    def tag_float(self, tag: bytes, value: float) -> "RecordBuilder":
        self._buf += tag + b"f" + struct.pack("<f", value)
        return self

    def tag_array_i16(self, tag: bytes, values) -> "RecordBuilder":
        arr = np.asarray(values, dtype=np.int16)
        self._buf += tag + b"Bs" + struct.pack("<I", arr.size) + arr.tobytes()
        return self

    def tag_array_u8(self, tag: bytes, values) -> "RecordBuilder":
        arr = np.asarray(values, dtype=np.uint8)
        self._buf += tag + b"BC" + struct.pack("<I", arr.size) + arr.tobytes()
        return self

    def finish(self) -> bytes:
        return bytes(self._buf)


class BamReader:
    """Sequential BAM reader yielding RawRecord over a BGZF/gzip stream."""

    def __init__(self, path_or_obj):
        owns = isinstance(path_or_obj, str)
        fileobj = open(path_or_obj, "rb") if owns else path_or_obj
        self._path = path_or_obj if owns else getattr(fileobj, "name", None)
        self._r = BgzfReader(fileobj, owns_fileobj=owns, name=self._path)
        self.header = BamHeader.decode_from(self._r.read)

    def __iter__(self):
        read = self._r.read
        while True:
            sz = read(4)
            if len(sz) < 4:
                return
            (block_size,) = struct.unpack("<I", sz)
            data = read(block_size)
            if len(data) < block_size:
                where = f" in {self._path}" if self._path else ""
                raise EOFError(
                    f"truncated BAM record{where} (expected {block_size} "
                    f"bytes, got {len(data)} before EOF)")
            yield RawRecord(data)

    def close(self):
        self._r.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _read_bgzf_block_at(f):
    """One BGZF block at the current file position -> (payload, csize),
    or None at EOF. Parses BSIZE from the BC extra subfield (BGZF spec)."""
    header = f.read(12)
    if len(header) < 12:
        return None
    if header[:4] != b"\x1f\x8b\x08\x04":
        raise ValueError("not a BGZF block (missing BC extra flag)")
    (xlen,) = struct.unpack_from("<H", header, 10)
    extra = f.read(xlen)
    bsize = None
    off = 0
    while off + 4 <= len(extra):
        si1, si2, slen = extra[off], extra[off + 1], \
            struct.unpack_from("<H", extra, off + 2)[0]
        if si1 == 66 and si2 == 67 and slen == 2:
            bsize = struct.unpack_from("<H", extra, off + 4)[0] + 1
        off += 4 + slen
    if bsize is None:
        raise ValueError("BGZF block lacks BSIZE")
    cdata_len = bsize - 12 - xlen - 8
    cdata = f.read(cdata_len)
    footer = f.read(8)
    if len(cdata) < cdata_len or len(footer) < 8:
        raise EOFError("truncated BGZF block")
    payload = zlib.decompress(cdata, wbits=-15)
    (isize,) = struct.unpack_from("<I", footer, 4)
    if len(payload) != isize:
        raise ValueError("BGZF ISIZE mismatch")
    return payload, bsize


class BamIndexedReader:
    """Random-access BAM reader over a coordinate-sorted BAM + .bai index.

    Analog of the reference's indexed reader
    (/root/reference/crates/fgumi-raw-bam/src/indexed_reader.rs): BAI bins +
    linear index select candidate chunks, BGZF blocks are decompressed from
    each chunk's virtual offset, and records are filtered by actual overlap.
    """

    def __init__(self, path: str, index_path: str = None):
        """`index_path`: explicit .bai/.csi path; by default .bai is tried
        first, then .csi (both expose the same query_chunks interface)."""
        import os

        with BamReader(path) as r:
            self.header = r.header
        from .bai import BaiIndex, CsiIndex

        if index_path is None:
            index_path = path + ".bai" if os.path.exists(path + ".bai") \
                else path + ".csi"
        self.index = CsiIndex(index_path) if index_path.endswith(".csi") \
            else BaiIndex(index_path)
        self._f = open(path, "rb")

    def query(self, tid: int, beg: int, end: int):
        """Yield RawRecords overlapping [beg, end) on reference `tid`."""
        for vo_beg, vo_end in self.index.query_chunks(tid, beg, end):
            yield from self._scan_chunk(vo_beg, vo_end, tid, beg, end)

    def _scan_chunk(self, vo_beg, vo_end, tid, beg, end):
        f = self._f
        coffset = vo_beg >> 16
        f.seek(coffset)
        got = _read_bgzf_block_at(f)
        if got is None:
            return
        payload, csize = got
        buf = bytearray(payload[vo_beg & 0xFFFF:])
        # markers: (buf_pos, block_file_offset, offset_of_buf_pos_in_block)
        markers = [(0, coffset, vo_beg & 0xFFFF)]
        next_coffset = coffset + csize
        pos = 0
        while True:
            if pos > (1 << 20):
                # stream with bounded memory: drop the consumed prefix and
                # rebase the block markers (whole-chromosome queries would
                # otherwise hold the full decompressed chunk)
                keep = max(i for i, m in enumerate(markers) if m[0] <= pos)
                rebased = []
                for bpos, blk_off, in_blk in markers[keep:]:
                    if bpos < pos:  # the block containing `pos`
                        rebased.append((0, blk_off, in_blk + pos - bpos))
                    else:
                        rebased.append((bpos - pos, blk_off, in_blk))
                markers = rebased
                del buf[:pos]
                pos = 0
            while len(buf) < pos + 4:
                got = _read_bgzf_block_at(f)
                if got is None:
                    return
                markers.append((len(buf), next_coffset, 0))
                buf += got[0]
                next_coffset += got[1]
            # virtual offset of this record's first byte
            m = next(m for m in reversed(markers) if m[0] <= pos)
            rec_vo = (m[1] << 16) | (m[2] + pos - m[0])
            if rec_vo >= vo_end:
                return
            (block_size,) = struct.unpack_from("<I", buf, pos)
            while len(buf) < pos + 4 + block_size:
                got = _read_bgzf_block_at(f)
                if got is None:
                    raise EOFError("truncated BAM record in indexed read")
                markers.append((len(buf), next_coffset, 0))
                buf += got[0]
                next_coffset += got[1]
            rec = RawRecord(bytes(buf[pos + 4:pos + 4 + block_size]))
            pos += 4 + block_size
            if rec.ref_id != tid or rec.pos >= end:
                if rec.ref_id > tid or (rec.ref_id == tid and rec.pos >= end):
                    return  # coordinate order: nothing later can overlap
                continue
            rec_end = rec.pos + max(rec.reference_length(), 1)
            if rec_end > beg:
                yield rec

    def close(self):
        self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# default BGZF level for BamWriter (reference CompressionOptions default 1,
# commands/common.rs); the CLI's --compression-level sets it per invocation.
# Level 0 = stored blocks — used by the `pipeline` command for intermediates
# that are read back immediately. Context-scoped (not a bare module global)
# so two serve-daemon jobs with different levels in one process cannot
# clobber each other; the module constant is the fallback.
DEFAULT_COMPRESSION_LEVEL = 1

_level_var = contextvars.ContextVar("fgumi_tpu_bgzf_level", default=None)


def set_default_compression_level(level):
    """Set the context's default BGZF level (None = module default)."""
    _level_var.set(level)


def default_compression_level() -> int:
    lvl = _level_var.get()
    return DEFAULT_COMPRESSION_LEVEL if lvl is None else lvl


_audit_output_var = contextvars.ContextVar("fgumi_tpu_audit_output",
                                           default=False)


def set_audit_output(enabled: bool):
    """Arm (per invocation context) the ``--audit-output`` pre-commit
    integrity pass for BAM outputs (cli.py global flag)."""
    _audit_output_var.set(bool(enabled))


def audit_output_enabled() -> bool:
    import os

    return _audit_output_var.get() or \
        os.environ.get("FGUMI_TPU_AUDIT_OUTPUT", "").strip().lower() \
        in ("1", "true", "on", "all")


class _OutputTally:
    """The writer's own record accounting for the ``--audit-output``
    re-walk to check against: record count plus a streaming CRC32 over
    the exact record-stream bytes (block_size prefixes + payloads, in
    write order) as they were handed to the writer — so the audit proves
    not just "N records survived" but "the bytes on disk are, in order,
    the bytes the pipeline wrote": any loss, duplication, reordering, or
    single-bit corruption between the writer's buffer and the page cache
    flips the digest. The order-sensitivity of the chained CRC is the
    sort-invariant check — the on-disk key sequence cannot differ from
    the written one without flipping it."""

    __slots__ = ("records", "content_crc", "header_crc")

    def __init__(self):
        self.records = 0
        self.content_crc = 0
        self.header_crc = 0  # CRC32 of the encoded BAM header block

    def add_record(self, framed):
        """One record WITH its 4-byte block_size prefix."""
        self.records += 1
        self.content_crc = zlib.crc32(framed, self.content_crc)

    def add_serialized(self, blob):
        """A block_size-prefixed record blob (the native batch
        serializer's output)."""
        view = memoryview(blob)
        off = 0
        n = len(view)
        while off + 4 <= n:
            size = int.from_bytes(view[off:off + 4], "little")
            self.records += 1
            off += 4 + size
        if off != n:
            # the writer itself was handed a torn blob: fail now, not at
            # the re-walk (this is a caller bug, not disk corruption)
            from .errors import OutputIntegrityError

            raise OutputIntegrityError(
                "serialized record blob is torn (partial block_size "
                "prefix)")
        self.content_crc = zlib.crc32(view, self.content_crc)

    def add_indexed(self, blob, starts):
        """A prefix-framed blob whose record boundaries the caller
        already delimited (``starts``: cumulative offsets, one past the
        record count) — no per-record Python walk needed; the pre-commit
        re-walk still catches any disagreement between ``starts`` and
        the actual framing."""
        self.records += len(starts) - 1
        self.content_crc = zlib.crc32(memoryview(blob), self.content_crc)


class _BamStreamAudit:
    """Incremental BAM structure walker over decompressed member payloads
    (the ``--audit-output`` record-layer pass): parses magic/header/refs,
    then counts records and CRCs their (refID, pos) keys exactly like
    :class:`_OutputTally`; optionally checks coordinate order."""

    def __init__(self, path: str, expect_coordinate: bool = False):
        self._path = path
        self._buf = bytearray()
        self._state = "magic"
        self._text_len = 0
        self._refs_left = None
        self.records = 0
        self.content_crc = 0
        self.header_crc = 0
        self._expect_coord = expect_coordinate
        self._last_key = None

    def _fail(self, message):
        from .errors import OutputIntegrityError

        raise OutputIntegrityError(message, path=self._path)

    def _eat_header(self, n: int):
        """Consume n header-section bytes, folding them into header_crc
        (the pre-record BAM structure is digest-checked too — a flipped
        bit in @HD/@SQ/@PG provenance is as published as one in a read)."""
        self.header_crc = zlib.crc32(memoryview(self._buf)[:n],
                                     self.header_crc)
        del self._buf[:n]

    def feed(self, data):
        self._buf += data
        buf = self._buf
        while True:
            if self._state == "magic":
                if len(buf) < 8:
                    return
                if bytes(buf[:4]) != BAM_MAGIC:
                    self._fail("decompressed stream does not start with "
                               "the BAM magic")
                self._text_len = int.from_bytes(buf[4:8], "little")
                self._eat_header(8)
                self._state = "text"
            elif self._state == "text":
                if len(buf) < self._text_len + 4:
                    return
                self._refs_left = int.from_bytes(
                    buf[self._text_len:self._text_len + 4], "little")
                self._eat_header(self._text_len + 4)
                self._state = "refs"
            elif self._state == "refs":
                if self._refs_left == 0:
                    self._state = "records"
                    continue
                if len(buf) < 4:
                    return
                l_name = int.from_bytes(buf[:4], "little")
                if len(buf) < 8 + l_name:
                    return
                self._eat_header(8 + l_name)
                self._refs_left -= 1
            else:  # records
                if len(buf) < 4:
                    return
                size = int.from_bytes(buf[:4], "little")
                if len(buf) < 4 + size:
                    return
                if size < 32:
                    self._fail(f"record #{self.records} shorter than the "
                               "fixed BAM record header")
                key = bytes(buf[4:12])
                self.records += 1
                self.content_crc = zlib.crc32(memoryview(buf)[:4 + size],
                                              self.content_crc)
                if self._expect_coord:
                    # the sorter's own key semantics (sort/keys.py):
                    # refID unsigned (-1 = 0xFFFFFFFF, unmapped tail
                    # last) but pos+1 — a mapped record with pos=-1
                    # (RNAME set, POS 0) legally sorts FIRST within its
                    # reference, so the raw unsigned pos would falsely
                    # reject the sorter's correct output
                    k = (int.from_bytes(key[:4], "little"),
                         int.from_bytes(key[4:8], "little",
                                        signed=True) + 1)
                    if self._last_key is not None and k < self._last_key:
                        self._fail(
                            f"record #{self.records} out of coordinate "
                            "order in an SO:coordinate file")
                    self._last_key = k
                del buf[:4 + size]

    def finish(self):
        if self._state != "records" or self._buf:
            self._fail("decompressed stream ends mid-structure "
                       f"(state={self._state}, {len(self._buf)} residual "
                       "bytes)")


class BamWriter:
    """Sequential BAM writer over BGZF.

    With ``--audit-output`` armed (and the atomic commit enabled), the
    writer tallies every record it is handed and, at close, re-walks the
    finished temp file — per-member BGZF CRC32/ISIZE, BAM structure,
    record count, and sort-key-order digest against its own tallies —
    BEFORE the atomic rename publishes it. A host-side DMA or page-cache
    corruption therefore fails the run (exit 5) instead of shipping a bad
    file (docs/resilience.md "Silent-corruption sentinel")."""

    def __init__(self, path_or_obj, header: BamHeader, level: int = None):
        if level is None:
            level = default_compression_level()
        owns = isinstance(path_or_obj, str)
        self._audit = None
        self._audit_coord = False
        self._audit_path = path_or_obj if owns else None
        if owns:
            # crash-safe commit: write .<name>.tmp.<pid>, atomic-rename on
            # close so an interrupted run never leaves a torn BAM under the
            # final name (utils/atomic.py; --no-atomic-output disables)
            from ..utils.atomic import open_output

            fileobj = open_output(path_or_obj)
            if audit_output_enabled():
                if hasattr(fileobj, "pre_commit_check"):
                    self._audit = _OutputTally()
                    self._audit_coord = "SO:coordinate" in header.text
                    fileobj.pre_commit_check = self._run_output_audit
                else:
                    import logging

                    logging.getLogger("fgumi_tpu").debug(
                        "--audit-output: atomic commit disabled for %s; "
                        "no pre-rename window to audit in — skipping",
                        path_or_obj)
        else:
            fileobj = path_or_obj
        self._w = BgzfWriter(fileobj, level=level, owns_fileobj=owns)
        try:
            enc = header.encode()
            if self._audit is not None:
                self._audit.header_crc = zlib.crc32(enc)
            self._w.write(enc)
        except BaseException:
            # construction failed: drop the temp eagerly rather than at GC
            self._w.discard()
            raise

    def write_record_bytes(self, data: bytes):
        framed = struct.pack("<I", len(data)) + data
        if self._audit is not None:
            self._audit.add_record(framed)
        self._w.write(framed)

    def write_record(self, rec: RawRecord):
        self.write_record_bytes(rec.data)

    def write_serialized(self, blob: bytes):
        """Append records already carrying their block_size prefixes
        (the native batch serializer's output)."""
        from ..observe.trace import span

        with span("sink.write", bytes=len(blob)):
            if self._audit is not None:
                self._audit.add_serialized(blob)
            self._w.write(blob)

    def write_indexed(self, blob, starts):
        """Append a prefix-framed record blob and return the BGZF virtual
        offset of each ``starts`` position (the BAI/CSI builders' bulk
        path — see :meth:`BgzfWriter.write_indexed`). Tallied like
        write_serialized so ``--audit-output`` covers indexed sorts."""
        if self._audit is not None:
            self._audit.add_indexed(blob, starts)
        return self._w.write_indexed(blob, starts)

    def _run_output_audit(self, tmp_path: str):
        """The pre-commit hook (utils/atomic.py): verify the finished
        temp end to end; raise OutputIntegrityError to abort the rename."""
        import logging
        import time as _time

        from ..observe.metrics import METRICS
        from .bgzf import verify_members
        from .errors import OutputIntegrityError

        t0 = _time.monotonic()
        walker = _BamStreamAudit(tmp_path,
                                 expect_coordinate=self._audit_coord)
        stats = {"members": 0, "data_bytes": 0, "eof_sentinel": False}
        try:
            stats = verify_members(tmp_path, sink=walker.feed)
            walker.finish()
            if not stats["eof_sentinel"]:
                raise OutputIntegrityError("missing BGZF EOF sentinel",
                                           path=tmp_path)
            if walker.header_crc != self._audit.header_crc:
                raise OutputIntegrityError(
                    "BAM header digest mismatch: the header block on disk "
                    "is not the header the writer encoded", path=tmp_path)
            if walker.records != self._audit.records:
                raise OutputIntegrityError(
                    f"record count mismatch: file holds {walker.records}, "
                    f"writer tallied {self._audit.records}", path=tmp_path)
            if walker.content_crc != self._audit.content_crc:
                raise OutputIntegrityError(
                    "record-stream digest mismatch: the record bytes on "
                    "disk are not (in order) the bytes the writer was "
                    "handed", path=tmp_path)
        except OutputIntegrityError as e:
            self._note_audit(self._audit_path, False,
                             stats_members=stats["members"],
                             records=walker.records, error=str(e))
            raise
        dt = _time.monotonic() - t0
        METRICS.observe("io.output_audit_s", dt)
        self._note_audit(self._audit_path, True,
                         stats_members=stats["members"],
                         records=walker.records)
        logging.getLogger("fgumi_tpu").info(
            "output audit: %d BGZF members / %d records verified clean "
            "in %.2fs", stats["members"], walker.records, dt)

    @staticmethod
    def _note_audit(path, ok, stats_members, records, error=None):
        # record the verdict on the sentinel (run report / stats `audit`
        # section). ops.sentinel is numpy-light — importing it here does
        # not drag in jax for IO-only commands.
        from ..ops.sentinel import SENTINEL

        SENTINEL.note_output_audit(path or "", ok, members=stats_members,
                                   records=records, error=error)

    def tell_virtual(self) -> int:
        """BGZF virtual offset of the next record (for BAI building)."""
        return self._w.tell_virtual()

    def close(self):
        self._w.close()

    def discard(self):
        """Abandon the output (error path): no EOF sentinel is written and
        an atomic temp file is removed instead of renamed."""
        self._w.discard()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self.close()
        else:
            self.discard()
