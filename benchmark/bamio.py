"""The benchmark's own BAM/BGZF reading and writing (SAM spec v1, section 4).

Nothing here imports the program: inputs are written and outputs are read
back by code a later PR cannot change.
"""

import struct
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_BGZF_HEAD = struct.Struct("<4BI2BH2BHH")  # ... BSIZE
_BGZF_EOF = bytes.fromhex(
    "1f8b08040000000000ff0600424302001b0003000000000000000000")
_BLOCK = 0xFF00  # uncompressed bytes per BGZF block


def _bgzf_block(chunk, level):
    comp = zlib.compressobj(level, zlib.DEFLATED, -15)
    body = comp.compress(chunk) + comp.flush()
    head = _BGZF_HEAD.pack(31, 139, 8, 4, 0, 0, 255, 6, 66, 67, 2,
                           len(body) + 25)
    return head + body + struct.pack("<II", zlib.crc32(chunk), len(chunk))


def write_bgzf(path, data, level=1, threads=8):
    """Write ``data`` (bytes-like) as one BGZF file; zlib releases the GIL,
    so the blocks are compressed on ``threads`` threads."""
    view = memoryview(data)
    chunks = [view[i:i + _BLOCK] for i in range(0, len(view), _BLOCK)]
    with ThreadPoolExecutor(threads) as pool, open(path, "wb") as f:
        for block in pool.map(lambda c: _bgzf_block(c, level), chunks,
                              chunksize=64):
            f.write(block)
        f.write(_BGZF_EOF)


def read_bgzf(path):
    """The whole decompressed payload of a BGZF file, block by block (each
    block names its own compressed size in its BC extra field)."""
    with open(path, "rb") as f:
        raw = memoryview(f.read())
    out = []
    pos = 0
    while pos < len(raw):
        if len(raw) - pos < 18 or bytes(raw[pos:pos + 4]) != b"\x1f\x8b\x08\x04" \
                or bytes(raw[pos + 12:pos + 14]) != b"BC":
            raise ValueError(f"{path}: no BGZF block at byte {pos}")
        size = int.from_bytes(raw[pos + 16:pos + 18], "little") + 1
        if pos + size > len(raw):
            raise ValueError(f"{path}: truncated BGZF block at byte {pos}")
        out.append(zlib.decompress(raw[pos + 18:pos + size - 8], -15))
        pos += size
    return b"".join(out)


def bam_header(text, refs=()):
    """Serialised BAM header: magic, text, reference dictionary."""
    t = text.encode()
    out = [b"BAM\x01", struct.pack("<i", len(t)), t,
           struct.pack("<i", len(refs))]
    for name, length in refs:
        n = name.encode() + b"\x00"
        out += [struct.pack("<i", len(n)), n, struct.pack("<i", length)]
    return b"".join(out)


def split_bam(payload):
    """(header text, offset of the first record) of a decompressed BAM."""
    if payload[:4] != b"BAM\x01":
        raise ValueError("not a BAM payload")
    (l_text,) = struct.unpack_from("<i", payload, 4)
    text = payload[8:8 + l_text].decode()
    pos = 8 + l_text
    (n_ref,) = struct.unpack_from("<i", payload, pos)
    pos += 4
    for _ in range(n_ref):
        (l_name,) = struct.unpack_from("<i", payload, pos)
        pos += 4 + l_name + 4
    return text, pos


def record_offsets(payload, start):
    """Start offsets of every record (each begins with its block_size), plus
    the end offset as a last element."""
    offs = []
    pos = start
    n = len(payload)
    unpack = struct.Struct("<i").unpack_from
    while pos < n:
        offs.append(pos)
        pos += 4 + unpack(payload, pos)[0]
    if pos != n:
        raise ValueError("BAM payload ends inside a record")
    offs.append(n)
    return np.asarray(offs, dtype=np.int64)


_SEQ_CODE = "=ACMGRSVTWYHKDBN"
_TAG_SIZE = {"A": 1, "c": 1, "C": 1, "s": 2, "S": 2, "i": 4, "I": 4, "f": 4}
_TAG_FMT = {"c": "<b", "C": "<B", "s": "<h", "S": "<H", "i": "<i", "I": "<I",
            "f": "<f"}


def decode_record(payload, off):
    """One record as a dict (for messages and tests; the comparison itself
    works on bytes)."""
    (size, ref_id, pos, l_name, mapq, _bin, n_cig, flag, l_seq, nref, npos,
     tlen) = struct.unpack_from("<iiiBBHHHiiii", payload, off)
    p = off + 36
    name = payload[p:p + l_name - 1].decode()
    p += l_name
    cigar = struct.unpack_from(f"<{n_cig}I", payload, p)
    p += 4 * n_cig
    packed = payload[p:p + (l_seq + 1) // 2]
    p += (l_seq + 1) // 2
    seq = "".join(_SEQ_CODE[b >> 4] + _SEQ_CODE[b & 15] for b in packed)[:l_seq]
    qual = bytes(payload[p:p + l_seq])
    p += l_seq
    end = off + 4 + size
    tags = {}
    while p < end:
        tag = payload[p:p + 2].decode()
        typ = chr(payload[p + 2])
        p += 3
        if typ == "Z":
            z = payload.index(b"\x00", p)
            tags[tag] = payload[p:z].decode()
            p = z + 1
        elif typ == "B":
            sub = chr(payload[p])
            (cnt,) = struct.unpack_from("<i", payload, p + 1)
            p += 5
            tags[tag] = list(struct.unpack_from(
                f"<{cnt}{_TAG_FMT[sub][1]}", payload, p))
            p += cnt * _TAG_SIZE[sub]
        elif typ == "A":
            tags[tag] = chr(payload[p])
            p += 1
        else:
            tags[tag] = struct.unpack_from(_TAG_FMT[typ], payload, p)[0]
            p += _TAG_SIZE[typ]
    return {"name": name, "flag": flag, "ref_id": ref_id, "pos": pos,
            "mapq": mapq, "cigar": cigar, "seq": seq, "qual": qual,
            "next_ref": nref, "next_pos": npos, "tlen": tlen, "tags": tags}
