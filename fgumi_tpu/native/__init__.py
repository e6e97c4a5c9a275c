"""ctypes bindings for the C++ native runtime (libdeflate BGZF codec).

The shared library is built lazily from the bundled source on first use
(g++ -O3 against the system libdeflate) and cached next to this module;
every consumer degrades to the pure-Python/zlib path when the toolchain or
libdeflate is unavailable — with a warning that carries g++'s stderr (set
FGUMI_TPU_NO_NATIVE=1 to force the fallback). Mirrors the reference's
native layering (SURVEY.md §2 intro: C++ equivalents for the L1-L4 hot
paths).
"""

import ctypes
import logging
import os
import subprocess
import threading

log = logging.getLogger("fgumi_tpu")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO_PATH = os.path.join(_HERE, "libfgumi_native.so")
_SRC_PATH = os.path.join(_HERE, "fgumi_native.cc")

_lock = threading.Lock()
_lib = None
_lib_failed = False
# must equal fgumi_abi_version() in fgumi_native.cc (stale-.so guard)
_ABI_VERSION = 21


def build() -> bool:
    """Compile the bundled source into ``libfgumi_native.so``.

    g++ writes to a name private to this process and the result is
    renamed into place, so several processes that reach first use
    together (a fleet start, a test run) each load a complete library:
    nobody can dlopen a half-written file. A failure is a warning with
    the compiler's own words — the pure-Python fallback is an order of
    magnitude slower and must not be entered silently."""
    tmp = f"{_SO_PATH}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-o", tmp,
           _SRC_PATH, "-ldeflate"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            log.warning("native build failed (%s); using the pure-Python "
                        "fallbacks:\n%s", " ".join(cmd), proc.stderr)
            return False
        os.replace(tmp, _SO_PATH)
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native build failed (%s); using the pure-Python "
                    "fallbacks", e)
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return True


def _declare(lib):
    """ctypes restype/argtypes for every export (one copy, used by both
    the cached-build path and the FGUMI_TPU_NATIVE_SO override)."""
    p = ctypes.c_void_p
    lib.fgumi_duplex_rx_fast.restype = ctypes.c_long
    lib.fgumi_duplex_rx_fast.argtypes = [
        p, p, p, p, p, p, ctypes.c_long, p, ctypes.c_long, p, p, p, p]
    lib.fgumi_codec_combine.restype = None
    lib.fgumi_codec_combine.argtypes = [
        p, p, p, p, p, p, p, p, ctypes.c_long, ctypes.c_int, ctypes.c_ubyte,
        ctypes.c_ubyte, ctypes.c_int, p, p, p, p, p, p]
    lib.fgumi_codec_place.restype = None
    lib.fgumi_codec_place.argtypes = (
        [p] * 11 + [ctypes.c_long, p, ctypes.c_int, ctypes.c_int,
                    ctypes.c_ubyte] + [p] * 4)
    lib.fgumi_bgzf_decompress.restype = ctypes.c_long
    lib.fgumi_bgzf_decompress.argtypes = [
        ctypes.c_void_p, ctypes.c_long, ctypes.c_void_p, ctypes.c_long,
        ctypes.POINTER(ctypes.c_long)]
    lib.fgumi_gzip_decompress.restype = ctypes.c_long
    lib.fgumi_gzip_decompress.argtypes = [
        ctypes.c_void_p, ctypes.c_long, ctypes.c_void_p, ctypes.c_long]
    lib.fgumi_umi_neighbor_pairs.restype = ctypes.c_long
    lib.fgumi_umi_neighbor_pairs.argtypes = [
        p, ctypes.c_long, p, ctypes.c_long, ctypes.c_long, ctypes.c_int,
        p, p, ctypes.c_long]
    lib.fgumi_umi_bktree_pairs.restype = ctypes.c_long
    lib.fgumi_umi_bktree_pairs.argtypes = [
        p, ctypes.c_long, p, ctypes.c_long, ctypes.c_long, ctypes.c_int,
        p, p, ctypes.c_long]
    lib.fgumi_adjacency_bfs.restype = None
    lib.fgumi_adjacency_bfs.argtypes = [p, p, p, ctypes.c_long, p]
    lib.fgumi_within_bits_lists.restype = ctypes.c_long
    lib.fgumi_within_bits_lists.argtypes = [
        p, ctypes.c_long, ctypes.c_long, ctypes.c_long, p, p]
    lib.fgumi_bgzf_compress_block.restype = ctypes.c_long
    lib.fgumi_bgzf_compress_block.argtypes = [
        ctypes.c_char_p, ctypes.c_long, ctypes.c_int, ctypes.c_char_p,
        ctypes.c_long]
    lib.fgumi_zlib_compress.restype = ctypes.c_long
    lib.fgumi_zlib_compress.argtypes = [
        ctypes.c_char_p, ctypes.c_long, ctypes.c_int, ctypes.c_char_p,
        ctypes.c_long]
    lib.fgumi_zlib_decompress.restype = ctypes.c_long
    lib.fgumi_zlib_decompress.argtypes = [
        ctypes.c_char_p, ctypes.c_long, ctypes.c_char_p, ctypes.c_long]
    lib.fgumi_find_record_boundaries.restype = ctypes.c_long
    lib.fgumi_find_record_boundaries.argtypes = [
        ctypes.c_char_p, ctypes.c_long,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_long,
        ctypes.POINTER(ctypes.c_int64)]
    # batch record layer: all pointers passed as raw addresses (numpy
    # array .ctypes.data); see fgumi_tpu/native/batch.py wrappers.
    lib.fgumi_decode_fields.restype = None
    lib.fgumi_decode_fields.argtypes = [p, p, ctypes.c_long] + [p] * 12
    lib.fgumi_scan_tags.restype = None
    lib.fgumi_scan_tags.argtypes = [p, p, p, ctypes.c_long, p,
                                    ctypes.c_long, p, p, p]
    lib.fgumi_group_starts.restype = ctypes.c_long
    lib.fgumi_group_starts.argtypes = [p, p, p, ctypes.c_long, p]
    lib.fgumi_pack_reads.restype = None
    lib.fgumi_pack_reads.argtypes = [p, p, p, p, p, p, ctypes.c_long,
                                     ctypes.c_int, ctypes.c_long,
                                     ctypes.c_int, p, p, p]
    lib.fgumi_mate_clips.restype = None
    lib.fgumi_mate_clips.argtypes = [p] * 11 + [ctypes.c_long, p]
    lib.fgumi_alignment_filter.restype = None
    lib.fgumi_alignment_filter.argtypes = [p] * 6 + [ctypes.c_long, p]
    lib.fgumi_codec_cigar_prepare.restype = None
    lib.fgumi_codec_cigar_prepare.argtypes = (
        [p] * 6 + [ctypes.c_long, p] + [ctypes.c_long] * 3 + [p] * 17)
    lib.fgumi_overlap_correct_pairs.restype = None
    lib.fgumi_overlap_correct_pairs.argtypes = [
        p, p, p, ctypes.c_long, ctypes.c_int, ctypes.c_int, p]
    lib.fgumi_build_consensus_records.restype = ctypes.c_long
    lib.fgumi_build_consensus_records.argtypes = (
        [p] * 6 + [ctypes.c_long, p, ctypes.c_int, p, p, p, p, p,
                   ctypes.c_int, ctypes.c_int, p, ctypes.c_long, p])
    lib.fgumi_build_duplex_records.restype = ctypes.c_long
    lib.fgumi_build_duplex_records.argtypes = (
        [p] * 5 + [ctypes.c_long, p, ctypes.c_int, p, p]
        + [p] * 5 + [p] * 6 + [p, p, p, ctypes.c_int, ctypes.c_int,
                               p, ctypes.c_long, p])
    lib.fgumi_build_codec_records.restype = ctypes.c_long
    lib.fgumi_build_codec_records.argtypes = (
        [p] * 11 + [p, ctypes.c_long] + [p] * 6
        + [p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
           p, ctypes.c_long, p])
    lib.fgumi_segment_depth_errors.restype = None
    lib.fgumi_segment_depth_errors.argtypes = (
        [p, p, p, ctypes.c_long, ctypes.c_long, p, p])
    lib.fgumi_segment_depth_errors_ranges.restype = None
    lib.fgumi_segment_depth_errors_ranges.argtypes = (
        [p, ctypes.c_long, p, p, p, p, ctypes.c_long, ctypes.c_long, p, p])
    lib.fgumi_build_wire.restype = ctypes.c_long
    lib.fgumi_build_wire.argtypes = (
        [p, p, ctypes.c_long, p, ctypes.c_long, ctypes.c_long, ctypes.c_long]
        + [p] * 4)
    lib.fgumi_consensus_segments.restype = ctypes.c_long
    lib.fgumi_consensus_segments.argtypes = (
        [p, p, p, ctypes.c_long, ctypes.c_long, p, p, ctypes.c_double,
         ctypes.c_int, ctypes.c_int] + [p] * 8 + [p, p, p, ctypes.c_long])
    lib.fgumi_ranges_equal.restype = None
    lib.fgumi_ranges_equal.argtypes = [p] * 5 + [ctypes.c_long, p]
    lib.fgumi_hash_ranges.restype = None
    lib.fgumi_hash_ranges.argtypes = [p, p, p, ctypes.c_long, p]
    lib.fgumi_template_coord_keys.restype = ctypes.c_long
    lib.fgumi_template_coord_keys.argtypes = (
        [p] * 15 + [ctypes.c_long, p, p])
    lib.fgumi_natural_name_keys.restype = ctypes.c_long
    lib.fgumi_natural_name_keys.argtypes = (
        [p] * 4 + [ctypes.c_long, p, p, p])
    lib.fgumi_unclipped_5prime.restype = None
    lib.fgumi_unclipped_5prime.argtypes = [p] * 5 + [ctypes.c_long, p]
    lib.fgumi_umi_scan.restype = None
    lib.fgumi_umi_scan.argtypes = [p, p, p, ctypes.c_long, p, p, p]
    lib.fgumi_rewrite_tag_records.restype = ctypes.c_long
    lib.fgumi_rewrite_tag_records.argtypes = (
        [p] * 4 + [ctypes.c_long, ctypes.c_ubyte, ctypes.c_ubyte]
        + [p] * 5)
    lib.fgumi_qual_scores.restype = None
    lib.fgumi_qual_scores.argtypes = (
        [p, p, p, ctypes.c_long, ctypes.c_int, ctypes.c_long, p])
    lib.fgumi_gather_u16_arrays.restype = None
    lib.fgumi_gather_u16_arrays.argtypes = (
        [p, p, ctypes.c_long, ctypes.c_long, p, p])
    lib.fgumi_apply_masks.restype = None
    lib.fgumi_apply_masks.argtypes = (
        [p, p, p, p, ctypes.c_long, p, ctypes.c_long, ctypes.c_int,
         p, p])
    lib.fgumi_rx_unanimous.restype = None
    lib.fgumi_rx_unanimous.argtypes = [p, p, p, p, ctypes.c_long, p, p]
    lib.fgumi_extract_records.restype = ctypes.c_long
    lib.fgumi_extract_records.argtypes = (
        [ctypes.c_long, ctypes.c_long] + [p] * 6 + [ctypes.c_long]
        + [p] * 3 + [ctypes.c_int, p, ctypes.c_int, ctypes.c_int, p,
                     ctypes.c_long, p])
    lib.fgumi_ref_spans.restype = None
    lib.fgumi_ref_spans.argtypes = [p, p, p, p, ctypes.c_long, p]
    lib.fgumi_concat_spans.restype = ctypes.c_long
    lib.fgumi_concat_spans.argtypes = [p, p, p, p, ctypes.c_long, p, p]
    lib.fgumi_tag_name_list.restype = None
    lib.fgumi_tag_name_list.argtypes = [p, p, p, ctypes.c_long,
                                        ctypes.c_long, p, p]
    lib.fgumi_cigar_strings.restype = ctypes.c_long
    lib.fgumi_cigar_strings.argtypes = [p, p, p, ctypes.c_long, p, p]
    lib.fgumi_rebuild_aux_records.restype = ctypes.c_long
    lib.fgumi_rebuild_aux_records.argtypes = [p] * 4 + [ctypes.c_long] \
        + [p] * 6
    lib.fgumi_bgzf_compress_many.restype = ctypes.c_long
    lib.fgumi_bgzf_compress_many.argtypes = [
        p, ctypes.c_long, ctypes.c_int, ctypes.c_int, p, ctypes.c_long,
        ctypes.c_long, p, ctypes.POINTER(ctypes.c_long)]
    lib.fgumi_sort_spans.restype = None
    lib.fgumi_sort_spans.argtypes = [p, p, p, ctypes.c_long, p]
    lib.fgumi_gather_spans.restype = ctypes.c_long
    lib.fgumi_gather_spans.argtypes = [p, p, p, p, ctypes.c_long, p]
    lib.fgumi_write_run.restype = ctypes.c_long
    lib.fgumi_write_run.argtypes = (
        [ctypes.c_char_p] + [p] * 7 + [ctypes.c_long, ctypes.c_long,
                                       ctypes.c_int])
    lib.fgumi_merge_open.restype = ctypes.c_void_p
    lib.fgumi_merge_open.argtypes = [ctypes.c_char_p, ctypes.c_long,
                                     ctypes.c_long]
    lib.fgumi_merge_open2.restype = ctypes.c_void_p
    lib.fgumi_merge_open2.argtypes = [ctypes.c_char_p, ctypes.c_long,
                                      ctypes.c_long, ctypes.c_int,
                                      ctypes.c_long]
    lib.fgumi_merge_next.restype = ctypes.c_long
    lib.fgumi_merge_next.argtypes = [
        ctypes.c_void_p, p, ctypes.c_long, p, ctypes.c_long,
        ctypes.POINTER(ctypes.c_long)]
    lib.fgumi_merge_close.restype = None
    lib.fgumi_merge_close.argtypes = [ctypes.c_void_p]



def get_lib():
    """The loaded native library, or None (pure-Python fallback)."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    from ..observe.process import startup_span

    with _lock, startup_span("startup.native_load"):
        if _lib is not None or _lib_failed:
            return _lib
        if os.environ.get("FGUMI_TPU_NO_NATIVE"):
            _lib_failed = True
            return None
        def _abi_ok(candidate):
            # one copy of the versioned-ABI check (bumped in fgumi_native.cc
            # on any signature change), shared by the override and
            # cached-build paths
            if not hasattr(candidate, "fgumi_abi_version"):
                return False
            candidate.fgumi_abi_version.restype = ctypes.c_long
            return candidate.fgumi_abi_version() == _ABI_VERSION

        override = os.environ.get("FGUMI_TPU_NATIVE_SO")
        if override:
            # explicit prebuilt library (e.g. the ASAN/UBSAN test lane):
            # load it as-is — no rebuild fallback, loud failure
            try:
                lib = ctypes.CDLL(override)
            except OSError as e:
                log.warning("FGUMI_TPU_NATIVE_SO=%s failed to load: %s",
                            override, e)
                _lib_failed = True
                return None
            if not _abi_ok(lib):
                log.warning("FGUMI_TPU_NATIVE_SO=%s missing or mismatched "
                            "ABI (expected %d)", override, _ABI_VERSION)
                _lib_failed = True
                return None
            _declare(lib)
            _lib = lib
            return _lib
        if not os.path.exists(_SO_PATH) or (
                os.path.exists(_SRC_PATH)
                and os.path.getmtime(_SRC_PATH) > os.path.getmtime(_SO_PATH)):
            if not build():
                _lib_failed = True
                return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as e:
            log.warning("native library load failed: %s", e)
            _lib_failed = True
            return None
        # stale-.so guard: a cached build whose mtime ties the source (e.g.
        # archive extraction) passes the rebuild check but may predate newer
        # symbols OR carry old signatures; rebuild on ABI mismatch
        if not _abi_ok(lib):
            if not build():
                _lib_failed = True
                return None
            try:
                lib = ctypes.CDLL(_SO_PATH)
            except OSError as e:
                log.warning("native library reload failed: %s", e)
                _lib_failed = True
                return None
            if not _abi_ok(lib):
                _lib_failed = True
                return None
        _declare(lib)
        _lib = lib
        log.debug("native library loaded from %s", _SO_PATH)
        return _lib


def bgzf_decompress(data, out_cap: int = None):
    """Decompress complete BGZF blocks from `data` (bytes/bytearray/view).

    Returns (decoded, consumed) or None when the native library is
    unavailable; `decoded` is a uint8 numpy array view over a fresh buffer
    (callers append it to their own buffers — returning bytes would add a
    full extra copy, and ctypes string buffers would add a zero-fill on top:
    both showed up as ~0.3s/stage on chain profiles). Raises ValueError on
    malformed input.
    """
    import numpy as np

    lib = get_lib()
    if lib is None:
        return None
    src = np.frombuffer(memoryview(data), dtype=np.uint8)  # zero-copy
    n = len(src)
    # Spec bound: each block is >=26 bytes and expands to at most 64 KiB, so
    # the true output can never exceed this cap. An ISIZE claiming more is
    # corrupt — the codec returns -2 and we report it rather than growing.
    max_cap = (n // 26 + 1) * (1 << 16)
    if out_cap is None:
        out_cap = min(max(4 * n + (1 << 16), 1 << 16), max_cap)
    out = np.empty(out_cap, dtype=np.uint8)
    consumed = ctypes.c_long(0)
    produced = lib.fgumi_bgzf_decompress(src.ctypes.data, n, out.ctypes.data,
                                         out_cap, ctypes.byref(consumed))
    # release the caller's buffer BEFORE any raise: a ValueError traceback
    # would otherwise pin this frame's view and turn the caller's recovery
    # (`self._raw.clear()` in BgzfReader._demote_to_zlib) into a BufferError
    src = None
    if produced == -2:
        if out_cap >= max_cap:
            raise ValueError("malformed BGZF block (ISIZE exceeds spec bound)")
        return bgzf_decompress(data, min(out_cap * 2, max_cap))
    if produced < 0:
        raise ValueError("malformed BGZF block")
    if out_cap - produced > produced // 2 + (1 << 20):
        # poorly-compressible input: a view would pin the 4x over-allocation
        # in callers that retain the chunk (batch_reader accumulation)
        return out[:produced].copy(), consumed.value
    return out[:produced], consumed.value


def gzip_decompress_all(data, max_out: int = None) -> "object":
    """Whole-buffer (multi-member) gzip decompression via libdeflate.

    Returns a uint8 numpy array; None when the native library is unavailable
    OR the output would exceed `max_out` (the caller's cue to stream with
    bounded memory instead — a highly compressible input can expand far past
    any compressed-size heuristic). Raises ValueError on malformed input.
    """
    import numpy as np

    lib = get_lib()
    if lib is None:
        return None
    src = np.frombuffer(memoryview(data), dtype=np.uint8)
    n = len(src)
    # seed the capacity from the ISIZE footer (uncompressed size of the
    # LAST member mod 2^32 — exact for the single-member files `gzip`
    # produces), so the common case never pays a wasted full decompression
    # before an INSUFFICIENT_SPACE retry; multi-member or lying footers
    # fall back to the retry loop
    isize = int.from_bytes(bytes(src[-4:]), "little") if n >= 18 else 0
    # clamp the footer-seeded guess to a sane expansion ratio: a corrupt or
    # truncated footer is arbitrary bytes and must not size the allocation
    cap = max(min(isize + 64, 1024 * n), 4 * n, 1 << 16)
    # hard retry ceiling even without an explicit max_out: deflate expands
    # at most ~1032x, so a crafted multi-member stream with lying ISIZE
    # footers cannot drive the doubling loop to MemoryError (ADVICE r4)
    hard_cap = 1040 * n + (1 << 16)
    max_out = hard_cap if max_out is None else min(max_out, hard_cap)
    cap = min(cap, max_out)
    while True:
        out = np.empty(cap, dtype=np.uint8)
        produced = lib.fgumi_gzip_decompress(src.ctypes.data, n,
                                             out.ctypes.data, cap)
        if produced == -2:
            if cap >= max_out:
                return None  # too big to materialize: stream instead
            cap = min(cap * 2, max_out)
            continue
        src = None
        data = None
        if produced < 0:
            raise ValueError("malformed gzip stream")
        if cap - produced > (32 << 20):
            # a view would pin the whole over-allocation for the stream's
            # lifetime; copy down when the slack is significant
            return out[:produced].copy()
        return out[:produced]


def zlib_compress(data: bytes, level: int = 1):
    """zlib-format compression via libdeflate, or None (fallback to zlib)."""
    lib = get_lib()
    if lib is None:
        return None
    cap = len(data) + len(data) // 8 + 256
    out = ctypes.create_string_buffer(cap)
    n = lib.fgumi_zlib_compress(bytes(data), len(data), level, out, cap)
    if n < 0:
        raise ValueError("zlib compression failed")
    return out.raw[:n]


def zlib_decompress(data: bytes, out_size: int):
    """Decompress a zlib-format buffer of known output size, or None."""
    lib = get_lib()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(out_size)
    n = lib.fgumi_zlib_decompress(bytes(data), len(data), out, out_size)
    if n < 0:
        raise ValueError("malformed zlib frame")
    return out.raw[:n]


_COMPRESS_THREADS = None


def compress_threads() -> int:
    """Worker threads for multi-block BGZF compression. Default: min(4,
    cpus//2) — enough to keep the writer off the critical path without
    oversubscribing XLA's pool; override with FGUMI_TPU_COMPRESS_THREADS."""
    global _COMPRESS_THREADS
    if _COMPRESS_THREADS is None:
        env = os.environ.get("FGUMI_TPU_COMPRESS_THREADS", "")
        if env.isdigit():
            _COMPRESS_THREADS = max(int(env), 1)
        else:
            _COMPRESS_THREADS = max(min(4, (os.cpu_count() or 2) // 2), 1)
    return _COMPRESS_THREADS


def bgzf_compress_many(data, level: int = 1, threads: int = None):
    """Compress `data` into consecutive complete BGZF blocks (one native
    call, optionally multi-threaded). Returns the block stream bytes and the
    (n_blocks+1,) int64 block-offset table, or None (fallback)."""
    import numpy as np

    lib = get_lib()
    if lib is None:
        return None
    if threads is None:
        threads = compress_threads()
    src = np.frombuffer(memoryview(data), dtype=np.uint8)  # zero-copy
    n = len(src)
    n_blocks = (n + 0xFEFF) // 0xFF00
    bound = 0xFF00 + (0xFF00 >> 2) + 64  # >= deflate bound + BGZF framing
    out = np.empty(max(n_blocks, 1) * bound, dtype=np.uint8)
    block_off = np.empty(n_blocks + 1, dtype=np.int64)
    n_out = ctypes.c_long(0)
    total = lib.fgumi_bgzf_compress_many(
        src.ctypes.data, n, level, threads, out.ctypes.data, len(out), bound,
        block_off.ctypes.data, ctypes.byref(n_out))
    # release the caller's buffer before any raise (see bgzf_decompress) —
    # including `data` itself, which is typically the caller's memoryview
    # export over a bytearray it will resize during cleanup
    src = None
    data = None
    if total < 0:
        raise ValueError("BGZF multi-block compression failed")
    # a view, not .tobytes(): callers hand it straight to file.write()
    return out[:total], block_off


def bgzf_compress_block(data: bytes, level: int = 1):
    """One BGZF block for <=0xFF00 input bytes, or None (fallback)."""
    lib = get_lib()
    if lib is None:
        return None
    cap = len(data) + (1 << 12) + 64
    out = ctypes.create_string_buffer(cap)
    size = lib.fgumi_bgzf_compress_block(bytes(data), len(data), level, out,
                                         cap)
    if size < 0:
        raise ValueError("BGZF block compression failed")
    return out.raw[:size]
