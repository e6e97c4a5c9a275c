"""What the allocator holds: glibc's own counters, read once at a job's
start and once where its run report is built.

``read()`` asks the C library the process already runs on (``ctypes`` on the
global symbol table: no library is loaded for it) for ``mallinfo2`` (glibc
2.33+; sums over every arena) and for the arena count, which only
``malloc_info``'s XML gives. A C library without one of them leaves its keys
out of the record: absent, never zero. Nothing here runs unless a run report
was asked for (``cli._main_scoped``, ``report.build_report``).
"""

import ctypes
import resource

#: ``struct mallinfo2`` (all ``size_t``), in declaration order
_MALLINFO2_FIELDS = ("arena", "ordblks", "smblks", "hblks", "hblkhd",
                     "usmblks", "fsmblks", "uordblks", "fordblks",
                     "keepcost")
#: report key -> ``mallinfo2`` field
_KEYS = (("arena_bytes", "arena"), ("arena_free_bytes", "fordblks"),
         ("in_use_bytes", "uordblks"), ("mmap_chunks", "hblks"),
         ("mmap_bytes", "hblkhd"))


class _Mallinfo2(ctypes.Structure):
    _fields_ = [(name, ctypes.c_size_t) for name in _MALLINFO2_FIELDS]


_libc = None  # (mallinfo2 or None, malloc_info's functions or None)


def _bind():
    """The C library's functions, declared once; None for each it lacks."""
    global _libc
    if _libc is None:
        try:
            lib = ctypes.CDLL(None)
        except OSError:
            _libc = (None, None)
            return _libc
        mallinfo2 = getattr(lib, "mallinfo2", None)
        if mallinfo2 is not None:
            mallinfo2.argtypes = []
            mallinfo2.restype = _Mallinfo2
        info = None
        if all(hasattr(lib, f) for f in ("malloc_info", "open_memstream",
                                         "fclose", "free")):
            lib.open_memstream.argtypes = [ctypes.POINTER(ctypes.c_void_p),
                                           ctypes.POINTER(ctypes.c_size_t)]
            lib.open_memstream.restype = ctypes.c_void_p
            lib.malloc_info.argtypes = [ctypes.c_int, ctypes.c_void_p]
            lib.malloc_info.restype = ctypes.c_int
            lib.fclose.argtypes = [ctypes.c_void_p]
            lib.fclose.restype = ctypes.c_int
            lib.free.argtypes = [ctypes.c_void_p]
            lib.free.restype = None
            info = lib
        _libc = (mallinfo2, info)
    return _libc


def _arena_count(lib):
    """``<heap nr=...>`` elements of ``malloc_info``: one an arena."""
    buf = ctypes.c_void_p()
    size = ctypes.c_size_t()
    stream = lib.open_memstream(ctypes.byref(buf), ctypes.byref(size))
    if not stream:
        return None
    rc = lib.malloc_info(0, stream)
    lib.fclose(stream)  # flushes: buf and size are final from here
    try:
        if rc != 0 or not buf.value:
            return None
        return ctypes.string_at(buf.value, size.value).count(b"<heap nr=")
    finally:
        lib.free(buf)


def read() -> dict:
    """``{"maxrss_kb"[, "arenas"][, "arena_bytes", "arena_free_bytes",
    "in_use_bytes", "mmap_chunks", "mmap_bytes"]}`` as of now."""
    out = {"maxrss_kb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss}
    mallinfo2, info = _bind()
    if info is not None:
        arenas = _arena_count(info)
        if arenas is not None:
            out["arenas"] = arenas
    if mallinfo2 is not None:
        m = mallinfo2()
        for key, field in _KEYS:
            out[key] = int(getattr(m, field))
    return out
