"""What the process did before and between its commands.

A run report covers one command, but a process pays for things once: the
jax import, the native library, every executable it compiles or loads from
the persistent cache. A harness or daemon that runs several commands in one
process writes reports for only some of them, so this record rides in every
report's ``process`` section: seconds from process start to the first
``cli.main``, the ``startup.*`` spans, and one entry per backend compile and
per persistent-cache load of the whole process so far. A reader splits the
entries at a job's ``started_unix`` (an entry ended at ``start_unix +
at_s``): older is start-up, the rest is that job's (or its window's).
"""

import contextlib
import os
import threading
import time

#: compile / cache-load records kept (the oldest: start-up is what a later
#: reader cannot get from anywhere else)
MAX_COMPILE_RECORDS = 128

_IMPORT_UNIX = time.time()
_lock = threading.Lock()
_spans = {}        # name -> {"s", "at_s"}: first occurrence only
_compiles = []
_compiles_dropped = 0
_first_main_s = None


def _kernel_start_unix():
    """Wall-clock start of this process as the kernel has it
    (``/proc/self/stat`` field 22 against the boot-time clock), else None."""
    try:
        with open("/proc/self/stat", "rb") as f:
            stat = f.read().decode("ascii", "replace")
        ticks = int(stat.rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
        return time.time() - age
    except (OSError, ValueError, IndexError, AttributeError):
        return None


_start_unix = None


def start_unix() -> float:
    """Process start: the kernel's, else this package's import (read on
    first use, not at import)."""
    global _start_unix
    if _start_unix is None:
        _start_unix = _kernel_start_unix() or _IMPORT_UNIX
    return _start_unix


def since_start() -> float:
    return time.time() - start_unix()


def note_main():
    """Stamp the first ``cli.main`` of the process (idempotent), and give
    the main thread its OS name when that is who runs the command: it is
    the processing thread, which sets the pace, and a profiler's host plane
    shows OS names (the executable's, otherwise). A profiler keeps the name
    a thread had at its first event, so the name is set once, before any
    session a harness may open round a later command, and stays."""
    global _first_main_s
    if _first_main_s is None:
        _first_main_s = since_start()
        if threading.current_thread() is threading.main_thread():
            from .scope import name_os_thread

            name_os_thread("fgumi-process")


@contextlib.contextmanager
def startup_span(name: str):
    """Time a once-per-process start-up phase (``startup.jax_import``,
    ``startup.native_load``). These run before any telemetry scope exists
    in a harness, so the seconds go to the process record; the ordinary
    span inside shows them in an armed command's own sinks too."""
    from .trace import span

    at = since_start()
    t0 = time.monotonic()
    try:
        with span(name):
            yield
    finally:
        dt = time.monotonic() - t0
        with _lock:
            _spans.setdefault(name, {"s": round(dt, 6),
                                     "at_s": round(at, 6)})


def note_compile(kind: str, seconds: float, shape=None, fun=None):
    """One backend compile (``kind="compile"``) or persistent-cache load
    (``"cache_load"``), stamped when it ended."""
    global _compiles_dropped
    rec = {"kind": kind, "s": round(float(seconds), 6),
           "at_s": round(since_start(), 6)}
    if shape:
        rec["shape"] = shape
    if fun:
        rec["fun"] = str(fun)
    with _lock:
        if len(_compiles) < MAX_COMPILE_RECORDS:
            _compiles.append(rec)
        else:
            _compiles_dropped += 1


def snapshot() -> dict:
    """The run report's ``process`` section."""
    with _lock:
        out = {"start_unix": round(start_unix(), 6),
               "spans": {k: dict(v) for k, v in sorted(_spans.items())},
               "compiles": [dict(r) for r in _compiles]}
        if _first_main_s is not None:
            out["first_main_s"] = round(_first_main_s, 6)
        if _compiles_dropped:
            out["compiles_dropped"] = _compiles_dropped
    return out
