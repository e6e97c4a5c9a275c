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

It also holds one entry per finished top-level invocation (``jobs``), with
or without a run report: its wall from ``cli.main``'s entry and the seconds
of each phase of it (:class:`JobClock`), so that the first report a process
writes also tells what the jobs before it spent outside their pipelines.
"""

import contextlib
import contextvars
import os
import threading
import time

#: compile / cache-load records kept (the oldest: start-up is what a later
#: reader cannot get from anywhere else)
MAX_COMPILE_RECORDS = 128
#: job entries kept, likewise the oldest
MAX_JOB_RECORDS = 128

_IMPORT_UNIX = time.time()
_lock = threading.Lock()
_spans = {}        # name -> {"s", "at_s"}: first occurrence only
_compiles = []
_compiles_dropped = 0
_jobs = []
_jobs_dropped = 0
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


#: the phases of a job in their order; the run between ``fill`` and
#: ``drain`` is no phase: it is the job's wall less the phases
PHASES = ("before_arm", "open", "fill", "drain", "close", "report")
#: those a span of the same name with ``job.`` before it covers (the command
#: thread's own: nothing is armed before ``open``, and ``report`` is the
#: report's own making)
_SPANNED = frozenset(("open", "fill", "drain", "close"))

_job = contextvars.ContextVar("fgumi_tpu_job_clock", default=None)
#: the clock a job's boundaries read
_clock = time.monotonic


class JobClock:
    """The phases of one top-level invocation, on the thread that runs it.

    ``before_arm``: ``cli.main``'s entry to the point where spans are armed
    (or would be); ``open``: from there until the command thread waits for
    the pipeline's first item; ``fill``: that wait; ``drain``: from the
    pipeline's last item until its stage threads are joined; ``close``:
    from ``run_stages``' return to the command's; ``report``: the run
    report's own making. A phase begins where the one before it ends, save
    the pipeline's run between ``fill`` and ``drain``. A command that
    starts no pipeline on its thread stays in ``open`` until it returns.

    One monotonic clock read a boundary, whether spans are armed or not;
    where they are, ``open``, ``fill``, ``drain`` and ``close`` are also
    spans ``job.<phase>`` of the command thread: ``fill`` and ``drain``
    declared waits, ``open`` and ``close`` work with the thread's
    ``getrusage`` deltas (which, as roots of the thread, they read anyway)."""

    __slots__ = ("at_s", "thread", "phase", "seconds", "_t0", "_t",
                 "_span")

    def __init__(self):
        self._t0 = self._t = _clock()
        self.at_s = since_start()
        self.thread = threading.get_ident()
        self.phase = "before_arm"
        self.seconds = dict.fromkeys(PHASES, 0.0)
        self._span = None

    def begin(self, phase, wait=False):
        """End the phase under way and begin ``phase`` (``None``: the
        pipeline's run, which is no phase); ``wait``: its span is a declared
        wait."""
        t = _clock()
        if self.phase is not None:
            self.seconds[self.phase] += t - self._t
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        self.phase, self._t = phase, t
        if phase in _SPANNED:
            from .trace import NULL_SPAN, span

            sp = span("job." + phase, wait=wait, rusage=not wait)
            if sp is not NULL_SPAN:
                self._span = sp
                sp.__enter__()

    def entry(self) -> dict:
        """The finished job's record entry (the phase under way ended)."""
        self.begin(None)
        out = {"at_s": round(self.at_s, 6),
               "wall_s": round(self._t - self._t0, 6)}
        for phase in PHASES:
            out[phase + "_s"] = round(self.seconds[phase], 6)
        return out


def current_job():
    """The running top-level invocation's :class:`JobClock`, else None."""
    return _job.get()


def job_here():
    """The running job's clock where the caller runs on its command thread
    and the job is still opening, else None: a nested stage of a chained
    command, a stage thread and a second pipeline of one command stamp
    nothing."""
    job = _job.get()
    if job is None or job.phase != "open" \
            or job.thread != threading.get_ident():
        return None
    return job


@contextlib.contextmanager
def job_record(nested=False):
    """Clock a top-level invocation and add its entry to the record when it
    returns; ``nested``: an invocation inside another one's (a stage of
    ``pipeline``), which gets no clock and hides the outer one's."""
    global _jobs_dropped
    job = None if nested else JobClock()
    token = _job.set(job)
    try:
        yield job
    finally:
        _job.reset(token)
        if job is not None:
            entry = job.entry()
            with _lock:
                if len(_jobs) < MAX_JOB_RECORDS:
                    # in the order the jobs ended (a daemon's overlap)
                    _jobs.append(entry)
                    _jobs.sort(key=job_end)
                else:
                    _jobs_dropped += 1


def job_end(entry) -> float:
    """Seconds from process start to where a record entry's job ended."""
    return entry["at_s"] + entry["wall_s"]


def snapshot() -> dict:
    """The run report's ``process`` section."""
    with _lock:
        out = {"start_unix": round(start_unix(), 6),
               "spans": {k: dict(v) for k, v in sorted(_spans.items())},
               "compiles": [dict(r) for r in _compiles],
               "jobs": [dict(r) for r in _jobs]}
        if _first_main_s is not None:
            out["first_main_s"] = round(_first_main_s, 6)
        if _compiles_dropped:
            out["compiles_dropped"] = _compiles_dropped
        if _jobs_dropped:
            out["jobs_dropped"] = _jobs_dropped
    return out
