"""Job-scoped telemetry: per-command registries resolved through a contextvar.

PR 2 gave every top-level CLI command clean counters by *resetting* the
process-global ``METRICS``/``DEVICE_STATS`` singletons at command entry.
That is correct for one command at a time but wrong the moment two commands
share a process concurrently — the serve daemon runs jobs on a worker pool,
and one job's reset would zero a neighbour's live counters mid-run.

This module replaces the reset with scoping: a :class:`TelemetryScope`
bundles one ``MetricsRegistry``, one ``DeviceStats``, and (optionally) one
tracer, and a :data:`contextvars.ContextVar` names the active scope. The
singletons in ``observe.metrics`` / ``ops.kernel`` / ``observe.trace``
become thin proxies that resolve the active scope on every call and fall
back to the old process-global objects when none is active — so library
users, tests, and single-command CLI runs see exactly the old behaviour,
while the daemon gets per-job isolation by entering one scope per job.

Contextvars do not cross ``threading.Thread`` boundaries on their own, so
every helper thread that contributes telemetry (pipeline reader/writer/
workers, BGZF prefetch, the device feeder, the heartbeat) is spawned
through :func:`spawn_thread` / a captured :func:`contextvars.copy_context`
— a job's counters follow its whole thread tree, not just the submitting
thread.
"""

import contextvars
import itertools
import threading

#: ordinals of the scopes created in this process: what a span's ``job`` is
#: when no serve job id names it (the Nth top-level invocation)
_ORDINALS = itertools.count(1)

_SCOPE = contextvars.ContextVar("fgumi_tpu_telemetry_scope", default=None)
#: Effective command line (argv list) override for output provenance (@PG
#: CL lines). The serve daemon sets this to the *client's* command line so a
#: job's outputs are byte-identical to the same command run standalone.
_ARGV = contextvars.ContextVar("fgumi_tpu_command_argv", default=None)
#: Pending job context for the NEXT telemetry scope created underneath: the
#: serve daemon re-enters ``cli.main`` per job, and main() builds the job's
#: scope itself — this is how the daemon hands the job id, the propagated
#: W3C-style trace context, and the upstream hop timestamps across that
#: re-entry (same pattern as :class:`command_argv`).
_JOB_CTX = contextvars.ContextVar("fgumi_tpu_job_context", default=None)


class TelemetryScope:
    """One command's telemetry world: metrics + device stats + tracer.

    Registries are created lazily: the ``DeviceStats`` in particular lives
    in ``ops.kernel`` and is only materialized when a kernel actually
    touches it, so numpy-free commands never pay that import."""

    __slots__ = ("label", "metrics", "tracer", "spans", "ordinal",
                 "_device_stats", "_lock", "trace_id", "parent_span_id",
                 "job_id", "hops")

    def __init__(self, label: str = None):
        from .metrics import MetricsRegistry

        self.label = label
        self.metrics = MetricsRegistry()
        self.tracer = None  # set by trace.start_trace inside the scope
        self.spans = None   # SpanAggregate, set by trace.arm_spans
        self.ordinal = next(_ORDINALS)
        self._device_stats = None
        self._lock = threading.Lock()
        #: fleet trace context (W3C-style ids propagated over the serve
        #: protocol): set by the daemon before running a job so the run
        #: report, the per-job trace, and every flight dump written inside
        #: this scope carry the client-visible correlation ids
        self.trace_id = None
        self.parent_span_id = None
        self.job_id = None
        #: upstream hop wall-clock timestamps for end-to-end latency
        #: attribution (client_sent_unix / balancer_recv_unix /
        #: balancer_sent_unix / admitted_unix / started_unix as available)
        self.hops = None

    def device_stats(self, factory):
        """This scope's DeviceStats, created on first use via ``factory``
        (the class object, passed in to avoid an import cycle with
        ops.kernel)."""
        with self._lock:
            if self._device_stats is None:
                self._device_stats = factory()
            return self._device_stats

    def device_stats_if_any(self):
        with self._lock:
            return self._device_stats


def current_scope():
    """The active :class:`TelemetryScope`, or None (process-global mode)."""
    return _SCOPE.get()


class scoped_telemetry:
    """Context manager entering a fresh (or given) telemetry scope.

    ``with scoped_telemetry("simplex"):`` gives the body — and every thread
    it spawns through :func:`spawn_thread` — its own metrics/device/trace
    registries, isolated from any other scope and from the process globals.
    """

    def __init__(self, label: str = None, scope: TelemetryScope = None):
        self.scope = scope if scope is not None else TelemetryScope(label)
        self._token = None

    def __enter__(self):
        self._token = _SCOPE.set(self.scope)
        return self.scope

    def __exit__(self, *exc):
        _SCOPE.reset(self._token)
        return False


class command_argv:
    """Context manager overriding the provenance command line (@PG CL).

    Outputs written inside the context record ``" ".join(argv)`` instead of
    the process's ``sys.argv`` — how a daemon job reproduces the exact
    header bytes of a standalone invocation."""

    def __init__(self, argv):
        self._argv = list(argv)
        self._token = None

    def __enter__(self):
        self._token = _ARGV.set(self._argv)
        return self._argv

    def __exit__(self, *exc):
        _ARGV.reset(self._token)
        return False


class job_context:
    """Context manager naming the fleet job context for scopes created
    inside it (the serve daemon wraps each job's ``cli.main`` re-entry).

    ``trace_id``/``parent_span_id`` are the propagated W3C-style ids (or
    None), ``hops`` the upstream wall-clock timestamps for end-to-end
    latency attribution (``client_sent_unix`` / ``balancer_recv_unix`` /
    ``balancer_sent_unix`` / ``admitted_unix`` / ``started_unix``)."""

    def __init__(self, job_id: str = None, trace_id: str = None,
                 parent_span_id: str = None, hops: dict = None):
        self._ctx = {"job_id": job_id, "trace_id": trace_id,
                     "parent_span_id": parent_span_id,
                     "hops": dict(hops) if hops else None}
        self._token = None

    def __enter__(self):
        self._token = _JOB_CTX.set(self._ctx)
        return self._ctx

    def __exit__(self, *exc):
        _JOB_CTX.reset(self._token)
        return False


def adopt_job_context(scope: TelemetryScope):
    """Stamp any pending :class:`job_context` onto a fresh scope (called
    by ``cli.main`` right after it creates the per-command scope)."""
    ctx = _JOB_CTX.get()
    if ctx is None:
        return
    scope.job_id = ctx["job_id"]
    scope.trace_id = ctx["trace_id"]
    scope.parent_span_id = ctx["parent_span_id"]
    scope.hops = ctx["hops"]


def current_argv():
    """The effective command line for provenance: the override set by
    :class:`command_argv` when inside one, else ``sys.argv``."""
    override = _ARGV.get()
    if override is not None:
        return override
    import sys

    return sys.argv


def publish_to_global(scope: TelemetryScope):
    """Copy a finished scope's counters onto the process-global fallbacks.

    The CLI calls this as each top-level command exits so the legacy
    inspection surface — ``METRICS`` / ``DEVICE_STATS`` read *after*
    ``cli_main`` returns by bench harnesses, probes, and tests — shows the
    finished command's numbers exactly as the old reset-at-entry globals
    did. Concurrent daemon jobs race here by design (last finisher wins):
    the per-job truth lives in each job's own scope and run report."""
    from . import metrics as _metrics

    _metrics._GLOBAL_REGISTRY.replace(scope.metrics.snapshot())
    # histograms MERGE instead of replacing: the global surface is the
    # cumulative-since-process-start view (Prometheus semantics — the serve
    # daemon's /metrics endpoint reads it), while each scope's run report
    # still carries only its own distributions
    _metrics._GLOBAL_REGISTRY.merge_histograms(scope.metrics.histograms())
    import sys

    kern = sys.modules.get("fgumi_tpu.ops.kernel")
    if kern is not None:
        stats = scope.device_stats_if_any()
        if stats is not None:
            kern._GLOBAL_DEVICE_STATS.load_from(stats)
        else:
            # the command never touched the device: the legacy surface must
            # read zero, exactly like the old reset-at-entry did — leaving a
            # previous command's dispatches visible would misattribute them
            kern._GLOBAL_DEVICE_STATS.reset()


_setname = None  # libc's pthread_setname_np; False once known missing


def os_thread_name(name: str) -> str:
    """``name`` in the 15 bytes the kernel keeps for a thread: without its
    ``fgumi-`` / ``chain-`` prefix where it is longer (fgumi-device-feeder
    -> device-feeder), and if that is still too long both its ends
    (chain-simplex-worker-0 -> simplexworker-0: the head says whose, the
    tail which; a plain cut would give two workers one name)."""
    if len(name) > 15 and name.startswith(("fgumi-", "chain-")):
        name = name[6:]
    if len(name) > 15:
        name = name[:7] + name[-8:]
    return name


def name_os_thread(name: str):
    """Give the calling thread its OS name (``pthread_setname_np``), which
    is what a profiler's host plane and ``top -H`` show: Python 3.12 names
    only its own Thread objects, so every thread of the process otherwise
    reads ``python3``. Best-effort; a platform without the call keeps the
    old names."""
    global _setname
    if _setname is None:
        try:
            import ctypes

            fn = ctypes.CDLL(None, use_errno=True).pthread_setname_np
            fn.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
            fn.restype = ctypes.c_int
            _setname = fn
        except (OSError, AttributeError):
            _setname = False
    if _setname:
        # CPython's thread ident is the pthread_t on POSIX
        _setname(threading.get_ident(), os_thread_name(name).encode()[:15])


#: what replaces the ``fgumi`` of a helper thread's name: a fused chain's
#: stage thread sets ``chain-<stage>``, so that the readers, writers and
#: workers its ``run_stages`` starts are told apart from the next stage's
_thread_prefix = contextvars.ContextVar("fgumi_tpu_thread_prefix",
                                        default=None)


def set_thread_prefix(prefix: str):
    """Name the helper threads started from this context (and its copies)
    ``<prefix>-reader`` ... instead of ``fgumi-reader`` ...: a name only."""
    _thread_prefix.set(prefix)


def spawn_thread(target, *, name=None, daemon=True, args=()):
    """A ``threading.Thread`` whose target runs in a copy of the caller's
    context — the one-line way to keep a job's telemetry scope attached to
    its helper threads — under its OS thread name. Returned un-started
    (call ``.start()``)."""
    ctx = contextvars.copy_context()
    prefix = _thread_prefix.get()
    if prefix and name and name.startswith("fgumi-"):
        name = prefix + name[5:]

    def run():
        if name:
            name_os_thread(name)
        ctx.run(target, *args)

    return threading.Thread(target=run, name=name, daemon=daemon)
