"""Thread-aware span tracing: one span call, three sinks.

``span("engine.pack", batch=3)`` times a region of one thread. Spans are
live when the invocation has ``--trace`` **or** ``--run-report`` (armed in
``cli._main_scoped``); a live span knows the span that was open on the same
thread when it began (its parent, from a thread-local stack) and goes to:

- **the aggregate** (:class:`SpanAggregate`, always when armed): per scope
  and span name ``count``, ``wall_s``, ``self_s`` (duration minus the
  direct children's cover), ``wait_s`` (cover of the ``wait=True`` spans
  below it on the same thread) and the 50th percentile; for spans opened
  with ``rusage=True`` also the thread's ``RUSAGE_THREAD`` deltas. It
  becomes the run report's ``spans`` section. The same records, kept per
  thread, are the ``threads`` section: a thread's *root* spans (no parent
  on its stack) give its wall, its declared waits and, from one
  ``getrusage`` pair a root span, its CPU seconds.
- **the profiler's clock**: once ``jax`` is imported a live span also
  enters a ``jax.profiler.TraceAnnotation`` of the same name, so while a
  profiler session is open (``--xla-profile``, or a harness's
  ``jax.profiler.start_trace``) it lands in the xplane's host plane on the
  thread that ran it, beside the device operations. With no session open
  the annotation costs a flag test. jax is never imported for this.
- **the Chrome trace** under ``--trace``: a trace-event *complete* event
  (``ph: "X"``) that opens in Perfetto (or ``chrome://tracing``) with one
  timeline row per thread, the parent's name in ``args``.

Design constraints (the acceptance contract of the telemetry layer):

- **Zero overhead when disabled.** ``span()`` with neither flag returns one
  shared no-op context manager — no allocation, no lock, no time call, no
  ``getrusage``, no jax. Per-record loops that want even the dict-build of
  attrs gone hoist ``tracing_enabled()`` once and skip their span calls.
- **Thread attribution.** Events carry the OS thread id and the trace
  names each thread once via ``thread_name`` metadata events, so the
  fgumi-reader / fgumi-writer / fgumi-worker-N / fgumi-device-feeder rows
  are labelled.
- **Bounded memory.** The event buffer is capped (:data:`MAX_EVENTS`,
  override ``FGUMI_TPU_TRACE_MAX_EVENTS``); overflow drops further spans
  and reports the dropped count in the export rather than growing without
  bound on a long run.
- **Cross-process linkage.** A W3C-style trace context (32-hex trace-id +
  16-hex parent-span-id, carried as a ``traceparent`` string) can be
  attached to a tracer; the export then stamps it into ``otherData`` and a
  ``process_labels`` metadata event so ``fgumi-tpu trace-merge`` can stitch
  per-process files from one fleet-routed job into a single timeline.
  Every export also records a wall-clock anchor (``t_zero_unix`` paired
  with the monotonic ``t_zero``) — the merge tool aligns per-process
  timelines on these anchors (docs/observability.md "Fleet tracing").
"""

import functools
import json
import os
import sys
import threading
import time

from .scope import current_scope

# ---------------------------------------------------------------------------
# W3C-style trace context (trace-id + parent-span-id)

#: traceparent wire format, a strict subset of W3C Trace Context:
#: ``00-<32 hex trace-id>-<16 hex span-id>-01``. Malformed values are
#: IGNORED by every consumer (dropped, never rejected) so a buggy or
#: future-version peer can't fail a submission over telemetry garnish.
_TRACEPARENT_VERSION = "00"


def mint_trace_id() -> str:
    """A fresh 32-hex trace id (random, collision-safe across the fleet)."""
    return os.urandom(16).hex()


def mint_span_id() -> str:
    """A fresh 16-hex span id."""
    return os.urandom(8).hex()


def format_traceparent(trace_id: str, span_id: str) -> str:
    """``00-<trace-id>-<span-id>-01`` (sampled flag always set: fgumi-tpu
    traces are explicitly requested, never probabilistically sampled)."""
    return f"{_TRACEPARENT_VERSION}-{trace_id}-{span_id}-01"


def _is_hex(s: str, n: int) -> bool:
    if len(s) != n:
        return False
    try:
        int(s, 16)
    except ValueError:
        return False
    return True


def parse_traceparent(value):
    """``(trace_id, span_id)`` for a well-formed traceparent, else None.

    None for anything malformed — wrong type, wrong field count, non-hex,
    all-zero ids — per the propagation contract: telemetry context is
    best-effort garnish and must never fail a request."""
    if not isinstance(value, str):
        return None
    parts = value.split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if not (_is_hex(version, 2) and _is_hex(trace_id, 32)
            and _is_hex(span_id, 16) and _is_hex(flags, 2)):
        return None
    if version == "ff" or set(trace_id) == {"0"} or set(span_id) == {"0"}:
        return None
    return trace_id, span_id


# ---------------------------------------------------------------------------
# no-op fast path


class _NullSpan:
    """Shared do-nothing context manager returned while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        """No-op attr update (mirrors the live span's API)."""


NULL_SPAN = _NullSpan()

# process-global sinks, used when no telemetry scope is active
_tracer = None     # _Tracer (the Chrome trace), or None
_aggregate = None  # SpanAggregate: spans are live iff this is set


def _current_sinks():
    """``(aggregate, tracer)`` spans should record into: the active
    telemetry scope's (one per daemon job) when inside one, else the
    process-global pair. A scope with spans off shades the global sinks on
    purpose — job A tracing must not collect job B's spans. The aggregate
    is set whenever spans are live; the tracer only under ``--trace``."""
    scope = current_scope()
    if scope is not None:
        return scope.spans, scope.tracer
    return _aggregate, _tracer


def _current_tracer():
    return _current_sinks()[1]


def tracing_enabled() -> bool:
    """True when spans are live (``--trace`` or ``--run-report``)."""
    return _current_sinks()[0] is not None


def current_aggregate():
    """The live :class:`SpanAggregate`, or None (the run report reads it)."""
    return _current_sinks()[0]


# ---------------------------------------------------------------------------
# live spans

MAX_EVENTS = 500_000

#: spans whose names start so are per-block I/O: they never reach the
#: flight ring, which is for dispatches and breaker transitions, and as a
#: thread's root spans they read no ``getrusage``
_PER_BLOCK_PREFIXES = ("bgzf.", "io.")

#: per-thread stack of the open live spans, outermost first
_tls = threading.local()

try:
    import resource as _resource

    _RUSAGE_THREAD = getattr(_resource, "RUSAGE_THREAD", None)
except ImportError:  # pragma: no cover - non-POSIX
    _resource = None
    _RUSAGE_THREAD = None

#: ``getrusage`` fields a ``rusage=True`` span records, as report keys
_RUSAGE_FIELDS = (("utime_s", "ru_utime"), ("stime_s", "ru_stime"),
                  ("minflt", "ru_minflt"))
#: those of them a thread's record keeps, summed over its root spans
_THREAD_RUSAGE = ("utime_s", "stime_s")
_THREAD_RUSAGE_AT = tuple(i for i, (key, _f) in enumerate(_RUSAGE_FIELDS)
                          if key in _THREAD_RUSAGE)


def _rusage_value(key, v):
    """A summed ``getrusage`` delta as the report has it: seconds to the
    microsecond, counts whole."""
    return round(v, 6) if key.endswith("_s") else int(v)


_annotation = None  # jax.profiler.TraceAnnotation once jax is imported


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` if jax is already imported, else
    None: the mirror onto the profiler's clock never imports jax itself
    (and tolerates a jax another thread is still half-way through
    importing)."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        _annotation = getattr(getattr(jax, "profiler", None),
                              "TraceAnnotation", None)
    return _annotation


class _Span:
    """One in-flight span: recorded in every armed sink on exit."""

    __slots__ = ("_agg", "_tracer", "name", "args", "_t0", "_parent",
                 "_child_s", "_wait_s", "_wait", "_rusage", "_ru0", "_annot",
                 "_counts")

    def __init__(self, agg, tracer, name, args, rusage, wait):
        self._agg = agg
        self._tracer = tracer
        self.name = name
        self.args = args
        self._parent = None
        self._child_s = 0.0  # cover of the direct children
        self._wait_s = 0.0   # cover of the wait spans anywhere below
        self._wait = wait
        self._rusage = rusage  # the call site's: deltas in the name's record
        self._ru0 = rusage   # True until __enter__ reads the counters
        self._annot = None
        self._counts = None
        self._t0 = None

    def set(self, **attrs):
        """Attach attrs discovered mid-span (recorded at exit)."""
        if self.args is None:
            self.args = attrs
        else:
            self.args.update(attrs)

    def __enter__(self):
        # few Python-level calls on purpose: a profiler's Python tracer
        # charges every one of them to the span's parent
        try:
            stack = _tls.stack
        except AttributeError:
            stack = _tls.stack = []
            _tls.name = threading.current_thread().name
        if stack:
            self._parent = stack[-1]
        elif not (self._wait or self.name.startswith(_PER_BLOCK_PREFIXES)):
            self._ru0 = True  # a root span: the thread's record reads them
        stack.append(self)
        cls = _annotation or _annotation_cls()
        if cls is not None:
            kw = {"job": self._agg.job}
            if self.args and "batch" in self.args:
                kw["batch"] = self.args["batch"]
            self._annot = cls(self.name, **kw)
            self._annot.__enter__()
        if self._ru0:
            self._ru0 = (_resource.getrusage(_RUSAGE_THREAD)
                         if _RUSAGE_THREAD is not None else None)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.monotonic()
        ru = None
        if self._ru0:
            ru1 = _resource.getrusage(_RUSAGE_THREAD)
            ru = [getattr(ru1, f) - getattr(self._ru0, f)
                  for _key, f in _RUSAGE_FIELDS]
        if self._annot is not None:
            self._annot.__exit__(exc_type, exc, tb)
        stack = _tls.stack
        if stack[-1] is self:
            stack.pop()
        else:  # a generator suspended inside a span
            stack.remove(self)
        dur = t1 - self._t0
        waited = dur if self._wait else self._wait_s
        parent = self._parent
        if parent is not None:
            parent._child_s += dur
            parent._wait_s += waited
        self._agg.record(self.name, dur, max(dur - self._child_s, 0.0),
                         waited, ru if self._rusage else None, self._counts,
                         _tls.name, parent is None, ru)
        if self._tracer is not None:
            args = self.args
            if parent is not None:
                args = dict(args or ())
                args["parent"] = parent.name
            self._tracer._complete(
                self.name, self._t0, t1, args,
                error=exc_type.__name__ if exc_type else None)
        return False


class _SpanStat:
    __slots__ = ("count", "wall_s", "self_s", "wait_s", "hist", "rusage",
                 "counts")

    def __init__(self):
        from .metrics import Histogram

        self.count = 0
        self.wall_s = 0.0
        self.self_s = 0.0
        self.wait_s = 0.0
        self.hist = Histogram()
        self.rusage = None
        self.counts = None


class _ThreadStat:
    """What one thread of the job did, from its root spans."""

    __slots__ = ("root_wall_s", "wait_s", "clocked_work_s", "rusage",
                 "roots", "self_s")

    def __init__(self):
        self.root_wall_s = 0.0
        self.wait_s = 0.0
        self.clocked_work_s = 0.0  # work of the roots that read getrusage
        self.rusage = None         # _THREAD_RUSAGE sums over those roots
        self.roots = {}            # root span name -> its work seconds
        self.self_s = {}           # span name -> self seconds on this thread


class SpanAggregate:
    """Per-scope totals by span name and by thread: the run report's
    ``spans`` and ``threads`` sections.

    ``job`` names whose spans these are: the serve job id, else the
    ordinal of the top-level invocation in this process (0 with no scope).
    ``alloc_start`` is the allocator record of the job's start when a run
    report was asked for (observe/alloc.py), else None.
    """

    def __init__(self, job=0):
        self.job = job
        self.alloc_start = None
        self._lock = threading.Lock()
        self._by_name = {}
        self._by_thread = {}

    def record(self, name, dur, self_s, wait_s=0.0, rusage=None,
               counts=None, thread=None, root=False, root_rusage=None):
        """Fold one finished span in. ``root``: it had no parent on its
        thread's stack, so it counts towards the thread's wall and waits,
        with ``root_rusage`` (the ``_RUSAGE_FIELDS`` deltas over it, or
        None where it read none) towards the thread's CPU seconds."""
        if thread is None:
            thread = threading.current_thread().name
        with self._lock:
            st = self._by_name.get(name)
            if st is None:
                st = self._by_name[name] = _SpanStat()
            st.count += 1
            st.wall_s += dur
            st.self_s += self_s
            st.wait_s += wait_s
            st.hist.observe(dur)
            if rusage is not None:
                if st.rusage is None:
                    st.rusage = [0] * len(_RUSAGE_FIELDS)
                for i, v in enumerate(rusage):
                    st.rusage[i] += v
            if counts:
                if st.counts is None:
                    st.counts = {}
                for k, v in counts.items():
                    st.counts[k] = st.counts.get(k, 0) + v
            th = self._by_thread.get(thread)
            if th is None:
                th = self._by_thread[thread] = _ThreadStat()
            th.self_s[name] = th.self_s.get(name, 0.0) + self_s
            if root:
                work = max(dur - wait_s, 0.0)
                th.root_wall_s += dur
                th.wait_s += wait_s
                th.roots[name] = th.roots.get(name, 0.0) + work
                if root_rusage is not None:
                    th.clocked_work_s += work
                    if th.rusage is None:
                        th.rusage = [0] * len(_THREAD_RUSAGE)
                    for i, at in enumerate(_THREAD_RUSAGE_AT):
                        th.rusage[i] += root_rusage[at]

    def snapshot(self) -> dict:
        """``{"job": ..., "by_name": {span name: record}, "threads":
        {thread name: record}}``, names sorted, both views as of one
        instant.

        A thread's record, of every thread that ended a span:
        ``root_wall_s`` = ``work_s`` + ``wait_s`` over its root spans,
        ``roots`` (root span name -> work seconds) and ``self_s`` by span
        name. A thread whose root spans read ``getrusage`` (all but waits
        and per-block I/O) also has ``utime_s`` and ``stime_s`` over them
        and ``offcpu_s``: the seconds of their work the thread was on no
        CPU, having declared no wait."""
        by_name = {}
        threads = {}
        with self._lock:
            for name in sorted(self._by_name):
                st = self._by_name[name]
                rec = {"count": st.count, "wall_s": round(st.wall_s, 6),
                       "self_s": round(st.self_s, 6),
                       "wait_s": round(st.wait_s, 6),
                       "p50_s": round(st.hist.quantile(0.50), 6),
                       "max_s": round(st.hist.max, 6)}
                if st.rusage is not None:
                    for (key, _f), v in zip(_RUSAGE_FIELDS, st.rusage):
                        rec[key] = _rusage_value(key, v)
                if st.counts:
                    rec.update(st.counts)
                by_name[name] = rec
            for thread in sorted(self._by_thread):
                th = self._by_thread[thread]
                rec = {"root_wall_s": round(th.root_wall_s, 6),
                       "wait_s": round(th.wait_s, 6),
                       "work_s": round(max(th.root_wall_s - th.wait_s, 0.0),
                                       6)}
                if th.rusage is not None:
                    for key, v in zip(_THREAD_RUSAGE, th.rusage):
                        rec[key] = _rusage_value(key, v)
                    rec["offcpu_s"] = round(max(
                        th.clocked_work_s - th.rusage[0] - th.rusage[1],
                        0.0), 6)
                rec["roots"] = {n: round(v, 6)
                                for n, v in sorted(th.roots.items())}
                rec["self_s"] = {n: round(v, 6)
                                 for n, v in sorted(th.self_s.items())}
                threads[thread] = rec
        return {"job": self.job, "by_name": by_name, "threads": threads}


class _Tracer:
    def __init__(self, max_events: int = None):
        if max_events is None:
            try:
                max_events = int(os.environ.get(
                    "FGUMI_TPU_TRACE_MAX_EVENTS", str(MAX_EVENTS)))
            except ValueError:
                max_events = MAX_EVENTS
        self.max_events = max_events
        # the clock anchor pair: one monotonic zero for in-file timestamps
        # and the wall-clock instant it corresponds to, captured
        # back-to-back. trace-merge aligns per-process files by shifting
        # each timeline so the anchors agree (the residual error is the
        # few-ns gap between these two calls plus any host clock skew,
        # correctable with the handshake offset estimate).
        self.t_zero = time.monotonic()
        self.t_zero_unix = time.time()
        #: W3C-style trace context (set via :meth:`set_context` when this
        #: process's work is part of a fleet-routed job); exported so
        #: trace-merge can group per-process files under one trace-id
        self.trace_id = None
        self.parent_span_id = None
        #: human label for this process's track group in a merged timeline
        #: (e.g. "client", "balancer", "backend j-3")
        self.process_label = None
        #: estimated local-minus-server wall clock skew (seconds), from
        #: the serve handshake round trip; trace-merge subtracts it from
        #: the anchor so cross-host timelines line up on the server clock
        self.clock_offset_s = None
        self.dropped = 0
        self._lock = threading.Lock()
        self._events = []
        self._named_tids = set()

    def set_context(self, trace_id: str = None, parent_span_id: str = None,
                    process_label: str = None):
        """Attach the fleet trace context (any subset; idempotent)."""
        if trace_id is not None:
            self.trace_id = trace_id
        if parent_span_id is not None:
            self.parent_span_id = parent_span_id
        if process_label is not None:
            self.process_label = process_label

    def _thread_meta_locked(self):
        """Emit a thread_name metadata event for the calling thread once."""
        tid = threading.get_ident()
        if tid not in self._named_tids:
            self._named_tids.add(tid)
            self._events.append({
                "name": "thread_name", "ph": "M", "pid": os.getpid(),
                "tid": tid,
                "args": {"name": threading.current_thread().name}})
        return tid

    def _complete(self, name, t0, t1, args, error=None):
        # span ends of a --trace run also feed the flight recorder's ring
        # (the black box shows the last spans even when the trace buffer
        # overflowed or was never exported); per-block I/O spans do not:
        # they would evict the dispatches and breaker transitions the
        # 512-entry ring is for
        if not name.startswith(_PER_BLOCK_PREFIXES):
            from .flight import FLIGHT

            FLIGHT.note("span", name=name,
                        dur_ms=round((t1 - t0) * 1e3, 3),
                        **({"error": error} if error else {}))
        ev = {"name": name, "ph": "X", "pid": os.getpid(),
              "ts": round((t0 - self.t_zero) * 1e6, 1),
              "dur": round((t1 - t0) * 1e6, 1)}
        if error is not None:
            args = dict(args or ())
            args["error"] = error
        if args:
            ev["args"] = args
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            ev["tid"] = self._thread_meta_locked()
            self._events.append(ev)

    def instant(self, name, args=None):
        ev = {"name": name, "ph": "i", "s": "t", "pid": os.getpid(),
              "ts": round((time.monotonic() - self.t_zero) * 1e6, 1)}
        if args:
            ev["args"] = args
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            ev["tid"] = self._thread_meta_locked()
            self._events.append(ev)

    def snapshot(self):
        with self._lock:
            return list(self._events)

    def to_json_obj(self):
        events = self.snapshot()
        if self.dropped:
            # an explicit truncation marker INSIDE the timeline: a human in
            # Perfetto sees where recording stopped instead of silently
            # reading a gap as "nothing happened after this"
            events.append({
                "name": "trace.truncated", "ph": "i", "s": "g",
                "pid": os.getpid(), "tid": 0,
                "ts": round((time.monotonic() - self.t_zero) * 1e6, 1),
                "args": {"dropped_events": self.dropped,
                         "max_events": self.max_events}})
        if self.process_label:
            # a process_name metadata event labels this pid's track group
            # when the file is merged with other processes' timelines
            events.append({"name": "process_name", "ph": "M",
                           "pid": os.getpid(), "tid": 0,
                           "args": {"name": self.process_label}})
        obj = {"traceEvents": events, "displayTimeUnit": "ms"}
        clock = {"t_zero_unix": round(self.t_zero_unix, 6)}
        if self.clock_offset_s is not None:
            clock["offset_estimate_s"] = round(self.clock_offset_s, 6)
        other = {"clock": clock,
                 "process": {"pid": os.getpid(),
                             "label": self.process_label}}
        if self.trace_id:
            other["trace_context"] = {"trace_id": self.trace_id,
                                      "parent_span_id": self.parent_span_id}
        if self.dropped:
            other["dropped_events"] = self.dropped
        obj["otherData"] = other
        return obj


# ---------------------------------------------------------------------------
# module API


def span(name: str, *, rusage: bool = False, wait: bool = False, **attrs):
    """Time a region of the current thread as a named span.

    With neither ``--trace`` nor ``--run-report`` this returns the shared
    :data:`NULL_SPAN` (no allocation, no clock read); armed, the span is
    recorded in every sink when the context exits, with its parent (the
    span open on this thread when it began). ``rusage=True`` (layer-level
    spans, not per-block ones) adds the thread's ``getrusage`` deltas;
    ``wait=True`` marks a span in which the thread only waits, so that an
    enclosing span's ``wall_s - wait_s`` is its own work. Never leave a
    span open across a ``yield``. Exceptions propagate (the Chrome event
    records ``error: <type>``)."""
    agg, tracer = _current_sinks()
    if agg is None:
        return NULL_SPAN
    return _Span(agg, tracer, name, attrs or None, rusage, wait)


def spanned(name: str, **span_kw):
    """Decorator: the whole call of a (non-generator) function is one span."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name, **span_kw):
                return fn(*args, **kwargs)
        return wrapper
    return deco


def spanned_iter(name: str, iterable, **span_kw):
    """Yield the items of ``iterable``, each pull inside a span of its own
    (never open across the ``yield``): the work of a generator stage happens
    in its pulls, on whichever thread drives them."""
    it = iter(iterable)
    while True:
        with span(name, **span_kw):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


def record_interval(name: str, t0: float, t1: float, **attrs):
    """Record a span that began on another thread (``time.monotonic()``
    stamps; e.g. a queue wait from submit to pick-up), on the thread that
    ends it. It has no parent and cannot be mirrored onto the profiler's
    clock, which takes no back-dated events."""
    agg, tracer = _current_sinks()
    if agg is None:
        return
    dur = max(t1 - t0, 0.0)
    agg.record(name, dur, dur)
    if tracer is not None:
        tracer._complete(name, t0, t1, attrs or None)


def count(span_name: str, key: str, n=1):
    """Add ``n`` to counter ``key`` of the innermost open span called
    ``span_name`` on this thread (folded into its aggregate record); a
    no-op when there is none."""
    for sp in reversed(getattr(_tls, "stack", ())):
        if sp.name == span_name:
            if sp._counts is None:
                sp._counts = {}
            sp._counts[key] = sp._counts.get(key, 0) + n
            return


def instant(name: str, **attrs):
    """Record a zero-duration instant event (a timeline marker)."""
    t = _current_tracer()
    if t is not None:
        t.instant(name, attrs or None)


def set_trace_context(trace_id: str = None, parent_span_id: str = None,
                      process_label: str = None):
    """Attach the fleet trace context to the active tracer (no-op when
    tracing is off — context is garnish, never a reason to allocate)."""
    t = _current_tracer()
    if t is not None:
        t.set_context(trace_id, parent_span_id, process_label)


def set_clock_offset(offset_s: float):
    """Record the handshake clock-offset estimate on the active tracer
    (no-op when tracing is off)."""
    t = _current_tracer()
    if t is not None:
        t.clock_offset_s = float(offset_s)


def arm_spans():
    """Make spans live for the active telemetry scope (one per daemon job),
    or process-wide when no scope is entered: the aggregate and the
    profiler-clock mirror, no Chrome trace. Idempotent; returns the
    :class:`SpanAggregate`."""
    global _aggregate
    scope = current_scope()
    if scope is not None:
        if scope.spans is None:
            scope.spans = SpanAggregate(scope.job_id or scope.ordinal)
        return scope.spans
    if _aggregate is None:
        _aggregate = SpanAggregate()
    return _aggregate


def start_trace(max_events: int = None):
    """Arm spans and attach a Chrome tracer (``--trace``), for the active
    telemetry scope or process-wide when no scope is entered. Idempotent
    (keeps the active tracer)."""
    global _tracer
    arm_spans()
    scope = current_scope()
    if scope is not None:
        if scope.tracer is None:
            scope.tracer = _Tracer(max_events)
        return scope.tracer
    if _tracer is None:
        _tracer = _Tracer(max_events)
    return _tracer


def stop_trace():
    """Disarm spans (scope-local when inside a scope) and return the Chrome
    tracer, if one was attached (caller may still export it)."""
    global _tracer, _aggregate
    scope = current_scope()
    if scope is not None:
        t, scope.tracer, scope.spans = scope.tracer, None, None
        return t
    t, _tracer, _aggregate = _tracer, None, None
    return t


def write_trace(path: str, tracer=None):
    """Export the trace as Chrome trace-event JSON, committed atomically.

    Writes the active tracer by default; pass the object returned by
    :func:`stop_trace` to export after disabling."""
    t = tracer if tracer is not None else _tracer
    if t is None:
        return
    if t.dropped:
        # overflow is an observability *defect* worth a counter: the run
        # report says how much of the timeline is missing
        from .metrics import METRICS

        METRICS.inc("trace.dropped_events", t.dropped)
    from ..utils.atomic import discard_output, open_output

    out = open_output(path, "w")
    try:
        json.dump(t.to_json_obj(), out, separators=(",", ":"))
    except BaseException:
        discard_output(out)
        raise
    out.close()
