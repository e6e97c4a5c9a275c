"""Fixed-role threaded host pipeline.

The simplified unified-pipeline analog SURVEY §7 step 9 calls for (reference:
/root/reference/src/lib/unified_pipeline/base.rs:1123-1150 9-step pool;
worker loop base.rs:4439-4600): fixed-role stages — reader (BGZF decompress +
boundary scan, native), processor (decode/group/pack/device, main thread),
writer (BGZF compress, native) — joined by bounded queues for backpressure.
The native calls release the GIL, so stages genuinely overlap; the
14-scheduler zoo is deliberately skipped (fixed roles saturate a device-fed
pipeline).

`threads <= 1` runs everything inline on the caller thread. Commands
without a resolve stage get the strictly serial fast path (the semantic
reference, reference bam.rs:3301, performance-tuning.md:28-40); with a
resolve stage the default holds one output in flight so a device dispatch
overlaps the next item's host work (FGUMI_TPU_INLINE_FLIGHT=1 restores
strict serial order for bisection).
"""

import logging
import queue
import threading
import time

log = logging.getLogger("fgumi_tpu")


class StageTimes:
    """Per-stage busy/blocked wall time + queue-occupancy samples
    (PipelineStats-lite, reference base.rs:2853-3379: per-step timers and
    QueueSample history; VERDICT r4 item 9)."""

    def __init__(self):
        self.busy = {}
        self.blocked = {}
        self.q_samples = 0
        self.q_in_sum = 0
        self.q_in_max = 0
        self.q_out_sum = 0
        self.q_out_max = 0

    def add_busy(self, stage: str, dt: float):
        self.busy[stage] = self.busy.get(stage, 0.0) + dt

    def add_blocked(self, stage: str, dt: float):
        self.blocked[stage] = self.blocked.get(stage, 0.0) + dt

    def sample_queues(self, q_in_depth: int, q_out_depth: int):
        """One occupancy sample per processed item (the analog of the
        reference's QueueSample monitor history, bam.rs:3640-3690)."""
        self.q_samples += 1
        self.q_in_sum += q_in_depth
        self.q_in_max = max(self.q_in_max, q_in_depth)
        self.q_out_sum += q_out_depth
        self.q_out_max = max(self.q_out_max, q_out_depth)

    def format_table(self) -> str:
        stages = sorted(set(self.busy) | set(self.blocked))
        lines = ["stage        busy_s   blocked_s"]
        for s in stages:
            lines.append(f"{s:<12} {self.busy.get(s, 0.0):7.3f}   "
                         f"{self.blocked.get(s, 0.0):7.3f}")
        if self.q_samples:
            lines.append(
                f"queues       in avg {self.q_in_sum / self.q_samples:.1f} "
                f"max {self.q_in_max}; out avg "
                f"{self.q_out_sum / self.q_samples:.1f} max {self.q_out_max} "
                f"({self.q_samples} samples)")
        return "\n".join(lines)


class _Err:
    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


_DONE = object()


# The input queue's bytes-in-flight governor (the byte-accurate analog of
# the reference's MemoryTracker hysteresis, base.rs:466-625, now the shared
# dynamic-budget primitive): producers block while admitting another item
# would exceed the limit, except that one item is always admitted (an
# oversized batch degrades to serial flow instead of deadlocking);
# limit <= 0 disables accounting. run_stages registers it with the
# process-wide ResourceGovernor so a demand-starved input queue can borrow
# budget from idle ones (utils/governor.py).
from .utils.governor import DynamicBudget as _ByteBudget  # noqa: E402


class _Watchdog:
    """Stall detector for the threaded pipeline (deadlock-watchdog-lite,
    reference deadlock.rs:1-60): a daemon timer samples the stage counters
    every `interval` seconds; when no stage made progress between samples
    while work remains, it logs a queue/stage snapshot so a wedged run is
    diagnosable from the log instead of silent. With recover=True it also
    doubles the queue and byte limits on each stall (the reference's
    --deadlock-recover adaptive widening, deadlock.rs:409)."""

    def __init__(self, counters, q_in, q_out, interval: float,
                 recover: bool = False, budget: "_ByteBudget" = None):
        self._counters = counters
        self._q_in = q_in
        self._q_out = q_out
        self._interval = interval
        self._recover = recover
        self._budget = budget
        self._widenings_left = 4  # a deadlock-breaking nudge, not unbounded
        # (0,0,0) start: a pipeline wedged on its very first item reports at
        # t=interval, not 2x
        self._last = (0, 0, 0)
        self._stop = threading.Event()
        self._t = None
        if interval > 0:  # <= 0 disables the watchdog entirely
            from .observe.scope import spawn_thread

            self._t = spawn_thread(self._loop, name="fgumi-watchdog")
            self._t.start()

    def _loop(self):
        while not self._stop.wait(self._interval):
            snap = tuple(self._counters)
            if snap == self._last:
                log.warning(
                    "pipeline stalled for %.0fs: read=%d processed=%d "
                    "written=%d q_in=%d/%d q_out=%d/%d — no stage progressed "
                    "(device hang or downstream block?)",
                    self._interval, snap[0], snap[1], snap[2],
                    self._q_in.qsize(), self._q_in.maxsize,
                    self._q_out.qsize(), self._q_out.maxsize)
                if self._recover and self._widenings_left > 0 \
                        and self._capacity_bound():
                    self._widenings_left -= 1
                    self._widen()
            self._last = snap

    def _capacity_bound(self):
        """Only widen when a limit is actually saturated — a stall with idle
        queues (device hang, slow stage) is not a capacity deadlock, and
        widening there just unbounds memory."""
        full_in = 0 < self._q_in.maxsize <= self._q_in.qsize()
        full_out = 0 < self._q_out.maxsize <= self._q_out.qsize()
        b = self._budget
        saturated = b is not None and b.limit > 0 and b.used >= b.limit
        return full_in or full_out or saturated

    def _widen(self):
        for q in (self._q_in, self._q_out):
            with q.mutex:
                if q.maxsize > 0:
                    q.maxsize *= 2
                q.not_full.notify_all()
        if self._budget is not None:
            self._budget.widen()
        log.warning("deadlock-recover: queue limits doubled to "
                    "q_in=%d q_out=%d bytes=%s", self._q_in.maxsize,
                    self._q_out.maxsize,
                    self._budget.limit if self._budget else "n/a")

    def stop(self):
        """Stop AND join the timer thread: a failed command must not leave
        a daemon watchdog sampling dead queues behind it (the error path
        out of run_stages calls this in its finally)."""
        self._stop.set()
        if self._t is not None:
            self._t.join(timeout=5)


def _traced_source(source_iter):
    """Wrap a source iterator so each pull is a pipeline.read span (runs on
    whichever thread drives the iterator — the reader thread when threaded,
    the caller inline — so thread attribution is automatic)."""
    from .observe.trace import spanned_iter

    return spanned_iter("pipeline.read", source_iter)


def _traced_stage(name, fn):
    """Wrap a stage callable in a named span."""
    from .observe.trace import span

    def wrapped(item):
        with span(name):
            return fn(item)
    return wrapped


def _traced_process(name, fn):
    """Wrap the processing callable so that its work is spanned where it
    happens and nowhere else is changed: a stage that returns a list works
    inside the call, a generator stage on each pull, so each pull is a span
    (as ``_traced_source`` does for the reader) and outputs still reach the
    next stage one by one."""
    from .observe.trace import span, spanned_iter

    def wrapped(item):
        with span(name):
            out = fn(item)
            if isinstance(out, (list, tuple)):
                it = None
            else:
                it = iter(out)
                try:
                    first = next(it)  # the call and the first pull: one span
                except StopIteration:
                    return
        if it is None:
            yield from out
            return
        yield first
        yield from spanned_iter(name, it)
    return wrapped


def run_stages(source_iter, process_fn, sink_fn, threads: int = 0,
               queue_items: int = 4, stats: StageTimes = None,
               watchdog_interval: float = 120.0, resolve_fn=None,
               max_bytes: int = 0, item_bytes=None,
               deadlock_recover: bool = False, resolve_workers: int = None):
    """source -> process [-> resolve workers] -> sink, with optional threads.

    - source_iter: yields work items (e.g. RecordBatch)
    - process_fn(item) -> iterable of outputs (serial stage: carry/group
      state lives here, like the reference's exclusive Group step,
      base.rs:1123-1150)
    - resolve_fn(output) -> resolved output (optional PARALLEL stage: must be
      thread-safe and pure per item — e.g. consensus _PendingChunk.resolve,
      whose shared counters are lock-guarded). With threads >= 4 a pool of
      (threads - 3) workers applies it concurrently; outputs are re-ordered
      by serial number before the sink (the reference's Q7 write-reorder,
      base.rs:1724-1920).
    - sink_fn(resolved output) (serial, input order)
    - max_bytes + item_bytes(item): byte-accurate input-queue governance —
      the reader blocks while admitting another item would exceed max_bytes
      (one item always admits, so an oversized batch serializes instead of
      deadlocking). Items vary widely in bytes, so this is what makes
      --max-memory actually bound a streaming command's working set
      (reference MemoryTracker, base.rs:466-625).
    - deadlock_recover: the stall watchdog doubles queue/byte limits on each
      stall instead of only logging (reference deadlock.rs:409).

    threads <= 1: fully inline; with a resolve_fn the default keeps one
    output in flight (FGUMI_TPU_INLINE_FLIGHT outputs, default 2, =1 for
    strict serial order) so device dispatches overlap the next item's host
    prep. threads 2..3: reader + writer threads around the processing
    caller thread (resolve_fn runs on the writer). threads >= 4 with
    resolve_fn: reader + workers + writer. Exceptions from any stage
    propagate to the caller; the first exception wins and the pipeline
    drains. A stall watchdog logs a queue snapshot if no stage progresses.
    """
    if stats is None:
        stats = StageTimes()
    from .observe import trace as _trace

    if _trace.tracing_enabled():
        # wrap only when spans are live: with flags off the hot path runs
        # the caller's bare callables (zero telemetry overhead, no new
        # per-item allocations — the acceptance contract of observe/)
        source_iter = _traced_source(source_iter)
        process_fn = _traced_process("pipeline.process", process_fn)
        if resolve_fn is not None:
            resolve_fn = _traced_stage("pipeline.resolve", resolve_fn)
        sink_fn = _traced_stage("pipeline.sink", sink_fn)
    try:
        return _run_stages_impl(
            source_iter, process_fn, sink_fn, threads, queue_items, stats,
            watchdog_interval, resolve_fn, max_bytes, item_bytes,
            deadlock_recover, resolve_workers)
    finally:
        # fold per-stage timings into the metrics registry on every exit
        # path (success AND failure) so the run report can always answer
        # "where did the time go"
        from .observe.metrics import record_stage_times

        record_stage_times(stats)


def _run_stages_impl(source_iter, process_fn, sink_fn, threads, queue_items,
                     stats, watchdog_interval, resolve_fn, max_bytes,
                     item_bytes, deadlock_recover, resolve_workers):
    from .utils import faults

    if faults.armed("pipeline.process"):
        inner_process = process_fn

        def process_fn(item):
            faults.fire("pipeline.process")
            return inner_process(item)
    has_resolve = resolve_fn is not None
    if resolve_fn is None:
        resolve_fn = lambda out: out  # noqa: E731
    if threads <= 1:
        # Double buffering (only when a real resolve stage exists): hold one
        # output back so a device dispatch made inside process_fn overlaps
        # the NEXT item's read + host prep instead of being awaited
        # immediately. Semantically identical to the threaded resolve pool
        # at depth 1 (outputs stay FIFO); it takes the fetch wait of each
        # dispatch off the critical path.
        from collections import deque

        max_pend = 1
        if has_resolve:
            import os

            try:
                max_pend = max(int(os.environ.get(
                    "FGUMI_TPU_INLINE_FLIGHT", "2")), 1)
            except ValueError:
                max_pend = 2
        if max_pend == 1:
            t_last = time.monotonic()
            for item in source_iter:
                now = time.monotonic()
                stats.add_busy("read", now - t_last)
                for out in process_fn(item):
                    sink_fn(resolve_fn(out))
                t_last = time.monotonic()
                stats.add_busy("process+write", t_last - now)
            return stats
        pend = deque()
        in_resolve = False
        try:
            t_last = time.monotonic()
            for item in source_iter:
                now = time.monotonic()
                stats.add_busy("read", now - t_last)
                for out in process_fn(item):
                    pend.append(out)
                    while len(pend) >= max_pend:
                        in_resolve = True
                        sink_fn(resolve_fn(pend.popleft()))
                        in_resolve = False
                t_last = time.monotonic()
                stats.add_busy("process+write", t_last - now)
            now = time.monotonic()
            while pend:
                in_resolve = True
                sink_fn(resolve_fn(pend.popleft()))
                in_resolve = False
            stats.add_busy("process+write", time.monotonic() - now)
        except BaseException:
            # a source/process failure still writes the outputs it had in
            # flight — the serial path wrote output N before touching item
            # N+1, and a deferred resolve must not lose it. When the resolve
            # or sink ITSELF raised, draining would write outputs past the
            # failed one (a holed file the serial path can't produce), so
            # in-flight outputs are dropped exactly like the threaded error
            # path does. The original error wins either way.
            if not in_resolve:
                try:
                    while pend:
                        sink_fn(resolve_fn(pend.popleft()))
                except BaseException:
                    pass
            raise
        return stats

    # resolve_workers overrides the threads-3 pool size (device-attached
    # runs want >=2 so a worker blocked on a device fetch never starves a
    # host-engine chunk queued behind it; fetch waits hold no GIL, so
    # oversubscribing a 1-core host is free)
    if resolve_workers is not None and threads >= 2:
        n_workers = max(int(resolve_workers), 0)
    else:
        n_workers = max(threads - 3, 0)
    q_in = queue.Queue(maxsize=queue_items)
    # the sink queue may carry deferred work holding whole padded batches
    # (consensus _PendingChunk), so its depth bounds in-flight memory too
    q_out = queue.Queue(maxsize=queue_items * 2)
    writer_exc = []
    counters = [0, 0, 0]  # read, processed, written
    # a StopSignal, not a bare Event: budget.acquire subscribes its
    # condition so cancellation wakes a blocked reader immediately instead
    # of at the next 100 ms poll tick
    from .utils.governor import GOVERNOR, StopSignal

    stop = StopSignal()  # error path: tell the reader to die promptly
    budget = _ByteBudget("pipeline.input",
                         max_bytes if item_bytes is not None else 0)
    # under governance the input budget competes for the process cap with
    # the fused-chain channels and the device feeder; its demand signal is
    # the reader's own acquire wait (producer starved) vs the process
    # stage's empty-queue wait (consumer starved)
    gov_token = None
    if budget.limit > 0:
        gov_token = GOVERNOR.register_budget(
            budget,
            demand_fn=lambda: {
                "put_wait_s": budget.wait_s,
                "get_wait_s": stats.blocked.get("process", 0.0)})

    def put_in(item) -> bool:
        while not stop.is_set():
            try:
                q_in.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def reader():
        # real items travel as (charged_bytes, item) pairs so the charge is
        # released exactly once per admission (keying a side table by
        # id(item) would double-charge duplicate/interned objects)
        try:
            t_last = time.monotonic()
            for item in source_iter:
                now = time.monotonic()
                stats.add_busy("read", now - t_last)
                nb = 0
                if budget.limit > 0:
                    nb = int(item_bytes(item))
                    if not budget.acquire(nb, stop):
                        return
                if not put_in((nb, item)):
                    return
                counters[0] += 1
                t_last = time.monotonic()
                stats.add_blocked("read", t_last - now)
            put_in(_DONE)
        except BaseException as e:  # noqa: BLE001 - relayed to caller
            put_in(_Err(e))

    # ---- resolve worker pool (threads >= 4): q_out carries (serial, item);
    # workers push (serial, resolved | _Err) to q_done; the writer restores
    # serial order with a holdback map (bounded by in-flight = q_out depth +
    # n_workers, so memory stays bounded by queue_items)
    q_done = queue.Queue() if n_workers else None

    def worker(widx):
        while True:
            got = q_out.get()
            if got is _DONE:
                q_done.put(_DONE)
                return
            serial, item = got
            t0 = time.monotonic()
            try:
                q_done.put((serial, resolve_fn(item)))
            except BaseException as e:  # noqa: BLE001 - relayed via writer
                q_done.put((serial, _Err(e)))
            stats.add_busy(f"resolve[{widx}]", time.monotonic() - t0)

    def writer_pooled():
        next_serial = 0
        holdback = {}
        done_workers = 0
        try:
            while done_workers < n_workers:
                t0 = time.monotonic()
                got = q_done.get()
                now = time.monotonic()
                stats.add_blocked("write", now - t0)
                if got is _DONE:
                    done_workers += 1
                    continue
                serial, resolved = got
                holdback[serial] = resolved
                while next_serial in holdback:
                    out = holdback.pop(next_serial)
                    next_serial += 1
                    if isinstance(out, _Err):
                        raise out.exc
                    sink_fn(out)
                    counters[2] += 1
                stats.add_busy("write", time.monotonic() - now)
            # workers exited; flush any stragglers in serial order
            while next_serial in holdback:
                out = holdback.pop(next_serial)
                next_serial += 1
                if isinstance(out, _Err):
                    raise out.exc
                sink_fn(out)
                counters[2] += 1
        except BaseException as e:  # noqa: BLE001 - relayed to caller
            writer_exc.append(e)
            while done_workers < n_workers:
                if q_done.get() is _DONE:
                    done_workers += 1

    def writer_direct():
        try:
            while True:
                t0 = time.monotonic()
                out = q_out.get()
                now = time.monotonic()
                stats.add_blocked("write", now - t0)
                if out is _DONE:
                    return
                sink_fn(resolve_fn(out))
                counters[2] += 1
                stats.add_busy("write", time.monotonic() - now)
        except BaseException as e:  # noqa: BLE001 - relayed to caller
            writer_exc.append(e)
            # drain so the processor never blocks on a dead writer
            while q_out.get() is not _DONE:
                pass

    # stage threads run in a copy of the caller's context so a scoped
    # command's telemetry (metrics/trace/device stats — one scope per serve
    # daemon job) follows its whole thread tree (observe.scope)
    from .observe.scope import spawn_thread

    rt = spawn_thread(reader, name="fgumi-reader")
    wt = spawn_thread(writer_pooled if n_workers else writer_direct,
                      name="fgumi-writer")
    wts = [spawn_thread(worker, args=(i,), name=f"fgumi-worker-{i}")
           for i in range(n_workers)]
    watchdog = _Watchdog(counters, q_in, q_out, watchdog_interval,
                         recover=deadlock_recover, budget=budget)
    # publish the watchdog's view (stage counters + queue depths) to the
    # periodic heartbeat for the lifetime of this pipeline
    from .observe import heartbeat as _hb

    hb_token = _hb.register_gauge(lambda: {
        "read": counters[0], "processed": counters[1],
        "written": counters[2],
        "q_in": f"{q_in.qsize()}/{q_in.maxsize}",
        "q_out": f"{q_out.qsize()}/{q_out.maxsize}"})
    rt.start()
    wt.start()
    for t in wts:
        t.start()
    serial = 0
    from .observe.trace import span

    try:
        while True:
            t0 = time.monotonic()
            with span("pipeline.wait_in", wait=True):
                item = q_in.get()
            now = time.monotonic()
            stats.add_blocked("process", now - t0)
            if item is _DONE:
                break
            if isinstance(item, _Err):
                raise item.exc
            nb, item = item
            try:
                for out in process_fn(item):
                    with span("pipeline.wait_out", wait=True):
                        if n_workers:
                            q_out.put((serial, out))
                            serial += 1
                        else:
                            q_out.put(out)
            finally:
                if nb:
                    budget.release(nb)
            counters[1] += 1
            stats.add_busy("process", time.monotonic() - now)
            stats.sample_queues(q_in.qsize(), q_out.qsize())
            if writer_exc:
                raise writer_exc[0]
    finally:
        for _ in range(max(n_workers, 1)):
            q_out.put(_DONE)
        for t in wts:
            t.join()
        wt.join()  # watchdog stays armed while the writer drains
        watchdog.stop()
        # stop + drain until the reader exits: it re-checks the stop event on
        # every bounded put, so it cannot re-block and leak (with its open
        # source) past this join
        stop.set()
        while rt.is_alive():
            try:
                while True:
                    q_in.get_nowait()
            except queue.Empty:
                pass
            rt.join(timeout=0.2)
        _hb.unregister_gauge(hb_token)
        GOVERNOR.unregister_budget(gov_token)
    if writer_exc:
        raise writer_exc[0]
    if budget.limit > 0:
        stats.peak_in_flight_bytes = budget.peak
        # used/peak/limit land in METRICS as governor.budget.* gauges so
        # the run report can answer "was the input queue budget-bound"
        from .observe.metrics import METRICS

        p = f"governor.budget.{budget.name}"
        METRICS.set(f"{p}.limit", budget.limit)
        METRICS.max(f"{p}.peak", budget.peak)
        METRICS.inc(f"{p}.wait_s", round(budget.wait_s, 6))
    return stats
