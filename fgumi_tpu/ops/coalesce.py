"""Cross-job dispatch coalescer: merged device launches for the serve fleet.

ROADMAP §2's perf half (ISSUE 15). In the warm serve daemon every job
dispatches its own wire-format segment batches, so N concurrent small jobs
each pay the full pack→upload→launch overhead on a chip that could serve
them in one launch — BENCH_r05 measured that overhead at ~400x the kernel
compute, which is exactly the regime where amortizing it across jobs wins.
This module is the continuous-batching analog for consensus dispatches:
instead of serializing per-job launches, *compatible* pending batches are
admitted into one in-flight super-batch.

Mechanics
---------

- ``ConsensusKernel.device_call_segments_wire`` offers every plain (non-
  resident, non-filter, non-mesh) wire dispatch to :meth:`DispatchCoalescer.
  maybe_submit`. While the window is armed, the batch is held for up to
  ``FGUMI_TPU_COALESCE_WINDOW_MS`` (default 2 ms; 0 disables) waiting for
  partners with the same merge key — same kernel variant (``full`` flag,
  wire/packed2 chosen per merged batch like solo), same constant-table
  content (the quality-table/pre-UMI fingerprint), same padded read length
  — then all partners concatenate along the family/segment axis into one
  shape-bucketed dispatch through the ordinary feeder pipeline. The
  feeder's governed byte budget is charged ONCE for the merged upload.
- The window arms only when it can pay for itself: the serve scheduler
  reports the live running-job count (:meth:`set_active_jobs`) and the
  window opens at >= 2 (auto-off for single jobs — zero hold, zero
  regression), and the hold is additionally priced against the router's
  measured per-dispatch overhead (merging k batches saves ~(k-1) x
  overhead, so holding longer than one overhead can only lose to just
  dispatching now). ``FGUMI_TPU_COALESCE=1`` forces the window regardless
  (bench/chaos harnesses); ``0`` disables it entirely.
- At resolve each partner receives exactly its own family slice of the
  merged fetch and runs the UNCHANGED host completion — unpack, no-call
  restore, f64 oracle patch, shadow-audit tap — over its own dense rows
  under its own telemetry scope, so per-job output stays byte-identical to
  standalone (the PR 3 invariant: every integer output is oracle-exact on
  both paths, whatever the f32 reduction order of the merged shape did).
  Dispatch wall/bytes are attributed proportionally: each partner charges
  its own scope the flops/bytes/pad its solo dispatch would have.
- Faults degrade per partner: a raise/hang/OOM inside a merged dispatch
  (chaos point ``serve.coalesce``) surfaces to every partner's resolve,
  and each one independently falls back — deadline abandon, transient
  host fallback, or OOM split-halving over its OWN rows (re-dispatched
  halves bypass the window via :func:`bypassed`).

Fairness
--------

A large job cannot starve small partners: a batch above
``FGUMI_TPU_COALESCE_PARTNER_ROWS`` (default 64 Ki rows) never rides — or
holds open — a merge window (it dispatches solo immediately), a group
closes at ``FGUMI_TPU_COALESCE_PARTNERS`` partners or
``FGUMI_TPU_COALESCE_MAX_ROWS`` merged rows, and admission is strictly
arrival-ordered — a newcomer that would overflow a group flushes it and
opens the next, never reorders past it. Priority classes are respected
upstream: the scheduler already orders job *execution* by priority, so
arrival order at the coalescer inherits it.

Telemetry (satellite): ``device.coalesce.*`` counters + histograms —
``merged_batches`` / ``solo_flushes`` / ``partners`` / ``oversize_solo``
counters, ``fill_ratio`` and ``window_wait_s`` histograms (the per-partner
wait lands in the partner's scope, so per-job run reports carry it), a
flight-ring note per merge, and :meth:`snapshot` feeding the serve
``stats`` op / ``/metrics`` ``coalesce`` section.
"""

import contextlib
import contextvars
import logging
import os
import threading
import time

import numpy as np

from ..constants import N_CODE

log = logging.getLogger("fgumi_tpu")

_BYPASS = contextvars.ContextVar("fgumi_tpu_coalesce_bypass", default=False)


@contextlib.contextmanager
def bypassed():
    """Disable coalescing for dispatches made inside the block (the OOM
    split-halving recovery: re-dispatched halves must not re-enter the
    window their parent just failed out of)."""
    token = _BYPASS.set(True)
    try:
        yield
    finally:
        _BYPASS.reset(token)


class CoalesceFlushError(RuntimeError):
    """The merged build/submit itself failed. Routed through the ordinary
    host-fallback recovery per partner — a coalescer defect degrades
    throughput, never correctness (and never kills a job)."""


def window_s() -> float:
    """Configured hold window: ``FGUMI_TPU_COALESCE_WINDOW_MS`` (default
    2 ms; 0 disables coalescing entirely)."""
    try:
        ms = float(os.environ.get("FGUMI_TPU_COALESCE_WINDOW_MS", "2"))
    except ValueError:
        ms = 2.0
    return max(ms, 0.0) / 1e3


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


def max_partners() -> int:
    """Group closes (flushes) at this many partners."""
    return max(_env_int("FGUMI_TPU_COALESCE_PARTNERS", 8), 2)


def partner_row_cap() -> int:
    """Fairness guard: a batch with more padded rows than this never
    joins (or holds open) a merge window — it dispatches solo now."""
    return max(_env_int("FGUMI_TPU_COALESCE_PARTNER_ROWS", 1 << 16), 1)


def merged_row_cap() -> int:
    """Merged-batch row budget; a joining partner that would overflow it
    flushes the group and opens the next (arrival order preserved)."""
    return max(_env_int("FGUMI_TPU_COALESCE_MAX_ROWS", 1 << 18), 1)


def _force_mode() -> str:
    v = os.environ.get("FGUMI_TPU_COALESCE", "").strip().lower()
    if v in ("1", "true", "on", "force"):
        return "force"
    if v in ("0", "false", "off"):
        return "off"
    return "auto"


def _raw_fetch(dev, deadline):
    """Deadline-bounded device_get WITHOUT DeviceStats accounting: the
    merged fetch is shared, so its bytes/wait are attributed per partner
    (DeviceStats.add_fetch shares) rather than charged wholesale to
    whichever partner's scope happened to resolve first."""
    from . import kernel as K

    def _get():
        got = K.jax.device_get(dev)
        return tuple(np.asarray(g) for g in got)

    if deadline is None:
        return _get()
    return K._FETCH_RUNNER.run(_get, deadline, "coalesced fetch")


class CoalescedTicket:
    """Resolve-side handle for one partner of a merged dispatch.

    Returned by ``device_call_segments_wire`` in place of a
    :class:`~fgumi_tpu.ops.kernel.DispatchTicket`; the matching
    ``resolve_segments_wire`` call detects it and routes through
    :meth:`DispatchCoalescer.resolve_partner`."""

    __slots__ = ("group", "index")
    #: never a fused consensus→filter dispatch (those dispatch solo), so
    #: resolve_segments_wire_filtered's ``ticket.filter_mode`` gate holds
    filter_mode = False

    def __init__(self, group, index: int):
        self.group = group
        self.index = index


class _Partner:
    """One job's pending batch inside a merge group."""

    __slots__ = ("kernel", "codes", "quals", "seg_ids", "f_pad", "j",
                 "rows", "pred_s", "slot", "ctx", "t_submit")

    def __init__(self, kernel, codes, quals, seg_ids, f_pad, j, pred_s,
                 slot):
        self.kernel = kernel
        self.codes = codes
        self.quals = quals
        self.seg_ids = seg_ids
        self.f_pad = f_pad
        self.j = int(j)
        self.rows = int(codes.shape[0])
        self.pred_s = pred_s
        self.slot = slot
        # the submitter's context: merged-dispatch accounting raised on
        # the flusher/feeder threads must resolve THIS job's telemetry
        # scope, exactly like the feeder's own context copy
        self.ctx = contextvars.copy_context()
        self.t_submit = time.monotonic()


class _MergeGroup:
    """Partners sharing one merged dispatch + its shared fetch."""

    __slots__ = ("key", "seq", "partners", "deadline", "opened", "closed",
                 "rows", "total_j", "dispatched", "feeder_ticket",
                 "flush_failure", "seg_bases", "upload", "t_flush",
                 "_fetch_lock", "_result", "_failure", "_settle_lock",
                 "_ticket_settled")

    def __init__(self, key, seq: int, deadline: float):
        self.key = key
        self.seq = seq
        self.partners = []
        self.opened = time.monotonic()
        self.deadline = deadline
        self.closed = False
        self.rows = 0
        self.total_j = 0
        #: set once the merged dispatch is in the feeder (or failed)
        self.dispatched = threading.Event()
        self.feeder_ticket = None
        self.flush_failure = None
        self.seg_bases = None
        self.upload = 0
        self.t_flush = None
        self._fetch_lock = threading.Lock()
        self._result = None
        self._failure = None
        # feeder-slot settlement: exactly one of {first fetcher, flusher}
        # must abandon/mark_resolved the feeder ticket, even when every
        # partner's deadline fired BEFORE the flush submitted it (a
        # leaked slot would stall the upload pipeline at depth)
        self._settle_lock = threading.Lock()
        self._ticket_settled = False

    # ------------------------------------------------------ shared fetch

    def fetch(self, deadline):
        """(arrays, total_bytes, fetch_wall_s) of the merged result.

        The first partner to arrive performs the wait+fetch (bounded by
        its dispatch deadline) and settles the group; every later partner
        gets the cached result or re-raises the recorded failure — each
        then degrades over its OWN rows, which is what makes a merged
        fault a per-partner event."""
        with self._fetch_lock:
            if self._result is None and self._failure is None:
                self._do_fetch(deadline)
            if self._failure is not None:
                raise self._failure
            return self._result

    def settle_ticket(self, completed=False):
        """Release the feeder ticket's slot exactly once.

        Callable from the first fetcher (either verdict), the flusher's
        exception handler, and the flusher's orphan sweep (every
        partner's deadline fired before the flush submitted — nobody is
        coming back for the ticket): whoever arrives first settles it,
        later callers no-op, and a settle attempt before the ticket
        exists defers to the flusher (the only later caller).

        ``completed=True`` means the ticket's wait finished (result or
        dispatch exception) and ``mark_resolved`` may recycle its
        staging buffers; anything else must ``abandon`` — the dispatch
        may still be running, and recycling a staging buffer under a
        live upload would corrupt whoever reuses it (abandon reclaims
        the slot at late completion and leaks the staging on purpose,
        the feeder's standing wedge contract)."""
        from . import kernel as K

        with self._settle_lock:
            if self._ticket_settled or self.feeder_ticket is None:
                return
            self._ticket_settled = True
            ticket = self.feeder_ticket
        if completed:
            K.DEVICE_FEEDER.mark_resolved(ticket)
        else:
            K.DEVICE_FEEDER.abandon(ticket)

    def _do_fetch(self, deadline):
        from ..utils import faults
        from . import kernel as K

        t0 = time.monotonic()
        try:
            if not self.dispatched.wait(deadline):
                raise K.DeadlineExceeded(
                    f"coalesced dispatch was not flushed within "
                    f"{deadline:.1f}s")
            if self.flush_failure is not None:
                raise CoalesceFlushError(
                    f"merged dispatch build failed: "
                    f"{type(self.flush_failure).__name__}: "
                    f"{self.flush_failure}") from self.flush_failure
            # the router-feed wall starts HERE, once the dispatch is in
            # the feeder — matching the solo resolve's fetch_wait_s
            # (ticket.wait + fetch); the window hold and flush build
            # before this point are queue-shaped time observe_device's
            # contract excludes
            t_disp = time.monotonic()
            if deadline is not None and self.feeder_ticket.new_shape:
                # a merged shape seen for the first time compiles inside
                # the dispatch: wait to the ceiling (K.ticket_deadline_s)
                deadline = max(deadline, K.dispatch_deadline_s() or 0.0)
            left = None if deadline is None else \
                max(deadline - (time.monotonic() - t0), 0.1)
            dev = self.feeder_ticket.wait(left)
            left = None if deadline is None else \
                max(deadline - (time.monotonic() - t0), 1.0)
            # scope-NEUTRAL fetch on purpose: DEVICE_STATS.fetch would
            # charge the full merged bytes + wall to whichever partner
            # resolved first, double-counting against the per-partner
            # add_fetch shares resolve_partner attributes
            got = _raw_fetch(dev, left)
            # SDC chaos point: merged results corrupt exactly like solo
            # ones; the per-partner audit tap attributes the damage
            got = faults.fire("device.fetch", got)
        except BaseException as e:  # noqa: BLE001 - replayed per partner
            self._failure = e
            # only a failure raised BY the ticket's wait proves the
            # dispatch finished; deadline/flush failures must abandon
            # (the dispatch may still be mid-upload)
            self.settle_ticket(
                completed=not isinstance(
                    e, (K.DeadlineExceeded, CoalesceFlushError)))
            return
        self.settle_ticket(completed=True)
        total = sum(int(g.nbytes) for g in got)
        wall = time.monotonic() - t_disp
        self._result = (got, total, wall)
        from .breaker import BREAKER

        BREAKER.record_success()
        # one cost-model feed with the true merged economics — this is
        # what keeps the router's overhead EWMA (and hence the pricing
        # gate in _effective_window_s) honest about merged dispatches.
        # The lambda defers the DEVICE_STATS proxy resolution INTO the
        # leader's context: an eagerly-bound method would read the
        # resolving partner's DeviceStats, where the leader's slot id
        # names an unrelated dispatch.
        leader = self.partners[0]
        tl = leader.ctx.run(
            lambda: K.DEVICE_STATS.timeline_entry(leader.slot))
        # a first-sight merged shape compiled inside `wall`: not a sample
        if tl is not None and not self.feeder_ticket.new_shape:
            from .router import ROUTER

            up_s = tl.get("upload_s", 0.0)
            ROUTER.observe_device(self.upload, total, up_s, wall,
                                  up_s + wall)


class DispatchCoalescer:
    """Process-wide merge window between the engines and the feeder."""

    #: flusher pool cap: distinct-key groups (different jobs' configs)
    #: build independently, so solo flushes from incompatible jobs are
    #: not serialized onto one core in exactly the many-small-jobs
    #: regime the coalescer targets
    MAX_FLUSHERS = 4

    def __init__(self):
        self._lock = threading.Condition()
        self._groups = {}    # key -> the currently OPEN group
        self._pending = []   # open/closed groups not yet flushed
        self._threads = []
        self._seq = 0
        self._serving = False
        self._active_jobs = 0
        self._reset_counters_locked()

    def _reset_counters_locked(self):
        self.merged_batches = 0
        self.solo_flushes = 0
        self.partners_merged = 0
        self.max_partners_seen = 0
        self.oversize_solo = 0
        self.rows_in = 0
        self.rows_dispatched = 0

    def reset(self):
        """Tests: flush pending groups, zero the counters, keep arming
        state (env is re-read per call anyway)."""
        self.drain(timeout=10.0)
        with self._lock:
            self._reset_counters_locked()

    # ----------------------------------------------------------- arming

    def set_serving(self, serving: bool):
        """Daemon lifecycle signal (serve/daemon.py): the window can only
        auto-arm inside a serve process."""
        with self._lock:
            self._serving = bool(serving)

    def set_active_jobs(self, n: int):
        """Live running-job count from the scheduler; the window auto-arms
        at >= 2 and auto-disarms below (single jobs pay zero hold)."""
        with self._lock:
            self._active_jobs = int(n)
        from ..observe.metrics import METRICS

        METRICS.set("device.coalesce.active_jobs", int(n))

    def armed(self) -> bool:
        mode = _force_mode()
        if mode == "off" or window_s() <= 0:
            return False
        if mode == "force":
            return True
        with self._lock:
            return self._serving and self._active_jobs >= 2

    def _effective_window_s(self) -> float:
        """min(configured window, the router's measured per-dispatch
        overhead): merging k batches saves ~(k-1) x overhead, so a hold
        longer than one overhead can only lose to dispatching now — the
        pricing that keeps coalescing strictly non-regressive when
        dispatch is cheap."""
        win = window_s()
        if win <= 0:
            return 0.0
        from .router import ROUTER

        return min(win, max(ROUTER.device_overhead_s(), 0.0))

    # --------------------------------------------------------- admission

    def maybe_submit(self, kernel, codes2d_padded, quals2d_padded, seg_ids,
                     num_segments: int, J: int, full: bool = False,
                     pack_t0: float = None, pred_s: float = None):
        """Admit one plain wire dispatch into the window, or return None
        (caller dispatches solo, unchanged). Runs on the submitting
        engine thread, under the job's telemetry scope."""
        if J <= 0 or _BYPASS.get() or not self.armed():
            return None
        # force mode honors the configured window verbatim (the bench /
        # chaos harness contract: FGUMI_TPU_COALESCE=1 merges regardless
        # of what the overhead EWMA thinks of this host); only auto mode
        # prices the hold against the router
        win = window_s() if _force_mode() == "force" \
            else self._effective_window_s()
        if win <= 0:
            return None
        from ..observe.metrics import METRICS

        rows = int(codes2d_padded.shape[0])
        if rows > partner_row_cap():
            # fairness guard: an oversized batch neither rides nor holds
            # open a merge window
            with self._lock:
                self.oversize_solo += 1
            METRICS.inc("device.coalesce.oversize_solo")
            return None
        from . import kernel as K

        # per-partner accounting under the SUBMITTER's scope — exactly
        # what this batch's solo dispatch would have charged, so per-job
        # run reports stay proportional by construction. The merged
        # upload itself is charged once, to the feeder's byte budget.
        L = int(codes2d_padded.shape[1])
        K.DEVICE_STATS.add_dispatch(K.segments_flops(rows, L, num_segments))
        t0 = pack_t0 if pack_t0 is not None else time.monotonic()
        slot = K.DEVICE_STATS.begin_in_flight(
            rows * L + seg_ids.nbytes, pack_s=time.monotonic() - t0)
        if pred_s is not None:
            K.DEVICE_STATS.note_pred(slot, pred_s)
        partner = _Partner(kernel, codes2d_padded, quals2d_padded, seg_ids,
                           num_segments, J, pred_s, slot)
        key = (kernel._coalesce_key(), L, bool(full))
        now = time.monotonic()
        with self._lock:
            self.rows_in += rows
            group = self._groups.get(key)
            if group is not None and (
                    group.closed
                    or group.rows + rows > merged_row_cap()
                    or len(group.partners) >= max_partners()):
                # arrival order: a newcomer that would overflow flushes
                # the full group and opens the next — never reorders past
                self._close_locked(group)
                group = None
            if group is None:
                self._seq += 1
                group = _MergeGroup(key, self._seq, deadline=now + win)
                self._groups[key] = group
                self._pending.append(group)
            group.partners.append(partner)
            group.rows += rows
            group.total_j += partner.j
            ticket = CoalescedTicket(group, len(group.partners) - 1)
            # early flush once every live job has joined: with the
            # scheduler reporting N running jobs, an N-partner group has
            # nobody left to wait for — the window bounds the straggler
            # case, it is not a mandatory tax on the common one
            target = self._active_jobs if (self._serving
                                           and self._active_jobs >= 2) \
                else None
            if (len(group.partners) >= max_partners()
                    or group.rows >= merged_row_cap()
                    or (target is not None
                        and len(group.partners) >= target)):
                self._close_locked(group)
            self._ensure_thread_locked()
            self._lock.notify_all()
        METRICS.inc("device.coalesce.joined")
        return ticket

    def _close_locked(self, group: _MergeGroup):
        group.closed = True
        if self._groups.get(group.key) is group:
            del self._groups[group.key]

    # ------------------------------------------------------------ flusher

    def _ensure_thread_locked(self):
        self._threads = [t for t in self._threads if t.is_alive()]
        want = min(self.MAX_FLUSHERS, max(len(self._pending), 1))
        while len(self._threads) < want:
            t = threading.Thread(
                target=self._loop,
                name=f"fgumi-coalesce-flush-{len(self._threads)}",
                daemon=True)
            t.start()
            self._threads.append(t)

    def _loop(self):
        while True:
            with self._lock:
                group = None
                while group is None:
                    now = time.monotonic()
                    for g in self._pending:
                        if g.closed or g.deadline <= now:
                            group = g
                            break
                    if group is not None:
                        self._pending.remove(group)
                        self._close_locked(group)
                        break
                    nxt = min((g.deadline for g in self._pending),
                              default=None)
                    self._lock.wait(None if nxt is None
                                    else max(nxt - now, 0.0005))
            try:
                self._flush(group)
            except BaseException as e:  # noqa: BLE001 - degrade, don't die
                log.exception("coalesce: merged dispatch build failed; "
                              "%d partner(s) will degrade to host",
                              len(group.partners))
                group.flush_failure = e
                group.dispatched.set()
                # a raise AFTER the feeder submit with every partner
                # already deadline-expired would otherwise orphan the
                # ticket (idempotent; no-op when no ticket exists yet)
                group.settle_ticket()

    def drain(self, timeout: float = 5.0) -> bool:
        """Flush every held group now (daemon shutdown; tests). True when
        everything reached the feeder within ``timeout``."""
        with self._lock:
            pend = list(self._pending)
            for g in pend:
                g.closed = True
            self._lock.notify_all()
        deadline = time.monotonic() + timeout
        for g in pend:
            left = max(deadline - time.monotonic(), 0.0)
            if not g.dispatched.wait(left):
                return False
        return True

    def _flush(self, group: _MergeGroup):
        """Build + submit one merged dispatch (flusher thread)."""
        from ..observe.flight import FLIGHT
        from ..observe.metrics import METRICS
        from ..utils import faults
        from . import kernel as K
        from .datapath import SHAPE_REGISTRY, STAGING_POOL

        group.t_flush = time.monotonic()
        partners = group.partners
        leader = partners[0]
        kernel = leader.kernel
        k = len(partners)
        L = int(leader.codes.shape[1])
        full = bool(group.key[2])
        real_rows = sum(p.rows for p in partners)
        if k == 1:
            # a window that closed alone dispatches the partner's own
            # arrays verbatim — the solo shape, the solo executable
            codes_m, quals_m = leader.codes, leader.quals
            seg_m, f_pad_m, j_m = leader.seg_ids, leader.f_pad, leader.j
            release, rows_m = (), leader.rows
            group.seg_bases = (0,)
        else:
            # concatenate the PADDED partner layouts: each partner's pad
            # rows are all-N no-ops carrying its last real family id, so
            # the merged seg ids stay sorted after offsetting and the pad
            # rows keep contributing nothing (the pad_segments invariant)
            j_m = group.total_j
            f_pad_m = SHAPE_REGISTRY.bucket_segments(j_m)
            n_pad = SHAPE_REGISTRY.bucket_rows(real_rows)
            codes_m = STAGING_POOL.acquire_filled((n_pad, L), np.uint8,
                                                  N_CODE)
            quals_m = STAGING_POOL.acquire_filled((n_pad, L), np.uint8, 0)
            seg_m = np.full(n_pad, j_m - 1, dtype=np.int32)
            seg_bases = []
            row = base = 0
            for p in partners:
                seg_bases.append(base)
                codes_m[row:row + p.rows] = p.codes
                quals_m[row:row + p.rows] = p.quals
                seg_m[row:row + p.rows] = p.seg_ids
                seg_m[row:row + p.rows] += np.int32(base)
                row += p.rows
                base += p.j
            group.seg_bases = tuple(seg_bases)
            release, rows_m = (codes_m, quals_m), n_pad
        plan = kernel._wire_dispatch_plan(codes_m, quals_m, seg_m, f_pad_m,
                                          j_m, full=full)
        # the merged staging rows were only inputs to the wire build —
        # the plan holds its own (wire/packed) upload buffers
        for arr in release:
            STAGING_POOL.release(arr)
        group.upload = plan.upload

        def _fn():
            # chaos point (utils/faults.py serve.coalesce): a raise/hang
            # INSIDE a merged dispatch must degrade only its partners
            faults.fire("serve.coalesce")
            return plan.dispatch(leader.slot)

        def _submit():
            with SHAPE_REGISTRY.attribute_compiles(plan.new):
                t = K.DEVICE_FEEDER.submit(
                    lambda: K.device_retry_call(_fn,
                                                "coalesced wire dispatch"),
                    upload_bytes=plan.upload, slot=leader.slot)
            t.staging = plan.staging or None
            return t

        # submit inside the leader's context so feeder-side stamps
        # (upload wall, compile events) land in the leader job's scope
        group.feeder_ticket = leader.ctx.run(_submit)
        fill = real_rows / max(rows_m, 1)
        with self._lock:
            self.rows_dispatched += rows_m
            if k > 1:
                self.merged_batches += 1
                self.partners_merged += k
                if k > self.max_partners_seen:
                    self.max_partners_seen = k
            else:
                self.solo_flushes += 1
        if k > 1:
            METRICS.inc("device.coalesce.merged_batches")
            METRICS.inc("device.coalesce.partners", k)
        else:
            METRICS.inc("device.coalesce.solo_flushes")
        METRICS.observe("device.coalesce.fill_ratio", fill)
        FLIGHT.note("device.coalesce.merge", partners=k, rows=rows_m,
                    segments=j_m, upload=plan.upload,
                    fill=round(fill, 4))
        group.dispatched.set()
        # orphan sweep: if every partner's deadline already fired while
        # this flush was still building (their wait-for-flush timed out
        # BEFORE the ticket existed), nobody is coming back to resolve
        # it — settle the slot here or the feeder pipeline leaks it
        if group._failure is not None:
            group.settle_ticket()

    # ------------------------------------------------------------ resolve

    def resolve_partner(self, kernel, ticket: CoalescedTicket, codes2d,
                        quals2d, starts, split_depth: int = 0,
                        want_extras: bool = False):
        """One partner's half of resolve_segments_wire: shared fetch,
        per-partner slice, unchanged host completion — or per-partner
        degrade over its own rows on any merged-dispatch failure."""
        from ..observe.metrics import METRICS
        from . import kernel as K

        group = ticket.group
        partner = group.partners[ticket.index]
        t0 = time.monotonic()
        deadline = K.dispatch_deadline_s(partner.pred_s)
        failure = None
        share = 0
        got = None
        try:
            got, total, _wall = group.fetch(deadline)
            share = int(total * partner.j / max(group.total_j, 1))
        except BaseException as e:  # noqa: BLE001 - classified below
            failure = e
        wait = time.monotonic() - t0
        # proportional attribution under the partner's own scope: its
        # bytes share, its measured resolve wait, its own timeline slot
        K.DEVICE_STATS.add_fetch(share, wait)
        K.DEVICE_STATS.end_in_flight(partner.slot, share, wait)
        METRICS.observe(
            "device.coalesce.window_wait_s",
            max((group.t_flush or t0) - partner.t_submit, 0.0))
        if failure is not None:
            METRICS.inc("device.coalesce.partner_degraded")
            starts64 = np.asarray(starts, dtype=np.int64)
            if isinstance(failure, K.DeadlineExceeded):
                out = kernel._deadline_fallback_segments(
                    failure, codes2d, quals2d, starts64)
            elif (isinstance(failure, CoalesceFlushError)
                    or K._is_oom(failure) or K._is_transient(failure)):
                out = kernel._recover_segments(failure, codes2d, quals2d,
                                               starts64, split_depth)
            else:
                raise failure
            return out + (K._no_extras(),) if want_extras else out
        base = group.seg_bases[ticket.index]
        j = partner.j
        if len(got) == 4:
            qs, wp, d16, e16 = got
            d_sl, e_sl = d16[base:base + j], e16[base:base + j]
        else:
            qs, wp = got
            d_sl = e_sl = None
        return kernel._complete_wire_columns(
            qs[base:base + j], wp[base:base + j], d_sl, e_sl,
            codes2d, quals2d, starts, want_extras=want_extras,
            slot=partner.slot,
            partner={"group": group.seq, "index": ticket.index,
                     "partners": len(group.partners)})

    # ----------------------------------------------------------- surface

    def has_activity(self) -> bool:
        with self._lock:
            return bool(self.merged_batches or self.solo_flushes
                        or self.oversize_solo or self._pending)

    def snapshot(self) -> dict:
        """The serve ``stats`` op / ``/metrics`` ``coalesce`` section."""
        armed = self.armed()
        with self._lock:
            return {
                "armed": armed,
                "mode": _force_mode(),
                "window_ms": round(window_s() * 1e3, 3),
                "serving": self._serving,
                "active_jobs": self._active_jobs,
                "merged_batches": self.merged_batches,
                "solo_flushes": self.solo_flushes,
                "partners": self.partners_merged,
                "max_partners": self.max_partners_seen,
                "oversize_solo": self.oversize_solo,
                "rows_in": self.rows_in,
                "rows_dispatched": self.rows_dispatched,
                "pending_groups": len(self._pending),
            }


#: process-wide singleton: the merge window spans every job in the daemon.
COALESCER = DispatchCoalescer()
