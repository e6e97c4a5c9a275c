"""Batched TPU consensus kernel (JAX/XLA).

Re-expresses the reference's per-position scalar hot loop
(/root/reference/crates/fgumi-consensus/src/base_builder.rs:612-644,795-852 — the
reset/add/call loop at vanilla_caller.rs:1396-1437) as one fused XLA computation over a
whole batch of padded UMI families at once:

    codes (F, R, L) uint8, quals (F, R, L) uint8  ->  per-position consensus
    winner/qual/depth/errors (F, L)

Numerics strategy (SURVEY.md §7 "architecture stance"): the device computes in f32
using per-quality tables precomputed in f64 on host, with a *suspect mask*: positions
whose result could plausibly round to a different integer Phred (or whose winner margin
is within f32 noise) are flagged and recomputed on host by the f64 oracle
(fgumi_tpu.ops.oracle). This mirrors the reference's own fast-path-with-margin-gate
design (base_builder.rs:186-263): a fast path that is exact outside a guard band,
deferring to the exact computation inside it.

Key algebraic reformulation (device only; guarded by the suspect mask): the four lane
likelihoods are ll[b] = S_err + C[b], where S_err = sum over valid observations of
ln(err/3) is lane-independent and C[b] = sum over observations matching b of
(ln_correct - ln_err) >= 0 is the per-lane match contribution. Winner selection and
every posterior quantity depend only on lane *differences*, so S_err is never
materialized: gaps = C_max - C[b], s = sum_losers exp(-gap), and
ln_consensus_error = ln(s) - log1p(s). This is the same shifted-gap frame the
reference uses for its unanimous fast path (base_builder.rs:364-385) generalized to
non-unanimous positions, and it keeps f32 magnitudes at ~|C| (tens per matching read)
instead of |ll| (hundreds to thousands), which is what makes f32 viable at depth.
"""

import collections
import logging
import threading
import time
from functools import wraps

import numpy as np

log = logging.getLogger("fgumi_tpu")

# jax is imported lazily (_ensure_jax): a CPU-pinned run that routes every
# dispatch to the native f64 host engine (host_kernel.py) never pays the
# ~2s jax import — which lands on every stage of a multi-process chain.
# The module globals `jax`/`jnp` start as import-on-first-touch proxies and
# are rebound to the real modules by _ensure_jax, so traced bodies resolve
# them normally at trace time — including when an external module (e.g.
# parallel/mesh.py) wraps this module's body functions in its own jit
# without ever calling a lazy-jit entry point here.
_jax_ready = False


class _LazyJaxProxy:
    def __init__(self, which):
        self._which = which

    def __getattr__(self, attr):
        _ensure_jax()
        return getattr(jax if self._which == "jax" else jnp, attr)


jax = _LazyJaxProxy("jax")
jnp = _LazyJaxProxy("jnp")


_jax_import_lock = threading.Lock()


def _ensure_jax():
    """Import jax once, on one thread at a time. jax's packages import each
    other in cycles, and two threads that start the first import together
    (the fused chain's group and simplex stages) can be handed each
    other's half-initialised modules by the interpreter's import-deadlock
    avoidance — an ImportError that kills the run. Every code path that
    may be the first to need jax comes through here."""
    global jax, jnp, _jax_ready
    if not _jax_ready:
        with _jax_import_lock:
            if _jax_ready:
                return jax
            from ..observe import compilewatch, process

            with process.startup_span("startup.jax_import"):
                import jax as _jax
                import jax.numpy as _jnp

                jax = _jax
                jnp = _jnp
                # before the first jit compile so device executables land
                # on disk and compile events are counted
                # (device.backend_compiles — the warm-kernel evidence the
                # serve smoke gate asserts on)
                _enable_persistent_compile_cache()
                compilewatch.install()
            _jax_ready = True
    return jax


def _lazy_jit(fn=None, *, static_argnames=()):
    """@jax.jit that defers both the jax import and the jit wrapping to the
    first call (same compiled-function caching afterwards)."""
    def deco(f):
        box = []

        @wraps(f)
        def wrapper(*a, **k):
            if not box:
                _ensure_jax()
                kwargs = {}
                if static_argnames:
                    kwargs["static_argnames"] = static_argnames
                box.append(jax.jit(f, **kwargs))
            return box[0](*a, **k)

        return wrapper

    return deco(fn) if fn is not None else deco


from ..constants import MAX_PHRED, MIN_PHRED, N_CODE
from ..observe.trace import count, record_interval, span
from .datapath import CONST_CACHE, SHAPE_REGISTRY, as_device_operand
from .tables import QualityTables

def _enable_persistent_compile_cache():
    """Cross-process XLA compile cache (kernel shapes are a small fixed set,
    so warm-up compiles amortize to ~zero across CLI invocations). Called at
    ConsensusKernel construction, not import, so merely importing the library
    never mutates global jax config. One shared implementation with the CLI
    (utils/compile_cache.py); opt out with FGUMI_TPU_NO_XLA_CACHE=1."""
    from ..utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()

_LN10_F32 = np.float32(np.log(10.0))
_LN_4_3_F32 = np.float32(np.log(4.0 / 3.0))
_EPS32 = np.float32(np.finfo(np.float32).eps)
_PHRED_PER_LN = np.float32(10.0 / np.log(10.0))

# ---------------------------------------------------------------------------
# Suspect guard band — derivation (the analog of the reference's fast-path
# margin proof, base_builder.rs:186-301).
#
# Sources of f32 error in a lane contribution C[b] = sum over matching
# observations of delta[q] (delta = ln_correct - ln_err >= 0, from tables
# computed in f64 and rounded once to f32):
#
#   (1) table rounding:  |fl(delta) - delta| <= eps32/2 * delta per term;
#   (2) accumulation:    summing n nonnegative terms in ANY order (XLA may
#       reduce sequentially or as a tree) has error <= eps32 * n * sum(x_i)
#       to first order, since every partial sum is <= the final sum for
#       nonnegative terms. sum(x_i) = C[b] <= max_c.
#
# A position's lane has at most `depth` matching observations, so
#   |C_err| <= eps32 * (depth + 1) * max_c.
# The gap g = max_c - C[b] adds one subtraction (a half-ulp of max_c) and is
# computed from two such sums, giving the per-gap bound used below:
#   |g_err| <= eps_gap = eps32 * (depth + 2) * (1 + max_c),
# where the "+1" inside the parenthesis covers max_c < 1 (absolute floor).
# This is depth-aware on purpose: a fixed multiplier is unsound for deep
# families (n grows) and wastefully wide for shallow ones.
#
# Downstream of the gaps:
#   s = sum over losing lanes of exp(-g): |ds| <= s * eps_gap + O(eps32)*s
#       (exp is 1-ulp; d exp(-g) = exp(-g) |dg|);
#   ln_cons_err = ln(s) - log1p(s): |d| <= |ds|/s + |ds|/(1+s) + 2 ulp
#       <= 2 * eps_gap + O(eps32).
# So the Phred-scale error is  err_phred <= PHRED_PER_LN * 2 * eps_gap plus
# a handful of 1-ulp function evaluations; PHRED_PER_LN * 5 * eps32 ~ 2.6e-6,
# absorbed by _QUAL_GUARD_FLOOR = 3e-4 (kept < the 0.001 fgbio precision
# nudge so the floor can never mask the intended rounding offset).
#
# Guard gates (any triggers the exact f64 host recompute):
#   tie:      winner margin <= 2 * eps_gap + _TIE_GUARD_FLOOR  (the margin is
#             a difference of two gap-accurate quantities; the floor covers
#             exact-tie ulp jitter);
#   quality:  distance of phred_f to the nearest integer boundary <=
#             err_phred + _QUAL_GUARD_FLOOR;
#   branch:   |diff - 6| within the gap error of the f32/f64 quick-path
#             disagreement region of the two-trials combination;
#   NaN:      any non-finite contribution (Q0 -inf table entries).
#
# tests/test_kernel_parity.py + the adversarial edge sweep in
# tests/test_guard_band.py assert the safety property this analysis promises:
# no non-suspect position ever disagrees with the f64 oracle.
# ---------------------------------------------------------------------------
_QUAL_GUARD_FLOOR = 3e-4  # Phred units; absorbs O(eps32) evaluation error
_TIE_GUARD_FLOOR = 1e-5  # ln units; exact-tie ulp jitter

# sentinel returned by device_call_segments in host mode: the resolve half
# runs the native f64 engine on the rows it receives (no device round-trip)
HOST_DISPATCH = ("host-dispatch",)


class DeadlineExceeded(Exception):
    """A device dispatch overran its deadline and was abandoned.

    Raised by the deadline-aware waits in the resolve paths — never by the
    device itself. The batch reroutes to the native f64 host engine
    (byte-identical by construction) and the breaker records a wedge."""


def _deadline_bounds():
    """(floor_s, ceiling_s) from ``FGUMI_TPU_DISPATCH_DEADLINE_S``, or
    (None, None) when dispatch deadlines are disabled.

    Accepted forms: ``""`` (defaults 30:300), ``"CEILING"``,
    ``"FLOOR:CEILING"``, or ``0``/``off``/``inf`` to disable. The floor
    absorbs first-dispatch XLA compiles (which run inside the dispatch
    wall); the ceiling bounds what a wedged chip can cost even when the
    cost model has no prediction yet."""
    import os

    spec = os.environ.get("FGUMI_TPU_DISPATCH_DEADLINE_S", "").strip().lower()
    if spec in ("off", "none", "inf"):
        return None, None
    floor, ceil = 30.0, 300.0
    if spec:
        try:
            parts = [float(p) for p in spec.split(":", 1)]
        except ValueError:
            log.warning("FGUMI_TPU_DISPATCH_DEADLINE_S=%r is not "
                        "S or FLOOR:CEILING; using the default", spec)
            return floor, ceil
        if len(parts) == 1:
            ceil = parts[0]
            floor = min(floor, ceil)
        else:
            floor, ceil = parts
        if ceil <= 0:
            return None, None
        floor = min(max(floor, 0.01), ceil)
    return floor, ceil


def dispatch_deadline_s(pred_s=None):
    """Deadline (seconds) for one dispatch's resolve wait, or None when
    disabled. ``pred_s``: the router cost model's predicted dispatch wall
    — the deadline is predicted wall x safety factor
    (``FGUMI_TPU_DEADLINE_FACTOR``, default 20), clamped to the
    floor/ceiling; with no prediction the ceiling applies."""
    import os

    floor, ceil = _deadline_bounds()
    if ceil is None:
        return None
    if pred_s is None or pred_s <= 0:
        return ceil
    try:
        factor = float(os.environ.get("FGUMI_TPU_DEADLINE_FACTOR", "20"))
    except ValueError:
        factor = 20.0
    return min(max(pred_s * factor, floor), ceil)


def ticket_deadline_s(ticket):
    """Resolve-wait deadline for one feeder ticket. The jit call compiles
    inside the dispatch wall, so the first dispatch of a new shape waits
    to the ceiling: a cold compile must not be read as a wedge (and
    abandoned, finished on the host, and counted against the breaker).
    Every later dispatch gets the predicted wall x safety factor."""
    if ticket.new_shape:
        return dispatch_deadline_s()
    tl = DEVICE_STATS.timeline_entry(ticket.slot)
    return dispatch_deadline_s((tl or {}).get("pred_s"))


def use_host_engine() -> bool:
    """Whether consensus dispatches route to the native f64 host engine.

    Uncached on purpose (kernel instances cache per-instance): tests flip
    FGUMI_TPU_HOST_ENGINE between in-process CLI runs. Env semantics as in
    ConsensusKernel.host_mode."""
    import os

    env = os.environ.get("FGUMI_TPU_HOST_ENGINE", "auto").lower()
    from ..native import batch as nb

    if env in ("1", "true", "force"):
        if not nb.available():
            import logging

            logging.getLogger("fgumi_tpu").warning(
                "FGUMI_TPU_HOST_ENGINE=1 but the native library is "
                "unavailable; using the device kernel")
        return nb.available()
    if env in ("0", "false", "off"):
        return False
    if not nb.available():
        return False
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        # CPU explicitly pinned: decide without importing jax (the whole
        # point of host mode on a multi-process chain)
        return True
    _ensure_jax()
    return jax.default_backend() == "cpu"

# bf16 systolic peak FLOP/s and HBM bytes/s per chip, keyed by the exact
# ``jax.devices()[0].device_kind`` — for the MFU estimate below. The
# consensus kernel is VPU/elementwise-dominated, so low MFU is expected.
# A device that is not listed prints "unknown device", never a default.
#   "TPU v5 lite": Google Cloud documentation, "TPU v5e" system
#   architecture — 197 TFLOP/s bf16, 819 GB/s HBM per chip.
_DEVICE_PEAKS = {"TPU v5 lite": (197e12, 819e9)}


def device_identity() -> dict:
    """Which platform did this process's consensus work: jax's devices
    once jax was initialised here, else the native f64 host engine (a
    CPU-pinned run never imports jax). Rides in the run report's
    ``device`` section and the ``--stats`` line so nothing downstream has
    to guess whether a chip was involved."""
    if not _jax_ready:
        return {"platform": "cpu", "device_kind": "native f64 host engine",
                "device_count": 0}
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


class DeviceStats:
    """Device-interaction accounting (the §5.1 analog of PipelineStats'
    per-step timers, scoped to the device boundary): dispatch count, host
    time blocked on fetch (dispatch-to-fetch on an async backend ==
    remaining compute + transfer), bytes fetched, and a model-FLOP tally
    from the dispatched shapes. Thread-safe; one module-wide instance
    aggregates across kernels so a CLI run can report a single device
    fraction regardless of how many callers it built."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        """Zero the counters (e.g. between a warm-up and a timed run)."""
        self.dispatches = 0
        self.fetch_wait_s = 0.0
        self.bytes_fetched = 0
        self.bytes_uploaded = 0
        self.model_flops = 0
        self.rows_real = 0
        self.rows_padded = 0
        self.in_flight = 0
        # resilience accounting (retry / degrade path, docs/resilience.md):
        # transient-dispatch retries, RESOURCE_EXHAUSTED batch halvings,
        # whole-batch falls back to the native f64 host engine, and batches
        # abandoned at their dispatch deadline (self-healing layer)
        self.retries = 0
        self.batch_splits = 0
        self.host_fallbacks = 0
        self.deadline_fallbacks = 0
        # pipelined-upload accounting (docs/device-datapath.md): feeder-fn
        # seconds that overlapped an earlier dispatch's device compute, the
        # feeder queue's high-water mark, and constant-cache traffic
        self.upload_overlap_s = 0.0
        self.feeder_queue_peak = 0
        self.const_uploads = 0
        self.const_hits = 0
        self.const_upload_bytes = 0
        # adaptive-offload accounting (ops/router.py): batches routed to
        # the device vs the native f64 host engine
        self.route_device = 0
        self.route_host = 0
        # device-resident pipeline accounting (ISSUE 11): the live/peak
        # bytes of ResidentHandles arrays pinned on the device between stages
        self.resident_bytes = 0
        self.resident_bytes_peak = 0
        # kernel-backend accounting (ISSUE 19): wire dispatches executed
        # by the hand-tiled Pallas kernel vs the XLA-lowered oracle
        self.kernel_pallas = 0
        self.kernel_xla = 0
        self.timeline = []  # per-dispatch dicts (capped; --stats report)
        # stamps for dispatches past the timeline cap, alive only until
        # resolve (begin_in_flight/end_in_flight; bounded)
        self._tail_entries = {}
        self._next_slot = 0
        self._t0 = time.monotonic()

    def add_retry(self):
        with self._lock:
            self.retries += 1

    def add_split(self):
        with self._lock:
            self.batch_splits += 1

    def add_host_fallback(self):
        with self._lock:
            self.host_fallbacks += 1

    def add_deadline_fallback(self):
        with self._lock:
            self.deadline_fallbacks += 1
            n = self.deadline_fallbacks
            dispatches = self.dispatches
        # the wedge signature: note it in the always-on flight ring and —
        # when a dump dir is configured — freeze a black box naming the
        # still-unresolved dispatch(es). Outside the stats lock: the dump
        # re-enters snapshot()/timeline_snapshot().
        from ..observe.flight import FLIGHT

        FLIGHT.note("device.deadline_fallback", count=n,
                    dispatches=dispatches)
        FLIGHT.dump("dispatch-deadline", deadline_fallbacks=n)

    def add_upload_overlap(self, dt: float):
        with self._lock:
            self.upload_overlap_s += dt

    def note_queue_depth(self, depth: int):
        with self._lock:
            if depth > self.feeder_queue_peak:
                self.feeder_queue_peak = depth

    def add_const_upload(self, nbytes: int):
        with self._lock:
            self.const_uploads += 1
            self.const_upload_bytes += int(nbytes)

    def add_const_hit(self):
        with self._lock:
            self.const_hits += 1

    def add_kernel_backend(self, slot: int, backend: str):
        """Record which kernel backend ran a wire dispatch (ISSUE 19):
        counter + timeline stamp, so a flight dump on a wedge names the
        kernel that wedged."""
        with self._lock:
            if backend == "pallas":
                self.kernel_pallas += 1
            else:
                self.kernel_xla += 1
            entry = self._entry_locked(slot)
            if entry is not None:
                entry["kernel_backend"] = backend
        from ..observe.metrics import METRICS

        METRICS.inc(f"device.kernel.{backend}")

    def add_resident_bytes(self, n: int):
        with self._lock:
            self.resident_bytes += int(n)
            if self.resident_bytes > self.resident_bytes_peak:
                self.resident_bytes_peak = self.resident_bytes
            now = self.resident_bytes
        from ..observe.metrics import METRICS

        METRICS.set("device.resident_bytes", now)

    def release_resident_bytes(self, n: int):
        with self._lock:
            self.resident_bytes -= int(n)
            now = self.resident_bytes
        from ..observe.metrics import METRICS

        METRICS.set("device.resident_bytes", now)

    def add_route(self, side: str):
        with self._lock:
            if side == "device":
                self.route_device += 1
            else:
                self.route_host += 1

    def add_dispatch(self, flops: int):
        with self._lock:
            self.dispatches += 1
            self.model_flops += int(flops)

    def begin_in_flight(self, upload_bytes: int, pack_s: float = 0.0) -> int:
        """Count a dispatch in flight (host->device submitted, result not
        yet fetched). Returns a timeline slot id for end_in_flight."""
        from ..observe import xprof

        if xprof.armed():  # one-shot --xla-profile capture (off: one call)
            xprof.on_dispatch_begin()
        with self._lock:
            self.in_flight += 1
            self.bytes_uploaded += int(upload_bytes)
            slot = self._next_slot
            self._next_slot += 1
            entry = {"t_dispatch": round(time.monotonic() - self._t0, 4),
                     "up_bytes": int(upload_bytes),
                     "pack_s": round(pack_s, 4)}
            if slot < 4096:
                self.timeline.append(entry)
            elif len(self._tail_entries) < 1024:
                # past the persistent-timeline cap, stamps live only until
                # resolve (end_in_flight pops them) so latency histograms
                # and router feedback keep working on arbitrarily long
                # runs; the side map is bounded against abandon leaks
                self._tail_entries[slot] = entry
            return slot

    def _entry_locked(self, slot: int):
        """The live entry for a slot — persistent timeline or tail map —
        or None. Caller holds the lock."""
        if 0 <= slot < len(self.timeline):
            return self.timeline[slot]
        return self._tail_entries.get(slot)

    def note_upload(self, slot: int, upload_s: float):
        """Record a dispatch's device_put wall time (feeder thread)."""
        with self._lock:
            entry = self._entry_locked(slot)
            if entry is not None:
                entry["upload_s"] = round(upload_s, 4)

    def note_exec(self, slot: int, run_s: float = 0.0):
        """Stamp upload+enqueue completion: the window from here to fetch
        start is device compute overlapped with host work. ``run_s`` is
        how long the dispatch occupied the feeder thread (upload, enqueue,
        and on a new shape the compile)."""
        with self._lock:
            entry = self._entry_locked(slot)
            if entry is not None:
                entry["t_exec"] = round(time.monotonic() - self._t0, 4)
                entry["run_s"] = round(run_s, 4)

    def note_pred(self, slot: int, pred_s: float):
        """Stamp the cost model's predicted dispatch time (ops/router.py)
        so BENCH artifacts carry predicted vs actual per dispatch."""
        with self._lock:
            entry = self._entry_locked(slot)
            if entry is not None:
                entry["pred_s"] = round(pred_s, 4)

    def note_mesh(self, slot: int, shards: int, shard_bytes: int,
                  psums: int):
        """Stamp a mesh dispatch's shard geometry into the timeline: shard
        count, upload bytes per shard, and hot-path psum count (0 on a
        dp-only mesh — families are independent; 2 with sp > 1: the
        contribution and observation combines)."""
        with self._lock:
            entry = self._entry_locked(slot)
            if entry is not None:
                entry["shards"] = int(shards)
                entry["shard_up_bytes"] = int(shard_bytes)
                entry["psums"] = int(psums)

    def timeline_entry(self, slot: int):
        """Copy of one timeline slot (router feedback at resolve time)."""
        with self._lock:
            entry = self._entry_locked(slot)
            return dict(entry) if entry is not None else None

    def end_in_flight(self, slot: int, fetched_bytes: int, wait_s: float):
        entry = None
        with self._lock:
            self.in_flight -= 1
            live = self._entry_locked(slot)
            if live is not None:
                live.update(
                    t_fetched=round(time.monotonic() - self._t0, 4),
                    down_bytes=int(fetched_bytes),
                    fetch_wait_s=round(wait_s, 4))
                entry = dict(live)
                self._tail_entries.pop(slot, None)
        if entry is not None:
            _observe_dispatch_latency(entry)

    def in_flight_count(self) -> int:
        with self._lock:
            return self.in_flight

    def add_pad(self, real_rows: int, padded_rows: int):
        """Padding-waste accounting: real vs device-layout rows per dispatch
        (ragged-batch economics, SURVEY hard-part #2)."""
        with self._lock:
            self.rows_real += int(real_rows)
            self.rows_padded += int(padded_rows)

    def add_fetch(self, nbytes: int, wait_s: float):
        """Credit fetch accounting without performing the device_get: the
        coalescer fetches a merged result once and attributes each
        partner's byte share + measured resolve wait to the partner's own
        scope (ops/coalesce.py)."""
        with self._lock:
            self.fetch_wait_s += float(wait_s)
            self.bytes_fetched += int(nbytes)

    def fetch(self, dev):
        """Timed jax.device_get — route every device->host fetch through
        here so fetch_wait_s captures all host time blocked on the device.
        Accepts a single array or a tuple (fetched in one device_get)."""
        _ensure_jax()
        t0 = time.monotonic()
        with span("device.fetch") as sp:
            got = jax.device_get(dev)
            if isinstance(got, (tuple, list)):
                out = tuple(np.asarray(g) for g in got)
                nbytes = sum(g.nbytes for g in out)
            else:
                out = np.asarray(got)
                nbytes = out.nbytes
            sp.set(bytes=nbytes)
        dt = time.monotonic() - t0
        with self._lock:
            self.fetch_wait_s += dt
            self.bytes_fetched += nbytes
        return out

    def snapshot(self):
        with self._lock:
            out = {"dispatches": self.dispatches,
                   "fetch_wait_s": round(self.fetch_wait_s, 3),
                   "bytes_fetched": self.bytes_fetched,
                   "model_gflops": round(self.model_flops / 1e9, 3)}
            if self.bytes_uploaded:
                out["bytes_uploaded"] = self.bytes_uploaded
            if self.rows_padded:
                out["pad_rows_real"] = self.rows_real
                out["pad_rows_device"] = self.rows_padded
                out["padding_waste"] = round(
                    self.rows_padded / max(self.rows_real, 1) - 1.0, 4)
            if self.retries:
                out["dispatch_retries"] = self.retries
            if self.batch_splits:
                out["batch_splits"] = self.batch_splits
            if self.host_fallbacks:
                out["host_fallbacks"] = self.host_fallbacks
            if self.deadline_fallbacks:
                out["deadline_fallbacks"] = self.deadline_fallbacks
            if self.upload_overlap_s:
                out["upload_overlap_s"] = round(self.upload_overlap_s, 3)
            if self.feeder_queue_peak:
                out["feeder_queue_depth"] = self.feeder_queue_peak
            if self.const_uploads or self.const_hits:
                out["const_uploads"] = self.const_uploads
                out["const_hits"] = self.const_hits
                out["const_upload_bytes"] = self.const_upload_bytes
            if self.route_device or self.route_host:
                out["route_device"] = self.route_device
                out["route_host"] = self.route_host
            if self.kernel_pallas or self.kernel_xla:
                out["kernel_pallas"] = self.kernel_pallas
                out["kernel_xla"] = self.kernel_xla
            if self.resident_bytes_peak:
                out["resident_bytes_peak"] = self.resident_bytes_peak
                if self.resident_bytes:
                    out["resident_bytes"] = self.resident_bytes
            return out

    def timeline_snapshot(self):
        """Per-dispatch device timeline for the --stats report (VERDICT r4
        item 9): dispatch time, upload/fetch bytes, fetch wait each.
        Entries carry their ``slot``; past the persistent cap the live
        (still-in-flight) tail-map entries are appended in slot order, so
        a flight dump on a >4096-dispatch run still names the wedged
        dispatch instead of showing only ancient history."""
        with self._lock:
            out = [dict(t, slot=i) for i, t in enumerate(self.timeline)]
            out.extend(dict(self._tail_entries[s], slot=s)
                       for s in sorted(self._tail_entries))
            return out

    def load_from(self, other: "DeviceStats"):
        """Adopt another instance's counters wholesale (scope publishing:
        a finished command's per-scope stats become the process-global view
        that bench/probe harnesses read after cli_main)."""
        with other._lock:
            state = {k: getattr(other, k) for k in (
                "dispatches", "fetch_wait_s", "bytes_fetched",
                "bytes_uploaded", "model_flops", "rows_real", "rows_padded",
                "in_flight", "retries", "batch_splits", "host_fallbacks",
                "deadline_fallbacks",
                "upload_overlap_s", "feeder_queue_peak", "const_uploads",
                "const_hits", "const_upload_bytes", "route_device",
                "route_host", "resident_bytes",
                "resident_bytes_peak", "kernel_pallas", "kernel_xla",
                "_t0", "_next_slot")}
            timeline = [dict(t) for t in other.timeline]
            tail = {s: dict(t) for s, t in other._tail_entries.items()}
        with self._lock:
            for k, v in state.items():
                setattr(self, k, v)
            self.timeline = timeline
            self._tail_entries = tail

    def format_summary(self, wall_s: float = None) -> str:
        s = self.snapshot()
        ident = device_identity()
        parts = [f"device: {ident['platform']} ({ident['device_kind']} "
                 f"x{ident['device_count']}), "
                 f"{s['dispatches']} dispatches, "
                 f"fetch-wait {s['fetch_wait_s']:.3f}s, "
                 f"{s['bytes_fetched'] / 1e6:.1f} MB fetched, "
                 f"model {s['model_gflops']:.2f} GFLOP"]
        if self.fetch_wait_s > 0 and _jax_ready:
            gfs = self.model_flops / self.fetch_wait_s / 1e9
            parts.append(f"~{gfs:.1f} GFLOP/s incl. transfer")
            peaks = _DEVICE_PEAKS.get(ident["device_kind"])
            parts.append(f"MFU ~{100.0 * gfs * 1e9 / peaks[0]:.4f}%"
                         if peaks else "MFU: unknown device")
        if wall_s:
            parts.append(f"fetch-wait {self.fetch_wait_s / wall_s:.2%} "
                         f"of {wall_s:.2f}s wall")
        return "; ".join(parts)


def _device_service_s(entry: dict) -> float:
    """One dispatch's serial occupancy of the feeder, the link and the
    device: its run on the feeder thread (upload + enqueue; a backend that
    executes inline computes here too) plus the part of the resolve's wait
    that follows the enqueue stamp (``t_exec``) — remaining compute and the
    download. A resolver that arrives before the feeder has even reached
    its dispatch (queued behind earlier uploads, or behind an earlier
    shape's compile on the one feeder thread) is waiting in a queue, and
    decide() prices the queue through its in-flight term; folding it in
    here would charge it twice and, after one compile, price the device
    out for good."""
    wait_s = entry.get("fetch_wait_s", 0.0)
    if "t_exec" not in entry or "t_fetched" not in entry:
        return entry.get("upload_s", 0.0) + wait_s
    after_enqueue = min(wait_s, max(entry["t_fetched"] - entry["t_exec"],
                                    0.0))
    return entry.get("run_s", entry.get("upload_s", 0.0)) + after_enqueue


def _feed_router(ticket, fetched: int) -> None:
    """Feed the offload cost model with one resolved dispatch's measured
    pieces (docs/device-datapath.md "Adaptive offload policy"). Slots past
    the timeline cap have no entry — skipped rather than polluting the
    EWMAs with degenerate zero samples — and so is a first-sight shape,
    whose feeder run is a compile."""
    tl = DEVICE_STATS.timeline_entry(ticket.slot)
    if tl is None or ticket.new_shape:
        return
    from .router import ROUTER

    up_s = tl.get("upload_s", 0.0)
    service_s = _device_service_s(tl)
    ROUTER.observe_device(ticket.upload_bytes, fetched, up_s,
                          max(service_s - up_s, 0.0), service_s,
                          devices=ticket.mesh_devices)


def _observe_dispatch_latency(entry: dict) -> None:
    """Fold one resolved dispatch's timeline stamps into the latency
    histograms (observe/metrics.py): per-dispatch pack/upload/compute/fetch
    walls, the end-to-end dispatch wall, and the offload cost model's
    predicted-vs-actual error. Called once per resolve, outside the
    DeviceStats lock."""
    from ..observe import xprof
    from ..observe.metrics import METRICS

    if xprof.armed():  # close an in-flight --xla-profile capture
        xprof.on_dispatch_end()

    METRICS.observe("device.dispatch.pack_s", entry.get("pack_s", 0.0))
    if "upload_s" in entry:
        METRICS.observe("device.dispatch.upload_s", entry["upload_s"])
    fetch_s = entry.get("fetch_wait_s", 0.0)
    METRICS.observe("device.dispatch.fetch_s", fetch_s)
    # per-dispatch fetched bytes (ISSUE 11): makes the fused-filter
    # "bytes-fetched reduced >= 5x" claim machine-readable from any run
    # report (device.dispatch.fetch_bytes histogram + the bytes_fetched
    # counter the device section already carries)
    METRICS.observe("device.dispatch.fetch_bytes",
                    entry.get("down_bytes", 0))
    t_fetched = entry.get("t_fetched")
    if t_fetched is not None and "t_exec" in entry:
        METRICS.observe("device.dispatch.compute_s",
                        max(t_fetched - fetch_s - entry["t_exec"], 0.0))
    if t_fetched is not None and "t_dispatch" in entry:
        wall = max(t_fetched - entry["t_dispatch"], 0.0)
        METRICS.observe("device.dispatch.wall_s", wall)
        pred = entry.get("pred_s")
        if pred is not None:
            METRICS.observe("device.router.pred_err_s", abs(wall - pred))
        # always-on dispatch history for the flight ring: a black box from
        # a run without --trace still shows the recent device activity
        # leading up to the failure (one note per dispatch, not per record)
        from ..observe.flight import FLIGHT

        FLIGHT.note("device.dispatch", wall_s=round(wall, 4),
                    up_bytes=entry.get("up_bytes", 0),
                    down_bytes=entry.get("down_bytes", 0),
                    kernel=entry.get("kernel_backend", "xla"))


#: Fallback instance used when no telemetry scope is active (library use,
#: tests, plain single-command CLI runs).
_GLOBAL_DEVICE_STATS = DeviceStats()


class _DeviceStatsProxy:
    """Scope-resolving stand-in for the old module-wide DeviceStats.

    Every attribute access (method or counter) resolves the active
    telemetry scope (observe.scope) first — one DeviceStats per daemon job
    — and falls back to the process-global instance, so the dozens of
    existing ``DEVICE_STATS.xxx`` call sites keep working unchanged while
    two concurrent jobs in one process never share counters."""

    __slots__ = ()

    @staticmethod
    def _target() -> DeviceStats:
        from ..observe.scope import current_scope

        scope = current_scope()
        if scope is not None:
            return scope.device_stats(DeviceStats)
        return _GLOBAL_DEVICE_STATS

    def __getattr__(self, name):
        return getattr(self._target(), name)

    def __setattr__(self, name, value):
        # tests monkeypatch counters (e.g. in_flight) straight through
        setattr(self._target(), name, value)


DEVICE_STATS = _DeviceStatsProxy()


class DispatchTicket:
    """Future for a device dispatch submitted to the feeder thread.

    wait() returns the device result handle (or re-raises the feeder
    exception); the fetch itself stays with the caller (resolve worker).
    A ticket whose wait timed out must be handed to
    :meth:`DeviceFeeder.abandon` — the late result is discarded and the
    feeder slot reclaimed whenever the wedged dispatch finally returns."""

    __slots__ = ("_event", "_result", "_exc", "slot", "upload_bytes",
                 "_released", "_abandoned", "mesh_gather", "mesh_devices",
                 "mesh_f_loc", "staging", "filter_mode", "filter_ctx",
                 "new_shape", "t_submit")

    def __init__(self):
        self._event = threading.Event()
        self._result = None
        self._exc = None
        self.slot = -1
        self.upload_bytes = 0
        self.t_submit = 0.0  # monotonic stamp of DeviceFeeder.submit
        self._released = False
        self._abandoned = False
        # first sight of this bucketed shape in the process: the dispatch
        # compiles (or loads from the persistent cache) before it runs
        self.new_shape = False
        # pooled host staging buffers backing this dispatch's upload —
        # recycled at mark_resolved (never on abandon: the wedged upload
        # may still be reading them)
        self.staging = None
        # fused consensus→filter dispatch (resolve_segments_wire_filtered)
        # + its host-side filter parameters, retained so the sentinel's
        # fused-route audit tap can rebuild the f64 oracle stats row
        self.filter_mode = False
        self.filter_ctx = None
        # mesh dispatches (device_call_segments_wire mesh=...): the
        # family-order gather over the shard-ordered device output, the
        # mesh size the router's per-mesh cost model is keyed by, and the
        # per-shard family count the audit sentinel attributes divergent
        # rows with (shard = gather[row] // F_loc)
        self.mesh_gather = None
        self.mesh_devices = 1
        self.mesh_f_loc = None

    def _set(self, result=None, exc=None):
        self._result = result
        self._exc = exc
        self._event.set()

    def wait(self, timeout: float = None):
        """Result handle, or raise. ``timeout`` seconds (None = forever);
        on expiry raises :class:`DeadlineExceeded` WITHOUT abandoning —
        deciding what to do with the wedged slot is the caller's call."""
        if not self._event.wait(timeout):
            raise DeadlineExceeded(
                f"device dispatch did not complete within {timeout:.1f}s")
        if self._exc is not None:
            raise self._exc
        return self._result


class DeviceFeeder:
    """Depth-N upload pipeline on one background thread.

    jax.device_put blocks the calling thread for the whole transfer, and
    the first jit call of a shape blocks it for the compile, so uploads and
    dispatches must not run on the processing thread. The feeder runs
    puts+dispatches in submission order on its own thread, keeping up to
    ``depth`` dispatches
    (default 2, ``FGUMI_TPU_FEEDER_DEPTH``) in flight — submitted to the
    device but not yet resolved — within a byte budget
    (``FGUMI_TPU_FEEDER_BYTES``, default 256 MiB of upload payload), so
    batch k+1's upload overlaps batch k's device compute while queued
    uploads can never pile unbounded input buffers onto the device.
    Device->host fetches run on the resolve workers and overlap the
    feeder's uploads from the other side, with ``copy_to_host_async``
    started the moment a dispatch is enqueued. This is the Q4->Process
    double-buffering analog (reference base.rs:1724-1920) lifted to the
    device boundary.

    Resolve sites MUST call :meth:`mark_resolved` (their ``finally``
    blocks do, next to the in-flight accounting) or the pipeline stalls at
    ``depth`` outstanding dispatches. Resolution must follow submission
    order per process — every caller already resolves in order, and
    ``depth >= 2`` tolerates the split-halving path's nested tickets.
    """

    def __init__(self):
        self._q = collections.deque()
        self._cv = threading.Condition()
        self._thread = None
        self._exit = False
        self._active = False  # an item is currently executing
        self._inflight = 0  # dispatched to device, not yet resolved
        self._inflight_bytes = 0
        # device bytes pinned by live ResidentHandles (ISSUE 11): counted
        # against the same governed byte budget as the in-flight uploads,
        # so resident stage-1 outputs can no longer pin HBM invisibly —
        # a held resident narrows the gate until its consumer releases it
        self._resident_bytes = 0
        self._depth = None
        self._byte_budget = None  # DynamicBudget once configured
        self._gov_token = None
        self.gate_wait_s = 0.0  # time the depth/byte gate held a dispatch
        self._async_copy_warned = set()  # leaf types logged once (debug)

    def _budget_resized(self):
        # a governor grow must release a gate-blocked feeder immediately
        with self._cv:
            self._cv.notify_all()

    def _config(self):
        # under the feeder condition (an RLock, so the feeder loop's locked
        # call re-enters fine): first use races the unlocked readers (the
        # depth property) against the feeder thread, and the governor
        # registration below must happen exactly once — a double register
        # would count a phantom 256 MiB against the global cap forever
        with self._cv:
            if self._depth is None:
                import os

                try:
                    # floor 2, not 1: the OOM-recovery path resolves a
                    # failed ticket and then dispatches+resolves its two
                    # halves in order, which needs one slot of headroom
                    # past the batch a deferred-resolve caller may still
                    # hold (the class invariant above: depth >= 2
                    # tolerates nested tickets)
                    depth = max(
                        int(os.environ.get("FGUMI_TPU_FEEDER_DEPTH", "2")),
                        2)
                except ValueError:
                    depth = 2
                try:
                    budget = max(
                        int(os.environ.get("FGUMI_TPU_FEEDER_BYTES",
                                           str(256 << 20))), 1 << 20)
                except ValueError:
                    budget = 256 << 20
                # the upload budget is a governed DynamicBudget: the env
                # value seeds it, the ResourceGovernor may grow it when the
                # gate is the contended queue (demand signal: gate_wait_s)
                # or shrink it toward the floor under memory pressure
                # (utils/governor.py)
                from ..utils.governor import GOVERNOR, DynamicBudget

                b = DynamicBudget("device.feeder", budget,
                                  floor=min(budget, 32 << 20))
                b.on_resize = self._budget_resized
                # re-registering (env-driven reconfigure, per-test feeders)
                # must not leak the previous entry: stale budgets would
                # keep counting against the governor's global cap forever
                GOVERNOR.unregister_budget(self._gov_token)
                self._gov_token = GOVERNOR.register_budget(
                    b, demand_fn=lambda: {"put_wait_s": self.gate_wait_s,
                                          "get_wait_s": 0.0})
                self._byte_budget = b
                self._depth = depth
            return self._depth, self._byte_budget.limit

    def ungovern(self):
        """Release this feeder's governor registration (tests tearing down
        throwaway feeders; the process singleton keeps its entry)."""
        from ..utils.governor import GOVERNOR

        GOVERNOR.unregister_budget(self._gov_token)
        self._gov_token = None

    @property
    def depth(self) -> int:
        """Configured in-flight pipeline depth (>= 2)."""
        return self._config()[0]

    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._exit = False
            self._thread = threading.Thread(target=self._loop,
                                            name="fgumi-device-feeder",
                                            daemon=True)
            self._thread.start()

    def submit(self, fn, upload_bytes: int = 0,
               slot: int = -1) -> DispatchTicket:
        """Run fn() (puts + jit dispatch) on the feeder thread.

        The submitter's context travels with the work item: the feeder is
        one process-wide thread shared by every job, so retry counters,
        dispatch spans, and compile events raised inside fn() must resolve
        the *submitting* job's telemetry scope, not the feeder's empty
        one. ``upload_bytes`` feeds the byte budget; ``slot`` is the
        DeviceStats timeline slot (set before submission so the feeder can
        stamp upload/exec times into it without racing the caller)."""
        import contextvars

        from .datapath import compile_is_shape_miss

        ticket = DispatchTicket()
        ticket.upload_bytes = int(upload_bytes)
        ticket.slot = slot
        ticket.t_submit = time.monotonic()
        # submit sites run under SHAPE_REGISTRY.attribute_compiles(new)
        ticket.new_shape = compile_is_shape_miss()
        ctx = contextvars.copy_context()
        with self._cv:
            self._ensure_thread()
            self._q.append((fn, ctx, ticket))
            depth_now = len(self._q) + (1 if self._active else 0)
            self._cv.notify_all()
        DEVICE_STATS.note_queue_depth(depth_now)
        return ticket

    def add_resident_bytes(self, n: int):
        """Count live ResidentHandles bytes against the byte gate."""
        with self._cv:
            self._resident_bytes += int(n)

    def release_resident_bytes(self, n: int):
        with self._cv:
            self._resident_bytes -= int(n)
            self._cv.notify_all()

    def mark_resolved(self, ticket: DispatchTicket):
        """Release a dispatch's in-flight pipeline slot + bytes
        (idempotent; resolve paths call it in their ``finally``)."""
        with self._cv:
            if ticket._released:
                return
            ticket._released = True
            self._inflight -= 1
            self._inflight_bytes -= ticket.upload_bytes
            staging = ticket.staging
            recycle = staging is not None and not ticket._abandoned
            ticket.staging = None
            self._cv.notify_all()
        if recycle:
            # by resolve time the device has consumed the upload (the
            # result was fetched or the dispatch failed), so the pooled
            # staging buffers are safe to hand out again — even on
            # backends where device_put aliases host memory. An abandoned
            # dispatch may still be mid-upload: its buffers are leaked to
            # the wedge instead of recycled.
            from .datapath import STAGING_POOL

            for arr in staging:
                STAGING_POOL.release(arr)

    def abandon(self, ticket: DispatchTicket):
        """Give up on a dispatch that overran its deadline.

        The resolver walks away NOW; whenever the wedged dispatch finally
        completes (or fails), its result is discarded and the feeder slot
        reclaimed through the ordinary :meth:`mark_resolved` path — so a
        single wedge degrades one batch, never wedges the pipeline's
        depth gate permanently. Safe against every interleaving with the
        worker loop: completion state is read under the lock, and
        ``mark_resolved`` is idempotent."""
        with self._cv:
            ticket._abandoned = True
            completed = ticket._event.is_set()
        from ..observe.flight import FLIGHT

        FLIGHT.note("device.feeder.abandon", slot=ticket.slot,
                    upload_bytes=ticket.upload_bytes,
                    completed_late=completed)
        if completed:
            # raced the completion: the result exists but the caller is
            # not going to fetch it — reclaim the slot here
            self.mark_resolved(ticket)

    def queue_depth(self) -> int:
        with self._cv:
            return len(self._q) + (1 if self._active else 0)

    def drain(self, timeout: float = None) -> bool:
        """Run the queue dry, then let the feeder thread exit when idle.

        The serve daemon's SIGTERM drain calls this after the scheduler
        quiesces so the process never leaves a dispatch half-uploaded.
        Returns True when the queue emptied (and the thread, if any,
        exited) within ``timeout`` seconds (None = wait indefinitely).
        The feeder restarts transparently on the next submit() — the
        worker clears ``_thread`` under the lock when it commits to exit,
        so a racing submit either lands on the live worker before that
        point or starts a fresh one."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            self._exit = True
            self._cv.notify_all()
            while self._q or self._active:
                left = None if deadline is None \
                    else deadline - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self._cv.wait(left if left is not None else 0.5)
            thread = self._thread
        if thread is not None and thread.is_alive():
            left = None if deadline is None else \
                max(deadline - time.monotonic(), 0.0)
            thread.join(left)
            return not thread.is_alive()
        return True

    def _run_item(self, fn, ticket, overlapped, t0):
        """Execute one work item inside the submitter's context (so
        DEVICE_STATS / METRICS / spans resolve the submitting job's
        scope)."""
        # submit -> this thread taking the ticket: queue + depth/byte gate
        record_interval("feeder.queue_wait", ticket.t_submit, t0,
                        slot=ticket.slot)
        result = fn()
        dt = time.monotonic() - t0
        if overlapped:
            # this fn ran while an earlier dispatch was still UNRESOLVED —
            # an upper bound on upload/compute overlap (in deferred-resolve
            # modes the earlier result may already sit on host), which is
            # how docs/observability.md defines upload_overlap_s
            DEVICE_STATS.add_upload_overlap(dt)
        if ticket.slot >= 0:
            DEVICE_STATS.note_exec(ticket.slot, run_s=dt)
        # start the device->host copy NOW (non-blocking): by the time the
        # resolve stage calls device_get, the result bytes are already on
        # host (or in flight), so the fetch costs a wait-for-arrival
        # instead of a full round trip. Backends without
        # copy_to_host_async just fetch at resolve time.
        try:
            for leaf in jax.tree_util.tree_leaves(result):
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()
        except Exception as e:  # noqa: BLE001 - fetch-time path still works
            # once per leaf/exception type, at debug: a silently dead
            # fetch-overlap path regresses e2e latency with zero signal
            key = type(e).__name__
            if key not in self._async_copy_warned:
                self._async_copy_warned.add(key)
                log.debug("copy_to_host_async failed (%s: %s); results "
                          "will be fetched synchronously at resolve time",
                          key, e)
        return result

    def _loop(self):
        from ..observe.scope import name_os_thread

        name_os_thread("fgumi-device-feeder")
        while True:
            with self._cv:
                self._active = False
                self._cv.notify_all()
                while not self._q:
                    if self._exit:
                        # commit to exit UNDER the lock: a concurrent
                        # submit() sees _thread is None and starts a fresh
                        # worker instead of queueing onto a dying one
                        self._thread = None
                        return
                    self._cv.wait()
                depth, _ = self._config()
                # depth/byte gate: hold the NEXT dispatch until an earlier
                # one resolves. Skipped in drain mode — the queue must run
                # dry even if no resolver is coming back for stragglers.
                # Bounded wait: a caller that died without resolving its
                # ticket (dropped pending chunk on a crashed pipeline)
                # must degrade to the old unpipelined behavior, never
                # freeze every later dispatch in the process. The byte
                # limit is re-read every iteration: the governor may grow it
                # mid-wait (its resize hook notifies this condition).
                ticket = self._q[0][2]
                deadline = None
                while (not self._exit and self._q
                       and (self._inflight >= depth
                            or (self._inflight > 0
                                and self._inflight_bytes
                                + self._resident_bytes
                                + ticket.upload_bytes
                                > self._byte_budget.limit))):
                    # the demand signal must name the *byte budget* as the
                    # gate, not the depth clause: growing bytes cannot
                    # release a depth-held dispatch, and a device-bound run
                    # waits here constantly — counting that would make the
                    # governor inflate this budget to its ceiling for
                    # nothing (starving genuinely byte-bound queues of the
                    # global cap)
                    byte_bound = self._inflight < depth
                    if deadline is None:
                        deadline = time.monotonic() + 60.0
                    left = deadline - time.monotonic()
                    if left <= 0:
                        log.warning(
                            "device feeder depth gate timed out with %d "
                            "dispatch(es) unresolved; proceeding (a "
                            "dispatch ticket was likely dropped without "
                            "resolution)", self._inflight)
                        break
                    t_wait = time.monotonic()
                    self._cv.wait(min(left, 1.0))
                    if byte_bound:
                        self.gate_wait_s += time.monotonic() - t_wait
                    ticket = self._q[0][2] if self._q else None
                if not self._q:
                    continue
                fn, ctx, ticket = self._q.popleft()
                if ticket._abandoned:
                    # abandoned while still queued (a deadline fired on a
                    # batch stuck behind a wedged dispatch): never start
                    # work nobody will fetch — especially not work that
                    # may hang this thread too
                    ticket._released = True  # never held a slot
                    ticket._set(exc=DeadlineExceeded(
                        "dispatch abandoned before it started"))
                    continue
                self._inflight += 1
                self._inflight_bytes += ticket.upload_bytes
                overlapped = self._inflight > 1
                self._active = True
            t0 = time.monotonic()
            try:
                result = ctx.run(self._run_item, fn, ticket, overlapped, t0)
                exc = None
            except BaseException as e:  # noqa: BLE001 - relayed to waiter
                result, exc = None, e
            with self._cv:
                ticket._set(result=result, exc=exc)
                late = ticket._abandoned
            if late:
                # the resolver gave up at its deadline while this dispatch
                # was running: discard the late result, reclaim the slot —
                # including any device-resident arrays it produced, whose
                # byte accounting would otherwise leak with the abandon
                log.warning("device dispatch completed %.1fs after its "
                            "deadline; late result discarded",
                            time.monotonic() - t0)
                _release_residents(result)
                self.mark_resolved(ticket)


DEVICE_FEEDER = DeviceFeeder()


@_lazy_jit
def _canary_sum_jit(x):
    return jnp.sum(x.astype(jnp.int32))


#: canary payload size: big enough that the upload wall is a usable link
#: sample, small enough that a health check costs the link next to nothing.
_CANARY_BYTES = 1 << 20


def device_canary(timeout_s: float = 10.0):
    """One tiny end-to-end device round trip under its own deadline.

    Returns ``(ok, wall_s, error)``. Goes through the ordinary feeder
    submit + bounded ticket wait, so a wedged feeder/link shows up as a
    timeout (the canary is abandoned like any other dispatch, never
    hangs the caller), and a healthy round trip feeds the router's
    link-rate EWMA. Used by the health monitor
    (:class:`fgumi_tpu.ops.breaker.HealthMonitor`); callers feed the
    breaker from the result."""
    t0 = time.monotonic()
    payload = np.zeros(_CANARY_BYTES, dtype=np.uint8)

    def _fn():
        # t_start is captured ON the feeder thread so the router sample
        # below excludes time spent queued behind real dispatches — the
        # resolve paths price queue wait via decide()'s in_flight term,
        # and a canary in a busy daemon must not fold it into the
        # overhead EWMA (it would overprice a healthy device)
        _ensure_jax()
        t_start = time.monotonic()
        dev = jax.device_put(payload)
        return _canary_sum_jit(dev), time.monotonic() - t_start, t_start

    ticket = DEVICE_FEEDER.submit(_fn, upload_bytes=payload.nbytes)
    try:
        dev_out, up_s, t_start = ticket.wait(timeout_s)
        left = max(timeout_s - (time.monotonic() - t0), 0.5)
        got = _fetch_with_deadline(dev_out, left)
    except DeadlineExceeded as e:
        DEVICE_FEEDER.abandon(ticket)
        return False, time.monotonic() - t0, str(e)
    except BaseException as e:  # noqa: BLE001 - canary outcome, not crash
        DEVICE_FEEDER.mark_resolved(ticket)
        if not (_is_oom(e) or _is_transient(e)):
            raise
        return False, time.monotonic() - t0, f"{type(e).__name__}: {e}"
    DEVICE_FEEDER.mark_resolved(ticket)
    wall = time.monotonic() - t0
    if int(got) != 0:  # payload is zeros; anything else is corruption
        return False, wall, f"canary sum mismatch: {int(got)}"
    from .router import ROUTER

    active_s = max(time.monotonic() - t_start, up_s)
    ROUTER.observe_device(payload.nbytes, 4, up_s,
                          max(active_s - up_s, 0.0), active_s)
    return True, wall, None


def default_max_inflight() -> int:
    """Hybrid backlog cap shared by the consensus engines (simplex /
    duplex / codec): dispatches in flight at or beyond it route to the
    native f64 host engine instead of queueing behind the link. Explicit
    ``FGUMI_TPU_MAX_INFLIGHT`` wins (``0`` = always host); the default
    tracks the feeder's pipeline depth + 1 (``depth`` uploads in flight
    plus one packed in its queue)."""
    import os

    env_cap = os.environ.get("FGUMI_TPU_MAX_INFLIGHT", "").strip()
    if env_cap:
        try:
            return int(env_cap)
        except ValueError:
            log.warning("FGUMI_TPU_MAX_INFLIGHT=%r is not an integer; "
                        "using the default", env_cap)
    return DEVICE_FEEDER.depth + 1


def device_backlogged(max_inflight: int) -> bool:
    """True when the upload pipeline already holds ``max_inflight``
    dispatches — the one backlog test behind every hybrid engine's
    route-to-host-engine decision (simplex / duplex / codec)."""
    return DEVICE_STATS.in_flight_count() >= max_inflight


# ---------------------------------------------------------------------------
# Device resilience: bounded retry on transient XLA failures, batch halving
# on RESOURCE_EXHAUSTED, final whole-batch fallback to the native f64 host
# engine. All three preserve output bytes exactly — the host engine and the
# device+oracle path share the same integer-exactness contract — so a flaky
# device degrades throughput, never correctness (docs/resilience.md).
# ---------------------------------------------------------------------------

def _runtime_status(exc):
    """The leading XLA status code of a ``jax.errors.JaxRuntimeError``
    ("UNAVAILABLE", "INTERNAL", ...), or None for any other exception —
    including every Python-level tracing/lowering error, which no retry
    or fallback may absorb."""
    import sys

    jax_mod = sys.modules.get("jax")
    if jax_mod is None or not isinstance(exc,
                                         jax_mod.errors.JaxRuntimeError):
        return None
    return str(exc).split(":", 1)[0].strip()


def _is_compile_failure(exc) -> bool:
    """XLA or Mosaic refused to compile the kernel. Whatever status code
    it arrives under (INTERNAL, UNKNOWN, even RESOURCE_EXHAUSTED for a
    VMEM overflow), it is a defect in the kernel, not device weather: it
    must end the run, never be retried or completed on the host engine."""
    s = str(exc).lower()
    return "compil" in s or "mosaic" in s


def _is_oom(exc) -> bool:
    """A run-time device out-of-memory (batch too big for device HBM):
    halve, don't retry — re-dispatching the same shape fails the same
    way. Injected faults carry the marker too (chaos tests)."""
    from ..utils.faults import InjectedFault

    if isinstance(exc, InjectedFault):
        return "RESOURCE_EXHAUSTED" in str(exc)
    return (_runtime_status(exc) == "RESOURCE_EXHAUSTED"
            and not _is_compile_failure(exc))


# XLA status codes that a retry can plausibly fix: the runtime or the host
# link hiccuped, or the device was preempted. INTERNAL and UNKNOWN are NOT
# here — they are what compiler failures ("INTERNAL: Mosaic failed to
# compile") and chip faults arrive under, and INVALID_ARGUMENT-class
# failures are programming errors: all of those re-raise immediately.
_TRANSIENT_STATUS = frozenset(
    ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED", "CANCELLED"))


def _is_transient(exc) -> bool:
    from ..utils.faults import InjectedFault

    if isinstance(exc, InjectedFault):
        return not _is_oom(exc)
    return (_runtime_status(exc) in _TRANSIENT_STATUS
            and not _is_compile_failure(exc))


def _retry_budget():
    import os

    tries = max(int(os.environ.get("FGUMI_TPU_DEVICE_RETRIES", "3")), 0)
    base = float(os.environ.get("FGUMI_TPU_DEVICE_BACKOFF_S", "0.05"))
    return tries, base


def device_retry_call(fn, what: str = "dispatch"):
    """Run fn() (device upload + jit dispatch) with bounded exponential
    backoff on transient errors. Non-transient errors and OOM re-raise
    immediately (OOM is handled by batch splitting at resolve time). The
    device.dispatch fault point fires on every attempt, so chaos tests
    exercise exactly this loop."""
    from ..utils import faults

    # chaos point for the wedge class of failure (kind `hang`, stall via
    # FGUMI_TPU_FAULT_HANG_S): fires ONCE per dispatch, before the retry
    # loop, on whichever thread runs the dispatch — for the async paths
    # that is the feeder thread, exactly where a wedged device_put stalls,
    # so the deadline/breaker machinery is exercised end to end
    faults.fire("device.wedge")
    retries, delay = _retry_budget()
    for attempt in range(retries + 1):
        try:
            faults.fire("device.dispatch")
            # one span per attempt, on whichever thread runs the dispatch
            # (the caller for sync paths, fgumi-device-feeder for async)
            with span("device.dispatch", what=what, attempt=attempt):
                return fn()
        except BaseException as e:  # noqa: BLE001 - classified below
            if _is_oom(e) or not _is_transient(e) or attempt >= retries:
                raise
            DEVICE_STATS.add_retry()
            # the device runtime may have restarted under us; resident
            # constants died with it, so the retry re-uploads fresh
            CONST_CACHE.invalidate()
            log.warning("device %s failed (%s: %s); retry %d/%d in %.2fs",
                        what, type(e).__name__, e, attempt + 1, retries,
                        delay)
            time.sleep(delay)
            delay = min(delay * 2, 2.0)


class _DeadlineRunner:
    """Reusable helper threads for deadline-bounded calls into jax.

    ``jax.device_get`` (and, on a wedged runtime, even ``device_put``/jit
    dispatch) can block indefinitely, so a bounded call runs on a helper
    thread (with the caller's context, so scope-resolved stats land
    correctly) while the caller waits at most the deadline. Workers are
    kept on a free list between calls — the deadline default is *on*, so
    every hot-path fetch comes through here and must not pay a
    thread-create — and each concurrent call gets its own worker, so
    bounding adds no serialization. A worker that blows its deadline is
    simply not returned to the free list: it is left to die with the
    wedge (daemon thread), and the next call starts a fresh one."""

    def __init__(self, name: str, wait_span: str):
        self._name = name
        self._wait_span = wait_span  # the caller's wait for its helper
        self._lock = threading.Lock()
        self._free = []     # idle worker queues
        self._seq = 0

    def run(self, fn, deadline_s, what: str):
        if deadline_s is None:
            return fn()
        import contextvars
        import queue as _queue

        ctx = contextvars.copy_context()
        box = {}
        done = threading.Event()
        with self._lock:
            if self._free:
                q = self._free.pop()
            else:
                q = _queue.SimpleQueue()
                self._seq += 1
                name = f"{self._name}-{self._seq}"
                threading.Thread(target=self._loop, args=(q, name),
                                 name=name, daemon=True).start()
        q.put((ctx, fn, box, done))
        with span(self._wait_span, wait=True):
            in_time = done.wait(deadline_s)
        if not in_time:
            # wedged: the worker is abandoned with its call (never reused;
            # if the wedge ever clears it parks in q.get() forever)
            raise DeadlineExceeded(
                f"{what} did not complete within {deadline_s:.1f}s")
        with self._lock:
            self._free.append(q)
        if "exc" in box:
            raise box["exc"]
        return box["result"]

    @staticmethod
    def _loop(q, name):
        from ..observe.scope import name_os_thread

        name_os_thread(name)
        while True:
            ctx, fn, box, done = q.get()
            try:
                box["result"] = ctx.run(fn)
            except BaseException as e:  # noqa: BLE001 - relayed to waiter
                box["exc"] = e
            finally:
                done.set()


_FETCH_RUNNER = _DeadlineRunner("fgumi-device-fetch", "device.fetch_wait")
_DISPATCH_RUNNER = _DeadlineRunner("fgumi-device-dispatch",
                                   "device.dispatch_wait")


def _fetch_with_deadline(dev, deadline_s):
    """DEVICE_STATS.fetch(dev) bounded by ``deadline_s`` seconds (None =
    plain inline fetch); raises :class:`DeadlineExceeded` on expiry."""
    if deadline_s is None:
        return DEVICE_STATS.fetch(dev)
    return _FETCH_RUNNER.run(lambda: DEVICE_STATS.fetch(dev), deadline_s,
                             "device fetch")


def segments_flops(n_rows: int, length: int, num_segments: int) -> int:
    """Model FLOPs for one _segments_body execution (counting f32 mul/add):
    one_hot*valid mask (4) + delta*one_hot (4) + two segment_sum adds (8)
    per (row, position), ~40 epilogue flops per (segment, position)."""
    return n_rows * length * 16 + num_segments * length * 40


def _observation_terms(codes, quals, correct_tab, err_tab):
    """Per-observation lane one-hot + match-contribution delta.

    codes/quals: any shape. Returns one_hot (..., 4) f32 (zeroed at N/pad
    observations) and delta (...,) f32 — the shared per-observation math of
    both the uniform-R and ragged-segment reductions.
    """
    q_idx = jnp.minimum(quals, MAX_PHRED).astype(jnp.int32)
    delta_tab = correct_tab - err_tab  # (94,) f32, >= 0 for sane rates
    valid = codes != N_CODE
    one_hot = jax.nn.one_hot(jnp.minimum(codes, 3), 4, dtype=jnp.float32)
    one_hot = one_hot * valid[..., None].astype(jnp.float32)
    delta = jnp.where(valid, delta_tab[q_idx], 0.0)
    return one_hot, delta


def _reduce_contributions(codes, quals, correct_tab, err_tab):
    """Per-position match-contribution + observation-count reduction over reads.

    codes/quals: (..., R, L). Returns C (..., L, 4) f32 (lane match contributions),
    obs (..., L, 4) int32. N/pad codes contribute nothing (base_builder.rs:616-619).
    """
    one_hot, delta = _observation_terms(codes, quals, correct_tab, err_tab)
    # HIGHEST precision: the guard-band derivation assumes true f32 products;
    # TPU MXU default precision multiplies in bf16 (~2e-3 relative), which
    # would blow straight through an eps32-scale band undetected.
    contrib = jnp.einsum("...rl,...rlb->...lb", delta, one_hot,
                         precision=jax.lax.Precision.HIGHEST)
    obs = jnp.sum(one_hot, axis=-3).astype(jnp.int32)  # (..., L, 4)
    return contrib, obs


def _pack_result(winner, qual, suspect):
    """The (qual | winner<<7 | suspect<<10) uint16 wire word (see
    _unpack_device_result for the inverse)."""
    packed = qual | (winner << 7) | (suspect.astype(jnp.int32) << 10)
    return packed.astype(jnp.uint16)


def _pack_result_split(winner, qual, suspect, out_segments):
    """Split packed result at 1.25 B/position, sliced to out_segments rows.

    qs (out_segments, L) uint8 = qual (7b) | suspect (1b); wp
    (out_segments, L/4) uint8 = winner 2-bit packed 4-per-byte along L.
    The N winner (tie or no-call) is NOT encoded: tie positions carry the
    suspect bit (the host's exact recompute overwrites them) and no-call
    positions are recomputed on host as depth==0 from the codes it already
    holds — so 2 bits per winner suffice and the fetch drops from 2 B to
    1.25 B per position (VERDICT r4 item 4)."""
    qs = (qual | (suspect.astype(jnp.int32) << 7))[:out_segments]
    w4 = jnp.where(winner > 3, 0, winner)[:out_segments]
    w4 = w4.reshape(out_segments, -1, 4)
    wp = w4[..., 0] | (w4[..., 1] << 2) | (w4[..., 2] << 4) | (w4[..., 3] << 6)
    return qs.astype(jnp.uint8), wp.astype(jnp.uint8)


def unpack_result_split(qs: np.ndarray, wp: np.ndarray, J: int):
    """(winner 0..3, qual, suspect) host arrays from a split packed fetch."""
    qs = qs[:J]
    qual = (qs & 0x7F).astype(np.uint8)
    suspect = (qs >> 7).astype(bool)
    shifts = np.array([0, 2, 4, 6], dtype=np.uint8)
    w4 = (wp[:J, :, None] >> shifts) & 3
    winner = w4.reshape(J, -1).astype(np.uint8)
    return winner, qual, suspect


def _call_epilogue(contrib, obs, ln_error_pre_umi):
    """Winner/tie/posterior/Phred epilogue over (..., L, 4) lane contributions.

    Returns winner (int32, N_CODE for no-call), qual (int32), depth, errors (int32),
    suspect (bool): positions requiring f64 host recomputation.
    """
    depth = jnp.sum(obs, axis=-1)
    max_c = jnp.max(contrib, axis=-1)
    winner = jnp.argmax(contrib, axis=-1).astype(jnp.int32)
    lane_is_winner = jax.nn.one_hot(winner, 4, dtype=jnp.bool_)

    # Loser-gap frame: s = sum over losing lanes of exp(-(max - C_b)).
    gaps = max_c[..., None] - contrib  # >= 0; 0 at the winner lane
    exp_neg = jnp.where(lane_is_winner, 0.0, jnp.exp(-gaps))
    s = jnp.sum(exp_neg, axis=-1)
    # ln consensus error = ln(s / (1 + s)); s == 0 underflows to -inf (cap region).
    ln_cons_err = jnp.log(s) - jnp.log1p(s)

    # two-trials combination with the pre-UMI prior (phred.rs:248-267), f32.
    pre = jnp.float32(ln_error_pre_umi)
    hi = jnp.maximum(pre, ln_cons_err)
    lo = jnp.minimum(pre, ln_cons_err)
    diff = hi - lo
    quick = ~(diff < 6.0)  # catches NaN (lo = -inf) as quick
    safe_diff = jnp.where(quick, 6.0, diff)
    term1 = hi + jnp.log1p(jnp.exp(-safe_diff))  # ln(exp(hi) + exp(lo))
    term2_minus_term1 = _LN_4_3_F32 + lo - jnp.log1p(jnp.exp(-safe_diff))
    full = term1 + jnp.log1p(-jnp.exp(jnp.minimum(term2_minus_term1, -_EPS32)))
    ln_final = jnp.where(quick, hi, full)

    phred_f = -ln_final * _PHRED_PER_LN + 0.001
    qual = jnp.clip(jnp.floor(phred_f), MIN_PHRED, MAX_PHRED).astype(jnp.int32)

    # ---- suspect guard band (derivation in the module-level comment) ----
    eps_gap = _EPS32 * (depth.astype(jnp.float32) + 2.0) * (1.0 + max_c)
    # winner margin: distance between best and second-best lane contribution
    second = jnp.max(jnp.where(lane_is_winner, -jnp.inf, contrib), axis=-1)
    margin = max_c - second
    tie_suspect = margin <= (2.0 * eps_gap + _TIE_GUARD_FLOOR)
    # Phred rounding proximity. The ln_final error is ~eps_gap on the consensus-error
    # path; when the quick path selected the pre-UMI constant the result is exact.
    took_pre = quick & (ln_cons_err < pre)
    err_phred = jnp.where(took_pre, 0.0, _PHRED_PER_LN * 2.0 * eps_gap)
    frac = phred_f - jnp.floor(phred_f)
    near_boundary = jnp.minimum(frac, 1.0 - frac) <= (err_phred + _QUAL_GUARD_FLOOR)
    clamped = (phred_f <= MIN_PHRED) | (phred_f >= MAX_PHRED + 0.5)
    # The quick-vs-full two-trials branch (diff >= 6) is decided in f32 here but f64
    # in the oracle; the formulas differ by up to ln(1+e^-6) ≈ 0.0215 Phred at the
    # boundary, so positions near it must fall back.
    branch_suspect = jnp.abs(diff - 6.0) <= (2.0 * eps_gap + 1e-4)
    # Non-finite contributions (a Q0 observation's -inf table entry times the one-hot
    # zero gives NaN through the einsum) poison every comparison below into False;
    # force those positions to the exact host path.
    nonfinite = ~jnp.isfinite(max_c)
    suspect = tie_suspect | branch_suspect | nonfinite | (near_boundary & ~clamped)

    no_call = depth == 0
    winner = jnp.where(no_call | tie_suspect, N_CODE, winner)
    qual = jnp.where(no_call | tie_suspect, MIN_PHRED, qual)
    suspect = suspect & ~no_call

    winner_obs = jnp.sum(obs * lane_is_winner.astype(jnp.int32), axis=-1)
    errors = depth - jnp.where(winner == N_CODE, 0, winner_obs)
    return winner, qual, depth, errors, suspect


@_lazy_jit
def _consensus_batch_jit(codes, quals, correct_tab, err_tab, ln_error_pre_umi):
    contrib, obs = _reduce_contributions(codes, quals, correct_tab, err_tab)
    return _call_epilogue(contrib, obs, ln_error_pre_umi)


def _segments_body(codes, quals, seg_ids, correct_tab, err_tab,
                   ln_error_pre_umi, num_segments):
    """Ragged-family consensus body: dense (N, L) read rows + sorted segment
    ids -> packed (num_segments, L) uint16. Shared by the single-device jit
    and the shard_map-per-device sharded variant."""
    one_hot, delta = _observation_terms(codes, quals, correct_tab, err_tab)
    row_contrib = delta[..., None] * one_hot  # (N, L, 4)
    contrib = jax.ops.segment_sum(row_contrib, seg_ids,
                                  num_segments=num_segments,
                                  indices_are_sorted=True)
    obs = jax.ops.segment_sum(one_hot, seg_ids, num_segments=num_segments,
                              indices_are_sorted=True).astype(jnp.int32)
    winner, qual, _depth, _errors, suspect = _call_epilogue(
        contrib, obs, ln_error_pre_umi)
    return _pack_result(winner, qual, suspect)


# ---------------------------------------------------------------------------
# 1-byte/position wire format: code (2b) | qual-dictionary index (6b), with
# index 63 reserved for invalid (N base or pad row). Sequencers emit a small
# set of distinct quality values (2-16 typical; overlap correction sums and
# differences push it to ~60), so a per-dispatch dictionary of <=63
# f64-derived f32 delta entries re-expresses the (94,) quality tables
# losslessly — identical f32 table values, just re-indexed — and HALVES
# upload bytes vs the 2-byte codes+quals layout.
# Numerics and the guard band are unchanged. Batches with >63 distinct
# quals fall back to 1.25 B/position (2-bit packed codes + qual bytes).
# ---------------------------------------------------------------------------
WIRE_INVALID = np.uint8(0xFC)  # qidx 63, code 0
QUAL_INVALID = np.uint8(127)  # fallback-layout qual sentinel for N/pad


def _wire_terms(wire, dict_tab):
    """Per-observation lane one-hot + delta from the 1-byte wire format.

    dict_tab: (64,) f32 delta values with dict_tab[63] == 0, so invalid
    positions contribute nothing without a separate select."""
    qidx = (wire >> 2).astype(jnp.int32)
    valid = qidx != 63
    one_hot = jax.nn.one_hot(wire & 3, 4, dtype=jnp.float32)
    one_hot = one_hot * valid[..., None].astype(jnp.float32)
    delta = dict_tab[qidx]
    return one_hot, delta


def _wire_epilogue(wire, seg_ids, dict_tab, ln_error_pre_umi, num_segments):
    """Shared reduction+epilogue of every wire-layout segment kernel:
    (N, L) wire rows -> (winner, qual, depth, errors, suspect)."""
    one_hot, delta = _wire_terms(wire, dict_tab)
    row_contrib = delta[..., None] * one_hot
    contrib = jax.ops.segment_sum(row_contrib, seg_ids,
                                  num_segments=num_segments,
                                  indices_are_sorted=True)
    obs = jax.ops.segment_sum(one_hot, seg_ids, num_segments=num_segments,
                              indices_are_sorted=True).astype(jnp.int32)
    return _call_epilogue(contrib, obs, ln_error_pre_umi)


def _packed2_terms(codes_packed, quals, correct_tab, err_tab):
    """Per-observation lane one-hot + delta from the 1.25 B/position
    fallback layout (>63 distinct quals): 2-bit packed codes + sentinel
    quals, device-side unpack is a shift-and-mask. The one copy of this
    math — shared by the single-device epilogue and the shard_map mesh
    kernel so the two can never drift apart."""
    shifts = jnp.arange(0, 8, 2, dtype=jnp.uint8)
    c4 = (codes_packed[..., None] >> shifts) & 3
    codes = c4.reshape(codes_packed.shape[0], -1)
    valid = quals != QUAL_INVALID
    q_idx = jnp.minimum(quals, MAX_PHRED).astype(jnp.int32)
    delta_tab = correct_tab - err_tab
    one_hot = jax.nn.one_hot(codes, 4, dtype=jnp.float32)
    one_hot = one_hot * valid[..., None].astype(jnp.float32)
    delta = jnp.where(valid, delta_tab[q_idx], 0.0)
    return one_hot, delta


def _packed2_epilogue(codes_packed, quals, seg_ids, correct_tab, err_tab,
                      ln_error_pre_umi, num_segments):
    """Shared reduction+epilogue of the 1.25 B/position fallback layout."""
    one_hot, delta = _packed2_terms(codes_packed, quals, correct_tab,
                                    err_tab)
    row_contrib = delta[..., None] * one_hot
    contrib = jax.ops.segment_sum(row_contrib, seg_ids,
                                  num_segments=num_segments,
                                  indices_are_sorted=True)
    obs = jax.ops.segment_sum(one_hot, seg_ids, num_segments=num_segments,
                              indices_are_sorted=True).astype(jnp.int32)
    return _call_epilogue(contrib, obs, ln_error_pre_umi)


def _wire_split_fn(wire, seg_ids, dict_tab, ln_error_pre_umi,
                   num_segments, out_segments):
    """Ragged-family consensus over the 1-byte wire layout with split packed
    output: (N, L) wire rows -> (out_segments, L) qs + (out_segments, L/4) wp.
    """
    winner, qual, _depth, _errors, suspect = _wire_epilogue(
        wire, seg_ids, dict_tab, ln_error_pre_umi, num_segments)
    return _pack_result_split(winner, qual, suspect, out_segments)


def _wire_full_fn(wire, seg_ids, dict_tab, ln_error_pre_umi, num_segments,
                  out_segments):
    """Full-column wire kernel: winner/qual AND depth/errors per column.

    The device computes the integer depth/error counts it already holds as
    lane observation sums (exact in f32 below 2^24 observations), so the
    host never re-walks the dense rows at resolve time — the family's data
    crosses the link once, as wire bytes. depth/errors fetch as uint16
    (+4 B/column); callers gate on max family size < 65536 (ROADMAP item 1,
    round 6)."""
    winner, qual, depth, errors, suspect = _wire_epilogue(
        wire, seg_ids, dict_tab, ln_error_pre_umi, num_segments)
    qs, wp = _pack_result_split(winner, qual, suspect, out_segments)
    return (qs, wp, depth[:out_segments].astype(jnp.uint16),
            errors[:out_segments].astype(jnp.uint16))


_W_STATIC = ("num_segments", "out_segments")
_consensus_segments_wire_jit = _lazy_jit(
    static_argnames=_W_STATIC)(_wire_split_fn)
_consensus_segments_wire_full_jit = _lazy_jit(
    static_argnames=_W_STATIC)(_wire_full_fn)


_I16_MAX = 32767  # fgbio Short tag clamp (vanilla.py I16_MAX twin)


class ResidentHandles:
    """Device-resident stage-1 outputs kept for a fused follow-up stage.

    NOT a jax pytree on purpose: the feeder's fetch-overlap pass
    (copy_to_host_async over tree leaves) must never start copying these —
    they exist precisely so their bytes never cross the link.

    Accounting (ISSUE 11 satellite): construction registers the byte
    total with DeviceStats (``device.resident_bytes`` gauge + peak) AND
    the device feeder's DynamicBudget byte gate, and every consumer calls
    :meth:`release` when the fused stage has used (or abandoned) the
    arrays — the fused filter's gather/degrade paths and the feeder's
    late-result discard all release, so a wedge cannot leak the
    accounting. Today the one producer is the fused consensus→filter
    kernel (consensus/device_filter.py)."""

    __slots__ = ("arrays", "nbytes", "_released")

    def __init__(self, arrays):
        self.arrays = arrays
        self.nbytes = sum(int(getattr(a, "nbytes", 0) or 0)
                          for a in arrays)
        self._released = False
        if self.nbytes:
            DEVICE_STATS.add_resident_bytes(self.nbytes)
            DEVICE_FEEDER.add_resident_bytes(self.nbytes)

    def release(self):
        """Drop the device arrays + their byte accounting (idempotent)."""
        if self._released:
            return
        self._released = True
        self.arrays = None
        if self.nbytes:
            DEVICE_STATS.release_resident_bytes(self.nbytes)
            DEVICE_FEEDER.release_resident_bytes(self.nbytes)


def _release_residents(result):
    """Release every ResidentHandles inside a discarded dispatch result
    (the feeder's late-completion path after an abandon)."""
    if isinstance(result, ResidentHandles):
        result.release()
    elif isinstance(result, (tuple, list)):
        for item in result:
            _release_residents(item)


def _wire_filter_fn(wire, seg_ids, dict_tab, ln_error_pre_umi, min_reads_c,
                    min_qual_c, lens, f_min_reads, f_emin_tab, f_min_base_q,
                    f_per_base, num_segments, out_segments):
    """Fused consensus→filter wire kernel (ISSUE 11 tentpole).

    One dispatch computes the full consensus columns, applies the
    consensus thresholds (apply_consensus_thresholds twin) AND the filter
    library's simplex per-base masks (mask_bases twin) over them, and
    reduces everything the read-level verdicts need to a 7-int32 stats row
    per read — the only thing fetched home by default. The masked output
    columns (fb/fq), the raw depth/error columns, and the pre-threshold
    packed winner/qual/suspect words stay DEVICE-RESIDENT for the
    survivors-only gather (:func:`ConsensusKernel.filter_gather_filtered` /
    :meth:`ConsensusKernel.filter_resolve_suspect_rows`).

    Exactness: every per-base decision here is integer arithmetic —
    ``f_emin_tab`` (consensus/filter.base_error_rate_table) reformulates
    the host's f64 error-rate division as a threshold-integer gather, so
    the device mask can never disagree with ``mask_bases``. Stats columns:
    [max d16, sum d16, sum e16, sum qual, N-after-mask, newly-masked,
    any-suspect] with every reduction restricted to positions < lens."""
    winner, qual, depth, errors, suspect = _wire_epilogue(
        wire, seg_ids, dict_tab, ln_error_pre_umi, num_segments)
    qs, wp = _pack_result_split(winner, qual, suspect, out_segments)
    w = winner[:out_segments]
    q = qual[:out_segments]
    d = depth[:out_segments]
    e = errors[:out_segments]
    sus = suspect[:out_segments]
    low_depth = d < min_reads_c
    low_qual = q < min_qual_c
    tb = jnp.where(low_depth | low_qual, N_CODE, w)
    tq = jnp.where(low_depth, 0, jnp.where(low_qual, MIN_PHRED, q))
    L = wire.shape[1]
    in_len = jnp.arange(L, dtype=jnp.int32)[None, :] < lens[:, None]
    d16 = jnp.minimum(d, _I16_MAX)
    e16 = jnp.minimum(e, _I16_MAX)
    fmask = (f_per_base > 0) & ((d16 < f_min_reads)
                                | ((d16 > 0) & (e16 >= f_emin_tab[d16])))
    fmask = fmask | ((f_min_base_q >= 0) & (tq < f_min_base_q))
    fmask = fmask & in_len
    fb = jnp.where(fmask, N_CODE, tb)
    fq = jnp.where(fmask, MIN_PHRED, tq)
    z32 = jnp.int32(0)
    stats = jnp.stack([
        jnp.max(jnp.where(in_len, d16, z32), axis=1),
        jnp.sum(jnp.where(in_len, d16, z32), axis=1),
        jnp.sum(jnp.where(in_len, e16, z32), axis=1),
        jnp.sum(jnp.where(in_len, tq, z32), axis=1),
        jnp.sum((in_len & (fb == N_CODE)).astype(jnp.int32), axis=1),
        jnp.sum((fmask & (tb != N_CODE)).astype(jnp.int32), axis=1),
        jnp.any(sus & in_len, axis=1).astype(jnp.int32),
    ], axis=1).astype(jnp.int32)
    return (stats, fb.astype(jnp.uint8), fq.astype(jnp.uint8),
            d.astype(jnp.uint16), e.astype(jnp.uint16), qs, wp)


_consensus_segments_wire_filter_jit = _lazy_jit(
    static_argnames=_W_STATIC)(_wire_filter_fn)


@_lazy_jit(static_argnames=("out_rows",))
def _filter_gather_jit(fb, fq, d16, e16, idx, out_rows):
    """Survivors-only gather over the fused filter kernel's resident
    columns: only the kept reads' masked bases/quals + depth/errors cross
    the link (6 B/position instead of 5.25 B/position for everyone)."""
    return (fb[idx][:out_rows], fq[idx][:out_rows],
            d16[idx][:out_rows], e16[idx][:out_rows])


@_lazy_jit(static_argnames=("out_rows",))
def _filter_gather_raw_jit(qs, wp, d16, e16, idx, out_rows):
    """Raw-column gather for suspect rows: the pre-threshold packed
    winner/qual/suspect words + depth/errors, exactly what the ordinary
    host completion (unpack + oracle patch) consumes."""
    return (qs[idx][:out_rows], wp[idx][:out_rows],
            d16[idx][:out_rows], e16[idx][:out_rows])


def _packed2_split_fn(codes_packed, quals, seg_ids, correct_tab,
                      err_tab, ln_error_pre_umi, num_segments,
                      out_segments):
    """1.25 B/position fallback of the wire dispatch (batches with >63
    distinct quals): 2-bit packed codes + sentinel quals, split packed
    output + fetch slice."""
    winner, qual, _depth, _errors, suspect = _packed2_epilogue(
        codes_packed, quals, seg_ids, correct_tab, err_tab,
        ln_error_pre_umi, num_segments)
    return _pack_result_split(winner, qual, suspect, out_segments)


def _packed2_full_fn(codes_packed, quals, seg_ids, correct_tab, err_tab,
                     ln_error_pre_umi, num_segments, out_segments):
    """Full-column variant of the >63-distinct-quals fallback: same
    on-device depth/error counts as the full wire kernel."""
    winner, qual, depth, errors, suspect = _packed2_epilogue(
        codes_packed, quals, seg_ids, correct_tab, err_tab,
        ln_error_pre_umi, num_segments)
    qs, wp = _pack_result_split(winner, qual, suspect, out_segments)
    return (qs, wp, depth[:out_segments].astype(jnp.uint16),
            errors[:out_segments].astype(jnp.uint16))


_consensus_segments_packed2_jit = _lazy_jit(
    static_argnames=_W_STATIC)(_packed2_split_fn)
_consensus_segments_packed2_full_jit = _lazy_jit(
    static_argnames=_W_STATIC)(_packed2_full_fn)


def _wire_dict(vals: np.ndarray, delta94: np.ndarray) -> np.ndarray:
    """The wire's 64-entry dictionary: delta of each distinct qual, in
    ascending qual order; unused entries (and qidx 63) are 0."""
    dict64 = np.zeros(64, dtype=np.float32)
    dict64[: len(vals)] = delta94[np.minimum(vals, MAX_PHRED)]
    return dict64


def build_wire(codes2d: np.ndarray, quals2d: np.ndarray, delta94: np.ndarray,
               out: np.ndarray = None):
    """Host-side wire build: (wire (N, L) uint8, dict64 (64,) f32) or None
    when the batch has more than 63 distinct quality values (fall back to
    the packed-codes layout). delta94 = correct_f32 - err_f32 per Phred.
    ``out``: optional preallocated (N, L) uint8 staging buffer (the
    feeder's recycled pool) filled in place instead of minting a fresh
    array per dispatch.

    One native pass that mints nothing where the library is loaded
    (native/batch.build_wire); the numpy body below returns the same bytes
    on a host without it and is the tests' oracle. ``engine.pack``'s
    counters ``wire_native`` / ``wire_numpy`` say which one ran."""
    from ..native import batch as nb

    if nb.wire_inputs_ok(codes2d, quals2d):
        count("engine.pack", "wire_native")
        wire = out if out is not None else np.empty(codes2d.shape, np.uint8)
        vals = nb.build_wire(codes2d, quals2d, None, *codes2d.shape, wire)
        return None if vals is None else (wire, _wire_dict(vals, delta94))
    count("engine.pack", "wire_numpy")
    hist = np.bincount(quals2d.ravel(), minlength=256)
    vals = np.nonzero(hist)[0]
    if len(vals) > 63:
        return None
    lut = np.full(256, 63, dtype=np.uint8)
    lut[vals] = np.arange(len(vals), dtype=np.uint8)
    if out is not None:
        np.take(lut, quals2d, out=out)
        np.left_shift(out, 2, out=out)
        np.bitwise_or(out, np.minimum(codes2d, 3), out=out)
        wire = out
    else:
        wire = (lut[quals2d] << 2) | np.minimum(codes2d, 3)
    wire[codes2d == N_CODE] = WIRE_INVALID
    return wire, _wire_dict(vals, delta94)


def pack_codes2(codes2d: np.ndarray, quals2d: np.ndarray):
    """Fallback 1.25 B/position layout: 2-bit codes packed 4-per-byte along
    L plus qual bytes with QUAL_INVALID marking N/pad positions (quals are
    irrelevant there — the kernel zeroes their contribution)."""
    c = np.minimum(codes2d, 3).astype(np.uint8)
    N, L = c.shape
    c4 = c.reshape(N, L // 4, 4)
    cp = (c4[..., 0] | (c4[..., 1] << 2) | (c4[..., 2] << 4)
          | (c4[..., 3] << 6))
    q = np.where(codes2d == N_CODE, QUAL_INVALID, quals2d).astype(np.uint8)
    return np.ascontiguousarray(cp), q


@_lazy_jit(static_argnames=("num_segments",))
def _consensus_segments_packed_jit(codes, quals, seg_ids, correct_tab,
                                   err_tab, ln_error_pre_umi, num_segments):
    """Ragged-family variant: dense (N, L) read rows + sorted segment ids.

    One execution covers every family of a record batch regardless of family
    size — the per-execution launch overhead is paid once, so the hot path
    runs exactly one dispatch and one uint16 fetch per batch. Pad rows are
    all-N (zero contribution) and may use any in-range id.
    """
    return _segments_body(codes, quals, seg_ids, correct_tab, err_tab,
                          ln_error_pre_umi, num_segments)


@_lazy_jit(static_argnames=("num_segments", "mesh"))
def _consensus_segments_sharded_jit(codes, quals, seg_ids, correct_tab,
                                    err_tab, ln_error_pre_umi, num_segments,
                                    mesh):
    """dp-sharded ragged variant: (dp, N, L) rows -> (dp, num_segments, L).

    Families are embarrassingly parallel (SURVEY §5.7), so each device runs
    the segment body on its own contiguous slice of families — data parallel
    over the dp mesh axis with zero collectives in the hot path; the host
    splits jobs into balanced contiguous shards (consensus/fast.py).
    """
    from jax.sharding import PartitionSpec as P

    def local(c, q, s):
        return _segments_body(c[0], q[0], s[0], correct_tab, err_tab,
                              ln_error_pre_umi, num_segments)[None]

    # shard the leading axis over every mesh axis (a dp-only mesh has sp=1)
    spec = P(tuple(mesh.axis_names))
    mapped = jax.shard_map(local, mesh=mesh,
                           in_specs=(spec, spec, spec), out_specs=spec)
    return mapped(codes, quals, seg_ids)


@_lazy_jit(static_argnames=("num_segments", "mesh"))
def _consensus_segments_dp_sp_jit(codes, quals, seg_ids, correct_tab,
                                  err_tab, ln_error_pre_umi, num_segments,
                                  mesh):
    """(dp, sp) ragged variant: (dp, sp, N, L) rows -> (dp, num_segments, L).

    The read axis shards over sp: each sp rank segment-sums its local rows'
    contributions, one psum over "sp" combines them (the only collective in
    the hot path, riding ICI — parallel/mesh.py design note), and the
    epilogue runs replicated. Segments may span sp chunk boundaries freely:
    partial sums are exact under addition. This is the production analog of
    the uniform-R sharded_consensus_fn, for the dense segment layout the
    fast engines actually ship (VERDICT r2 weakness 5)."""
    from jax.sharding import PartitionSpec as P

    def local(c, q, s):
        c, q, s = c[0, 0], q[0, 0], s[0, 0]
        one_hot, delta = _observation_terms(c, q, correct_tab, err_tab)
        row_contrib = delta[..., None] * one_hot
        contrib = jax.ops.segment_sum(row_contrib, s,
                                      num_segments=num_segments,
                                      indices_are_sorted=True)
        obs = jax.ops.segment_sum(one_hot, s, num_segments=num_segments,
                                  indices_are_sorted=True)
        contrib = jax.lax.psum(contrib, "sp")
        obs = jax.lax.psum(obs, "sp").astype(jnp.int32)
        winner, qual, _depth, _errors, suspect = _call_epilogue(
            contrib, obs, ln_error_pre_umi)
        return _pack_result(winner, qual, suspect)[None]

    spec = P("dp", "sp")
    mapped = jax.shard_map(local, mesh=mesh,
                           in_specs=(spec, spec, spec),
                           out_specs=P("dp"))
    return mapped(codes, quals, seg_ids)


# ---------------------------------------------------------------------------
# Production mesh compile path (ISSUE 10): shard_map-wrapped variants of the
# full-column wire kernels with explicit PartitionSpec rules. The host packs
# the dense row layout into dp x sp chunks (pad_segments_mesh); each (d, s)
# shard segment-sums its local rows' contributions over its dp shard's LOCAL
# segment ids, one psum over "sp" combines the read-axis partials (the only
# collective in the hot path), and the epilogue + wire packing run per dp
# shard. Outputs concatenate over "dp" to the (dp * F_loc, ...) global
# layout; the host's gather index (mesh_gather) restores family order at
# resolve time. A 1-device mesh never reaches these: the callers fall back
# to the single-device jit path (SNIPPETS [3]'s mesh-size-aware compile).
# ---------------------------------------------------------------------------

def _wire_mesh_local(wire, seg_ids, dict_tab, ln_error_pre_umi, num_local):
    """Per-shard body of the mesh wire kernels: local segment reduction,
    sp psum combine, shared epilogue."""
    one_hot, delta = _wire_terms(wire, dict_tab)
    row_contrib = delta[..., None] * one_hot
    contrib = jax.ops.segment_sum(row_contrib, seg_ids,
                                  num_segments=num_local,
                                  indices_are_sorted=True)
    obs = jax.ops.segment_sum(one_hot, seg_ids, num_segments=num_local,
                              indices_are_sorted=True)
    contrib = jax.lax.psum(contrib, "sp")
    obs = jax.lax.psum(obs, "sp").astype(jnp.int32)
    return _call_epilogue(contrib, obs, ln_error_pre_umi)


@_lazy_jit(static_argnames=("num_local", "mesh", "full"))
def _consensus_segments_wire_mesh_jit(wire, seg_ids, dict_tab,
                                      ln_error_pre_umi, num_local, mesh,
                                      full):
    """Mesh variant of _consensus_segments_wire_{jit,full_jit}.

    wire/seg_ids: (dp * sp * N_chunk, L) / (dp * sp * N_chunk,) in the
    chunked global layout (pad_segments_mesh), row axis sharded over every
    mesh axis. Returns (dp * F_loc, ...) outputs sharded along dp."""
    from jax.sharding import PartitionSpec as P

    rows = P(mesh.axis_names)
    out = P("dp")

    def local(w, s):
        winner, qual, depth, errors, suspect = _wire_mesh_local(
            w, s, dict_tab, ln_error_pre_umi, num_local)
        qs, wp = _pack_result_split(winner, qual, suspect, num_local)
        if full:
            return (qs, wp, depth.astype(jnp.uint16),
                    errors.astype(jnp.uint16))
        return qs, wp

    mapped = jax.shard_map(local, mesh=mesh, in_specs=(rows, rows),
                           out_specs=(out,) * (4 if full else 2))
    return mapped(wire, seg_ids)


@_lazy_jit(static_argnames=("num_local", "mesh", "full"))
def _consensus_segments_packed2_mesh_jit(codes_packed, quals, seg_ids,
                                         correct_tab, err_tab,
                                         ln_error_pre_umi, num_local, mesh,
                                         full):
    """Mesh variant of the 1.25 B/position >63-distinct-quals fallback
    (_consensus_segments_packed2_{jit,full_jit}): same chunked row layout,
    2-bit packed codes + sentinel quals sharded over every mesh axis."""
    from jax.sharding import PartitionSpec as P

    rows = P(mesh.axis_names)
    out = P("dp")

    def local(cp, q, s):
        one_hot, delta = _packed2_terms(cp, q, correct_tab, err_tab)
        row_contrib = delta[..., None] * one_hot
        contrib = jax.ops.segment_sum(row_contrib, s,
                                      num_segments=num_local,
                                      indices_are_sorted=True)
        obs = jax.ops.segment_sum(one_hot, s, num_segments=num_local,
                                  indices_are_sorted=True)
        contrib = jax.lax.psum(contrib, "sp")
        obs = jax.lax.psum(obs, "sp").astype(jnp.int32)
        winner, qual, depth, errors, suspect = _call_epilogue(
            contrib, obs, ln_error_pre_umi)
        qs, wp = _pack_result_split(winner, qual, suspect, num_local)
        if full:
            return (qs, wp, depth.astype(jnp.uint16),
                    errors.astype(jnp.uint16))
        return qs, wp

    mapped = jax.shard_map(local, mesh=mesh,
                           in_specs=(rows, rows, rows),
                           out_specs=(out,) * (4 if full else 2))
    return mapped(codes_packed, quals, seg_ids)


@_lazy_jit
def _consensus_batch_packed_jit(codes, quals, correct_tab, err_tab,
                                ln_error_pre_umi):
    """Packed variant: one (F, L) uint16 output, qual | winner<<7 | suspect<<10.

    Fetched bytes are host time blocked on the link, so the device returns
    2 bytes/position — only what the host cannot cheaply recompute: depth
    and errors are pure integer counts over the uint8 codes the host already
    holds (ConsensusKernel._host_counts), and qual (7 bits), winner
    (3 bits), suspect (1 bit) share one uint16.
    """
    winner, qual, _depth, _errors, suspect = _consensus_batch_jit(
        codes, quals, correct_tab, err_tab, ln_error_pre_umi)
    return _pack_result(winner, qual, suspect)


def _pad_rows(n: int) -> int:
    """Row-count bucket: smallest shape-registry ladder value >= n.

    The geometric ladder (ops/datapath.py, default x1.0625 steps aligned
    to 16, configurable via --shape-buckets / FGUMI_TPU_SHAPE_BUCKETS)
    replaces the old per-octave pow2-fraction scheme: waste is bounded by
    one ladder step (<= 6.25% worst case, ~3% expected, vs 41%/25%/12.5%
    at the old octave bottoms), the vocabulary of XLA row shapes is fixed
    per process AND per fleet — every run quantizes to the same ladder,
    so the persistent compile cache hits across runs instead of each
    run's batch sizes minting private shapes (VERDICT r4 item 5,
    BENCH_r05 padding_waste 7-10%).
    """
    return SHAPE_REGISTRY.bucket_rows(n)


def _pad_out_segments(j: int, f_pad: int) -> int:
    """Fetch-slice bucket for the real segment count: multiple of f_pad/8.

    segment_sum still runs over the bucketed f_pad, but only the first
    j-rounded-up segments cross the link — the padded tail was up to half
    the fetched bytes (VERDICT r4 items 4/5). <=8 slice shapes per f_pad
    keeps the jit vocabulary bounded."""
    m = max(f_pad // 8, 1)
    return min(-(-j // m) * m, f_pad)


def pad_segments(codes2d: np.ndarray, quals2d: np.ndarray,
                 counts: np.ndarray):
    """Bucket-pad a dense (N, L) row layout for device_call_segments.

    Returns (codes_dev, quals_dev, seg_ids, starts, num_segments): rows pad
    to the next shape-registry ladder bucket (_pad_rows) with all-N no-op
    rows carrying the LAST real segment's id (keeps seg_ids sorted without
    growing num_segments — kernel pad invariant), and num_segments pads to
    the registry's segment ladder so the XLA shape vocabulary stays tiny
    under the persistent compile cache. Shared by the fast simplex engine
    and the classic callers (VERDICT r2: one copy of this subtle pad
    logic).
    """
    counts = np.asarray(counts, dtype=np.int64)
    starts = np.concatenate(([0], np.cumsum(counts)))
    N = int(starts[-1])
    J = len(counts)
    N_pad = _pad_rows(N)
    F_pad = SHAPE_REGISTRY.bucket_segments(J)
    seg_ids = np.repeat(np.arange(J, dtype=np.int32), counts)
    DEVICE_STATS.add_pad(N, N_pad)
    if N_pad != N:
        L = codes2d.shape[1]
        pad_c = np.full((N_pad - N, L), N_CODE, dtype=np.uint8)
        pad_q = np.zeros((N_pad - N, L), dtype=np.uint8)
        codes_dev = np.concatenate([codes2d[:N], pad_c])
        quals_dev = np.concatenate([quals2d[:N], pad_q])
        seg_ids = np.concatenate(
            [seg_ids, np.full(N_pad - N, J - 1, dtype=np.int32)])
    else:
        codes_dev, quals_dev = codes2d, quals2d
    return codes_dev, quals_dev, seg_ids, starts, F_pad


def _gather_layout(counts: np.ndarray):
    """Row bookkeeping of the gathered device layout (pad_segments'
    invariants): (seg_ids (N_pad,), starts (J+1,), F_pad, N, N_pad)."""
    counts = np.asarray(counts, dtype=np.int64)
    starts = np.concatenate(([0], np.cumsum(counts)))
    N = int(starts[-1])
    J = len(counts)
    N_pad = _pad_rows(N)
    F_pad = SHAPE_REGISTRY.bucket_segments(J)
    DEVICE_STATS.add_pad(N, N_pad)
    seg_ids = np.full(N_pad, max(J - 1, 0), dtype=np.int32)
    seg_ids[:N] = np.repeat(np.arange(J, dtype=np.int32), counts)
    return seg_ids, starts, F_pad, N, N_pad


def _gather_rows(codes, quals, rows, L_max: int, N_pad: int):
    """numpy gather of ``rows`` into fresh padded (N_pad, L_max) dense views
    (pad rows all-N / qual 0)."""
    codes_dev = np.full((N_pad, L_max), N_CODE, dtype=np.uint8)
    quals_dev = np.zeros((N_pad, L_max), dtype=np.uint8)
    codes_dev[:len(rows)] = codes[rows, :L_max]
    quals_dev[:len(rows)] = quals[rows, :L_max]
    return codes_dev, quals_dev


def pad_segments_gather(codes: np.ndarray, quals: np.ndarray,
                        rows: np.ndarray, L_max: int, counts: np.ndarray):
    """Fused gather + bucket-pad: one copy instead of pad_segments' two.

    Gathers `rows` out of the packed (R, L_stride) arrays directly into the
    padded (N_pad, L_max) device layout (same pad invariants as
    pad_segments). Returns (codes_dev, quals_dev, seg_ids, starts, F_pad, N);
    codes_dev[:N] / quals_dev[:N] are the dense views resolve_segments needs.
    The numpy form of ConsensusKernel.pack_segments_wire's layout: what a
    host without the native library runs, and the tests' oracle.
    """
    with span("engine.pack.gather"):
        seg_ids, starts, F_pad, N, N_pad = _gather_layout(counts)
        codes_dev, quals_dev = _gather_rows(codes, quals, rows, L_max, N_pad)
    return codes_dev, quals_dev, seg_ids, starts, F_pad, N


def split_row_balanced(counts, dp):
    """Job boundaries for dp contiguous row-balanced shards over segments of
    `counts` rows each: (dp+1,) indices into the job list.

    The target-crossing job goes to whichever side leaves the row split
    closer to the target (plain searchsorted+1 can collapse a 2-job batch
    onto one device).
    """
    n_jobs = len(counts)
    cum = np.cumsum(counts)
    total = int(cum[-1])
    targets = (np.arange(1, dp) * total) // dp
    i = np.searchsorted(cum, targets, side="left")
    prev = np.where(i > 0, cum[np.maximum(i - 1, 0)], 0)
    jb = i + ((cum[np.minimum(i, n_jobs - 1)] - targets)
              <= (targets - prev))
    jb = np.concatenate(([0], jb, [n_jobs]))
    return np.minimum(np.maximum.accumulate(jb), n_jobs)


def pad_segments_mesh(codes2d: np.ndarray, quals2d: np.ndarray,
                      counts: np.ndarray, mesh):
    """Chunked global row layout for the shard_map wire kernels.

    Splits the J families into dp contiguous shards (row-balanced where
    that stays within the per-shard segment bucket, equal-count otherwise),
    splits each shard's rows into sp contiguous chunks, and pads every
    chunk to a common ladder-bucketed N_chunk — so the global
    (dp * sp * N_chunk, L) array shards evenly over the mesh with
    ``PartitionSpec(mesh.axis_names)`` and every ``jax.device_put`` lands
    one slice per device (the overlapping per-shard upload, ISSUE 10 (b)).
    Segment ids are LOCAL to each dp shard (0..F_loc-1, sorted within
    every chunk; pad rows carry their chunk's last real id — all-N no-ops,
    the pad_segments invariant). The family axis rounds to dp * F_loc with
    F_loc from the same 8-aligned segment ladder as the single-device
    path, one shape vocabulary across mesh sizes.

    Returns (codes_g, quals_g, seg_g, starts, F_loc, gather) where
    ``gather[j]`` is family j's row in the (dp * F_loc, ...) shard-ordered
    device output (resolve_segments_wire applies it).
    """
    counts = np.asarray(counts, dtype=np.int64)
    starts = np.concatenate(([0], np.cumsum(counts)))
    J = len(counts)
    N = int(starts[-1])
    dp = int(mesh.shape["dp"])
    sp = int(dict(mesh.shape).get("sp", 1))
    L = codes2d.shape[1]
    F_loc = SHAPE_REGISTRY.bucket_segments_sharded(J, dp)
    jb = split_row_balanced(counts, dp) if J else np.zeros(dp + 1, np.int64)
    if J and int(np.diff(jb).max()) > F_loc:
        # a row-balanced split that overflows the per-shard segment bucket
        # (deep-family skew) falls back to equal family counts: the static
        # shape stays a function of (J, dp) only, never of the skew
        per = -(-J // dp)
        jb = np.minimum(np.arange(dp + 1, dtype=np.int64) * per, J)
    n_rows = starts[jb[1:]] - starts[jb[:-1]]
    chunk = -(-np.maximum(n_rows, 1) // sp)
    N_chunk = _pad_rows(int(chunk.max()) if J else 1)
    codes_g = np.full((dp * sp * N_chunk, L), N_CODE, dtype=np.uint8)
    quals_g = np.zeros((dp * sp * N_chunk, L), dtype=np.uint8)
    seg_g = np.zeros(dp * sp * N_chunk, dtype=np.int32)
    gather = np.zeros(J, dtype=np.int64)
    for d in range(dp):
        lo_j, hi_j = int(jb[d]), int(jb[d + 1])
        if hi_j <= lo_j:
            continue
        base = int(starts[lo_j])
        n = int(starts[hi_j]) - base
        seg_local = np.repeat(
            np.arange(hi_j - lo_j, dtype=np.int32),
            counts[lo_j:hi_j])
        c = int(chunk[d])
        for s in range(sp):
            lo = min(s * c, n)
            hi = min(lo + c, n)
            m = hi - lo
            row0 = (d * sp + s) * N_chunk
            if m:
                codes_g[row0:row0 + m] = codes2d[base + lo:base + hi]
                quals_g[row0:row0 + m] = quals2d[base + lo:base + hi]
                seg_g[row0:row0 + m] = seg_local[lo:hi]
                seg_g[row0 + m:row0 + N_chunk] = seg_local[hi - 1]
        gather[lo_j:hi_j] = d * F_loc + np.arange(hi_j - lo_j)
    DEVICE_STATS.add_pad(N, dp * sp * N_chunk)
    # what the layout cost in rows, on the pack span of the batch it serves
    # (a no-op for a caller outside one): rows as add_pad counts them, and
    # the fullest dp shard's real rows, which set the mesh kernel's time
    count("engine.pack", "mesh.rows", N)
    count("engine.pack", "mesh.rows_padded", dp * sp * N_chunk)
    count("engine.pack", "mesh.shard_rows_max", int(n_rows.max()))
    count("engine.pack", "mesh.families", J)
    return codes_g, quals_g, seg_g, starts, F_loc, gather


class _WirePlan:
    """One built-but-unsubmitted wire dispatch (ConsensusKernel.
    _wire_dispatch_plan): the dispatch closure plus everything the
    submitter needs to account/submit it — shared by the solo path and
    the cross-job coalescer (ops/coalesce.py)."""

    __slots__ = ("dispatch", "upload", "new", "staging", "filter_mode")

    def __init__(self, dispatch, upload, new, staging, filter_mode):
        self.dispatch = dispatch
        self.upload = upload
        self.new = new
        self.staging = staging
        self.filter_mode = filter_mode


def _full_column_ok(counts) -> bool:
    """Whether the full-column kernels' uint16 depth fetch can hold every
    family of the batch."""
    return bool(np.max(counts) < 65536)


def _predicted_s():
    """The cost model's predicted dispatch seconds for this thread's latest
    routing decision (stamped into the timeline), None when it was forced."""
    from .router import ROUTER

    pred = ROUTER.last_prediction()
    return pred[0] if pred else None


class PendingSegments:
    """One submitted segment batch between its dispatch and its columns:
    what :meth:`ConsensusKernel.submit_ragged` / ``submit_dense`` return.

    Holds the feeder ticket (``HOST_DISPATCH`` on the host route: the native
    f64 engine then computes the batch at resolve time) and what resolve
    needs of the batch: the unpadded dense row views and the (J+1,) segment
    row boundaries. The padded device layout (segment ids, buckets, the
    mesh's shard order) stays behind it."""

    __slots__ = ("kernel", "ticket", "codes2d", "quals2d", "starts")

    def __init__(self, kernel, ticket, codes2d, quals2d, starts):
        self.kernel = kernel
        self.ticket = ticket
        self.codes2d = codes2d
        self.quals2d = quals2d
        self.starts = starts

    def resolve(self):
        """(winner, qual, depth, errors) (J, L) arrays, exact on every
        route."""
        k = self.kernel
        if self.ticket is HOST_DISPATCH:
            return k.resolve_segments(HOST_DISPATCH, self.codes2d,
                                      self.quals2d, self.starts)
        return k.resolve_segments_wire(self.ticket, self.codes2d,
                                       self.quals2d, self.starts)

    def resolve_filtered(self):
        """The fused-filter resolve (consensus/device_filter.py):
        resolve_segments_wire_filtered's ``("stats", stats, resident)``
        where the fused kernel ran, ``("columns", winner, qual, depth,
        errors)`` on every other route."""
        if self.ticket is HOST_DISPATCH:
            return ("columns",) + self.resolve()
        return self.kernel.resolve_segments_wire_filtered(
            self.ticket, self.codes2d, self.quals2d, self.starts)

    def discard(self):
        """Hand an unresolved dispatch back, as a resolver that ran out of
        time does, without waiting for the device: the feeder slot and the
        resident-byte accounting of a batch nobody will fetch."""
        ticket = self.ticket
        if ticket is HOST_DISPATCH:
            return
        DEVICE_STATS.end_in_flight(ticket.slot, 0, 0.0)
        DEVICE_FEEDER.abandon(ticket)
        try:
            dev = ticket.wait(0)
        except Exception:  # noqa: BLE001 - nothing of it to release
            # still queued or running (the feeder discards it when it
            # ends), or the dispatch itself failed
            return
        if isinstance(dev[-1], ResidentHandles):
            dev[-1].release()


def _unpack_device_result(packed: np.ndarray):
    """(winner uint8, qual uint8, suspect bool) from the packed uint16."""
    qual = (packed & 0x7F).astype(np.uint8)
    winner = ((packed >> 7) & 0x7).astype(np.uint8)
    suspect = (packed >> 10).astype(bool)
    return winner, qual, suspect


class ConsensusKernel:
    """Compiled batched consensus caller for one (pre, post) error-rate pair.

    Call with padded uint8 arrays codes/quals of shape (F, R, L); returns NumPy
    arrays (winner, qual, depth, errors) with all suspect positions already
    recomputed on host by the f64 oracle, so results are integer-exact against
    fgumi_tpu.ops.oracle by construction.
    """

    def __init__(self, tables: QualityTables):
        # f32 table casts stay host-side numpy: jit accepts them directly
        # (tiny per-dispatch transfer), and building jnp arrays here would
        # force backend init even when every dispatch routes to the host
        # engine. The persistent compile cache is enabled at first device
        # dispatch for the same reason.
        self.tables = tables
        self._correct_f32 = np.asarray(tables.adjusted_correct, dtype=np.float32)
        self._err_f32 = np.asarray(tables.adjusted_error_per_alt, dtype=np.float32)
        self._pre = np.float32(tables.ln_error_pre_umi)
        self.fallback_positions = 0
        self.total_positions = 0
        # fallback counters are updated from whichever thread resolves a
        # dispatch (the pipeline's writer stage as well as the caller thread)
        self._counter_lock = threading.Lock()
        self._host_engine = None
        self._use_host = None
        self._hybrid = None
        self._delta94 = self._correct_f32 - self._err_f32
        self._coalesce_key_cache = None

    def host_mode(self) -> bool:
        """True when segment dispatches should run on the native f64 host
        engine instead of XLA (ops/host_kernel.py): no accelerator attached
        (jax backend == cpu) and the native library is available.
        FGUMI_TPU_HOST_ENGINE=1/0 forces either way (parity tests run both)."""
        if self._use_host is None:
            self._use_host = use_host_engine()
        return self._use_host

    def set_force_device(self, force: bool = True):
        """Public pin to the XLA device path (ADVICE r4: benches were poking
        the private _use_host cache). force=False re-enables auto."""
        self._use_host = False if force else None

    def hybrid_mode(self) -> bool:
        """True when an accelerator is attached AND the native f64 host
        engine is available: batches the device link cannot absorb run on
        the host engine concurrently, so throughput is device + host rather
        than min(device, host) (the round-5 answer to 'the TPU loses to its
        own host engine'). FGUMI_TPU_HYBRID=0 disables (device-only)."""
        if self._hybrid is None:
            import os

            if self.host_mode():
                self._hybrid = False
            else:
                from ..native import batch as nb

                env = os.environ.get("FGUMI_TPU_HYBRID", "auto").lower()
                self._hybrid = (env not in ("0", "false", "off")
                                and nb.available())
        return self._hybrid

    def _host(self):
        if self._host_engine is None:
            from .host_kernel import HostConsensusEngine

            self._host_engine = HostConsensusEngine(self.tables)
        return self._host_engine

    def _tables_dev(self):
        """Device-resident quality tables via the process-wide constant
        cache: uploaded once per (device, content), reused by every later
        dispatch of any kernel instance with the same error rates. Callers
        run inside dispatch closures, after jax init."""
        return (CONST_CACHE.put("correct_tab", self._correct_f32),
                CONST_CACHE.put("err_tab", self._err_f32))

    def _coalesce_key(self) -> str:
        """Constant-table content fingerprint for cross-job merge
        compatibility (ops/coalesce.py): two kernels whose f32 quality
        tables and pre-UMI prior are byte-identical produce identical
        per-family results inside a merged dispatch — the wire dictionary
        re-indexes the same delta values, and every suspect/oracle gate is
        derived from them. Content-keyed like the constant cache, so warm
        serve jobs with the same error rates merge across kernel
        instances."""
        if self._coalesce_key_cache is None:
            import hashlib

            h = hashlib.blake2b(digest_size=16)
            h.update(self._correct_f32.tobytes())
            h.update(self._err_f32.tobytes())
            h.update(np.float32(self._pre).tobytes())
            self._coalesce_key_cache = h.hexdigest()
        return self._coalesce_key_cache

    def device_call(self, codes, quals):
        """Raw device outputs (winner, qual, depth, errors, suspect) as jax arrays."""
        codes = as_device_operand(codes)
        quals = as_device_operand(quals)
        _ensure_jax()
        ct, et = self._tables_dev()
        return _consensus_batch_jit(codes, quals, ct, et, self._pre)

    def device_call_packed(self, codes, quals):
        """One (F, L) uint16 device output (see _consensus_batch_packed_jit).

        2 bytes/position crosses the link instead of 17 (4 x int32 + bool), and
        one fetch instead of five; depth/errors come from _host_counts.
        """
        F, R, L = codes.shape
        DEVICE_STATS.add_dispatch(segments_flops(F * R, L, F))
        codes = as_device_operand(codes)
        quals = as_device_operand(quals)
        new = SHAPE_REGISTRY.observe("batch", F, R, L)

        def _dispatch():
            _ensure_jax()
            ct, et = self._tables_dev()
            return _consensus_batch_packed_jit(codes, quals, ct, et,
                                               self._pre)

        def _bounded():
            with SHAPE_REGISTRY.attribute_compiles(new):
                return device_retry_call(_dispatch, "batch dispatch")

        # sync path: the dispatch itself runs under the deadline (a wedged
        # device_put/jit call would otherwise hang the CALLER thread
        # unboundedly — the async paths get the same protection from the
        # feeder's bounded ticket wait). __call__ degrades the overrun.
        return _DISPATCH_RUNNER.run(_bounded, dispatch_deadline_s(),
                                    "batch dispatch")

    @staticmethod
    def _host_counts(codes: np.ndarray, winner: np.ndarray):
        """depth/errors (F, L) int32 recomputed from host-resident codes.

        depth = valid (non-N) observations per position; errors = valid
        observations disagreeing with the winner (all of them when the winner
        is N) — exactly _call_epilogue's obs arithmetic, in integer space.
        """
        valid = codes != N_CODE
        depth = valid.sum(axis=-2, dtype=np.int32)
        winner_obs = ((codes == winner[..., None, :]) & valid).sum(
            axis=-2, dtype=np.int32)
        return depth, depth - winner_obs

    def resolve_packed(self, dev, codes: np.ndarray, quals: np.ndarray):
        """Fetch + unpack a device_call_packed result: host depth/error counts,
        counter updates, and exact f64 fallback on suspect positions.

        Thread-safe; this is the single completion path shared by the direct
        __call__ and the pipeline's deferred (writer-stage) resolution.
        """
        try:
            packed = _fetch_with_deadline(dev, dispatch_deadline_s())
        except DeadlineExceeded as e:
            return self._recover_packed(e, codes, quals, overran=True)
        except BaseException as e:  # noqa: BLE001 - classified below
            if not (_is_oom(e) or _is_transient(e)):
                raise
            return self._recover_packed(e, codes, quals)
        from .breaker import BREAKER

        BREAKER.record_success()  # clean resolve: resets the failure score
        winner, qual, suspect = _unpack_device_result(packed)
        depth, errors = self._host_counts(codes, winner)
        depth = depth.astype(np.int64)
        errors = errors.astype(np.int64)
        self._count_suspects(suspect)
        if suspect.any():
            self._oracle_patch(suspect, winner, qual, depth, errors,
                               lambda f: (codes[f], quals[f]))
        return winner, qual, depth, errors

    def _recover_packed(self, exc, codes: np.ndarray, quals: np.ndarray,
                        overran: bool = False):
        """Host-engine completion of a failed uniform-batch fetch: the
        (F, R, L) batch is one R-row segment per family for the native f64
        engine. Re-raises when the native library is unavailable.
        ``overran``: the fetch hit its dispatch deadline rather than
        erroring — counted and breaker-fed as a wedge, not a failure."""
        from ..native import batch as nb

        if not nb.available():
            raise exc
        from .breaker import BREAKER

        F, R, L = codes.shape
        if overran:
            DEVICE_STATS.add_deadline_fallback()
            BREAKER.record_deadline_overrun()
        else:
            DEVICE_STATS.add_host_fallback()
            if not _is_oom(exc):
                BREAKER.record_transient_failure()
        log.warning(
            "device fetch %s (%s: %s); computing %d "
            "families on the native f64 host engine",
            "overran its deadline" if overran else "failed after retries",
            type(exc).__name__, exc, F)
        starts = np.arange(F + 1, dtype=np.int64) * R
        engine = self._host()
        winner, qual, depth, errors, n_slow = engine.call_segments_counted(
            codes.reshape(F * R, L), quals.reshape(F * R, L), starts)
        with self._counter_lock:
            self.total_positions += winner.size
            self.fallback_positions += n_slow
        return (winner, qual, depth.astype(np.int64),
                errors.astype(np.int64))

    def __call__(self, codes: np.ndarray, quals: np.ndarray):
        try:
            dev = self.device_call_packed(codes, quals)
        except DeadlineExceeded as e:
            return self._recover_packed(e, codes, quals, overran=True)
        except BaseException as e:  # noqa: BLE001 - classified below
            # dispatch-time failure (sync path): same degradation contract
            # as the resolve paths — OOM or exhausted retries run the batch
            # on the native f64 host engine rather than aborting the run
            if not (_is_oom(e) or _is_transient(e)):
                raise
            return self._recover_packed(e, codes, quals)
        return self.resolve_packed(dev, codes, quals)

    # ------------------------------------------------------- ragged (segment)

    def device_call_segments(self, codes2d, quals2d, seg_ids,
                             num_segments: int):
        """Dispatch dense (N, L) read rows with sorted per-row segment ids.

        In host mode this is a no-op returning HOST_DISPATCH: the matching
        resolve_segments call runs the native f64 engine on the unpadded
        rows it receives, so callers that pre-padded simply wasted the pad
        (the hot simplex path skips padding entirely in host mode)."""
        if self.host_mode():
            return HOST_DISPATCH
        DEVICE_STATS.add_dispatch(segments_flops(
            codes2d.shape[0], codes2d.shape[1], num_segments))
        codes2d = as_device_operand(codes2d)
        quals2d = as_device_operand(quals2d)
        seg_ids = as_device_operand(seg_ids)
        new = SHAPE_REGISTRY.observe("seg", codes2d.shape[0],
                                     codes2d.shape[1], num_segments)

        def _dispatch():
            _ensure_jax()
            ct, et = self._tables_dev()
            return _consensus_segments_packed_jit(
                codes2d, quals2d, seg_ids, ct, et, self._pre, num_segments)

        def _bounded():
            with SHAPE_REGISTRY.attribute_compiles(new):
                return device_retry_call(_dispatch, "segment dispatch")

        try:
            # sync path: the dispatch itself runs under the deadline (see
            # device_call_packed) — a wedge here must not hang the caller
            return _DISPATCH_RUNNER.run(_bounded, dispatch_deadline_s(),
                                        "segment dispatch")
        except DeadlineExceeded as e:
            from ..native import batch as nb

            if not nb.available():
                raise  # nothing to degrade to
            from .breaker import BREAKER

            DEVICE_STATS.add_deadline_fallback()
            BREAKER.record_deadline_overrun()
            log.warning(
                "device dispatch overran its deadline (%s); completing on "
                "the native f64 host engine", e)
            # the matching resolve_segments call completes byte-identically
            # on the unpadded rows it receives
            return HOST_DISPATCH

    def pack_segments_wire(self, codes, quals, rows, L_max: int, counts):
        """pad_segments_gather plus the wire, in one native pass over the
        ragged rows: the single-device route's whole pack.

        Returns pad_segments_gather's tuple plus ``prebuilt`` for
        :meth:`device_call_segments_wire`: the finished (wire, dict64) in a
        pooled staging buffer, or None where that call still has the wire
        to build (no native library: the numpy gather ran; or more than
        63 distinct quals: the packed-codes layout). The pass fills fresh
        ``np.empty`` dense views and mints nothing else."""
        from ..native import batch as nb

        if not nb.wire_inputs_ok(codes, quals, rows, L_max):
            return pad_segments_gather(codes, quals, rows, L_max,
                                       counts) + (None,)
        from .datapath import STAGING_POOL

        with span("engine.pack.gather"):
            seg_ids, starts, F_pad, N, N_pad = _gather_layout(counts)
            codes_dev = np.empty((N_pad, L_max), dtype=np.uint8)
            quals_dev = np.empty((N_pad, L_max), dtype=np.uint8)
        with span("engine.pack.wire"):
            wire = STAGING_POOL.acquire((N_pad, L_max), np.uint8)
            vals = nb.build_wire(codes, quals, rows, N_pad, L_max, wire,
                                 codes_dev, quals_dev)
            if vals is not None:
                count("engine.pack", "wire_native")
                return (codes_dev, quals_dev, seg_ids, starts, F_pad, N,
                        (wire, _wire_dict(vals, self._delta94)))
            STAGING_POOL.release(wire)
        with span("engine.pack.gather", layout="packed2"):
            codes_dev, quals_dev = _gather_rows(codes, quals, rows, L_max,
                                                N_pad)
        return codes_dev, quals_dev, seg_ids, starts, F_pad, N, None

    def submit_ragged(self, codes, quals, rows, L_max: int, counts,
                      route: str, filter_params=None) -> PendingSegments:
        """Turn ragged rows into a dispatched batch (single device).

        ``rows`` index the batch's packed (R, L_stride) ``codes``/``quals``
        in segment order, ``counts`` rows per segment, ``L_max`` the
        4-multiple width; ``route`` is the caller's ROUTER.decide_batch
        verdict (``"host"`` where there is no device). The device route is
        pack_segments_wire + device_call_segments_wire under one
        ``engine.pack`` span that ends with the dispatch handed to the
        feeder, a few microseconds after the timeline's pack_s stamp
        (begin_in_flight), which it must agree with. ``filter_params``:
        device_call_segments_wire's fused consensus→filter selection (the
        caller gates it on every family being under 65536 rows); resolve
        such a batch with :meth:`PendingSegments.resolve_filtered`."""
        if route == "host":
            # the native engine eats the batch CONCURRENTLY on the resolve
            # pool, so e2e throughput is device + host, not min of the two.
            # No pad, no device layout: it consumes ragged rows.
            with span("engine.host_gather", rusage=True):
                starts = np.concatenate(([0], np.cumsum(counts)))
                return PendingSegments(
                    self, HOST_DISPATCH,
                    np.ascontiguousarray(codes[rows, :L_max]),
                    np.ascontiguousarray(quals[rows, :L_max]), starts)
        full = _full_column_ok(counts)
        with span("engine.pack", rusage=True):
            count("engine.pack", "entry_ragged")
            t_pack0 = time.monotonic()
            codes_dev, quals_dev, seg_ids, starts, F_pad, N, prebuilt = \
                self.pack_segments_wire(codes, quals, rows, L_max, counts)
            ticket = self.device_call_segments_wire(
                codes_dev, quals_dev, seg_ids, F_pad, len(counts),
                pack_t0=t_pack0, full=full,
                pred_s=_predicted_s(), filter_params=filter_params,
                prebuilt=prebuilt)
            return PendingSegments(self, ticket, codes_dev[:N],
                                   quals_dev[:N], starts)

    def submit_dense(self, gather, counts, route: str,
                     mesh=None) -> PendingSegments:
        """Turn dense rows into a dispatched batch (one device or a mesh).

        ``gather()`` returns the batch's dense (N, L) ``(codes2d,
        quals2d)`` in segment order (L % 4 == 0); it runs inside the pack
        span, so a caller's row copies are charged to the batch they
        belong to. ``counts``/``route`` as in :meth:`submit_ragged`. The
        device route pads (pad_segments, or pad_segments_mesh's chunked
        layout where ``mesh`` has more than one device: the child span
        ``engine.pack.mesh_layout``, so the parent's self time is the
        caller's copies) under ``engine.pack.gather`` and dispatches
        through device_call_segments_wire."""
        if route == "host":
            with span("engine.host_gather", rusage=True):
                codes2d, quals2d = gather()
            starts = np.concatenate(([0], np.cumsum(counts)))
            return PendingSegments(self, HOST_DISPATCH, codes2d, quals2d,
                                   starts)
        full = _full_column_ok(counts)
        with span("engine.pack", rusage=True):
            count("engine.pack", "entry_dense")
            t_pack0 = time.monotonic()
            with span("engine.pack.gather"):
                codes2d, quals2d = gather()
                if mesh is not None and mesh.size > 1:
                    with span("engine.pack.mesh_layout"):
                        cd, qd, seg_ids, starts, F_pad, order = \
                            pad_segments_mesh(codes2d, quals2d, counts, mesh)
                else:
                    cd, qd, seg_ids, starts, F_pad = pad_segments(
                        codes2d, quals2d, counts)
                    order = None
            ticket = self.device_call_segments_wire(
                cd, qd, seg_ids, F_pad, len(counts), pack_t0=t_pack0,
                full=full, pred_s=_predicted_s(), mesh=mesh,
                mesh_gather=order)
        return PendingSegments(self, ticket, codes2d, quals2d, starts)

    def device_call_segments_wire(self, codes2d_padded, quals2d_padded,
                                  seg_ids, num_segments: int, J: int,
                                  pack_t0: float = None, full: bool = False,
                                  pred_s: float = None, mesh=None,
                                  mesh_gather=None, filter_params=None,
                                  prebuilt=None):
        """Async wire-format dispatch via the feeder pipeline.

        codes2d_padded/quals2d_padded: the full padded (N_pad, L) row layout
        (L % 4 == 0). Builds the 1-byte wire (or the 1.25 B/position
        packed-codes fallback when the batch has >63 distinct quals),
        submits the upload + jit dispatch
        to the feeder thread, and returns a DispatchTicket immediately —
        the processing thread never blocks on the link, and with feeder
        depth >= 2 this batch's upload overlaps the previous batch's
        device compute. The wire dictionary rides the constant cache (a
        stable sequencer qual set re-uploads nothing). ``pack_t0``: when
        the caller timed its own gather/pad start, the timeline's pack_s
        covers it too. Resolve with
        resolve_segments_wire(ticket, dense_codes, dense_quals, starts).

        ``full=True`` selects the full-column kernels: depth/errors are
        computed on device and fetched as uint16 (+4 B/column), so the
        resolve never re-walks the dense rows — callers must gate on max
        family size < 65536 (the engines do, from their counts arrays).
        ``pred_s``: the cost model's predicted dispatch seconds, stamped
        into the timeline.

        ``filter_params=(min_reads, min_qual, lens_padded, DeviceFilterParams)``
        selects the fused consensus→filter kernel (ISSUE 11): per-read
        stats are the only default fetch, every column stays resident for
        the survivors-only gather, and the ticket resolves through
        :meth:`resolve_segments_wire_filtered`. Wire layout only (callers
        must pass ``full=True``); the >63-distinct-quals fallback silently
        dispatches the ordinary full-column kernel instead and the filter
        runs host-side on the fetched columns (``ticket.filter_mode``
        records which happened).

        ``mesh``: a live jax Mesh with > 1 device selects the shard_map
        compile path — the inputs must be in pad_segments_mesh's chunked
        layout with ``num_segments`` the PER-SHARD F_loc and
        ``mesh_gather`` its family-order gather; uploads go through
        ``jax.device_put(..., NamedSharding)`` so every device's slice
        copies concurrently, and the device output is the shard-ordered
        (dp * F_loc, ...) global that resolve_segments_wire re-gathers.
        A 1-device (or None) mesh is exactly the legacy single-device
        path — bit-for-bit, including the compiled executables.

        ``prebuilt``: pack_segments_wire's finished (wire, dict64) for
        these rows (single-device route only); the wire build is skipped
        and its pooled buffer recycles with the ticket."""
        t_pack0 = pack_t0 if pack_t0 is not None else time.monotonic()
        mesh_active = mesh is not None and mesh.size > 1
        if mesh_active:
            return self._dispatch_wire_mesh(
                codes2d_padded, quals2d_padded, seg_ids, num_segments, J,
                t_pack0, full, pred_s, mesh, mesh_gather)
        if filter_params is None:
            # cross-job coalescing seam (ops/coalesce.py): while the serve
            # daemon's merge window is armed, compatible plain wire
            # dispatches from concurrent jobs merge into one device launch.
            # The CoalescedTicket resolves through the same
            # resolve_segments_wire call — sliced back per partner there.
            from .coalesce import COALESCER

            merged = COALESCER.maybe_submit(
                self, codes2d_padded, quals2d_padded, seg_ids,
                num_segments, J, full=full, pack_t0=t_pack0, pred_s=pred_s)
            if merged is not None:
                if prebuilt is not None:
                    # the merged launch builds its own wire over every
                    # partner's rows: this one goes back to the pool unsent
                    from .datapath import STAGING_POOL

                    STAGING_POOL.release(prebuilt[0])
                return merged
        plan = self._wire_dispatch_plan(
            codes2d_padded, quals2d_padded, seg_ids, num_segments, J,
            full=full, filter_params=filter_params, prebuilt=prebuilt)
        DEVICE_STATS.add_dispatch(segments_flops(
            codes2d_padded.shape[0], codes2d_padded.shape[1], num_segments))
        slot = DEVICE_STATS.begin_in_flight(
            plan.upload, pack_s=time.monotonic() - t_pack0)
        if pred_s is not None:
            DEVICE_STATS.note_pred(slot, pred_s)
        with SHAPE_REGISTRY.attribute_compiles(plan.new):
            ticket = DEVICE_FEEDER.submit(
                lambda: device_retry_call(lambda: plan.dispatch(slot),
                                          "wire dispatch"),
                upload_bytes=plan.upload, slot=slot)
        ticket.filter_mode = plan.filter_mode
        if plan.filter_mode:
            # retained for the sentinel's fused-route audit tap
            # (resolve_segments_wire_filtered -> SENTINEL.maybe_audit_filter)
            ticket.filter_ctx = filter_params
        if plan.staging:
            ticket.staging = plan.staging
        return ticket

    def _wire_dispatch_plan(self, codes2d_padded, quals2d_padded, seg_ids,
                            num_segments: int, J: int, full: bool = False,
                            filter_params=None, prebuilt=None):
        """Build — but do not submit — one wire-layout dispatch.

        The shared dispatch seam of the solo path and the cross-job
        coalescer (ops/coalesce.py): the coalescer builds a merged row
        layout and submits this plan under its own per-partner
        accounting. Returns a :class:`_WirePlan` holding the dispatch
        closure (runs on the feeder thread), the upload byte count for
        the feeder's governed budget, the shape-registry new-shape flag,
        the pooled staging buffers to recycle at resolve, and whether the
        fused-filter kernel was actually selected. ``prebuilt``: the
        (wire, dict64) that pack_segments_wire already built for these
        rows in a pooled buffer — taken as is."""
        out_segments = _pad_out_segments(J, num_segments)
        from .datapath import STAGING_POOL

        if prebuilt is not None:
            w = prebuilt
            staging = [w[0]]
        else:
            with span("engine.pack.wire"):
                staging = [STAGING_POOL.acquire(codes2d_padded.shape,
                                                np.uint8)]
                w = build_wire(codes2d_padded, quals2d_padded, self._delta94,
                               out=staging[0])
        pre = self._pre
        tables_dev = self._tables_dev
        filt = filter_params is not None
        if w is not None:
            wire, dict32 = w
            upload = wire.nbytes + seg_ids.nbytes
            # ISSUE 19: the hand-tiled Pallas kernel covers the
            # full-column and fused-filter wire dispatches; plain, packed2
            # and mesh stay XLA. The backend is pinned at plan-build time
            # so the shape registry attributes compiles to the kernel that
            # actually runs.
            use_pallas = False
            if filt or full:
                from . import pallas_kernel as _pk

                use_pallas = _pk.selected_backend() == "pallas"
            kind = (("segwxp" if use_pallas else "segwx") if filt
                    else (("segwfp" if use_pallas else "segwf") if full
                          else "segw"))
            dims = (wire.shape[0], wire.shape[1], num_segments, out_segments)
            if use_pallas:
                # the window bucket is compiled in: part of the shape
                windows = _pk.plan_windows(seg_ids, num_segments)
                dims += (windows.w_tiles,)
            new = SHAPE_REGISTRY.observe(kind, *dims)
            if filt:
                mr, mq, lens_j, fparams = filter_params
                lens_pad = np.zeros(out_segments, dtype=np.int32)
                lens_pad[:J] = lens_j

            def _dispatch(slot):
                _ensure_jax()
                if use_pallas:
                    # the wire dictionary rides the kernel's
                    # scalar-prefetch channel (256 B) instead of the
                    # constant cache
                    t0 = time.monotonic()
                    with span("feeder.upload", bytes=upload):
                        prep = _pk.upload(wire, seg_ids, dict32, windows)
                    DEVICE_STATS.note_upload(slot, time.monotonic() - t0)
                    DEVICE_STATS.add_kernel_backend(slot, "pallas")
                    if filt:
                        out = _pk.call_filter(prep, pre, mr, mq, lens_pad,
                                              fparams, out_segments)
                        return (out[0], ResidentHandles(out[1:]))
                    return _pk.call_full(prep, pre, out_segments)
                t0 = time.monotonic()
                with span("feeder.upload", bytes=upload):
                    wd = jax.device_put(wire)
                    sd = jax.device_put(seg_ids)
                    dtab = CONST_CACHE.put("dict_tab", dict32)
                DEVICE_STATS.note_upload(slot, time.monotonic() - t0)
                DEVICE_STATS.add_kernel_backend(slot, "xla")
                if filt:
                    ld = jax.device_put(lens_pad)
                    etab = CONST_CACHE.put("filter_emin", fparams.emin_tab)
                    out = _consensus_segments_wire_filter_jit(
                        wd, sd, dtab, pre, mr, mq, ld,
                        fparams.min_reads, etab, fparams.min_base_q,
                        np.int32(1 if fparams.per_base else 0),
                        num_segments, out_segments)
                    return (out[0], ResidentHandles(out[1:]))
                fn = (_consensus_segments_wire_full_jit if full
                      else _consensus_segments_wire_jit)
                return fn(wd, sd, dtab, pre, num_segments, out_segments)
        else:
            STAGING_POOL.release(staging.pop())
            with span("engine.pack.wire", layout="packed2"):
                cp, qsent = pack_codes2(codes2d_padded, quals2d_padded)
            upload = cp.nbytes + qsent.nbytes + seg_ids.nbytes
            new = SHAPE_REGISTRY.observe(
                "segp2f" if full else "segp2", cp.shape[0], cp.shape[1],
                num_segments, out_segments)

            def _dispatch(slot):
                _ensure_jax()
                t0 = time.monotonic()
                with span("feeder.upload", bytes=upload):
                    cd = jax.device_put(cp)
                    qd = jax.device_put(qsent)
                    sd = jax.device_put(seg_ids)
                    ct, et = tables_dev()
                DEVICE_STATS.note_upload(slot, time.monotonic() - t0)
                DEVICE_STATS.add_kernel_backend(slot, "xla")
                fn = (_consensus_segments_packed2_full_jit if full
                      else _consensus_segments_packed2_jit)
                return fn(cd, qd, sd, ct, et, pre, num_segments,
                          out_segments)
        return _WirePlan(_dispatch, upload, new, staging,
                         filt and w is not None)

    def _dispatch_wire_mesh(self, codes_g, quals_g, seg_g, F_loc: int,
                            J: int, t_pack0: float, full: bool, pred_s,
                            mesh, mesh_gather):
        """The mesh half of device_call_segments_wire: NamedSharding
        uploads + the shard_map wire kernels (see the caller's docstring).
        Split out so the single-device fast path stays exactly the legacy
        code path when no mesh is configured."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        rows_sh = NamedSharding(mesh, P(mesh.axis_names))
        repl_sh = NamedSharding(mesh, P())
        dp = int(mesh.shape["dp"])
        sp = int(dict(mesh.shape).get("sp", 1))
        pre = self._pre
        with span("engine.pack.wire"):
            w = build_wire(codes_g, quals_g, self._delta94)
        if w is not None:
            wire, dict32 = w
            upload = wire.nbytes + seg_g.nbytes
            new = SHAPE_REGISTRY.observe(
                "segwfm" if full else "segwm", wire.shape[0], wire.shape[1],
                F_loc, dp, sp)

            def _dispatch(slot):
                _ensure_jax()
                t0 = time.monotonic()
                with span("feeder.upload", bytes=upload):
                    wd = jax.device_put(wire, rows_sh)
                    sd = jax.device_put(seg_g, rows_sh)
                    dtab = CONST_CACHE.put("dict_tab", dict32,
                                           sharding=repl_sh)
                DEVICE_STATS.note_upload(slot, time.monotonic() - t0)
                DEVICE_STATS.add_kernel_backend(slot, "xla")
                return _consensus_segments_wire_mesh_jit(
                    wd, sd, dtab, pre, F_loc, mesh, full)
        else:
            with span("engine.pack.wire", layout="packed2"):
                cp, qsent = pack_codes2(codes_g, quals_g)
            upload = cp.nbytes + qsent.nbytes + seg_g.nbytes
            new = SHAPE_REGISTRY.observe(
                "segp2fm" if full else "segp2m", cp.shape[0], cp.shape[1],
                F_loc, dp, sp)

            def _dispatch(slot):
                _ensure_jax()
                t0 = time.monotonic()
                with span("feeder.upload", bytes=upload):
                    cd = jax.device_put(cp, rows_sh)
                    qd = jax.device_put(qsent, rows_sh)
                    sd = jax.device_put(seg_g, rows_sh)
                    ct = CONST_CACHE.put("correct_tab", self._correct_f32,
                                         sharding=repl_sh)
                    et = CONST_CACHE.put("err_tab", self._err_f32,
                                         sharding=repl_sh)
                DEVICE_STATS.note_upload(slot, time.monotonic() - t0)
                DEVICE_STATS.add_kernel_backend(slot, "xla")
                return _consensus_segments_packed2_mesh_jit(
                    cd, qd, sd, ct, et, pre, F_loc, mesh, full)
        DEVICE_STATS.add_dispatch(segments_flops(
            codes_g.shape[0], codes_g.shape[1], dp * F_loc))
        slot = DEVICE_STATS.begin_in_flight(
            upload, pack_s=time.monotonic() - t_pack0)
        psums = 2 if sp > 1 else 0
        DEVICE_STATS.note_mesh(slot, mesh.size, upload // mesh.size, psums)
        count("engine.pack", "mesh.dispatches")
        count("engine.pack", "mesh.psums", psums)
        if pred_s is not None:
            DEVICE_STATS.note_pred(slot, pred_s)
        with SHAPE_REGISTRY.attribute_compiles(new):
            ticket = DEVICE_FEEDER.submit(
                lambda: device_retry_call(lambda: _dispatch(slot),
                                          "mesh wire dispatch"),
                upload_bytes=upload, slot=slot)
        ticket.mesh_gather = mesh_gather
        ticket.mesh_devices = mesh.size
        ticket.mesh_f_loc = F_loc
        return ticket

    def resolve_segments_wire(self, ticket, codes2d: np.ndarray,
                              quals2d: np.ndarray, starts: np.ndarray,
                              _split_depth: int = 0):
        """Fetch + complete a device_call_segments_wire ticket.

        Same contract as resolve_segments: (winner, qual, depth, errors)
        (J, L) arrays, suspects recomputed exactly by the f64 oracle. A
        full-column dispatch carries device-computed depth/errors (no host
        re-walk of the dense rows); a classic 2-tuple recomputes them here. A
        dispatch/fetch failure that survived the feeder's bounded retry
        degrades instead of raising: RESOURCE_EXHAUSTED batches are halved
        and re-dispatched (output order preserved), anything else falls
        back to the native f64 host engine for this batch.

        A :class:`~fgumi_tpu.ops.coalesce.CoalescedTicket` (the dispatch
        was merged with other jobs' batches) resolves through the
        coalescer: shared fetch, this job's family slice, the identical
        host completion below — per-partner degrade on failure."""
        from .coalesce import COALESCER, CoalescedTicket

        if isinstance(ticket, CoalescedTicket):
            return COALESCER.resolve_partner(
                self, ticket, codes2d, quals2d, starts,
                split_depth=_split_depth)
        t0 = time.monotonic()
        fetched = 0
        failure = None
        d16 = e16 = None
        deadline = ticket_deadline_s(ticket)
        try:
            with span("resolve.wait", wait=True):
                dev = ticket.wait(deadline)
            left = None if deadline is None else \
                max(deadline - (time.monotonic() - t0), 1.0)
            got = _fetch_with_deadline(dev, left)
            # SDC chaos point (ops/sentinel.py): `corrupt-result` flips
            # bits in the fetched arrays exactly where a defective chip
            # would have — after the device, before any host consumer
            from ..utils import faults

            got = faults.fire("device.fetch", got)
            if len(got) == 4:
                qs, wp, d16, e16 = got
            else:
                qs, wp = got
            fetched = sum(g.nbytes for g in got)
        except BaseException as e:  # noqa: BLE001 - recovered below
            failure = e
        finally:
            # decrement even when the feeder/fetch raised — a leaked
            # in-flight count would silently route every later hybrid batch
            # to the host engine while the run still claims platform=tpu,
            # and a leaked feeder slot would stall the upload pipeline at
            # depth outstanding dispatches. A deadline overrun abandons
            # instead: the slot is reclaimed when (if) the wedged dispatch
            # finally returns, and its late result is discarded.
            DEVICE_STATS.end_in_flight(ticket.slot, fetched,
                                       time.monotonic() - t0)
            if isinstance(failure, DeadlineExceeded):
                DEVICE_FEEDER.abandon(ticket)
            else:
                DEVICE_FEEDER.mark_resolved(ticket)
        if failure is not None:
            # only device weather is recoverable; KeyboardInterrupt /
            # SystemExit and INVALID_ARGUMENT-class programming errors
            # propagate (in-flight accounting above already balanced).
            if isinstance(failure, DeadlineExceeded):
                out = self._deadline_fallback_segments(failure, codes2d,
                                                       quals2d, starts)
            elif not (_is_oom(failure) or _is_transient(failure)):
                raise failure
            else:
                out = self._recover_segments(failure, codes2d, quals2d,
                                             starts, _split_depth)
            return out
        from .breaker import BREAKER

        BREAKER.record_success()
        _feed_router(ticket, fetched)
        with span("resolve.unpack", rusage=True):
            return self._complete_wire_columns(
                qs, wp, d16, e16, codes2d, quals2d, starts,
                gather=ticket.mesh_gather, devices=ticket.mesh_devices,
                f_loc=ticket.mesh_f_loc, slot=ticket.slot)

    def _complete_wire_columns(self, qs, wp, d16, e16,
                               codes2d: np.ndarray, quals2d: np.ndarray,
                               starts, gather=None, devices: int = 1,
                               f_loc=None, slot: int = -1, partner=None):
        """Host completion of fetched wire columns: unpack, depth/error
        counts, no-call restore, f64 oracle patch, shadow-audit tap.

        The shared resolve tail of resolve_segments_wire and the
        coalescer's per-partner split (ops/coalesce.py resolves each
        partner's family slice through exactly this code, so a merged
        job's bytes can never diverge from its solo run). ``partner``:
        merge attribution forwarded to the audit sentinel — a divergence
        inside a merged dispatch names the affected partner slice."""
        J = len(starts) - 1
        if J == 0:
            L = qs.shape[-1]
            z = np.zeros((0, L))
            return (z.astype(np.uint8), z.astype(np.uint8),
                    z.astype(np.int64), z.astype(np.int64))
        if gather is not None:
            # mesh dispatch: the fetched global arrays are shard-ordered
            # (dp * F_loc rows); one host gather restores family order
            with span("resolve.mesh_gather"):
                qs = qs[gather]
                wp = wp[gather]
                if d16 is not None:
                    d16 = d16[gather]
                    e16 = e16[gather]
        winner, qual, suspect = unpack_result_split(qs, wp, J)
        if d16 is not None:
            # full-column dispatch: the device already counted depth/errors
            # (exact integer lane sums); the dense rows are not re-walked
            depth = d16[:J].astype(np.int32)
            errors = e16[:J].astype(np.int32)
        else:
            from ..native import batch as nb

            if nb.available():
                # int32 end to end (host_kernel.call_segments_counted keeps
                # the same dtype): every consumer is dtype-agnostic, so the
                # old whole-(J,L) int64 casts were pure memory traffic
                depth, errors = nb.segment_depth_errors(codes2d, winner,
                                                        starts)
            else:
                valid = (codes2d != N_CODE).astype(np.int32)
                depth = np.add.reduceat(valid, starts[:-1], axis=0)
                counts = np.diff(starts)
                winner_rows = np.repeat(winner, counts, axis=0)
                match = ((codes2d == winner_rows)
                         & (codes2d != N_CODE)).astype(np.int32)
                errors = depth - np.add.reduceat(match, starts[:-1], axis=0)
        # no-call: depth==0 is not encodable in the 2-bit winner — restore it
        # from the depth counts (device guaranteed qual=MIN_PHRED there)
        no_call = depth == 0
        if no_call.any():
            winner[no_call] = N_CODE
            qual[no_call] = MIN_PHRED
            errors[no_call] = 0
        self._count_suspects(suspect)
        if suspect.any():
            self._oracle_patch(
                suspect, winner, qual, depth, errors,
                lambda f: (codes2d[starts[f]:starts[f + 1]],
                           quals2d[starts[f]:starts[f + 1]]))
        # shadow-audit tap (ops/sentinel.py): a deterministic sample of
        # clean device resolves is re-executed on the f64 host oracle and
        # compared exactly; an inline (`all`/quarantine-probe) audit that
        # catches a divergence hands back the oracle tuple to publish
        # instead of the corrupt device buffers
        from .sentinel import SENTINEL

        repaired = SENTINEL.maybe_audit(
            self, codes2d, quals2d, starts, winner, qual, depth, errors,
            devices=devices, gather=gather, f_loc=f_loc, slot=slot,
            partner=partner)
        if repaired is not None:
            return repaired
        return winner, qual, depth, errors

    # ------------------------------------------- fused consensus→filter

    def resolve_segments_wire_filtered(self, ticket, codes2d: np.ndarray,
                                       quals2d: np.ndarray,
                                       starts: np.ndarray):
        """Resolve a ``filter_params`` wire ticket (ISSUE 11).

        Returns ``("stats", stats, resident)`` on the fused path — stats
        is the (J, 7) int32 per-read reduction fetch, resident the
        device-side (fb, fq, d16, e16, qs, wp) columns for the
        survivors-only gather — or ``("columns", winner, qual, depth,
        errors)`` when the dispatch took the >63-qual fallback or degraded
        (deadline / transient / OOM): full post-oracle columns, the
        caller's host filter pass takes over. Byte-identity holds on every
        branch by the same exactness contract as resolve_segments_wire."""
        if not ticket.filter_mode:
            out = self.resolve_segments_wire(ticket, codes2d, quals2d,
                                             starts)
            return ("columns",) + out
        t0 = time.monotonic()
        fetched = 0
        failure = None
        resident = None
        deadline = ticket_deadline_s(ticket)
        try:
            stats_dev, resident = ticket.wait(deadline)
            left = None if deadline is None else \
                max(deadline - (time.monotonic() - t0), 1.0)
            stats = _fetch_with_deadline(stats_dev, left)
            from ..utils import faults

            # fault-injection seam (tools/chaos_smoke.py): the fused
            # route's only default fetch is the stats rows — corrupt-result
            # SDC drills must be able to hit it like any other fetch
            stats = faults.fire("device.fetch", stats)
            fetched = stats.nbytes
        except BaseException as e:  # noqa: BLE001 - recovered below
            failure = e
        finally:
            DEVICE_STATS.end_in_flight(ticket.slot, fetched,
                                       time.monotonic() - t0)
            if isinstance(failure, DeadlineExceeded):
                DEVICE_FEEDER.abandon(ticket)
            else:
                DEVICE_FEEDER.mark_resolved(ticket)
        if failure is not None:
            if resident is not None:
                resident.release()
            if isinstance(failure, DeadlineExceeded):
                out = self._deadline_fallback_segments(failure, codes2d,
                                                       quals2d, starts)
            elif not (_is_oom(failure) or _is_transient(failure)):
                raise failure
            else:
                out = self._recover_segments(failure, codes2d, quals2d,
                                             np.asarray(starts, np.int64),
                                             0)
            return ("columns",) + out
        from .breaker import BREAKER

        BREAKER.record_success()
        _feed_router(ticket, fetched)
        J = len(starts) - 1
        stats = np.asarray(stats[:J])
        # fused-route audit tap (ISSUE 19, closing the PR 13 gap): the
        # sentinel re-derives the stats rows (and, inline, the survivor
        # gather) from the f64 host oracle. An inline divergence returns
        # repaired pre-threshold columns — hand those to the caller's
        # host filter pass exactly like a degraded dispatch.
        from .sentinel import SENTINEL

        repaired = SENTINEL.maybe_audit_filter(
            self, codes2d, quals2d, starts, stats, resident,
            ticket.filter_ctx, slot=ticket.slot)
        if repaired is not None:
            resident.release()
            return ("columns",) + repaired
        return ("stats", stats, resident)

    def filter_resolve_suspect_rows(self, resident, rows, starts,
                                    codes2d: np.ndarray,
                                    quals2d: np.ndarray):
        """Ordinary host completion of the fused route's suspect rows.

        Gathers the raw packed winner/qual/suspect words + depth/errors
        for ``rows`` (indices into the dispatch's J segments) off the
        resident columns, then runs exactly the standard resolve tail:
        unpack, no-call restore, f64 oracle patch over the host-side
        dense rows. Returns post-oracle (winner, qual, depth, errors)
        for those rows — PRE consensus-thresholds, like every resolve."""
        _fb, _fq, d16, e16, qs_full, wp_full = resident.arrays
        rows = np.asarray(rows, dtype=np.int64)
        got = self._filter_gather(
            (qs_full, wp_full, d16, e16), rows, "fgathr")
        qs_r, wp_r, d_r, e_r = got
        k = len(rows)
        winner, qual, suspect = unpack_result_split(qs_r, wp_r, k)
        depth = d_r[:k].astype(np.int32)
        errors = e_r[:k].astype(np.int32)
        no_call = depth == 0
        if no_call.any():
            winner[no_call] = N_CODE
            qual[no_call] = MIN_PHRED
            errors[no_call] = 0
        self._count_suspects(suspect)
        starts = np.asarray(starts, dtype=np.int64)
        if suspect.any():
            self._oracle_patch(
                suspect, winner, qual, depth, errors,
                lambda f: (codes2d[starts[rows[f]]:starts[rows[f] + 1]],
                           quals2d[starts[rows[f]]:starts[rows[f] + 1]]))
        return winner, qual, depth, errors

    def filter_gather_filtered(self, resident, rows):
        """Survivors-only gather off the fused route's resident columns:
        (masked bases u8, masked quals u8, depth i32, errors i32) for
        ``rows``, in row order — the only per-position bytes the fused
        route ever fetches."""
        fb, fq, d16, e16 = resident.arrays[:4]
        rows = np.asarray(rows, dtype=np.int64)
        fb_r, fq_r, d_r, e_r = self._filter_gather(
            (fb, fq, d16, e16), rows, "fgath")
        k = len(rows)
        return (fb_r[:k], fq_r[:k], d_r[:k].astype(np.int32),
                e_r[:k].astype(np.int32))

    def _filter_gather(self, arrays, rows, kind: str):
        """One synchronous gather dispatch over four resident arrays
        (shape-bucketed index upload, sliced fetch, the usual retry +
        accounting). Raises on device failure — the fused stage falls
        back to the host engine for the affected rows."""
        K = len(rows)
        K_pad = SHAPE_REGISTRY.bucket(K, 8)
        K_out = _pad_out_segments(K, K_pad)
        idx = np.zeros(K_pad, dtype=np.int32)
        idx[:K] = rows
        L = int(arrays[0].shape[1])
        new = SHAPE_REGISTRY.observe(kind, K_pad, L, K_out)
        DEVICE_STATS.add_dispatch(K_pad * L * 4)
        slot = DEVICE_STATS.begin_in_flight(idx.nbytes)
        t0 = time.monotonic()
        fetched = 0
        try:
            fn = (_filter_gather_raw_jit if kind == "fgathr"
                  else _filter_gather_jit)

            def _dispatch():
                _ensure_jax()
                return fn(*arrays, idx, K_out)

            with SHAPE_REGISTRY.attribute_compiles(new):
                dev = device_retry_call(_dispatch, "filter gather")
            got = DEVICE_STATS.fetch(dev)
            fetched = sum(g.nbytes for g in got)
        finally:
            DEVICE_STATS.end_in_flight(slot, fetched,
                                       time.monotonic() - t0)
        return got

    def _recover_segments(self, exc, codes2d: np.ndarray,
                          quals2d: np.ndarray, starts, split_depth: int):
        """Degraded completion of a failed segment dispatch (never changes
        output bytes — both recovery paths share the exactness contract).

        RESOURCE_EXHAUSTED with more than one segment: halve at a segment
        boundary and re-dispatch both halves through the wire path (depth
        bounded by FGUMI_TPU_MAX_SPLITS, default 4), concatenating results
        in order. Everything else — transient errors that exhausted the
        bounded retry, OOM on a single segment, or split-depth exhaustion —
        runs this batch on the native f64 host engine. Re-raises only when
        the native library is unavailable."""
        import os

        starts = np.asarray(starts, dtype=np.int64)
        J = len(starts) - 1
        max_splits = int(os.environ.get("FGUMI_TPU_MAX_SPLITS", "4"))
        # the wire layout packs 4 positions/byte, so halving re-dispatches
        # only layouts the wire path can express (L % 4 == 0)
        can_split = (_is_oom(exc) and J > 1 and split_depth < max_splits
                     and codes2d.ndim == 2 and codes2d.shape[1] % 4 == 0)
        if can_split:
            DEVICE_STATS.add_split()
            mid = J // 2
            log.warning(
                "device batch exhausted memory (%s); halving %d segments "
                "into %d + %d and re-dispatching", exc, J, mid, J - mid)
            halves = []
            from .coalesce import bypassed as _coalesce_bypassed

            # halves bypass the merge window: they exist because the
            # (possibly merged) parent OOM'd, so re-entering the window
            # could re-merge them straight back into an over-size batch
            with _coalesce_bypassed():
                for lo, hi in ((0, mid), (mid, J)):
                    row_lo, row_hi = int(starts[lo]), int(starts[hi])
                    c = codes2d[row_lo:row_hi]
                    q = quals2d[row_lo:row_hi]
                    counts = np.diff(starts[lo:hi + 1])
                    cd, qd, seg_ids, sub_starts, f_pad = pad_segments(
                        c, q, counts)
                    ticket = self.device_call_segments_wire(
                        cd, qd, seg_ids, f_pad, hi - lo)
                    halves.append((ticket, c, q, sub_starts))
            # resolve BOTH halves even if the first raises: an unresolved
            # ticket would leak its in-flight slot (and silently route
            # every later hybrid batch to the host engine)
            parts, first_exc = [], None
            for t, c, q, s in halves:
                try:
                    parts.append(self.resolve_segments_wire(
                        t, c, q, s, _split_depth=split_depth + 1))
                except BaseException as e:  # noqa: BLE001 - re-raised below
                    if first_exc is None:
                        first_exc = e
            if first_exc is not None:
                raise first_exc
            return tuple(np.concatenate([p[i] for p in parts], axis=0)
                         for i in range(4))
        from ..native import batch as nb

        if not nb.available():
            raise exc
        DEVICE_STATS.add_host_fallback()
        if not _is_oom(exc):
            # repeated permanent transient failures are breaker fuel; an
            # OOM is a sizing problem, not device weather
            from .breaker import BREAKER

            BREAKER.record_transient_failure()
        log.warning(
            "device dispatch failed after retries (%s: %s); computing "
            "batch of %d segments on the native f64 host engine",
            type(exc).__name__, exc, J)
        return self._host_engine_complete(codes2d, quals2d, starts)

    def _host_engine_complete(self, codes2d, quals2d, starts):
        """Native-f64-host-engine completion of one segment batch (the
        shared tail of every degraded path: transient-failure fallback,
        deadline abandonment). Byte-identical to the device path by the
        engines' shared exactness contract."""
        engine = self._host()
        t0 = time.monotonic()
        winner, qual, depth, errors, n_slow = engine.call_segments_counted(
            codes2d, quals2d, np.asarray(starts, dtype=np.int64))
        from .router import ROUTER

        ROUTER.observe_host(codes2d.size, time.monotonic() - t0)
        with self._counter_lock:
            self.total_positions += winner.size
            self.fallback_positions += n_slow
        return winner, qual, depth, errors

    def _deadline_fallback_segments(self, exc, codes2d, quals2d, starts):
        """Degraded completion of a dispatch abandoned at its deadline:
        count it, feed the breaker (a wedge is categorical evidence), and
        complete on the native f64 host engine. Re-raises only when the
        native library is unavailable — there is nothing to degrade to."""
        from ..native import batch as nb

        if not nb.available():
            raise exc
        from .breaker import BREAKER

        DEVICE_STATS.add_deadline_fallback()
        BREAKER.record_deadline_overrun()
        log.warning(
            "%s; abandoning the in-flight dispatch and computing batch of "
            "%d segments on the native f64 host engine",
            exc, len(starts) - 1)
        return self._host_engine_complete(codes2d, quals2d, starts)

    def device_call_segments_sharded(self, codes3d, quals3d, seg_ids2d,
                                     num_segments: int, mesh):
        """Dispatch (dp, N, L) rows, one contiguous family shard per device.

        Dryrun/test surface (``__graft_entry__.dryrun_multichip``,
        tests/test_mesh.py): production traffic routes through the wire
        mesh path (:meth:`_dispatch_wire_mesh`) instead."""
        dp, N, L = codes3d.shape
        DEVICE_STATS.add_dispatch(segments_flops(dp * N, L, dp * num_segments))
        SHAPE_REGISTRY.observe("shard", dp, N, L, num_segments)
        return _consensus_segments_sharded_jit(
            as_device_operand(codes3d), as_device_operand(quals3d),
            as_device_operand(seg_ids2d),
            self._correct_f32, self._err_f32, self._pre, num_segments, mesh)

    def device_call_segments_dp_sp(self, codes4, quals4, seg3,
                                   num_segments: int, mesh):
        """Dispatch (dp, sp, N, L) rows: family shards over dp, each shard's
        read rows over sp with a psum combine.

        Dryrun/test surface like :meth:`device_call_segments_sharded`;
        production traffic uses the wire mesh path."""
        dp, sp, N, L = codes4.shape
        DEVICE_STATS.add_dispatch(segments_flops(dp * sp * N, L,
                                                 dp * num_segments))
        SHAPE_REGISTRY.observe("shard_sp", dp, sp, N, L, num_segments)
        return _consensus_segments_dp_sp_jit(
            as_device_operand(codes4), as_device_operand(quals4),
            as_device_operand(seg3),
            self._correct_f32, self._err_f32, self._pre, num_segments, mesh)

    def resolve_segments(self, dev, codes2d: np.ndarray, quals2d: np.ndarray,
                         starts: np.ndarray):
        """Fetch + complete a device_call_segments result.

        `starts` is the (J+1,) row-boundary array of the J real segments (the
        device result may be padded to more segments; extras are dropped).
        Returns (winner, qual, depth, errors) as (J, L) arrays with suspect
        positions recomputed exactly by the f64 oracle.
        """
        if dev is HOST_DISPATCH:
            engine = self._host()
            t0 = time.monotonic()
            with span("resolve.host_engine", rusage=True):
                winner, qual, depth, errors, n_slow = \
                    engine.call_segments_counted(
                        codes2d, quals2d, np.asarray(starts, dtype=np.int64))
            from .router import ROUTER

            ROUTER.observe_host(codes2d.size, time.monotonic() - t0)
            with self._counter_lock:
                self.total_positions += winner.size
                self.fallback_positions += n_slow
            return winner, qual, depth, errors
        try:
            packed = _fetch_with_deadline(dev, dispatch_deadline_s())
            from ..utils import faults

            packed = faults.fire("device.fetch", packed)
        except DeadlineExceeded as e:
            return self._deadline_fallback_segments(e, codes2d, quals2d,
                                                    starts)
        except BaseException as e:  # noqa: BLE001 - classified below
            if not (_is_oom(e) or _is_transient(e)):
                raise
            return self._recover_segments(e, codes2d, quals2d,
                                          np.asarray(starts, np.int64), 0)
        from .breaker import BREAKER

        BREAKER.record_success()  # clean resolve: resets the failure score
        out = self._finish_segments(packed, codes2d, quals2d, starts)
        if len(starts) - 1 > 0:
            # shadow-audit tap (see resolve_segments_wire): classic
            # packed-segment dispatches are sampled/audited the same way
            from .sentinel import SENTINEL

            repaired = SENTINEL.maybe_audit(
                self, codes2d, quals2d, starts, *out)
            if repaired is not None:
                out = repaired
        return out

    def _finish_segments(self, packed: np.ndarray, codes2d, quals2d, starts):
        J = len(starts) - 1
        if J == 0:  # empty shard (more devices than jobs)
            L = packed.shape[-1]
            z = np.zeros((0, L))
            return (z.astype(np.uint8), z.astype(np.uint8),
                    z.astype(np.int32), z.astype(np.int32))
        winner, qual, suspect = _unpack_device_result(packed)
        winner = winner[:J]
        qual = qual[:J]
        suspect = suspect[:J]
        # depth/errors per segment: one native pass over the dense rows when
        # available (i32, not i16: the i16 clamp happens at tag-write time
        # downstream, matching the reference); numpy reduceat fallback
        from ..native import batch as nb

        if nb.available():
            # int32 end to end (host_kernel.call_segments_counted keeps the
            # same dtype): every consumer is dtype-agnostic, so the old
            # whole-(J,L) int64 casts were pure memory traffic
            depth, errors = nb.segment_depth_errors(codes2d, winner, starts)
        else:
            valid = (codes2d != N_CODE).astype(np.int32)
            depth = np.add.reduceat(valid, starts[:-1], axis=0)
            counts = np.diff(starts)
            winner_rows = np.repeat(winner, counts, axis=0)
            match = ((codes2d == winner_rows)
                     & (codes2d != N_CODE)).astype(np.int32)
            errors = depth - np.add.reduceat(match, starts[:-1], axis=0)
        self._count_suspects(suspect)
        if suspect.any():
            self._oracle_patch(
                suspect, winner, qual, depth, errors,
                lambda f: (codes2d[starts[f]:starts[f + 1]],
                           quals2d[starts[f]:starts[f + 1]]))
        return winner, qual, depth, errors

    def _count_suspects(self, suspect: np.ndarray):
        with self._counter_lock:
            self.total_positions += suspect.size
            self.fallback_positions += int(suspect.sum())

    def _oracle_patch(self, suspect, winner, qual, depth, errors, family_rows):
        """Recompute suspect positions exactly with the f64 oracle (in place).

        `family_rows(f) -> (codes (R, L), quals (R, L))` abstracts the layout
        difference between the uniform-R batch and the ragged segment path.

        Suspect (family, position) pairs are stacked as columns of a shared
        (R_bucket, C) pileup and recomputed in one oracle call per pow2
        family-depth bucket — accumulate_likelihoods is already vectorized
        over its position axis, and end-padding with N rows is a no-op for
        it, so this is semantically identical to the per-family loop it
        replaces while doing ~C fewer Python/NumPy round trips (the patch
        showed up at ~20% of simplex CPU wall time as a per-family loop).
        Bucketing by depth class caps pad waste at 2x, so one deep family
        cannot inflate every other column to its row count.
        """
        from . import oracle

        fam_idx, pos_idx = np.nonzero(suspect)
        fams, first = np.unique(fam_idx, return_index=True)
        bounds = np.append(first, len(fam_idx))  # fam_idx is sorted (nonzero)
        buckets = {}  # depth class -> [(R_f, P_f) codes, quals, col pair idxs]
        for i, f in enumerate(fams):
            sel = slice(bounds[i], bounds[i + 1])
            positions = pos_idx[sel]
            fam_codes, fam_quals = family_rows(f)
            cls = max(int(fam_codes.shape[0]) - 1, 0).bit_length()
            buckets.setdefault(cls, []).append(
                (fam_codes[:, positions], fam_quals[:, positions], sel))
        for cols in buckets.values():
            r_max = max(cc.shape[0] for cc, _, _ in cols)
            c_tot = sum(cc.shape[1] for cc, _, _ in cols)
            col_codes = np.full((r_max, c_tot), N_CODE, dtype=np.uint8)
            col_quals = np.zeros((r_max, c_tot), dtype=np.uint8)
            c0 = 0
            for cc, cq, _ in cols:
                col_codes[:cc.shape[0], c0:c0 + cc.shape[1]] = cc
                col_quals[:cq.shape[0], c0:c0 + cq.shape[1]] = cq
                c0 += cc.shape[1]
            w, q, d, e = oracle.call_family(col_codes, col_quals, self.tables)
            c0 = 0
            for cc, _, sel in cols:
                c1 = c0 + cc.shape[1]
                fi, pi = fam_idx[sel], pos_idx[sel]
                winner[fi, pi] = w[c0:c1]
                qual[fi, pi] = q[c0:c1]
                depth[fi, pi] = d[c0:c1]
                errors[fi, pi] = e[c0:c1]
                c0 = c1


def route_and_call_segments(kernel: "ConsensusKernel", codes2d, quals2d,
                            counts, mesh=None):
    """Route one dense (N, L) segment batch through the adaptive offload
    policy and resolve it synchronously: the host f64 engine or the
    full-column wire kernel (sharded over ``mesh`` when one with > 1 device
    is passed). Decide -> submit_dense -> resolve for the one synchronous
    caller, the classic vanilla path; the async engines (simplex pending
    chunks, duplex's deferred batches, codec) call the same entries and
    resolve later."""
    from .router import ROUTER

    route = "host"
    if not kernel.host_mode():
        route = ROUTER.decide_batch(
            kernel, codes2d.shape[0], len(counts), codes2d.shape[1],
            devices=mesh.size if mesh is not None else 1)
    return kernel.submit_dense(lambda: (codes2d, quals2d), counts, route,
                               mesh=mesh).resolve()
