"""Host<->device data-path primitives: constant cache + shape buckets.

BENCH_r05 measured the consensus kernel at 7.3 ms/dispatch while the
end-to-end dispatch cost 2.9 s (``kernel_reads_per_sec`` 8.9M vs
``kernel_e2e_reads_per_sec`` 22.5k) — a ~400x gap that is entirely
host-side: per-dispatch ``device_put`` of constant tables, unbounded
padded-shape vocabulary (cold compiles), and serialized
upload/compute/fetch. This module holds the two stateless-ish pieces of
the fix; the pipelined feeder lives with the dispatch machinery in
``ops/kernel.py``:

- :class:`DeviceConstantCache` — quality tables (``correct_tab`` /
  ``err_tab``), wire dictionaries (``dict_tab``) and any other per-run
  lookup array are ``device_put`` **once per (device, table content)**
  and the resident handle is reused by every later dispatch. Keyed by
  content, not identity, so several :class:`~fgumi_tpu.ops.kernel.ConsensusKernel`
  instances with identical error rates (and every warm serve-daemon job)
  share entries.

- :class:`ShapeBucketRegistry` — pads ``(rows, segments)`` up to a small
  geometric ladder (default x1.0625 steps, configurable via
  ``--shape-buckets`` / ``FGUMI_TPU_SHAPE_BUCKETS``) so XLA compiles a
  bounded set of executables, padding waste stays below ~6.25% worst-case
  (~3% expected), and the persistent compile cache actually hits across
  runs. Each dispatch's final padded shape is ``observe()``-d:
  ``device.shape_bucket.hits`` / ``.misses`` / ``.shapes`` land in
  METRICS, and ``device.shape_bucket.recompiles`` counts the misses that
  triggered a *real* XLA backend compile (attributed through
  ``observe/compilewatch.py`` via a context flag that travels with the
  dispatch into the device-feeder thread).

Both are process-wide singletons (:data:`CONST_CACHE`,
:data:`SHAPE_REGISTRY`): device residency and the compiled-shape
vocabulary are per-process facts, not per-job ones — the scope-resolving
``METRICS`` proxy still attributes the counters to the submitting job.
"""

import contextlib
import contextvars
import hashlib
import os
import threading
from bisect import bisect_left
from collections import OrderedDict

import numpy as np

#: default geometric growth between adjacent ladder buckets; 6.25%
#: worst-case padding waste per dispatch, ~3% in expectation.
DEFAULT_GROWTH = 1.0625
#: ladder top; row counts beyond it pad to multiples of the cap instead
#: of growing the ladder (bounded vocabulary either way).
DEFAULT_CAP = 1 << 24


def parse_shape_buckets(spec):
    """``"GROWTH[:CAP]"`` -> (growth, cap), with loud errors.

    growth: geometric step between ladder buckets, in [1.01, 2.0] (2.0 ==
    pow2 padding). cap: largest ladder value (>= 1024); sizes above it
    round to multiples of the cap. None/"" -> defaults.
    """
    from ..utils.knobs import knob_error

    grammar = "GROWTH[:CAP] with growth in [1.01, 2.0] and cap >= 1024"

    def _err(problem):
        return ValueError(knob_error("FGUMI_TPU_SHAPE_BUCKETS", spec,
                                     problem, grammar))

    if spec is None or str(spec).strip() == "":
        return DEFAULT_GROWTH, DEFAULT_CAP
    parts = str(spec).strip().split(":")
    if len(parts) > 2:
        raise _err(f"{len(parts)} ':'-separated fields")
    try:
        growth = float(parts[0])
    except ValueError:
        raise _err(f"growth {parts[0]!r} is not a number") from None
    # 1.01 floor: growths within rounding of 1.0 degenerate into a ladder
    # with one entry per alignment step — ~1M entries built up front
    if not 1.01 <= growth <= 2.0:
        raise _err(f"growth {growth} is out of range")
    cap = DEFAULT_CAP
    if len(parts) == 2:
        try:
            cap = int(parts[1])
        except ValueError:
            raise _err(f"cap {parts[1]!r} is not an integer") from None
        if cap < 1024:
            raise _err(f"cap {cap} is below the 1024 floor")
    return growth, cap


# set while a dispatch whose bucketed shape is NEW this process is being
# built/submitted, to that shape's key; it rides contextvars.copy_context()
# into the device feeder thread, so a jax backend-compile event fired there
# can be attributed to the shape miss (device.shape_bucket.recompiles, and
# the shape named in the process record's compile entries).
_MISS_FLAG = contextvars.ContextVar("fgumi_tpu_shape_miss", default=False)
#: the key of the last shape each thread observed as new (observe() and
#: attribute_compiles() run on the thread that builds the dispatch)
_last_miss = threading.local()


def compile_is_shape_miss() -> bool:
    """True when the current (context-carried) dispatch was a shape miss."""
    return bool(_MISS_FLAG.get())


def compile_shape_key():
    """The ``kind:dims`` key of the shape-miss dispatch open in this
    context, or None — called by observe/compilewatch on every
    backend-compile event."""
    return _MISS_FLAG.get() or None


def _key_str(key) -> str:
    return f"{key[0]}:{'x'.join(map(str, key[1:]))}"


class ShapeBucketRegistry:
    """Geometric bucket ladder + compiled-shape accounting.

    ``bucket_rows`` / ``bucket_segments`` quantize a dimension up to the
    ladder; ``observe`` records whether a dispatch's final padded shape
    was already seen this process (a guaranteed jit-cache hit) or is new
    (a compile candidate — the persistent cache may still absorb the
    actual XLA work, which ``device.backend_compiles`` tracks
    separately). Thread-safe; dirt cheap (one bisect + one set lookup
    per dispatch).
    """

    def __init__(self, growth=None, cap=None):
        self._lock = threading.Lock()
        self._explicit = (growth, cap) if growth is not None else None
        self._growth = growth
        self._cap = cap if cap is not None else (
            DEFAULT_CAP if growth is not None else None)
        self._ladders = {}  # align -> ascending bucket list
        self._seen = set()
        self._gen = 0  # bumped per reconfigure (guarded restores)
        self.hits = 0
        self.misses = 0

    # ------------------------------------------------------------ config

    def _config(self):
        if self._growth is None:
            self._growth, self._cap = parse_shape_buckets(
                os.environ.get("FGUMI_TPU_SHAPE_BUCKETS"))
        return self._growth, self._cap

    def reconfigure(self, spec=None, only_if_gen=None) -> int:
        """Re-read configuration (``spec`` wins over the environment) and
        drop the ladders; the seen-shape set and counters survive — the
        process's compiled executables don't go away.

        Returns a generation token. ``only_if_gen``: apply only when no
        other reconfigure happened since that token was issued — the CLI's
        per-invocation restore passes it so a finished daemon job cannot
        clobber the ladder a *later* job just configured."""
        with self._lock:
            if only_if_gen is not None and self._gen != only_if_gen:
                return self._gen
            if spec is not None:
                self._growth, self._cap = parse_shape_buckets(spec)
            else:
                self._growth = self._cap = None
                if self._explicit is not None:
                    self._growth, self._cap = self._explicit
            self._ladders.clear()
            self._gen += 1
            return self._gen

    def reset(self):
        """Forget seen shapes + counters (tests; per-process otherwise)."""
        with self._lock:
            self._seen.clear()
            self.hits = 0
            self.misses = 0

    def shape_keys(self, limit: int = 128):
        """Every bucketed dispatch shape this process has seen, as sorted
        ``kind:d0xd1x...`` strings (run report ``device.shapes``): which
        executables a run needed, so two runs' compile counts can be
        compared shape for shape even when the router split their batches
        differently."""
        with self._lock:
            keys = sorted(_key_str(k) for k in self._seen)
        return keys[:limit]

    # ------------------------------------------------------------ ladder

    def _ladder(self, align: int):
        lad = self._ladders.get(align)
        if lad is None:
            growth, cap = self._config()
            v = max(align, 8)
            lad = [v]
            while v < cap:
                nxt = -(-int(v * growth) // align) * align
                v = max(nxt, v + align)  # strictly increasing
                lad.append(min(v, -(-cap // align) * align))
            self._ladders[align] = lad
        return lad

    def bucket(self, n: int, align: int = 16) -> int:
        """Smallest ladder value >= n (multiples of the cap above it)."""
        n = max(int(n), 1)
        with self._lock:
            lad = self._ladder(align)
            if n > lad[-1]:
                cap = lad[-1]
                return -(-n // cap) * cap
            return lad[bisect_left(lad, n)]

    def bucket_rows(self, n: int) -> int:
        """Padded row count for a dense (N, L) dispatch layout."""
        return self.bucket(n, 16)

    def bucket_segments(self, j: int) -> int:
        """Padded segment count (static ``num_segments`` jit arg).

        Multiples of 8 keep ``_pad_out_segments``'s fetch-slice arithmetic
        and the hard-column 4-per-byte winner packing exact.
        """
        return self.bucket(max(j, 1), 8)

    def bucket_segments_sharded(self, j: int, parts: int) -> int:
        """Per-shard segment count for a family axis split ``parts`` ways.

        Quantizes ``ceil(j / parts)`` up the SAME 8-aligned ladder as the
        single-device ``bucket_segments``, so the global family axis rounds
        to ``parts * F_loc`` (a multiple of the mesh's dp by construction)
        while each shard's static jit shape comes from the one fleet-wide
        shape vocabulary — a dp=4 run and a dp=8 run compile the same
        per-shard executables when their shard sizes land on the same
        ladder rung (ISSUE 10: one vocabulary across mesh sizes)."""
        parts = max(int(parts), 1)
        return self.bucket(max(-(-int(j) // parts), 1), 8)

    # ------------------------------------------------------- observation

    def observe(self, kind: str, *dims) -> bool:
        """Record a dispatch's final padded shape; True when new.

        Folds ``device.shape_bucket.{hits,misses}`` counters and the
        ``.shapes`` distinct-count gauge into METRICS (submitter scope).
        """
        key = (kind, *map(int, dims))
        with self._lock:
            new = key not in self._seen
            if new:
                self._seen.add(key)
                self.misses += 1
                _last_miss.key = _key_str(key)
            else:
                self.hits += 1
            n_shapes = len(self._seen)
        from ..observe.metrics import METRICS

        METRICS.inc("device.shape_bucket.misses" if new
                    else "device.shape_bucket.hits")
        METRICS.set("device.shape_bucket.shapes", n_shapes)
        return new

    @staticmethod
    @contextlib.contextmanager
    def attribute_compiles(is_miss: bool):
        """Flag the surrounding dispatch build/submit as a shape miss so a
        backend compile it triggers counts as ``.recompiles`` (the flag
        travels into the feeder via its context copy)."""
        if not is_miss:
            yield
            return
        token = _MISS_FLAG.set(getattr(_last_miss, "key", None) or "new")
        try:
            yield
        finally:
            _MISS_FLAG.reset(token)


class DeviceConstantCache:
    """Content-keyed cache of device-resident constant arrays.

    ``put(name, arr)`` returns a device handle for ``arr``, uploading at
    most once per (default device, name, content) per process. The
    quality tables are a few hundred bytes each — the win is not the
    bytes, it's skipping a blocking ``device_put`` round-trip per table
    per dispatch on a link where small transfers cost hundreds of ms of
    latency (DeviceFeeder docstring).

    LRU-bounded (pathological inputs could mint a new wire dictionary per
    batch); ``invalidate()`` drops every handle — called before a
    transient-error retry, since the device runtime may have restarted
    under us and old buffers died with it.
    """

    MAX_ENTRIES = 256

    def __init__(self):
        self._lock = threading.Lock()
        self._entries = OrderedDict()
        self.hits = 0
        self.uploads = 0
        self.upload_bytes = 0

    @staticmethod
    def _fingerprint(arr: np.ndarray):
        raw = arr.tobytes()
        if len(raw) > 4096:
            raw = hashlib.blake2b(raw, digest_size=16).digest()
        return arr.dtype.str, arr.shape, raw

    @staticmethod
    def _is_pending(entry) -> bool:
        return isinstance(entry, tuple) and entry and entry[0] == "pending"

    def put(self, name: str, arr: np.ndarray, sharding=None):
        """Device-resident handle for ``arr`` (jax must be initialized —
        callers sit inside dispatch closures, after ``_ensure_jax``).

        ``sharding``: optional ``jax.sharding.Sharding`` (the mesh compile
        path passes a replicated ``NamedSharding`` so constants live on
        every chip of the mesh); keyed into the cache alongside the
        content, so single-device and mesh dispatches of the same tables
        coexist without thrashing each other's residency.

        At-most-once per (device, content) even under concurrent misses
        (the sync dispatch paths run on arbitrary resolve workers, not
        just the feeder): the first thread to miss installs a pending
        marker under the lock and uploads with the lock RELEASED — a
        ``device_put`` blocks for the whole transfer, and holding
        the cache lock for it would serialize every other dispatch thread
        behind one upload. Racing threads wait on the marker's event and
        re-read."""
        import jax

        if sharding is not None:
            dev = sharding
            placement = ("mesh",
                         tuple(sorted(d.id for d in sharding.device_set)),
                         str(getattr(sharding, "spec", "")))
        else:
            dev = jax.devices()[0]
            placement = (dev.platform, dev.id)
        key = (*placement, name, *self._fingerprint(arr))
        from ..observe.metrics import METRICS
        from .kernel import DEVICE_STATS

        while True:
            with self._lock:
                entry = self._entries.get(key)
                if entry is None:
                    marker = ("pending", threading.Event())
                    self._entries[key] = marker
                    break  # this thread owns the upload
                if not self._is_pending(entry):
                    self._entries.move_to_end(key)
                    self.hits += 1
                    hit_handle = entry
                else:
                    hit_handle = None
            if hit_handle is not None:
                METRICS.inc("device.const_cache.hits")
                DEVICE_STATS.add_const_hit()
                return hit_handle
            entry[1].wait()  # another thread is uploading; re-read
        try:
            handle = jax.device_put(arr, dev)
        except BaseException:
            with self._lock:
                if self._entries.get(key) is marker:
                    del self._entries[key]
            marker[1].set()
            raise
        with self._lock:
            # only publish if our marker survived (an invalidate() during
            # the upload means the handle may point at dead device state)
            if self._entries.get(key) is marker:
                self._entries[key] = handle
            self.uploads += 1
            self.upload_bytes += arr.nbytes
            while len(self._entries) > self.MAX_ENTRIES:
                for k in list(self._entries):
                    if not self._is_pending(self._entries[k]):
                        del self._entries[k]
                        break
                else:
                    break
        marker[1].set()
        METRICS.inc("device.const_cache.misses")
        METRICS.inc("device.const_cache.bytes_uploaded", arr.nbytes)
        DEVICE_STATS.add_const_upload(arr.nbytes)
        return handle

    def invalidate(self):
        """Drop every cached handle (device weather: next dispatch
        re-uploads fresh)."""
        with self._lock:
            self._entries.clear()

    def reset(self):
        """invalidate + zero the counters (tests)."""
        with self._lock:
            self._entries.clear()
            self.hits = 0
            self.uploads = 0
            self.upload_bytes = 0

    def __len__(self):
        with self._lock:
            return len(self._entries)


class HostStagingPool:
    """Recycled host staging buffers for the upload path (ISSUE 11).

    The wire build used to mint a fresh (N_pad, L) array per dispatch; with
    the shape-bucket ladder bounding the vocabulary of padded shapes, a
    small keyed free-list turns that into zero per-dispatch staging
    allocations after warm-up (tests/test_device_filter.py
    test_staging_pool_reuses_after_warmup gates on exactly this). Buffers
    are released back at dispatch *resolve* time — by then the device has
    consumed the upload even on backends where ``device_put`` aliases host
    memory — via the feeder's ``mark_resolved`` (an abandoned/wedged
    dispatch leaks its buffer rather than risking a recycle under a
    still-running upload).

    Bounded by ``FGUMI_TPU_STAGING_POOL`` bytes (default 64 MiB; ``0``
    disables pooling entirely): the free list evicts oldest-first, and a
    buffer larger than the whole budget is simply never pooled.
    """

    def __init__(self, max_bytes: int = None):
        self._lock = threading.Lock()
        self._max_bytes = max_bytes
        self._free = {}          # (shape, dtype.str) -> [ndarray]
        self._order = []         # FIFO of keys for eviction
        self._held_bytes = 0
        self.allocs = 0
        self.reuses = 0

    def _budget(self) -> int:
        if self._max_bytes is None:
            try:
                self._max_bytes = max(
                    int(os.environ.get("FGUMI_TPU_STAGING_POOL",
                                       str(64 << 20))), 0)
            except ValueError:
                self._max_bytes = 64 << 20
        return self._max_bytes

    def acquire(self, shape, dtype) -> np.ndarray:
        """A writable array of exactly (shape, dtype) — recycled when one
        is free, freshly allocated (and counted) otherwise."""
        key = (tuple(shape), np.dtype(dtype).str)
        with self._lock:
            lst = self._free.get(key)
            if lst:
                arr = lst.pop()
                self._held_bytes -= arr.nbytes
                # keep the FIFO in lockstep with the free lists: one entry
                # per HELD buffer, so a steady acquire/release cycle cannot
                # grow it without bound
                self._order.remove(key)
                self.reuses += 1
                from ..observe.metrics import METRICS
                from ..observe.trace import count

                METRICS.inc("device.staging.reuses")
                count("engine.pack", "staging_reuses")
                return arr
            self.allocs += 1
        from ..observe.metrics import METRICS
        from ..observe.trace import count

        METRICS.inc("device.staging.allocs")
        count("engine.pack", "staging_allocs")
        return np.empty(shape, dtype=dtype)

    def acquire_filled(self, shape, dtype, fill) -> np.ndarray:
        """``acquire`` + constant fill: the coalescer's merged row layouts
        (ops/coalesce.py) start as all-pad buffers (N_CODE codes / zero
        quals) that partner blocks are copied into, so merged builds mint
        zero fresh allocations once the shape vocabulary is warm — the
        same recycling contract as the wire staging buffers."""
        arr = self.acquire(shape, dtype)
        arr.fill(fill)
        return arr

    def release(self, arr: np.ndarray):
        """Return a buffer to the pool (drop it when over budget)."""
        if arr is None:
            return
        budget = self._budget()
        if budget <= 0 or arr.nbytes > budget:
            return
        key = (arr.shape, arr.dtype.str)
        with self._lock:
            self._free.setdefault(key, []).append(arr)
            self._order.append(key)
            self._held_bytes += arr.nbytes
            while self._held_bytes > budget and self._order:
                old = self._order.pop(0)
                lst = self._free.get(old)
                if lst:
                    dropped = lst.pop(0)
                    self._held_bytes -= dropped.nbytes

    def snapshot(self):
        with self._lock:
            return {"allocs": self.allocs, "reuses": self.reuses,
                    "held_bytes": self._held_bytes}

    def reset(self):
        with self._lock:
            self._free.clear()
            self._order.clear()
            self._held_bytes = 0
            self.allocs = 0
            self.reuses = 0
            self._max_bytes = None


def as_device_operand(a, dtype=None):
    """``a`` itself when it is already a C-contiguous ndarray (of
    ``dtype``, when given), else one conversion copy. The dispatch paths
    used to run every operand through ``np.asarray`` /
    ``np.ascontiguousarray`` unconditionally; those are no-ops for the
    common already-dense case, but this makes the no-copy contract
    explicit and catches the genuinely strided inputs (sliced views,
    transposed gathers) that would otherwise force ``device_put`` to copy
    internally. The one rule for both the jax dispatch operands and the
    native C++ entry points (``native/batch._as_c`` is an alias).
    Regression-benched in microbench.py (``dispatch_prep_*``)."""
    if (isinstance(a, np.ndarray) and a.flags.c_contiguous
            and (dtype is None or a.dtype == dtype)):
        return a
    return np.ascontiguousarray(a, dtype)


#: process-wide singletons (see module docstring).
SHAPE_REGISTRY = ShapeBucketRegistry()
CONST_CACHE = DeviceConstantCache()
STAGING_POOL = HostStagingPool()
