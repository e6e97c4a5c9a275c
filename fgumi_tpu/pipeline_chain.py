"""Fused streaming chain: in-memory stage handoff for the pipeline command.

The reference ships FastqToConsensus as a Snakemake workflow over separate
process invocations (/root/reference/docs/FastqToConsensus-RnD.smk); our
``pipeline`` command chained the stages in one process but still
materialized full intermediate BAMs — four complete serialize+BGZF-encode
passes and four decompress+parse passes per run, with zero overlap between
stages. This module removes the files entirely: adjacent stages hand off
uncompressed BAM *wire chunks* (block_size-prefixed record runs, exactly
the bytes a level-0 intermediate would carry between its BGZF frames)
through a bounded in-memory channel, so

- the producer's serialized output feeds the consumer with no BGZF encode,
  no file write, no file read, and no BGZF decode in between;
- stages genuinely overlap (each runs on its own thread, blocking on the
  channel's byte budget for backpressure);
- byte identity with the staged run holds by construction: the handed-off
  bytes ARE the record wire bytes a file round trip would deliver, and
  headers travel through :func:`fgumi_tpu.io.bam.header_roundtrip` so
  header-derived provenance (@HD rewrites, @PG chaining) sees exactly what
  a decode-from-file would have produced.

Three pieces:

- :class:`ChainChannel` — the bounded, byte-budgeted blob queue with
  backpressure, abort/cancel propagation in both directions, the
  ``chain.handoff`` fault point, and ``pipeline.chain.*`` metrics.
- :class:`ChannelBamWriter` — a ``BamWriter``-compatible sink writing into
  a channel (the writer-to-channel adapter; pairs with
  ``io.bam.header_roundtrip`` for exact header handoff).
- :class:`ChannelBatchReader` — a ``BamBatchReader``-compatible source
  assembling channel blobs into :class:`~fgumi_tpu.io.batch_reader.RecordBatch`
  objects (the reader-from-batches adapter; shares the boundary-scan
  assembler with the file reader, so re-chunking behavior is identical).

The fused topology itself (extract ⇒ sort-ingest overlapped, sort-merge as
the natural barrier, group ⇒ simplex ⇒ filter as one streaming segment)
lives in ``cli.cmd_pipeline``; this module is deliberately topology-free.
"""

import logging
import struct
import threading
import time
from collections import deque

import numpy as np

log = logging.getLogger("fgumi_tpu")

#: Default per-channel byte budget. Two wire chunks of the default 16 MiB
#: batch target fit with headroom; FGUMI_TPU_CHAIN_BYTES overrides.
DEFAULT_CHANNEL_BYTES = 64 << 20


class ChainAborted(RuntimeError):
    """Control-flow signal inside a fused chain: the stage at the *other*
    end of a channel failed (or the driver cancelled the run), so this
    stage should unwind quietly — it is a cascade victim, not the root
    cause. Stage runners catch this and report "aborted" instead of an
    error of their own."""


def channel_bytes_budget() -> int:
    """Per-channel byte budget: FGUMI_TPU_CHAIN_BYTES or the default."""
    import os

    raw = os.environ.get("FGUMI_TPU_CHAIN_BYTES", "")
    if not raw.strip():
        return DEFAULT_CHANNEL_BYTES
    try:
        n = int(raw)
        if n <= 0:
            raise ValueError
        return n
    except ValueError:
        log.warning("FGUMI_TPU_CHAIN_BYTES=%s: not a positive integer; "
                    "using default %d", raw, DEFAULT_CHANNEL_BYTES)
        return DEFAULT_CHANNEL_BYTES


class ChainChannel:
    """Bounded in-memory handoff between two pipeline stages.

    Carries a header (published once by the producer, awaited by the
    consumer) followed by a stream of wire-chunk blobs (``bytes``,
    ``bytearray`` or uint8 ``ndarray``). Producers block while admitting
    another blob would exceed the byte budget — except that one blob is
    always admitted, so an oversized chunk degrades to serial flow instead
    of deadlocking (the same discipline as ``pipeline._ByteBudget``).

    Failure propagation is bidirectional: :meth:`abort` (producer died)
    makes every consumer call raise :class:`ChainAborted`; :meth:`cancel`
    (consumer died) makes every producer call raise it. Both are
    idempotent and keep the first reason.

    Every :meth:`put` passes through the ``chain.handoff`` fault point
    (kinds ``raise``/``oom``/``hang``/``corrupt-bytes``), so chaos tests can
    prove a mid-chain failure exits 3, commits no final output, and leaves
    no temp files behind.
    """

    def __init__(self, name: str, max_bytes: int = None):
        from .utils.governor import GOVERNOR, DynamicBudget

        self.name = name
        # the channel keeps its own byte accounting under its own condition
        # (header + blobs + cancel state share it); the DynamicBudget is
        # the governed *limit* holder. An explicit max_bytes (tests, tools)
        # stays static; the default budget registers with the process-wide
        # governor so a contended channel can borrow bytes from idle ones.
        if max_bytes is None:
            self._budget = DynamicBudget(f"chain.{name}",
                                         channel_bytes_budget())
            self._gov_token = GOVERNOR.register_budget(
                self._budget, demand_fn=self._demand)
        else:
            self._budget = DynamicBudget(f"chain.{name}", int(max_bytes),
                                         damp_s=0.0)
            self._gov_token = None
        # a grown budget must release producers already blocked on it
        self._budget.on_resize = self._notify_waiters
        self._cv = threading.Condition()
        self._header = None
        self._have_header = False
        self._blobs = deque()  # FIFO
        self._bytes = 0
        self._closed = False
        self._cancelled = False
        self._abort_reason = None
        # counters folded into METRICS once by fold_metrics()
        self.n_blobs = 0
        self.total_bytes = 0
        self.peak_bytes = 0
        self.put_wait_s = 0.0
        self.get_wait_s = 0.0
        self._metrics_folded = False
        from .utils import faults

        self._fault_armed = faults.armed("chain.handoff")
        from .observe import trace as _trace

        self._trace_on = _trace.tracing_enabled()

    @property
    def max_bytes(self) -> int:
        """The current (possibly governor-adjusted) byte budget."""
        return self._budget.limit

    def _demand(self) -> dict:
        """Live wait counters for the governor's rebalance tick: put_wait
        growing = producer starved on this budget; get_wait growing =
        consumer starved (budget irrelevant — a donor)."""
        return {"put_wait_s": self.put_wait_s,
                "get_wait_s": self.get_wait_s,
                "used": self._bytes}

    def _notify_waiters(self):
        with self._cv:
            self._cv.notify_all()

    def _ungovern(self):
        from .utils.governor import GOVERNOR

        GOVERNOR.unregister_budget(self._gov_token)
        self._gov_token = None

    # ------------------------------------------------------------- producer

    def put_header(self, header) -> None:
        """Publish the stream header (a ``BamHeader``), exactly as a file
        round trip would deliver it (see ``io.bam.header_roundtrip``)."""
        from .io.bam import header_roundtrip

        hdr = header_roundtrip(header)
        with self._cv:
            if self._cancelled or self._abort_reason is not None:
                raise ChainAborted(self._reason_locked())
            self._header = hdr
            self._have_header = True
            self._cv.notify_all()

    def put(self, blob) -> None:
        """Hand one wire-chunk blob to the consumer (blocks on the byte
        budget; ownership transfers — the producer must not reuse a
        mutable blob after putting it)."""
        if self._fault_armed:
            from .utils import faults

            blob = faults.fire("chain.handoff", blob)
            if blob is None:
                return
        n = len(blob)
        if n == 0:
            # an empty blob carries nothing, and the consumer's assembler
            # treats an empty chunk as end-of-stream — never enqueue one
            return
        if self._trace_on:
            from .observe.trace import span

            with span("chain.put", wait=True, channel=self.name, bytes=n):
                self._put(blob, n)
        else:
            self._put(blob, n)

    def _put(self, blob, n: int) -> None:
        from .utils.governor import GOVERNOR

        t0 = time.monotonic()
        with self._cv:
            while (self._bytes > 0 and self._bytes + n > self.max_bytes
                   and not self._cancelled
                   and self._abort_reason is None):
                # hard pressure fails the producing stage cleanly (exit 4,
                # chain abort cascade) instead of queueing into an OOM
                GOVERNOR.check_hard()
                self._cv.wait(0.1)
            if self._cancelled or self._abort_reason is not None:
                raise ChainAborted(self._reason_locked())
            if self._closed:
                raise RuntimeError(
                    f"chain channel {self.name}: put after close")
            self._blobs.append(blob)
            self._bytes += n
            self.n_blobs += 1
            self.total_bytes += n
            self.peak_bytes = max(self.peak_bytes, self._bytes)
            wait = time.monotonic() - t0
            self.put_wait_s += wait
            self._cv.notify_all()
        from .observe.metrics import METRICS

        METRICS.observe("pipeline.chain.put_wait_s", wait)

    def close(self) -> None:
        """Producer EOF: the consumer drains remaining blobs, then sees end
        of stream. Idempotent."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._ungovern()  # no more puts: stop competing for the cap

    def abort(self, reason: str) -> None:
        """Producer-side failure: every pending and future consumer call
        raises :class:`ChainAborted`. Idempotent (first reason wins)."""
        with self._cv:
            if self._abort_reason is None:
                self._abort_reason = reason
            self._closed = True
            self._blobs.clear()
            self._bytes = 0
            self._cv.notify_all()
        self._ungovern()

    @property
    def has_header(self) -> bool:
        """True once the producer has published the stream header (a
        non-blocking peek — the fused driver's heartbeat gauge uses it to
        tell a stage that is actually consuming from one still parked in
        its ``header`` wait)."""
        with self._cv:
            return self._have_header

    # ------------------------------------------------------------- consumer

    @property
    def header(self):
        """The stream's ``BamHeader`` (blocks until the producer publishes;
        raises :class:`ChainAborted` if it never will)."""
        from .observe.trace import span

        with span("chain.header", wait=True, channel=self.name), self._cv:
            while not self._have_header:
                if self._abort_reason is not None or self._cancelled:
                    raise ChainAborted(self._reason_locked())
                if self._closed:
                    raise ChainAborted(
                        f"chain channel {self.name}: closed with no header")
                self._cv.wait(0.1)
            return self._header

    def get(self):
        """Next blob, or None at end of stream."""
        if self._trace_on:
            from .observe.trace import span

            with span("chain.get", wait=True, channel=self.name):
                return self._get()
        return self._get()

    def _get(self):
        from .observe.metrics import METRICS

        t0 = time.monotonic()
        with self._cv:
            while True:
                if self._abort_reason is not None:
                    raise ChainAborted(self._reason_locked())
                if self._cancelled:
                    raise ChainAborted(self._reason_locked())
                if self._blobs:
                    blob = self._blobs.popleft()
                    self._bytes -= len(blob)
                    wait = time.monotonic() - t0
                    self.get_wait_s += wait
                    self._cv.notify_all()
                    break
                if self._closed:
                    self.get_wait_s += time.monotonic() - t0
                    return None
                self._cv.wait(0.1)
        # observe outside the channel lock (same discipline as put): the
        # registry lock must not extend this CV's critical section
        METRICS.observe("pipeline.chain.get_wait_s", wait)
        return blob

    def cancel(self) -> None:
        """Consumer-side failure / early exit: every blocked or future
        producer call raises :class:`ChainAborted`; buffered blobs are
        dropped. Idempotent."""
        with self._cv:
            self._cancelled = True
            self._blobs.clear()
            self._bytes = 0
            self._cv.notify_all()
        self._ungovern()

    def _reason_locked(self) -> str:
        if self._abort_reason is not None:
            return self._abort_reason
        return f"chain channel {self.name}: consumer cancelled"

    # -------------------------------------------------------------- metrics

    def fold_metrics(self) -> None:
        """Fold this channel's counters into METRICS under
        ``pipeline.chain.<name>.*`` (once; the driver calls this in its
        finally so failed runs still report)."""
        if self._metrics_folded:
            return
        self._metrics_folded = True
        self._ungovern()
        from .observe.metrics import METRICS

        p = f"pipeline.chain.{self.name}"
        METRICS.inc(f"{p}.batches", self.n_blobs)
        METRICS.inc(f"{p}.bytes", self.total_bytes)
        METRICS.max(f"{p}.peak_bytes", self.peak_bytes)
        METRICS.inc(f"{p}.put_wait_s", round(self.put_wait_s, 6))
        METRICS.inc(f"{p}.get_wait_s", round(self.get_wait_s, 6))
        # final (possibly governor-adjusted) budget + resize counters, so
        # a run report shows where the rebalancer moved bytes
        METRICS.set(f"{p}.budget_limit", self.max_bytes)
        if self._budget.grows or self._budget.shrinks:
            METRICS.inc(f"{p}.budget_grows", self._budget.grows)
            METRICS.inc(f"{p}.budget_shrinks", self._budget.shrinks)


class ChannelBamWriter:
    """``BamWriter``-compatible sink writing wire chunks into a channel.

    Small writes coalesce into ~``chunk_bytes`` blobs (one channel handoff
    per chunk, not per record); blobs already at or above the chunk size
    pass through with no copy after the pending buffer flushes, so a
    producer that hands over large wire chunks (the native serializers, the
    sort merge) pays zero re-buffering.
    """

    def __init__(self, channel: ChainChannel, header,
                 chunk_bytes: int = 1 << 20):
        self._chan = channel
        self._chunk_bytes = int(chunk_bytes)
        self._buf = bytearray()
        self._closed = False
        channel.put_header(header)

    def write_record_bytes(self, data: bytes) -> None:
        self._buf += struct.pack("<I", len(data))
        self._buf += data
        if len(self._buf) >= self._chunk_bytes:
            self._flush()

    def write_record(self, rec) -> None:
        self.write_record_bytes(rec.data)

    def write_serialized(self, blob) -> None:
        """Append records already carrying their block_size prefixes."""
        if len(blob) >= self._chunk_bytes:
            self._flush()
            self._chan.put(blob)
            return
        self._buf += memoryview(blob)
        if len(self._buf) >= self._chunk_bytes:
            self._flush()

    def _flush(self) -> None:
        if self._buf:
            # hand over a fresh buffer (the channel owns it from here); a
            # bytearray, not bytes, so the consumer can wrap it writable
            # without a second copy
            self._chan.put(bytearray(self._buf))
            self._buf.clear()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._flush()
        self._chan.close()

    def discard(self) -> None:
        """Abandon the stream (error path): the consumer sees an abort, not
        a truncated-looking EOF."""
        if self._closed:
            return
        self._closed = True
        self._buf.clear()
        self._chan.abort(
            f"chain channel {self._chan.name}: producer discarded output")

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self.close()
        else:
            self.discard()


class ChannelBatchReader:
    """``BamBatchReader``-compatible source decoding channel blobs into
    :class:`~fgumi_tpu.io.batch_reader.RecordBatch` objects.

    Re-chunks the producer's blob stream to ``target_bytes`` batches with
    the same accumulate → boundary-scan → tail-carry assembler the file
    reader uses (``io.batch_reader._BatchAssembler``), so a fused stage
    sees batches shaped like the file-backed run's. The single-blob case
    wraps the producer's buffer directly — no extra copy (the microbench
    ``chain_rechunk`` entry pins this). With ``writable=True`` (the safe
    default) read-only blobs (plain ``bytes``) are copied once, because
    ``RecordBatch.buf`` must be mutable for in-place edits like simplex's
    overlap correction or filter's native base masking; a consumer known
    to only *read* its batches (sort ingest, group) passes
    ``writable=False`` and skips that copy. The read-only flag is a
    guard against *numpy-level* writes only — native calls that take the
    raw pointer bypass it — so opt out strictly for consumers whose whole
    path is known read-only.
    """

    def __init__(self, channel: ChainChannel, target_bytes: int = 16 << 20,
                 writable: bool = True):
        from .io.batch_reader import _BatchAssembler

        self._chan = channel
        self._writable = writable
        self._asm = _BatchAssembler(self._read_chunk, target_bytes)
        self._exhausted = False

    @property
    def header(self):
        return self._chan.header

    def _read_chunk(self) -> np.ndarray:
        blob = self._chan.get()
        if blob is None:
            self._exhausted = True
            return np.empty(0, dtype=np.uint8)
        if isinstance(blob, np.ndarray):
            return blob
        arr = np.frombuffer(blob, dtype=np.uint8)
        if self._writable and not arr.flags.writeable:
            # this consumer mutates batches in place (overlap correction);
            # an immutable handoff pays one counted copy here
            arr = arr.copy()
            from .observe.metrics import METRICS

            METRICS.inc(f"pipeline.chain.{self._chan.name}.copies")
        return arr

    def __iter__(self):
        return iter(self._asm)

    def close(self) -> None:
        if not self._exhausted:
            # early exit (stage failed downstream of this reader): release
            # a producer blocked on the byte budget
            self._chan.cancel()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
