"""Dispatch-deadline units: env parsing, ticket abandonment + late-result
discard (the feeder slot is reclaimed and the next batch is not
corrupted), and the wedge-to-host-fallback path end to end on the CPU
backend. Hang durations are kept ~1s so the suite stays fast."""

import threading
import time

import numpy as np
import pytest

from fgumi_tpu.ops import breaker as breaker_mod
from fgumi_tpu.ops.kernel import (DEVICE_FEEDER, DEVICE_STATS,
                                  DeadlineExceeded, ConsensusKernel,
                                  dispatch_deadline_s, pad_segments)
from fgumi_tpu.ops.tables import quality_tables
from fgumi_tpu.utils import faults


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("FGUMI_TPU_FAULT", raising=False)
    faults.reset()
    breaker_mod.BREAKER.reset()
    yield
    faults.reset()
    breaker_mod.BREAKER.reset()
    # the wedge/fallback paths fed the process-global router EWMAs with
    # degenerate tiny-batch samples; leave later tests a pristine model
    from fgumi_tpu.ops.router import ROUTER

    ROUTER.reset()


# ---------------------------------------------------------------- env parse

def test_deadline_defaults(monkeypatch):
    monkeypatch.delenv("FGUMI_TPU_DISPATCH_DEADLINE_S", raising=False)
    assert dispatch_deadline_s() == 300.0          # ceiling, no prediction
    assert dispatch_deadline_s(0.001) == 30.0      # floor
    assert dispatch_deadline_s(10.0) == 200.0      # pred x factor(20)


def test_deadline_spec_forms(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", "60")
    assert dispatch_deadline_s() == 60.0
    assert dispatch_deadline_s(0.001) == 30.0      # default floor kept
    monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", "2:8")
    assert dispatch_deadline_s() == 8.0
    assert dispatch_deadline_s(0.001) == 2.0
    assert dispatch_deadline_s(1.0) == 8.0         # clamped to ceiling
    monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", "10")
    assert dispatch_deadline_s(0.001) == 10.0      # floor <= ceiling


def test_deadline_disabled(monkeypatch):
    for spec in ("0", "off", "inf"):
        monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", spec)
        assert dispatch_deadline_s() is None
        assert dispatch_deadline_s(5.0) is None


def test_deadline_garbage_falls_back(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", "fast:please")
    assert dispatch_deadline_s() == 300.0


# ----------------------------------------------------- feeder abandonment

def test_ticket_wait_timeout_raises():
    gate = threading.Event()
    ticket = DEVICE_FEEDER.submit(lambda: gate.wait(5))
    with pytest.raises(DeadlineExceeded):
        ticket.wait(0.05)
    gate.set()
    DEVICE_FEEDER.abandon(ticket)
    assert DEVICE_FEEDER.drain(timeout=5)


def test_abandon_reclaims_slot_on_late_completion():
    """A wedged dispatch holds its feeder slot only until it (eventually)
    returns; the late result is discarded and later submissions run."""
    release = threading.Event()
    t1 = DEVICE_FEEDER.submit(lambda: release.wait(10) or "late",
                              upload_bytes=1)
    with pytest.raises(DeadlineExceeded):
        t1.wait(0.05)
    DEVICE_FEEDER.abandon(t1)
    release.set()
    # the abandoned item's completion must release the in-flight slot
    deadline = time.monotonic() + 5
    while DEVICE_FEEDER._inflight and time.monotonic() < deadline:
        time.sleep(0.01)
    assert DEVICE_FEEDER._inflight == 0
    # and the pipeline still works: fresh submissions resolve normally
    t2 = DEVICE_FEEDER.submit(lambda: "fresh", upload_bytes=1)
    assert t2.wait(5) == "fresh"
    DEVICE_FEEDER.mark_resolved(t2)


def test_abandon_while_queued_never_runs():
    """An abandoned still-queued item is skipped, not executed — queued
    work behind a wedge must not hang the feeder again later."""
    gate = threading.Event()
    ran = []
    t1 = DEVICE_FEEDER.submit(lambda: gate.wait(10))
    t2 = DEVICE_FEEDER.submit(lambda: ran.append(1))
    with pytest.raises(DeadlineExceeded):
        t2.wait(0.05)
    DEVICE_FEEDER.abandon(t2)
    gate.set()
    DEVICE_FEEDER.abandon(t1)
    assert DEVICE_FEEDER.drain(timeout=5)
    assert not ran
    with pytest.raises(DeadlineExceeded):
        t2.wait(0)


def test_abandon_after_completion_is_safe():
    ticket = DEVICE_FEEDER.submit(lambda: 42, upload_bytes=1)
    assert ticket.wait(5) == 42
    DEVICE_FEEDER.abandon(ticket)  # acts as mark_resolved
    assert DEVICE_FEEDER._inflight == 0
    DEVICE_FEEDER.mark_resolved(ticket)  # idempotent


# ------------------------------------------------------- deadline runner

def test_deadline_runner_reuses_worker():
    """Steady state must not pay a thread-create per call: consecutive
    bounded calls run on the same helper thread."""
    from fgumi_tpu.ops.kernel import _DeadlineRunner

    r = _DeadlineRunner("test-runner", "test.runner_wait")
    names = [r.run(lambda: threading.current_thread().name, 5, "probe")
             for _ in range(4)]
    assert len(set(names)) == 1


def test_deadline_runner_replaces_wedged_worker():
    """A worker that blows its deadline is abandoned; the next call gets a
    fresh worker and still completes."""
    from fgumi_tpu.ops.kernel import _DeadlineRunner

    r = _DeadlineRunner("test-runner", "test.runner_wait")
    gate = threading.Event()
    with pytest.raises(DeadlineExceeded):
        r.run(lambda: gate.wait(10), 0.05, "wedge")
    assert r.run(lambda: "fresh", 5, "probe") == "fresh"
    gate.set()


# --------------------------------------------- wedge -> host fallback e2e

@pytest.fixture
def kernel(monkeypatch):
    from fgumi_tpu.native import batch as nb

    if not nb.available():
        pytest.skip("native engine unavailable")
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    return ConsensusKernel(quality_tables(45, 40))


def _batch(seed=0, families=12, reads=3, length=8):
    rng = np.random.default_rng(seed)
    counts = np.full(families, reads)
    n = families * reads
    codes = rng.integers(0, 4, size=(n, length), dtype=np.uint8)
    quals = rng.integers(5, 40, size=(n, length), dtype=np.uint8)
    starts = np.concatenate(([0], np.cumsum(counts)))
    return codes, quals, counts, starts


def _dispatch_resolve(kernel, codes, quals, counts, starts):
    cd, qd, seg, _st, fpad = pad_segments(codes, quals, counts)
    ticket = kernel.device_call_segments_wire(cd, qd, seg, fpad,
                                              len(counts), full=True)
    return kernel.resolve_segments_wire(ticket, codes, quals, starts)


def test_wedged_dispatch_falls_back_byte_identical(kernel, monkeypatch):
    codes, quals, counts, starts = _batch()
    ref = _dispatch_resolve(kernel, codes, quals, counts, starts)  # warm

    monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", "0.2:0.4")
    monkeypatch.setenv("FGUMI_TPU_FAULT_HANG_S", "1.5")
    monkeypatch.setenv("FGUMI_TPU_FAULT", "device.wedge:hang:1.0:1")
    faults.reset()
    before = DEVICE_STATS.deadline_fallbacks
    t0 = time.monotonic()
    out = _dispatch_resolve(kernel, codes, quals, counts, starts)
    wedge_cost = time.monotonic() - t0
    assert wedge_cost < 1.4  # bounded by the deadline, not the hang
    for a, b in zip(ref, out):
        assert np.array_equal(a, b)
    assert DEVICE_STATS.deadline_fallbacks == before + 1
    assert breaker_mod.BREAKER.state == "open"
    # slot reuse does not corrupt the next batch: once the wedge clears,
    # a fresh dispatch resolves to the same bytes
    time.sleep(1.6)
    monkeypatch.delenv("FGUMI_TPU_FAULT")
    faults.reset()
    out2 = _dispatch_resolve(kernel, codes, quals, counts, starts)
    for a, b in zip(ref, out2):
        assert np.array_equal(a, b)


def test_late_result_not_matched_to_next_batch(kernel, monkeypatch):
    """The wedged batch A's late result must be discarded — batch B,
    dispatched right after, resolves to B's answer (computed by whichever
    engine), not A's."""
    codes_a, quals_a, counts, starts = _batch(seed=1)
    codes_b, quals_b, _, _ = _batch(seed=2)
    ref_b = _dispatch_resolve(kernel, codes_b, quals_b, counts, starts)

    monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", "0.2:0.4")
    monkeypatch.setenv("FGUMI_TPU_FAULT_HANG_S", "1.0")
    monkeypatch.setenv("FGUMI_TPU_FAULT", "device.wedge:hang:1.0:1")
    faults.reset()
    out_a = _dispatch_resolve(kernel, codes_a, quals_a, counts, starts)
    out_b = _dispatch_resolve(kernel, codes_b, quals_b, counts, starts)
    for a, b in zip(ref_b, out_b):
        assert np.array_equal(a, b)
    # A's own (host-fallback) answer differs from B's: proves no cross-talk
    assert not all(np.array_equal(a, b) for a, b in zip(out_a, out_b))
    time.sleep(1.2)  # let the wedge clear before the next test


def test_sync_batch_dispatch_wedge_bounded(kernel, monkeypatch):
    """The uniform-batch sync path (__call__) dispatches on the caller
    thread; a wedge there must be deadline-bounded and degrade to the
    host engine byte-identically, like the async paths."""
    rng = np.random.default_rng(7)
    codes = rng.integers(0, 4, size=(6, 3, 8), dtype=np.uint8)
    quals = rng.integers(5, 40, size=(6, 3, 8), dtype=np.uint8)
    ref = kernel(codes, quals)  # warm

    monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", "0.2:0.4")
    monkeypatch.setenv("FGUMI_TPU_FAULT_HANG_S", "1.5")
    monkeypatch.setenv("FGUMI_TPU_FAULT", "device.wedge:hang:1.0:1")
    faults.reset()
    before = DEVICE_STATS.deadline_fallbacks
    t0 = time.monotonic()
    out = kernel(codes, quals)
    assert time.monotonic() - t0 < 1.4  # deadline, not the hang
    for a, b in zip(ref, out):
        assert np.array_equal(a, b)
    assert DEVICE_STATS.deadline_fallbacks == before + 1
    assert breaker_mod.BREAKER.state == "open"
    time.sleep(1.6)  # let the wedge clear before the next test


def test_sync_segment_dispatch_wedge_bounded(kernel, monkeypatch):
    """The classic-segments sync path (pad_segments + device_call_segments
    / resolve_segments) degrades a dispatch-time wedge to HOST_DISPATCH
    under the deadline."""
    codes, quals, counts, starts = _batch(seed=9)
    cd, qd, seg, st, fpad = pad_segments(codes, quals, counts)
    dev = kernel.device_call_segments(cd, qd, seg, fpad)
    ref = kernel.resolve_segments(dev, codes, quals, st)  # warm

    monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", "0.2:0.4")
    monkeypatch.setenv("FGUMI_TPU_FAULT_HANG_S", "1.5")
    monkeypatch.setenv("FGUMI_TPU_FAULT", "device.wedge:hang:1.0:1")
    faults.reset()
    before = DEVICE_STATS.deadline_fallbacks
    t0 = time.monotonic()
    dev = kernel.device_call_segments(cd, qd, seg, fpad)
    out = kernel.resolve_segments(dev, codes, quals, st)
    assert time.monotonic() - t0 < 1.4
    for a, b in zip(ref, out):
        assert np.array_equal(a, b)
    assert DEVICE_STATS.deadline_fallbacks == before + 1
    assert breaker_mod.BREAKER.state == "open"
    time.sleep(1.6)


def test_run_report_records_breaker_and_deadline(kernel, monkeypatch,
                                                tmp_path):
    """The report's device section carries deadline_fallbacks >= 1 and the
    breaker's opening transition after a wedge (ISSUE 7 acceptance)."""
    from fgumi_tpu.observe.report import build_report

    codes, quals, counts, starts = _batch(seed=3)
    _dispatch_resolve(kernel, codes, quals, counts, starts)  # warm
    monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", "0.2:0.4")
    monkeypatch.setenv("FGUMI_TPU_FAULT_HANG_S", "1.0")
    monkeypatch.setenv("FGUMI_TPU_FAULT", "device.wedge:hang:1.0:1")
    faults.reset()
    _dispatch_resolve(kernel, codes, quals, counts, starts)
    report = build_report("test", [], time.time(), 0.1, 0)
    dev = report.get("device", {})
    assert dev.get("deadline_fallbacks", 0) >= 1
    br = dev.get("breaker", {})
    assert br.get("state") in ("open", "half-open")
    assert any(t["to"] == "open" for t in br.get("transitions", []))
    time.sleep(1.2)
