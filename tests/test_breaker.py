"""Circuit-breaker state machine units (ops/breaker.py): trip thresholds,
half-open probe accounting, close hysteresis, and the router gate. All
CPU-only and fast — the breaker never touches a device here."""

import pytest

from fgumi_tpu.ops.breaker import (CLOSED, HALF_OPEN, OPEN, DeviceBreaker,
                                   monitor_period_s)


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture
def clock():
    return FakeClock()


@pytest.fixture
def breaker(clock, monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_BREAKER_FAILURES", "3")
    monkeypatch.setenv("FGUMI_TPU_BREAKER_COOLDOWN_S", "10")
    monkeypatch.setenv("FGUMI_TPU_BREAKER_PROBES", "2")
    return DeviceBreaker(now=clock)


def test_starts_closed_and_allows(breaker):
    assert breaker.state == CLOSED
    assert breaker.allow()


def test_transient_failures_trip_at_threshold(breaker):
    breaker.record_transient_failure()
    breaker.record_transient_failure()
    assert breaker.state == CLOSED
    breaker.record_transient_failure()
    assert breaker.state == OPEN
    assert not breaker.allow()


def test_success_resets_closed_score(breaker):
    breaker.record_transient_failure()
    breaker.record_transient_failure()
    breaker.record_success()  # score back to 0
    breaker.record_transient_failure()
    breaker.record_transient_failure()
    assert breaker.state == CLOSED


def test_deadline_overrun_trips_immediately(breaker):
    breaker.record_deadline_overrun()
    assert breaker.state == OPEN
    assert breaker.snapshot()["deadline_overruns"] == 1


def test_canary_failure_trips_immediately(breaker):
    breaker.record_canary_failure()
    assert breaker.state == OPEN


def test_cooldown_moves_to_half_open(breaker, clock):
    breaker.record_deadline_overrun()
    clock.advance(9.9)
    assert breaker.state == OPEN
    clock.advance(0.2)
    assert breaker.state == HALF_OPEN


def test_half_open_single_probe_accounting(breaker, clock):
    breaker.record_deadline_overrun()
    clock.advance(10.1)
    assert breaker.state == HALF_OPEN
    # exactly one probe slot: first allow() claims it, the second is
    # refused until the probe's outcome lands
    assert breaker.allow()
    assert not breaker.allow()
    breaker.record_success()  # probe 1 of 2
    assert breaker.state == HALF_OPEN
    assert breaker.allow()
    breaker.record_success()  # probe 2 of 2 -> closed
    assert breaker.state == CLOSED
    assert breaker.allow()


def test_half_open_stale_probe_slot_released(breaker, clock):
    """A probe batch that dies without feeding back (non-weather exception
    between allow() and the resolve) must not leak the probe slot — the
    breaker would otherwise deny the device for the rest of the process."""
    breaker.record_deadline_overrun()
    clock.advance(10.1)
    assert breaker.allow()          # claims the slot
    assert not breaker.allow()      # ...and nothing ever feeds back
    clock.advance(breaker._probe_timeout_s() + 1)
    assert breaker.state == HALF_OPEN
    assert breaker.allow()          # slot released: probing resumes
    breaker.record_success()
    breaker.record_success()
    assert breaker.state == CLOSED


def test_half_open_failure_reopens(breaker, clock):
    breaker.record_deadline_overrun()
    clock.advance(10.1)
    assert breaker.allow()
    breaker.record_transient_failure()  # ANY failure reopens from half-open
    assert breaker.state == OPEN


def test_reopen_hysteresis_doubles_cooldown(breaker, clock):
    breaker.record_deadline_overrun()
    clock.advance(10.1)
    assert breaker.allow()
    breaker.record_deadline_overrun()  # re-trip while half-open
    assert breaker.state == OPEN
    clock.advance(10.1)  # one base cooldown is no longer enough
    assert breaker.state == OPEN
    clock.advance(10.0)  # 2x base elapsed
    assert breaker.state == HALF_OPEN


def test_transitions_recorded_and_snapshot(breaker, clock):
    breaker.record_deadline_overrun()
    clock.advance(10.1)
    assert breaker.allow()
    breaker.record_success()
    breaker.record_success()
    snap = breaker.snapshot()
    assert snap["state"] == CLOSED
    path = [(t["from"], t["to"]) for t in snap["transitions"]]
    assert path == [(CLOSED, OPEN), (OPEN, HALF_OPEN), (HALF_OPEN, CLOSED)]
    assert all("reason" in t for t in snap["transitions"])


def test_disabled_breaker_never_blocks(breaker, monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_BREAKER", "0")
    breaker.record_deadline_overrun()
    assert breaker.allow()


def test_metrics_stamped_on_transition(breaker):
    from fgumi_tpu.observe.metrics import METRICS

    before = METRICS.get("device.breaker.transitions", 0)
    breaker.record_deadline_overrun()
    assert METRICS.get("device.breaker.state") == OPEN
    assert METRICS.get("device.breaker.transitions", 0) == before + 1
    assert METRICS.get("device.breaker.opened", 0) >= 1


def test_canary_skipped_while_feeder_busy(monkeypatch):
    """With real dispatches in flight the canary must stand down — queued
    behind them it would time out on queue wait alone and trip the breaker
    open on a busy-but-healthy device."""
    import threading

    from fgumi_tpu.ops import kernel as kern
    from fgumi_tpu.ops.breaker import DeviceBreaker, HealthMonitor

    monkeypatch.setattr(kern, "_jax_ready", True, raising=False)
    gate = threading.Event()
    ticket = kern.DEVICE_FEEDER.submit(lambda: gate.wait(5))
    mon = HealthMonitor(DeviceBreaker())
    try:
        mon._canary_once()
        assert mon.canaries == 0
    finally:
        gate.set()
        ticket.wait(5)
        kern.DEVICE_FEEDER.mark_resolved(ticket)


def test_monitor_period_parse(monkeypatch):
    monkeypatch.delenv("FGUMI_TPU_HEALTH_PERIOD_S", raising=False)
    assert monitor_period_s() == 0.0
    monkeypatch.setenv("FGUMI_TPU_HEALTH_PERIOD_S", "12.5")
    assert monitor_period_s() == 12.5
    monkeypatch.setenv("FGUMI_TPU_HEALTH_PERIOD_S", "junk")
    assert monitor_period_s() == 0.0


def test_router_gate_routes_host_when_open(monkeypatch):
    """decide() must route host with zero device waits while open — even
    under an explicit FGUMI_TPU_ROUTE=device."""
    from fgumi_tpu.native import batch as nb

    if not nb.available():
        pytest.skip("native engine unavailable")
    from fgumi_tpu.ops import breaker as breaker_mod
    from fgumi_tpu.ops.router import OffloadRouter
    from fgumi_tpu.ops.tables import quality_tables
    from fgumi_tpu.ops.kernel import ConsensusKernel

    monkeypatch.setenv("FGUMI_TPU_ROUTE", "device")
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    kernel = ConsensusKernel(quality_tables(45, 40))
    kernel._use_host = False
    kernel._hybrid = True
    router = OffloadRouter()
    breaker_mod.BREAKER.reset()
    assert router.decide(kernel, 1000, 100, 4000) == "device"
    breaker_mod.BREAKER.record_deadline_overrun()
    assert router.decide(kernel, 1000, 100, 4000) == "host"
    # disabling the breaker restores raw forced-device behavior
    monkeypatch.setenv("FGUMI_TPU_BREAKER", "0")
    assert router.decide(kernel, 1000, 100, 4000) == "device"
    breaker_mod.BREAKER.reset()
