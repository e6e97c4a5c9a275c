"""Adaptive host/device offload policy (ROADMAP item 1, round 6).

Which side wins a consensus batch — the device kernel or the native f64
host engine — depends on the batch shape, the host's free cores and the
host-device link, so it is a per-batch economic decision rather than a
compile-time constant. This module holds that decision in one place:

- :class:`OffloadRouter` — routes each consensus batch ``device`` (the
  full-column 1-byte-wire kernel) or ``host`` (the native f64 engine) from
  an online cost model: EWMAs of the measured upload link rate, the
  per-dispatch device overhead (compute + transfer latency, the part
  that does not scale with bytes), and the host engine's measured
  throughput in pileup cells/s. The predicted times

      t_device = up_bytes/link + down_bytes/link + overhead
                 + in_flight * ewma_dispatch_wall          (queue delay)
      t_host   = cells / host_cells_per_s

  are compared per batch, so a mixed-family config lands on the winning
  side of its crossover automatically instead of by a static threshold.
  Every route is byte-identical by construction (the device path patches
  its suspects through the f64 oracle; the host path IS the f64 engine),
  so routing is a pure performance decision — including the probe batches
  the model occasionally sends to the losing side to keep both EWMAs live.
  A side that has never been measured is not priced at all: with a device
  attached and no device sample yet the batch goes to the device (and an
  unmeasured host engine gets its probe as soon as the device has two
  samples), because the static priors below are a guess about hardware
  this process has not seen.

- :class:`AdaptiveChooser` — the same idea for cheap elementwise stages
  (the duplex strand-combine / CODEC concordance device stages): EWMA of
  seconds-per-cell on each side, alternate probes until both sides are
  measured, then pick the predicted winner with a periodic refresh probe.

Env contract (docs/performance-tuning.md):

- ``FGUMI_TPU_ROUTE=device|host|auto`` — force every batch to one side, or
  (default ``auto``) let the cost model decide. ``host`` falls back to
  ``device`` with a warning when the native engine is unavailable.
- ``FGUMI_TPU_MAX_INFLIGHT`` — when set explicitly, the pre-round-6 static
  backlog policy is honored verbatim (``0`` = always host; otherwise
  device unless that many dispatches are already in flight). Unset =
  adaptive (the backlog folds into the queue-delay term instead).
- ``FGUMI_TPU_ROUTE_PROBE`` — probe period (default 64): after this many
  consecutive same-side routes one batch goes to the other side so its
  EWMA tracks the link weather. ``0`` disables probing.

Like the datapath singletons, the measured rates are per-process facts
(the link and the host are shared by every job); the per-scope route
*counters* land in METRICS/DeviceStats via the callers.
"""

import os
import threading

import numpy as np  # noqa: F401  (kept: callers pass numpy scalars)

#: EWMA smoothing for rate estimates: ~the last dozen batches dominate.
ALPHA = 0.2
#: default probe period (batches of one side before sampling the other)
DEFAULT_PROBE = 64


def _env_route():
    v = os.environ.get("FGUMI_TPU_ROUTE", "auto").strip().lower()
    return v if v in ("device", "host", "auto") else "auto"


class _Ewma:
    __slots__ = ("value", "samples")

    def __init__(self):
        self.value = None
        self.samples = 0

    def add(self, x: float):
        x = float(x)
        self.value = x if self.value is None else \
            (1.0 - ALPHA) * self.value + ALPHA * x
        self.samples += 1

    def get(self, default: float):
        return self.value if self.value is not None else default

    def seed(self, value, samples: int = 1):
        """Install a measured prior (tune/profile.py). ``samples`` counts
        as real history for the decide() probe gates — a profile-seeded
        host rate must NOT re-fire probe-unmeasured — but live ``add()``
        measurements still converge away from it at the normal ALPHA."""
        if value is not None:
            self.value = float(value)
            self.samples = max(int(samples), 1)

    def export(self):
        return {"value": self.value, "samples": self.samples}

    def restore(self, state):
        if isinstance(state, dict) and state.get("value") is not None:
            self.seed(state["value"], state.get("samples", 1))


class OffloadRouter:
    """Per-batch device/host routing for the consensus engines."""

    # priors used before the first measurement lands. They never choose a
    # side on their own (decide() sends an unmeasured side its batch); they
    # price the deadline of the first dispatches and the coalescer's hold
    # window until measured EWMAs take over.
    PRIOR_LINK_BPS = 10e6
    PRIOR_HOST_CELLS_PER_S = 20e6
    PRIOR_OVERHEAD_S = 0.05

    def __init__(self):
        self._lock = threading.Lock()
        self._tls = threading.local()  # per-thread last prediction
        self._warned_no_host = False
        self.reset()

    def reset(self):
        with self._lock:
            # where the EWMAs' starting point came from: "cold" (static
            # class priors), "profile" (tune/profile.py seeded measured
            # priors), or "snapshot" (daemon warm-start restore). Stamped
            # into snapshot() + device.routing telemetry so a first-batch
            # routing decision is attributable to its prior.
            self.prior_source = "cold"
            # device-side EWMAs are PER MESH SIZE (ISSUE 10 (c)): an N-chip
            # mesh has its own link rate (N overlapping upload slices), its
            # own per-dispatch overhead (shard_map launch + collectives),
            # and its own service wall — pricing a dp4 dispatch with the
            # 1-device EWMAs would mis-place the host/device crossover in
            # exactly the configs the mesh exists for. Keyed by device
            # count; entry 1 is the classic single-device model.
            self._mesh = {1: self._new_mesh_ewmas()}
            self._host_cps = _Ewma()       # host engine cells/s (shared)
            # fused consensus→filter keep rate (ISSUE 11): the fraction of
            # device-routed reads the filter keeps, which is what the fused
            # route's fetch-bytes term scales with
            self._filter_keep = _Ewma()
            self._streak_side = None
            self._streak = 0
            # a batch is out measuring the unmeasured host engine; its
            # sample lands at resolve time, batches later
            self._host_probe_out = False
            self._last = {}                # last decision detail (snapshot)

    @staticmethod
    def _new_mesh_ewmas():
        return {"link_bps": _Ewma(), "overhead_s": _Ewma(),
                "dispatch_wall_s": _Ewma()}

    def _mesh_ewmas(self, devices: int):
        """The EWMA triple for one mesh size (caller holds the lock)."""
        e = self._mesh.get(devices)
        if e is None:
            e = self._mesh[devices] = self._new_mesh_ewmas()
        return e

    # ------------------------------------------------------------ feeding

    def observe_device(self, up_bytes: int, down_bytes: int,
                       upload_s: float, other_s: float, service_s: float,
                       devices: int = 1):
        """One resolved device dispatch. ``other_s`` is the non-upload,
        non-queue remainder (host fetch wait in practice); the download
        time it contains is netted out against the link estimate before
        feeding the overhead EWMA, since decide() prices down_bytes/link
        as its own term — without the subtraction the download would be
        charged twice and the device systematically overpriced near the
        crossover. ``service_s`` is the dispatch's serial occupancy of the
        feeder+link (upload + fetch wait), NOT including queue wait —
        decide() multiplies it by the in-flight count for the queue-delay
        term, so queue time must not be baked in twice. ``devices``: the
        mesh size this dispatch ran on (its own EWMA set)."""
        with self._lock:
            e = self._mesh_ewmas(int(devices) if devices else 1)
            if upload_s > 1e-6 and up_bytes > 0:
                e["link_bps"].add(up_bytes / upload_s)
            link = e["link_bps"].value
            if other_s >= 0:
                if link and down_bytes > 0:
                    other_s = max(other_s - down_bytes / link, 0.0)
                e["overhead_s"].add(other_s)
            if service_s > 0:
                e["dispatch_wall_s"].add(service_s)

    def observe_filter_keep(self, kept: int, total: int):
        """One fused-filter gather: how many device-routed reads survived.
        Feeds the keep-rate EWMA the fused route's fetch-bytes pricing
        scales with (``decide_batch(filtered=True)``)."""
        if total > 0:
            with self._lock:
                self._filter_keep.add(kept / total)

    def observe_host(self, cells: int, seconds: float):
        """One host-engine batch (cells = rows * positions of the pileup)."""
        if seconds > 1e-6 and cells > 0:
            with self._lock:
                self._host_cps.add(cells / seconds)
                self._host_probe_out = False

    def device_overhead_s(self, devices: int = 1) -> float:
        """Current per-dispatch device overhead estimate: the mesh size's
        measured EWMA, borrowing the 1-device chain (then the static
        prior) while unmeasured. The dispatch coalescer prices its hold
        window against this (ops/coalesce.py): merging saves ~one
        overhead per extra partner, so holding a batch longer than one
        overhead can only lose to just dispatching now."""
        with self._lock:
            e = self._mesh_ewmas(int(devices) if devices else 1)
            base = self._mesh[1]
            return e["overhead_s"].get(
                base["overhead_s"].get(self.PRIOR_OVERHEAD_S))

    # ----------------------------------------------------------- deciding

    @staticmethod
    def _probe_period():
        try:
            return max(int(os.environ.get("FGUMI_TPU_ROUTE_PROBE",
                                          str(DEFAULT_PROBE))), 0)
        except ValueError:
            return DEFAULT_PROBE

    def decide_batch(self, kernel, n_rows: int, n_segments: int,
                     L: int, devices: int = 1,
                     filtered: bool = False) -> str:
        """Route one consensus batch from its shape — the one place that
        knows the wire-path economics: upload is 1 B/position of dense rows
        plus 4 B/row of segment ids; the full-column fetch is 5.25 B/column
        (qual|suspect byte + 2-bit winner + uint16 depth + uint16 errors);
        host cost scales with the pileup cells (rows x positions).
        ``devices``: the mesh size a device route would dispatch on —
        selects that mesh's EWMA set so auto-routing stays correct when
        the device side is N chips. ``filtered``: price the fused
        consensus→filter route's fetch instead — a 28 B/read stats row
        plus the survivors' 6 B/position masked columns, scaled by the
        measured keep-rate EWMA (prior 0.5)."""
        from ..observe.trace import span

        with span("router.decide") as sp:
            if filtered:
                with self._lock:
                    keep = self._filter_keep.get(0.5)
                down = 28 * n_segments + int(keep * 6 * n_segments * L)
            else:
                down = (21 * n_segments * L) // 4
            side = self.decide(kernel, n_rows * L + 4 * n_rows, down,
                               n_rows * L, devices=devices)
            sp.set(side=side, why=self._last.get("why", ""))
        return side

    def decide(self, kernel, up_bytes: int, down_bytes: int,
               cells: int, devices: int = 1) -> str:
        """Route one batch: ``"device"`` or ``"host"``.

        ``kernel`` supplies the mode gates (hybrid/native availability);
        callers have already excluded host_mode(). The decision and its
        inputs are stamped into METRICS (``device.route.*``) so a wrong
        crossover is diagnosable from any run report.
        """
        from ..native import batch as nb
        from .kernel import DEVICE_STATS, default_max_inflight, log

        self._tls.pred = None  # only the cost branch produces a prediction
        forced = _env_route()
        if forced == "host":
            # an explicit ROUTE=host wins over FGUMI_TPU_HYBRID=0 (the
            # newer, more specific knob); only a missing native engine can
            # override it, and loudly
            if nb.available():
                return self._stamp("host", forced=True, why="forced")
            if not self._warned_no_host:  # once, not per batch
                self._warned_no_host = True
                log.warning("FGUMI_TPU_ROUTE=host but the native f64 engine "
                            "is unavailable; routing to the device")
            forced = "device"
        can_host = nb.available() and kernel.hybrid_mode()
        if not can_host:
            # nothing to degrade to: the device runs the batch regardless
            # of breaker state (the retry/fallback machinery still applies)
            return self._stamp("device", forced=forced != "auto",
                               why="forced" if forced == "device"
                               else "no-host-engine")
        # circuit breaker (ops/breaker.py): with the device declared
        # wedged, every batch routes host with ZERO device waits — the
        # feeder thread may be hung inside a dispatch, so queueing more
        # work behind it would stack deadlines. This overrides even an
        # explicit FGUMI_TPU_ROUTE=device (disable via FGUMI_TPU_BREAKER=0
        # to reproduce raw-device behavior); in half-open, allow() admits
        # one probe batch at a time and the resolve outcome feeds back.
        from .breaker import BREAKER

        if forced == "device":
            if not BREAKER.allow():
                return self._stamp("host", why=self._deny_reason(BREAKER))
            return self._stamp("device", forced=True, why="forced")

        env_cap = os.environ.get("FGUMI_TPU_MAX_INFLIGHT", "").strip()
        if env_cap:
            # legacy static policy, honored verbatim when explicitly set
            cap = default_max_inflight()
            side = "host" if (cap <= 0
                              or DEVICE_STATS.in_flight_count() >= cap) \
                else "device"
            if side == "device" and not BREAKER.allow():
                side = "host"
                return self._stamp(side, why=self._deny_reason(BREAKER))
            return self._stamp(side, why="max-inflight")

        with self._lock:
            e = self._mesh_ewmas(int(devices) if devices else 1)
            # an unmeasured mesh size borrows the 1-device EWMAs as its
            # prior (the link hardware is shared; only the measured
            # sharded behavior can correct it) before the static priors
            base = self._mesh[1]
            link = e["link_bps"].get(
                base["link_bps"].get(self.PRIOR_LINK_BPS))
            overhead = e["overhead_s"].get(
                base["overhead_s"].get(self.PRIOR_OVERHEAD_S))
            host_cps = self._host_cps.get(self.PRIOR_HOST_CELLS_PER_S)
            wall = e["dispatch_wall_s"].get(overhead)
            host_samples = self._host_cps.samples
            host_probe_out = self._host_probe_out
            # on the default 1-device path e IS base — summing would
            # double-count and fire the probe-unmeasured branch a batch
            # early (legacy-behavior regression)
            dev_samples = e["overhead_s"].samples + \
                (base["overhead_s"].samples if e is not base else 0)
        in_flight = DEVICE_STATS.in_flight_count()
        t_dev = (up_bytes + down_bytes) / link + overhead + in_flight * wall
        t_host = cells / host_cps
        self._tls.pred = (t_dev, t_host)
        side = "device" if t_dev <= t_host else "host"
        why = "cost"
        # keep both EWMAs alive: sample the unmeasured/stale side
        probe = self._probe_period()
        if (side == "device" and host_samples == 0 and dev_samples >= 2
                and not host_probe_out):
            # ONE batch: its sample lands when it resolves, and until then
            # the batches behind it follow the cost model instead of
            # queueing up as further "probes" on the unmeasured side
            side, why = "host", "probe-unmeasured"
            with self._lock:
                self._host_probe_out = True
        elif side == "host" and dev_samples == 0:
            # the device lost on priors alone; nothing has timed it yet
            side, why = "device", "probe-unmeasured"
        elif probe:
            with self._lock:
                streak = self._streak if self._streak_side == side else 0
            if streak >= probe:
                side = "host" if side == "device" else "device"
                why = "probe-refresh"
        if side == "device" and not BREAKER.allow():
            side, why = "host", self._deny_reason(BREAKER)
        return self._stamp(side, why=why, t_dev=t_dev, t_host=t_host,
                           link_bps=link, host_cps=host_cps,
                           overhead_s=overhead, in_flight=in_flight)

    @staticmethod
    def _deny_reason(breaker) -> str:
        """Why the breaker denied the device: an SDC quarantine (the
        shadow audit caught corruption — ops/sentinel.py) is stamped
        distinctly from an ordinary wedge/transient trip so a host-forced
        run's artifact names the actual cause."""
        return "sdc-quarantine" if breaker.sdc_quarantined() \
            else "breaker-open"

    def _stamp(self, side, forced=False, why="", t_dev=None, t_host=None,
               link_bps=None, host_cps=None, overhead_s=None, in_flight=0):
        from ..observe.metrics import METRICS

        with self._lock:
            if self._streak_side == side:
                self._streak += 1
            else:
                self._streak_side, self._streak = side, 1
            self._last = {"side": side, "why": why, "forced": forced}
            if t_dev is not None:
                self._last.update(pred_device_s=round(t_dev, 5),
                                  pred_host_s=round(t_host, 5))
        from .kernel import DEVICE_STATS

        METRICS.inc(f"device.route.{side}")
        if why:
            # why each batch went where it went, countable from any report
            METRICS.inc(f"device.route.why.{why}")
        DEVICE_STATS.add_route(side)
        if t_dev is not None:
            METRICS.set("device.route.pred_device_ms", round(t_dev * 1e3, 3))
            METRICS.set("device.route.pred_host_ms", round(t_host * 1e3, 3))
        if link_bps is not None:
            METRICS.set("device.route.link_mbps", round(link_bps / 1e6, 3))
            METRICS.set("device.route.host_mcells_per_s",
                        round(host_cps / 1e6, 3))
        return side

    def last_prediction(self):
        """(pred_device_s, pred_host_s) of THIS THREAD's latest cost-model
        decision, or None when it was forced/stamp-free — thread-local so a
        concurrent engine thread's decision cannot be paired with the wrong
        dispatch in the predicted-vs-actual timeline stamps."""
        return getattr(self._tls, "pred", None)

    # ------------------------------------------------- seeding / warm start

    def seed_priors(self, priors: dict, source: str = "profile") -> bool:
        """Install measured priors from a deployment profile
        (tune/profile.py). Only COLD EWMAs are seeded: once a live
        measurement has landed (samples > 0) the learned state wins — this
        also makes re-entry safe when daemon jobs re-run cli.main in fresh
        scoped contexts. Returns True when anything was seeded."""
        if not isinstance(priors, dict):
            return False
        seeded = False
        with self._lock:
            base = self._mesh_ewmas(1)
            for key, ewma in (("link_mbps", base["link_bps"]),
                              ("overhead_s", base["overhead_s"]),
                              ("dispatch_wall_s", base["dispatch_wall_s"])):
                v = priors.get(key)
                if v is not None and ewma.samples == 0:
                    ewma.seed(v * 1e6 if key == "link_mbps" else v)
                    seeded = True
            v = priors.get("host_mcells_per_s")
            if v is not None and self._host_cps.samples == 0:
                self._host_cps.seed(v * 1e6)
                seeded = True
            v = priors.get("filter_keep_rate")
            if v is not None and self._filter_keep.samples == 0:
                self._filter_keep.seed(v)
                seeded = True
            for n, mp in (priors.get("mesh") or {}).items():
                try:
                    e = self._mesh_ewmas(int(n))
                except (TypeError, ValueError):
                    continue
                for key, ewma in (("link_mbps", e["link_bps"]),
                                  ("overhead_s", e["overhead_s"]),
                                  ("dispatch_wall_s",
                                   e["dispatch_wall_s"])):
                    v = mp.get(key) if isinstance(mp, dict) else None
                    if v is not None and ewma.samples == 0:
                        ewma.seed(v * 1e6 if key == "link_mbps" else v)
                        seeded = True
            if seeded and self.prior_source == "cold":
                self.prior_source = source
        return seeded

    def export_state(self):
        """Full EWMA state (values + sample counts, every mesh size) for
        the daemon's warm-start snapshot — unlike the rounded snapshot()
        this is lossless, so a restore reproduces routing exactly."""
        with self._lock:
            return {
                "mesh": {str(n): {k: e[k].export() for k in e}
                         for n, e in self._mesh.items()},
                "host_cps": self._host_cps.export(),
                "filter_keep": self._filter_keep.export(),
            }

    def restore_state(self, state: dict, source: str = "snapshot") -> bool:
        """Reload an export_state() dict (daemon restart warm start).
        Cold-EWMA-only, like seed_priors: live measurements always win."""
        if not isinstance(state, dict):
            return False
        restored = False
        with self._lock:
            for n, me in (state.get("mesh") or {}).items():
                try:
                    e = self._mesh_ewmas(int(n))
                except (TypeError, ValueError):
                    continue
                if not isinstance(me, dict):
                    continue
                for k in ("link_bps", "overhead_s", "dispatch_wall_s"):
                    st = me.get(k)
                    if isinstance(st, dict) and st.get("value") is not None \
                            and e[k].samples == 0:
                        e[k].restore(st)
                        restored = True
            for attr, key in ((self._host_cps, "host_cps"),
                              (self._filter_keep, "filter_keep")):
                st = state.get(key)
                if isinstance(st, dict) and st.get("value") is not None \
                        and attr.samples == 0:
                    attr.restore(st)
                    restored = True
            if restored and self.prior_source == "cold":
                self.prior_source = source
        return restored

    # ----------------------------------------------------------- snapshot

    def snapshot(self):
        """Cost-model state for run reports / bench stamps."""
        with self._lock:
            base = self._mesh[1]
            out = {
                "prior_source": self.prior_source,
                "link_mbps": round(base["link_bps"].get(0.0) / 1e6, 3),
                "link_samples": base["link_bps"].samples,
                "overhead_s": round(base["overhead_s"].get(0.0), 5),
                "dispatch_wall_s": round(
                    base["dispatch_wall_s"].get(0.0), 5),
                "host_mcells_per_s": round(self._host_cps.get(0.0) / 1e6, 3),
                "host_samples": self._host_cps.samples,
            }
            if self._filter_keep.samples:
                out["filter_keep_rate"] = round(self._filter_keep.get(0.0),
                                                4)
            mesh_out = {}
            for n, e in sorted(self._mesh.items()):
                if n == 1 or not (e["link_bps"].samples
                                  or e["overhead_s"].samples):
                    continue
                mesh_out[str(n)] = {
                    "link_mbps": round(e["link_bps"].get(0.0) / 1e6, 3),
                    "link_samples": e["link_bps"].samples,
                    "overhead_s": round(e["overhead_s"].get(0.0), 5),
                    "dispatch_wall_s": round(
                        e["dispatch_wall_s"].get(0.0), 5),
                }
            if mesh_out:
                out["mesh"] = mesh_out
            if self._last:
                out["last_decision"] = dict(self._last)
            return out


class AdaptiveChooser:
    """Two-sided seconds-per-cell chooser for elementwise device stages.

    Used by the duplex strand-combine and CODEC concordance stages: both
    sides produce byte-identical output, so the chooser alternates probes
    until each side has two samples, then picks the predicted winner with
    a refresh probe every ``FGUMI_TPU_ROUTE_PROBE`` decisions. An env
    override (passed per call: ``"device"``/``"host"``) always wins."""

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._spc = {"device": _Ewma(), "host": _Ewma()}
        self._streak_side = None
        self._streak = 0

    def observe(self, side: str, cells: int, seconds: float):
        if cells > 0 and seconds >= 0:
            with self._lock:
                self._spc[side].add(seconds / cells)

    def seed(self, device_s_per_mcell=None, host_s_per_mcell=None) -> bool:
        """Install measured seconds-per-million-cells priors (profile
        units match snapshot()). Seeded with samples=2 so the first
        decide() picks the measured winner instead of alternating; cold
        sides only, so live daemons keep their learned state."""
        seeded = False
        with self._lock:
            for side, v in (("device", device_s_per_mcell),
                            ("host", host_s_per_mcell)):
                if v is not None and self._spc[side].samples == 0:
                    self._spc[side].seed(v / 1e6, samples=2)
                    seeded = True
        return seeded

    def export_state(self):
        with self._lock:
            return {side: e.export() for side, e in self._spc.items()}

    def restore_state(self, state: dict) -> bool:
        if not isinstance(state, dict):
            return False
        restored = False
        with self._lock:
            for side in ("device", "host"):
                st = state.get(side)
                if isinstance(st, dict) and st.get("value") is not None \
                        and self._spc[side].samples == 0:
                    self._spc[side].restore(st)
                    restored = True
        return restored

    def decide(self, cells: int, override: str = "auto") -> str:
        from ..observe.metrics import METRICS

        if override in ("device", "host"):
            METRICS.inc(f"device.route.{self.name}.{override}")
            return override
        probe = OffloadRouter._probe_period()
        with self._lock:
            d, h = self._spc["device"], self._spc["host"]
            if d.samples < 2 or h.samples < 2:
                # alternate until both sides are measured
                side = "device" if d.samples <= h.samples else "host"
            else:
                side = "device" if d.value <= h.value else "host"
                if probe and self._streak_side == side \
                        and self._streak >= probe:
                    side = "host" if side == "device" else "device"
            if self._streak_side == side:
                self._streak += 1
            else:
                self._streak_side, self._streak = side, 1
        METRICS.inc(f"device.route.{self.name}.{side}")
        return side

    def snapshot(self):
        with self._lock:
            return {side: {"s_per_mcell": round(e.get(0.0) * 1e6, 6),
                           "samples": e.samples}
                    for side, e in self._spc.items()}


def run_adaptive_stage(chooser: AdaptiveChooser, cells: int, override: str,
                       device_fn, host_fn):
    """Run one elementwise stage on the chooser's preferred side under the
    shared degrade contract: whichever side runs is timed and fed to its
    EWMA; a transient/OOM device failure is charged to the device side
    (including its retry/backoff time — the chooser must learn, not
    re-try a dead stage every batch), warned once per occurrence, and
    falls back to ``host_fn``; non-device-weather errors re-raise.
    Returns (result, side-that-produced-it)."""
    import time

    from .breaker import BREAKER
    from .kernel import _is_oom, _is_transient, log

    if cells > 0 and not BREAKER.blocked() \
            and chooser.decide(cells, override) == "device":
        t0 = time.monotonic()
        try:
            out = device_fn()
            chooser.observe("device", cells, time.monotonic() - t0)
            return out, "device"
        except BaseException as e:  # noqa: BLE001 - classified below
            if not (_is_oom(e) or _is_transient(e)):
                raise
            chooser.observe("device", cells, time.monotonic() - t0)
            log.warning("%s device stage failed (%s: %s); using the host "
                        "path", chooser.name, type(e).__name__, e)
    t0 = time.monotonic()
    out = host_fn()
    chooser.observe("host", cells, time.monotonic() - t0)
    return out, "host"


#: process-wide singletons (measured rates are per-process facts)
ROUTER = OffloadRouter()
DUPLEX_COMBINE = AdaptiveChooser("duplex_combine")
CODEC_COMBINE = AdaptiveChooser("codec_combine")
