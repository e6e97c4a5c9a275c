"""Schema-versioned machine-readable run report.

One JSON artifact per command (``--run-report out.json`` /
``FGUMI_TPU_RUN_REPORT``), committed atomically via ``utils/atomic`` at
command exit — success or failure — so a benchmark harness or CI gate can
answer "where did the time go, and did the device degrade?" without parsing
logs: wall time, per-stage busy/blocked seconds, queue occupancy mean/max,
device dispatches/retries/batch-splits/host-fallbacks, upload-pipeline
overlap + constant-cache traffic (``device.upload_overlap_s``,
``device.const_*``, ``device.shape_bucket.*`` — the data-path counters
``tools/perf_smoke.py`` gates on), bytes in/out, records processed, and
exit status.

The schema is versioned (:data:`SCHEMA_VERSION`) and validated structurally
by :func:`validate_report` — the same function the golden-file test and
``tools/telemetry_smoke.py`` gate on, so the shape cannot drift silently.
"""

import json
import os
import sys
import time

#: v2 (ISSUE 9): adds the optional ``latency`` section — per-histogram
#: ``{count, sum, p50, p90, p99, max}`` summaries from the latency
#: histograms (observe/metrics.py) — and optional ``flight_dumps`` (paths
#: of black boxes the flight recorder wrote during the run).
#: v3 (ISSUE 11): the ``device`` section may carry the device-resident
#: pipeline counters — ``resident_bytes_peak`` (+ live
#: ``resident_bytes`` when nonzero at exit), and the routing
#: snapshot's ``filter_keep_rate`` — and the latency section gains the
#: ``device.dispatch.fetch_bytes`` histogram, making the fused-filter
#: bytes-fetched claim machine-readable from any run.
#: v4 (ISSUE 14): optional ``audit`` section — the silent-corruption
#: sentinel's scoreboard (sampled/clean/divergent/dropped counts,
#: per-device attribution map, bounded ``divergence`` evidence records
#: carrying both result buffers' sha256 digests, and the
#: ``--audit-output`` pre-commit verification verdicts). A run whose
#: ``audit.divergence`` is non-empty produced at least one device result
#: the f64 oracle refutes — callers must treat that output as suspect
#: (in sampled mode the corrupt batch was already consumed).
#: v5 (ISSUE 17): optional ``trace_context`` (the fleet trace id / parent
#: span / job id this run executed under, when it was a routed serve job),
#: ``latency_decomposition`` (end-to-end attribution of where the time
#: went — client->balancer, balancer->admit, queue, coalesce hold, device,
#: commit, host-complete residual — components never summing past
#: ``total_s``), and ``xla_profile_dir`` (the --xla-profile capture
#: directory, when one was taken).
#: v6 (ISSUE 19): the ``device`` section may carry the kernel-backend
#: counters ``kernel_pallas`` / ``kernel_xla`` (wire dispatches executed
#: by the hand-tiled Pallas kernel vs the XLA-lowered oracle; absent when
#: no wire dispatch ran), and DeviceStats timeline entries (flight dumps,
#: ``--stats`` report) gain a per-dispatch ``kernel_backend`` stamp.
#: v7 (ISSUE 20): ``device.routing`` gains ``prior_source`` ("cold" /
#: "profile" / "snapshot" — where the cost model's starting EWMAs came
#: from, so first-batch routing is attributable), the metrics section may
#: carry ``tune.*`` gauges, and the optional top-level ``profile``
#: section records the applied deployment profile (path, knobs applied /
#: skipped by explicit overrides, fingerprint mismatches, whether router
#: priors were seeded — tune/profile.py).
#: v8 (ISSUE 21): the ``device`` section is present whenever the run loaded
#: the consensus kernel module, and always names which platform did the
#: work: ``platform``, ``device_kind``, ``device_count`` as jax reports
#: them in the process that ran (``cpu`` / "native f64 host engine" / 0
#: when the run never initialised jax). ``metrics.device.backend_compiles``
#: no longer counts executables loaded from the persistent compile cache.
#: ``device.shapes`` lists the bucketed dispatch shapes the process has
#: seen (``kind:dims``), and ``metrics.device.route.why.<reason>`` counts
#: why the router sent each batch where it did.
#: v9 (ISSUE 25): optional ``spans`` section — the span aggregate of this
#: run (``{"job", "by_name": {name: {count, wall_s, self_s, wait_s, p50_s,
#: max_s, threads[, utime_s, stime_s, minflt, majflt, nvcsw, nivcsw]
#: [, counters]}}}``; present whenever spans were live, which ``--trace`` or
#: ``--run-report`` makes them) — and the ``process`` section, in every
#: report: ``start_unix``, ``first_main_s`` (process start to the first
#: ``cli.main``), the once-per-process ``startup.*`` spans, and one entry
#: per backend compile and persistent-cache load of the whole process so
#: far (``kind``, ``s``, ``at_s`` since process start, ``shape``, ``fun``;
#: observe/process.py). ``metrics.device.compile_cache_load_s`` holds the
#: seconds of this run's cache loads.
#: v10 (ISSUE 35): optional ``threads`` section, beside ``spans`` whenever
#: spans were live — one record per thread of the job, from its root spans
#: (``root_wall_s`` = ``work_s`` + ``wait_s``; ``utime_s``, ``stime_s``
#: and ``offcpu_s`` where its roots read ``getrusage``; ``roots`` (root
#: span name -> work seconds) and ``self_s`` by span name) — with ``threads_pacing`` (the thread with the most
#: ``work_s``: ``thread``, ``role`` = the root span most of it was under,
#: ``work_s``), and the optional ``alloc`` section (``start`` / ``end``:
#: glibc's ``mallinfo2`` sums, the arena count and ``ru_maxrss`` at the
#: job's two ends; only when a run report was asked for). A ``spans``
#: record no longer lists the names of its threads: ``threads.*.self_s``
#: says where a span ran, and how long there.
SCHEMA_VERSION = 10


def _device_stats():
    """The module-wide DeviceStats, or None when ops.kernel was never
    imported this run — an unimported kernel has nothing to report, and
    importing it here would tax numpy-free commands (sort, fastq, ...)
    with the kernel import at exit. getattr-with-default also covers a
    *partially initialized* module: the heartbeat thread can observe
    sys.modules mid-import while another stage thread (fused chain,
    serve job) is still executing the kernel module body."""
    kern = sys.modules.get("fgumi_tpu.ops.kernel")
    return getattr(kern, "DEVICE_STATS", None)

#: Structural schema: top-level field -> required type (None = any JSON).
#: Sections marked optional may be absent when the command produced no such
#: activity (e.g. no device dispatch, no threaded pipeline).
_REQUIRED = {
    "schema_version": int,
    "tool": str,
    "command": str,
    "argv": list,
    "started_unix": (int, float),
    "wall_s": (int, float),
    "exit_status": int,
    "pid": int,
    "metrics": dict,
}
_OPTIONAL = {
    "stages": dict,     # stage -> {"busy_s": f, "blocked_s": f}
    "queues": dict,     # {"in_mean","in_max","out_mean","out_max","samples"}
    "device": dict,     # platform/device_kind/device_count of the process
                        # that ran (v8) + DeviceStats.snapshot()
    "io": dict,         # {"bytes_read","bytes_written"}
    "records": dict,    # progress label -> count
    "faults": dict,     # fault point -> fired count
    "resource": dict,   # governor snapshot: pressure state, events
                        # (enospc/watermarks), budget rebalancing counters
                        # (utils/governor.py)
    "latency": dict,    # histogram name -> {count,sum,p50,p90,p99,max}
                        # (observe/metrics.py latency histograms; v2)
    "audit": dict,      # silent-corruption sentinel scoreboard + output
                        # verification verdicts (ops/sentinel.py; v4)
    "flight_dumps": list,  # black-box paths the flight recorder wrote
                           # during this run (observe/flight.py; v2)
    "trace_path": str,
    "hostname": str,
    "trace_context": dict,  # fleet trace id / parent span / job id this
                            # run executed under (observe/trace.py; v5)
    "latency_decomposition": dict,  # end-to-end attribution: hop/queue/
                                    # device/commit components + residual,
                                    # summing <= total_s (v5)
    "xla_profile_dir": str,  # --xla-profile capture directory (v5)
    "profile": dict,  # applied deployment profile: path, knobs applied/
                      # skipped_explicit, fingerprint mismatches, whether
                      # router priors were seeded (tune/profile.py; v7)
    "spans": dict,    # span aggregate by name (observe/trace.py; v9)
    "threads": dict,  # the same spans by thread: wall, waits, CPU seconds
                      # of each thread's root spans (observe/trace.py; v10)
    "threads_pacing": dict,  # {thread, role, work_s} of the thread with
                             # the most work_s (v10)
    "alloc": dict,    # allocator counters at the job's start and end
                      # (observe/alloc.py; v10)
    "process": dict,  # process-level record: start-up spans and every
                      # compile / cache load so far (observe/process.py; v9)
}

#: Components a ``latency_decomposition`` section may carry besides
#: ``total_s`` (any subset; what was measurable for this run).
_DECOMP_COMPONENTS = (
    "client_to_balancer_s", "balancer_to_admit_s", "client_to_admit_s",
    "queue_s", "coalesce_hold_s", "device_s", "commit_s",
    "host_complete_s",
)

#: Required numeric fields of one ``latency`` summary entry, in the order
#: the quantile-monotonicity check walks them.
_LATENCY_FIELDS = ("count", "sum", "p50", "p90", "p99", "max")

#: Required integer counters of the ``audit`` section (v4).
_AUDIT_COUNTERS = ("sampled", "clean", "divergent", "dropped")

#: Required numeric fields of one ``spans.by_name`` record (v9).
_SPAN_FIELDS = ("count", "wall_s", "self_s", "wait_s", "p50_s", "max_s")

#: Required numeric fields of one ``threads`` record (v10), and those a
#: thread has, all or none, where its root spans read ``getrusage``.
_THREAD_FIELDS = ("root_wall_s", "wait_s", "work_s")
_THREAD_CLOCK_FIELDS = ("utime_s", "stime_s", "offcpu_s")


def _is_number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _validate_spans(spans: dict, errors: list):
    if not isinstance(spans.get("job"), (str, int)):
        errors.append("spans.job is not a job id or an ordinal")
    by_name = spans.get("by_name")
    if not isinstance(by_name, dict):
        errors.append("spans.by_name is not an object")
        return
    for name, rec in by_name.items():
        if not isinstance(rec, dict):
            errors.append(f"spans entry {name!r} is not an object")
            continue
        missing = [f for f in _SPAN_FIELDS if not _is_number(rec.get(f))]
        if missing:
            errors.append(f"spans entry {name!r} missing numeric fields "
                          f"{missing}")
            continue
        # a span's own time and its waits are parts of its wall
        if rec["self_s"] > rec["wall_s"] + 1e-6 \
                or rec["wait_s"] > rec["wall_s"] + 1e-6:
            errors.append(f"spans entry {name!r}: self_s or wait_s "
                          "exceeds wall_s")


def _numbers(obj) -> bool:
    return isinstance(obj, dict) and all(_is_number(v) for v in obj.values())


def _validate_threads(obj: dict, errors: list):
    threads = obj["threads"]
    for name, rec in threads.items():
        if not isinstance(rec, dict):
            errors.append(f"threads entry {name!r} is not an object")
            continue
        missing = [f for f in _THREAD_FIELDS if not _is_number(rec.get(f))]
        if missing:
            errors.append(f"threads entry {name!r} missing numeric fields "
                          f"{missing}")
            continue
        # a thread's root spans are its work and its declared waits
        if abs(rec["work_s"] + rec["wait_s"] - rec["root_wall_s"]) > 1e-5:
            errors.append(f"threads entry {name!r}: work_s + wait_s is not "
                          "root_wall_s")
        clock = [f for f in _THREAD_CLOCK_FIELDS if f in rec]
        if clock and (len(clock) != len(_THREAD_CLOCK_FIELDS)
                      or not all(_is_number(rec[f]) for f in clock)):
            errors.append(f"threads entry {name!r} has some of "
                          f"{list(_THREAD_CLOCK_FIELDS)} and not all, or "
                          "not as numbers")
        if not _numbers(rec.get("roots")) or not _numbers(rec.get("self_s")):
            errors.append(f"threads entry {name!r}: roots or self_s is not "
                          "{span name: seconds}")
    pacing = obj.get("threads_pacing")
    if pacing is not None and not (
            pacing.get("thread") in threads
            and isinstance(pacing.get("role"), str)
            and _is_number(pacing.get("work_s"))):
        errors.append("threads_pacing is not {thread, role, work_s} of a "
                      "thread of the threads section")
    # what the spans section says by name, this one says by thread
    by_name = (obj.get("spans") or {}).get("by_name")
    if isinstance(by_name, dict):
        for name, rec in by_name.items():
            by_thread = sum(t["self_s"].get(name, 0.0)
                            for t in threads.values()
                            if _numbers(t.get("self_s")))
            if _is_number(rec.get("self_s")) and abs(
                    by_thread - rec["self_s"]) > 1e-5 * (len(threads) + 1):
                errors.append(f"threads: self_s of {name!r} sums to "
                              f"{by_thread:.6f} over the threads, spans "
                              f"has {rec['self_s']:.6f}")


def _validate_alloc(alloc: dict, errors: list):
    for end in ("start", "end"):
        rec = alloc.get(end)
        if not _numbers(rec) or "maxrss_kb" not in rec:
            errors.append(f"alloc.{end} is not an object of numbers with "
                          "maxrss_kb")
    unknown = set(alloc) - {"start", "end"}
    if unknown:
        errors.append(f"alloc unknown fields {sorted(unknown)}")


def _validate_process(proc: dict, errors: list):
    if not _is_number(proc.get("start_unix")):
        errors.append("process.start_unix is not a number")
    if "first_main_s" in proc and not _is_number(proc["first_main_s"]):
        errors.append("process.first_main_s is not a number")
    spans = proc.get("spans")
    if not isinstance(spans, dict) or not all(
            isinstance(v, dict) and _is_number(v.get("s"))
            and _is_number(v.get("at_s")) for v in spans.values()):
        errors.append("process.spans is not {name: {s, at_s}}")
    compiles = proc.get("compiles")
    if not isinstance(compiles, list):
        errors.append("process.compiles is not a list")
        return
    for rec in compiles:
        if not (isinstance(rec, dict)
                and rec.get("kind") in ("compile", "cache_load")
                and _is_number(rec.get("s"))
                and _is_number(rec.get("at_s"))):
            errors.append(f"process.compiles entry {rec!r} is not "
                          "{kind, s, at_s}")


def validate_report(obj) -> list:
    """Return a list of human-readable schema violations (empty == valid)."""
    errors = []
    if not isinstance(obj, dict):
        return ["report is not a JSON object"]
    for key, typ in _REQUIRED.items():
        if key not in obj:
            errors.append(f"missing required field {key!r}")
        elif not isinstance(obj[key], typ):
            errors.append(f"field {key!r} has type {type(obj[key]).__name__}")
    for key, typ in _OPTIONAL.items():
        if key in obj and not isinstance(obj[key], typ):
            errors.append(f"field {key!r} has type {type(obj[key]).__name__}")
    unknown = set(obj) - set(_REQUIRED) - set(_OPTIONAL)
    if unknown:
        errors.append(f"unknown fields: {sorted(unknown)}")
    if isinstance(obj.get("schema_version"), int) \
            and obj["schema_version"] != SCHEMA_VERSION:
        errors.append(f"schema_version {obj['schema_version']} != "
                      f"{SCHEMA_VERSION}")
    if isinstance(obj.get("device"), dict):
        for key, typ in (("platform", str), ("device_kind", str),
                         ("device_count", int)):
            if not isinstance(obj["device"].get(key), typ):
                errors.append(f"device.{key} is missing or not "
                              f"{typ.__name__}")
    if isinstance(obj.get("metrics"), dict):
        for k in obj["metrics"]:
            if not isinstance(k, str) or not k:
                errors.append(f"metrics key {k!r} is not a dotted name")
    if isinstance(obj.get("latency"), dict):
        for name, summ in obj["latency"].items():
            if not isinstance(summ, dict):
                errors.append(f"latency entry {name!r} is not an object")
                continue
            missing = [f for f in _LATENCY_FIELDS if not isinstance(
                summ.get(f), (int, float)) or isinstance(summ.get(f), bool)]
            if missing:
                errors.append(f"latency entry {name!r} missing numeric "
                              f"fields {missing}")
                continue
            if not (summ["p50"] <= summ["p90"] <= summ["p99"]
                    <= summ["max"]):
                errors.append(f"latency entry {name!r} quantiles are not "
                              "ordered (p50 <= p90 <= p99 <= max)")
    if isinstance(obj.get("audit"), dict):
        audit = obj["audit"]
        for f in _AUDIT_COUNTERS:
            v = audit.get(f)
            if not isinstance(v, int) or isinstance(v, bool):
                errors.append(f"audit field {f!r} is not an integer")
        if audit.get("divergent", 0) and not audit.get("divergence"):
            errors.append("audit.divergent > 0 but no divergence records")
        if "divergence" in audit and not isinstance(audit["divergence"],
                                                    list):
            errors.append("audit.divergence is not a list")
        if "output" in audit and not isinstance(audit["output"], list):
            errors.append("audit.output is not a list")
        if "devices" in audit and not isinstance(audit["devices"], dict):
            errors.append("audit.devices is not an object")
    if isinstance(obj.get("trace_context"), dict):
        tc = obj["trace_context"]
        for f in ("trace_id", "parent_span_id", "job_id"):
            if f in tc and not isinstance(tc[f], str):
                errors.append(f"trace_context field {f!r} is not a string")
        unknown = set(tc) - {"trace_id", "parent_span_id", "job_id"}
        if unknown:
            errors.append(f"trace_context unknown fields {sorted(unknown)}")
    if isinstance(obj.get("latency_decomposition"), dict):
        dec = obj["latency_decomposition"]
        total = dec.get("total_s")
        if not isinstance(total, (int, float)) or isinstance(total, bool) \
                or total < 0:
            errors.append("latency_decomposition.total_s is not a "
                          "non-negative number")
            total = None
        comp_sum = 0.0
        for name, v in dec.items():
            if name == "total_s":
                continue
            if name not in _DECOMP_COMPONENTS:
                errors.append("latency_decomposition unknown component "
                              f"{name!r}")
            elif not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v < 0:
                errors.append(f"latency_decomposition component {name!r} "
                              "is not a non-negative number")
            else:
                comp_sum += v
        # the attribution invariant (small epsilon for per-field rounding)
        if total is not None and comp_sum > total + 0.005:
            errors.append("latency_decomposition components sum "
                          f"{comp_sum:.6f} past total_s {total:.6f}")
    if isinstance(obj.get("spans"), dict):
        _validate_spans(obj["spans"], errors)
    if isinstance(obj.get("threads"), dict):
        _validate_threads(obj, errors)
    elif "threads_pacing" in obj:
        errors.append("threads_pacing without a threads section")
    if isinstance(obj.get("alloc"), dict):
        _validate_alloc(obj["alloc"], errors)
    if isinstance(obj.get("process"), dict):
        _validate_process(obj["process"], errors)
    return errors


def _stage_sections(metrics: dict):
    """Derive the stages/queues sections from the flat dotted metrics."""
    stages = {}
    for name, v in metrics.items():
        if name.startswith("pipeline.stage.") and name.count(".") >= 3:
            _, _, stage, field = name.split(".", 3)
            stages.setdefault(stage, {})[field] = v
    queues = None
    samples = metrics.get("pipeline.queue.samples")
    if samples:
        queues = {
            "samples": samples,
            "in_mean": round(metrics.get("pipeline.queue.in.sum", 0)
                             / samples, 3),
            "in_max": metrics.get("pipeline.queue.in.max", 0),
            "out_mean": round(metrics.get("pipeline.queue.out.sum", 0)
                              / samples, 3),
            "out_max": metrics.get("pipeline.queue.out.max", 0),
        }
    return stages, queues


def _latency_decomposition(latency: dict, wall_s: float, scope) -> dict:
    """The v5 end-to-end attribution: where did submit-to-bytes-published
    go? Hop legs come from the propagated wall-clock timestamps on the
    telemetry scope (client_sent / balancer_recv / balancer_sent /
    admitted / started — a fleet-routed job has all five, a direct submit
    three, a plain CLI run none); in-process components are histogram sums
    (coalesce hold, device wall, output commit); ``host_complete_s`` is
    the residual. ``total_s`` spans client send to now when the client
    stamped its send time, else the command wall.

    Components are CAPPED in order so they can never sum past ``total_s``
    — this section is an *attribution* of the total (shares), not a raw
    measurement (raw sums stay in ``latency``); host clock skew or
    overlapped device work therefore shrinks later components instead of
    fabricating > 100% accounting. None when nothing was measurable
    (no hops and no timed component)."""
    hops = dict(scope.hops) if scope is not None and scope.hops else {}

    def hist_sum(name):
        summ = latency.get(name)
        return float(summ["sum"]) if isinstance(summ, dict) else 0.0

    cs = hops.get("client_sent_unix")
    br = hops.get("balancer_recv_unix")
    bs = hops.get("balancer_sent_unix")
    ad = hops.get("admitted_unix")
    st = hops.get("started_unix")
    measured = []
    if cs and br:
        measured.append(("client_to_balancer_s", br - cs))
    if bs and ad:
        measured.append(("balancer_to_admit_s", ad - bs))
    elif cs and ad and not br:
        measured.append(("client_to_admit_s", ad - cs))
    if ad and st:
        measured.append(("queue_s", st - ad))
    measured.append(("coalesce_hold_s",
                     hist_sum("device.coalesce.window_wait_s")))
    measured.append(("device_s", hist_sum("device.dispatch.wall_s")))
    measured.append(("commit_s", hist_sum("io.commit_s")))
    if not hops and not any(v > 0 for _, v in measured):
        return None
    total = (time.time() - cs) if cs else float(wall_s)
    if total <= 0:  # client clock ahead of ours: fall back to our wall
        total = max(float(wall_s), 0.0)
    out = {"total_s": round(total, 6)}
    spent = 0.0
    for name, v in measured:
        v = min(max(float(v), 0.0), max(total - spent, 0.0))
        if v <= 0 and name in ("coalesce_hold_s", "device_s", "commit_s"):
            continue  # component never armed this run: omit, not zero
        out[name] = round(v, 6)
        spent += v
    out["host_complete_s"] = round(max(total - spent, 0.0), 6)
    return out


def build_report(command: str, argv, started_unix: float, wall_s: float,
                 exit_status: int, trace_path: str = None) -> dict:
    """Assemble the report dict from the global registries.

    Reads :data:`fgumi_tpu.observe.metrics.METRICS`, the module-wide
    ``DEVICE_STATS`` (when the kernel module is loaded), and the fault
    registry; pure read — folding raw counters into METRICS is each
    component's job."""
    from ..utils import faults
    from .metrics import METRICS

    metrics = METRICS.snapshot()
    report = {
        "schema_version": SCHEMA_VERSION,
        "tool": "fgumi-tpu",
        "command": command,
        "argv": list(argv),
        "started_unix": round(started_unix, 3),
        "wall_s": round(wall_s, 4),
        "exit_status": int(exit_status),
        "pid": os.getpid(),
        "metrics": metrics,
    }
    try:
        import socket

        report["hostname"] = socket.gethostname()
    except OSError:
        pass
    stages, queues = _stage_sections(metrics)
    if stages:
        report["stages"] = stages
    if queues:
        report["queues"] = queues
    stats = _device_stats()
    dev = stats.snapshot() if stats is not None else {}
    # active production mesh (parallel/mesh.py publish_mesh): the device
    # section names the (dp, sp, devices) shape so a sharded run's artifact
    # is distinguishable from a single-device one at a glance (ISSUE 10).
    # Keyed off THIS scope's gauges — the process-global snapshot alone
    # would leak one daemon job's mesh into every later job's report; it
    # only contributes the platform label when it matches.
    m_dp = metrics.get("device.mesh.dp")
    if m_dp:
        mesh_sec = {"dp": m_dp, "sp": metrics.get("device.mesh.sp", 1),
                    "devices": metrics.get("device.mesh.devices", m_dp)}
        pm = sys.modules.get("fgumi_tpu.parallel.mesh")
        snap = getattr(pm, "LAST_MESH_SNAPSHOT", None) if pm else None
        if snap and snap.get("dp") == m_dp:
            mesh_sec["platform"] = snap.get("platform")
        dev["mesh"] = mesh_sec
    # offload cost-model state (link/host EWMAs + last decision) rides
    # along whenever batches were routed, so a wrong crossover is
    # diagnosable from the report alone (ISSUE 6 satellite) — including
    # the all-host case, where dispatches stays 0 but route_host > 0,
    # and the seeded-but-idle case (v7: a profile/snapshot-seeded router
    # must stamp prior_source even before its first routed batch)
    router = sys.modules.get("fgumi_tpu.ops.router")
    if router is not None and (dev.get("route_device")
                               or dev.get("route_host")
                               or router.ROUTER.prior_source != "cold"):
        dev["routing"] = router.ROUTER.snapshot()
    # wedge circuit breaker (ops/breaker.py): anything beyond pristine
    # closed rides along, so a degraded run's artifact explains itself —
    # the ISSUE 7 acceptance reads device.breaker.state transitions +
    # deadline_fallbacks straight out of the report
    breaker = sys.modules.get("fgumi_tpu.ops.breaker")
    if breaker is not None:
        bsnap = breaker.BREAKER.snapshot()
        if bsnap["transitions"] or bsnap["state"] != "closed" \
                or bsnap["deadline_overruns"]:
            dev["breaker"] = bsnap
    if stats is not None:
        kern = sys.modules["fgumi_tpu.ops.kernel"]
        dev.update(kern.device_identity())
        shapes = kern.SHAPE_REGISTRY.shape_keys()
        if shapes:
            dev["shapes"] = shapes
        report["device"] = dev
    io_sec = {k.split(".", 1)[1]: v for k, v in metrics.items()
              if k.startswith("io.")}
    if io_sec:
        report["io"] = io_sec
    records = {k.split(".", 1)[1]: v for k, v in metrics.items()
               if k.startswith("records.")}
    if records:
        report["records"] = records
    fired = {p: n for p, n in faults.snapshot().items() if n}
    if fired:
        report["faults"] = fired
    # resource governance: anything beyond a quiet run — a pressure
    # transition, an ENOSPC event, admission sheds, budget rebalancing —
    # rides along so a degraded or resource-failed run's artifact explains
    # itself (the ISSUE 8 acceptance reads the `resource` section straight
    # out of the report of an injected disk-full run)
    gov = sys.modules.get("fgumi_tpu.utils.governor")
    if gov is not None and gov.GOVERNOR.has_activity():
        report["resource"] = gov.GOVERNOR.snapshot()
    # silent-corruption sentinel (schema v4): anything beyond a quiet run
    # — sampled shadow audits, dropped samples, divergences, output-audit
    # verdicts — rides along, so an SDC-touched run's artifact names the
    # corrupt dispatch and which output to distrust (ops/sentinel.py)
    sentinel = sys.modules.get("fgumi_tpu.ops.sentinel")
    if sentinel is not None and sentinel.SENTINEL.has_activity():
        report["audit"] = sentinel.SENTINEL.snapshot()
    # latency histogram summaries (schema v2): every instrumented hot path
    # that observed at least one sample this run — the "how slow was the
    # tail" counterpart of the flat counters above
    latency = METRICS.summaries()
    if latency:
        report["latency"] = latency
    # fleet trace context + end-to-end attribution (schema v5): a daemon
    # job adopted its job id / trace context / hop timestamps onto the
    # telemetry scope at entry (observe/scope.py adopt_job_context); the
    # report is where they become a queryable artifact
    from .scope import current_scope

    scope = current_scope()
    if scope is not None and (scope.trace_id or scope.job_id):
        tc = {}
        if scope.trace_id:
            tc["trace_id"] = scope.trace_id
        if scope.parent_span_id:
            tc["parent_span_id"] = scope.parent_span_id
        if scope.job_id:
            tc["job_id"] = scope.job_id
        report["trace_context"] = tc
    decomposition = _latency_decomposition(latency, wall_s, scope)
    if decomposition:
        report["latency_decomposition"] = decomposition
    # black boxes written during this run (flight recorder): the report is
    # the breadcrumb from "this run degraded" to the full evidence file
    flight = sys.modules.get("fgumi_tpu.observe.flight")
    if flight is not None:
        dumps = flight.FLIGHT.dump_paths()
        if dumps:
            report["flight_dumps"] = dumps
    if trace_path:
        report["trace_path"] = trace_path
    # one-shot XLA device profile (--xla-profile): the capture directory
    # rides along so "device time regressed" links straight to the
    # op-level xprof timeline (observe/xprof.py; v5)
    xprof = sys.modules.get("fgumi_tpu.observe.xprof")
    if xprof is not None:
        captured = xprof.captured_dir()
        if captured:
            report["xla_profile_dir"] = captured
    # applied deployment profile (tune/profile.py; v7): which knobs the
    # profile filled vs explicit overrides, fingerprint mismatches, and
    # whether router priors were seeded — pairs with
    # device.routing.prior_source to make first-batch routing attributable
    tune_prof = sys.modules.get("fgumi_tpu.tune.profile")
    if tune_prof is not None:
        applied = tune_prof.applied_info()
        if applied:
            report["profile"] = {
                "path": applied["path"],
                "knobs_applied": list(applied["applied"]),
                "knobs_skipped_explicit":
                    list(applied["skipped_explicit"]),
                "fingerprint_mismatch":
                    list(applied["fingerprint_mismatch"]),
                "seeded_router": bool(applied["seeded_router"]),
                "seeded_choosers": list(applied["seeded_choosers"]),
            }
    # span aggregate (v9): per-name count / wall / self time of every layer
    # span of this run, whenever spans were live
    from .trace import current_aggregate

    agg = current_aggregate()
    if agg is not None:
        spans = agg.snapshot()
        threads = spans.pop("threads")
        if spans["by_name"]:
            report["spans"] = spans
        # the same spans by thread (v10), and which thread paces the job:
        # the one whose root spans hold the most seconds that are not
        # declared waits, named by the root span most of them were under
        if threads:
            report["threads"] = threads
            name, rec = max(threads.items(),
                            key=lambda kv: kv[1]["work_s"])
            if rec["roots"]:
                report["threads_pacing"] = {
                    "thread": name,
                    "role": max(rec["roots"], key=rec["roots"].get),
                    "work_s": rec["work_s"]}
        # allocator counters at the job's two ends (v10): only where the
        # invocation took the start record, which it does for a run report
        if agg.alloc_start is not None:
            from . import alloc

            report["alloc"] = {"start": agg.alloc_start,
                               "end": alloc.read()}
    # process-level record (v9): what this process paid once — start-up
    # spans, every compile and cache load so far — in every report, since
    # the commands that paid may have written none
    from . import process

    report["process"] = process.snapshot()
    return report


def write_report(path: str, report: dict):
    """Commit the report atomically (crash-safe like every other output)."""
    from ..utils.atomic import discard_output, open_output

    out = open_output(path, "w")
    try:
        json.dump(report, out, indent=1, sort_keys=False)
        out.write("\n")
    except BaseException:
        discard_output(out)
        raise
    out.close()


def emit(path: str, command: str, argv, started_unix: float, wall_s: float,
         exit_status: int, trace_path: str = None) -> dict:
    """Build + write in one step; never raises out of an exiting command
    (a telemetry failure must not turn a successful run into a failed one —
    it logs and returns None instead)."""
    import logging

    try:
        report = build_report(command, argv, started_unix, wall_s,
                              exit_status, trace_path)
        write_report(path, report)
        return report
    except Exception:
        logging.getLogger("fgumi_tpu").exception(
            "failed to write run report %s", path)
        return None


def fold_device_stats():
    """Fold the module-wide DeviceStats into METRICS under ``device.*``.

    Called once at command exit (before the report is built) so the flat
    metrics view carries the same numbers as the ``device`` section."""
    from .metrics import METRICS

    stats = _device_stats()
    snap = stats.snapshot() if stats is not None else {}
    if snap.get("dispatches"):
        METRICS.update(snap, prefix="device")
