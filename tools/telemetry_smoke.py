#!/usr/bin/env python3
"""Telemetry smoke: run small commands with --trace + --run-report and gate
the artifacts.

Checks (exit 0 when every scenario holds, one PASS/FAIL line each):

1. ``dedup --threads 4`` emits a well-formed Chrome trace-event JSON with
   complete events from >= 3 distinct threads (reader / processor / writer
   at minimum) including pipeline-stage spans, and a schema-valid run
   report whose stage timings and record counts are non-zero — and whose
   ``latency`` section (schema v2) carries ordered histogram summaries
   for the BGZF hot path.
2. ``simplex`` with the device kernel forced (FGUMI_TPU_HOST_ENGINE=0)
   additionally records device-dispatch/fetch spans, non-zero DeviceStats,
   and per-dispatch latency histograms in the report.
3. With both flags off, no trace/report/flight artifacts appear.
4. Chaos wedge: an injected ``device.wedge`` hang under a tight dispatch
   deadline exits 0 (host-engine degradation), and leaves a schema-valid
   flight-recorder black box naming the wedged dispatch, with the dump
   path carried in the run report's ``flight_dumps``.

The in-pytest equivalents live in tests/test_observe.py and
tests/test_run_report.py; this is the fast out-of-pytest gate, a sibling
of tools/chaos_smoke.py.

Usage:  python tools/telemetry_smoke.py [--keep]
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BASE_ENV = {
    **os.environ,
    "PYTHONPATH": REPO,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "",
}


def run(args, env=None, timeout=300, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", *args], cwd=cwd,
        env={**BASE_ENV, **(env or {})}, capture_output=True, text=True,
        timeout=timeout)


def check(name, ok, detail=""):
    print(f"{'PASS' if ok else 'FAIL'}  {name}" + (f"  ({detail})"
                                                   if detail else ""))
    return ok


def load_trace(path):
    """Parse a trace file; returns (span_events, tid_count, names) or None."""
    try:
        obj = json.load(open(path))
    except (OSError, ValueError):
        return None
    evs = obj.get("traceEvents")
    if not isinstance(evs, list):
        return None
    for ev in evs:
        if not {"name", "ph", "pid", "tid"} <= set(ev):
            return None
        if ev["ph"] == "X" and ("ts" not in ev or "dur" not in ev):
            return None
    spans = [e for e in evs if e["ph"] == "X"]
    return spans, len({e["tid"] for e in spans}), {e["name"] for e in spans}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--keep", action="store_true",
                    help="keep the scratch directory")
    opts = ap.parse_args()
    from fgumi_tpu.observe.report import validate_report

    tmp = tempfile.mkdtemp(prefix="fgumi_telemetry_")
    ok = True
    try:
        mapped = os.path.join(tmp, "mapped.bam")
        grouped = os.path.join(tmp, "grouped.bam")
        p = run(["simulate", "mapped-reads", "-o", mapped,
                 "--num-families", "50", "--family-size", "4", "--seed", "9"])
        assert p.returncode == 0, p.stderr
        p = run(["simulate", "grouped-reads", "-o", grouped,
                 "--num-families", "40", "--family-size", "4", "--seed", "9"])
        assert p.returncode == 0, p.stderr

        # 1) dedup: threaded pipeline -> >= 3 traced threads + valid report
        trace1 = os.path.join(tmp, "dedup.trace.json")
        rpt1 = os.path.join(tmp, "dedup.report.json")
        p = run(["--trace", trace1, "--run-report", rpt1, "dedup",
                 "-i", mapped, "-o", os.path.join(tmp, "dedup.bam"),
                 "--threads", "4"])
        ok &= check("dedup --trace/--run-report exits 0", p.returncode == 0,
                    f"rc={p.returncode}")
        got = load_trace(trace1)
        ok &= check("dedup trace is well-formed Chrome trace JSON",
                    got is not None)
        if got:
            spans, n_tids, names = got
            ok &= check("dedup trace has spans from >= 3 threads",
                        n_tids >= 3, f"threads={n_tids}")
            ok &= check("dedup trace has pipeline-stage spans",
                        {"pipeline.read", "pipeline.process",
                         "pipeline.sink"} <= names,
                        f"names={sorted(names)}")
        try:
            rpt = json.load(open(rpt1))
        except (OSError, ValueError):
            rpt = None
        errs = validate_report(rpt) if rpt else ["unreadable"]
        ok &= check("dedup run report is schema-valid", not errs,
                    "; ".join(errs[:3]))
        if rpt and not errs:
            busy = sum(v.get("busy_s", 0)
                       for v in rpt.get("stages", {}).values())
            ok &= check("dedup report stage timings non-zero", busy > 0)
            ok &= check("dedup report counts records",
                        sum(rpt.get("records", {}).values()) > 0)
            ok &= check("dedup report counts I/O bytes",
                        rpt.get("io", {}).get("bytes_read", 0) > 0
                        and rpt.get("io", {}).get("bytes_written", 0) > 0)
            lat = rpt.get("latency", {})
            ok &= check("dedup report carries BGZF latency histograms",
                        lat.get("io.bgzf.decompress_s", {})
                        .get("count", 0) > 0
                        and lat.get("io.bgzf.compress_s", {})
                        .get("count", 0) > 0,
                        f"latency keys={sorted(lat)[:6]}")
            ordered = all(
                s["p50"] <= s["p90"] <= s["p99"] <= s["max"]
                for s in lat.values())
            ok &= check("dedup latency quantiles ordered", ordered)

        # 2) simplex on the device kernel: device spans + DeviceStats
        trace2 = os.path.join(tmp, "simplex.trace.json")
        rpt2 = os.path.join(tmp, "simplex.report.json")
        p = run(["--trace", trace2, "--run-report", rpt2, "simplex",
                 "-i", grouped, "-o", os.path.join(tmp, "cons.bam"),
                 "--min-reads", "1", "--threads", "4"],
                # force the device route: the adaptive offload policy would
                # price this tiny workload host-side and emit no device spans
                env={"FGUMI_TPU_HOST_ENGINE": "0",
                     "FGUMI_TPU_ROUTE": "device"})
        ok &= check("simplex (device) exits 0", p.returncode == 0,
                    f"rc={p.returncode}")
        got = load_trace(trace2)
        if got:
            spans, n_tids, names = got
            ok &= check("simplex trace has device-dispatch spans",
                        "device.dispatch" in names and "device.fetch" in names,
                        f"names={sorted(names)}")
            ok &= check("simplex trace has spans from >= 3 threads",
                        n_tids >= 3, f"threads={n_tids}")
        else:
            ok &= check("simplex trace is well-formed", False)
        try:
            rpt = json.load(open(rpt2))
        except (OSError, ValueError):
            rpt = None
        errs = validate_report(rpt) if rpt else ["unreadable"]
        ok &= check("simplex run report is schema-valid", not errs,
                    "; ".join(errs[:3]))
        if rpt and not errs:
            ok &= check("simplex report device dispatches non-zero",
                        rpt.get("device", {}).get("dispatches", 0) > 0)
            lat = rpt.get("latency", {})
            ok &= check("simplex report carries dispatch latency "
                        "histograms",
                        lat.get("device.dispatch.wall_s", {})
                        .get("count", 0) > 0
                        and lat.get("device.dispatch.fetch_s", {})
                        .get("count", 0) > 0,
                        f"latency keys={sorted(lat)[:8]}")

        # 3) flags off -> no artifacts
        off_dir = os.path.join(tmp, "off")
        os.mkdir(off_dir)
        p = run(["dedup", "-i", mapped,
                 "-o", os.path.join(off_dir, "out.bam")])
        residue = [f for f in os.listdir(off_dir) if f != "out.bam"]
        ok &= check("flags off -> no telemetry artifacts",
                    p.returncode == 0 and not residue, f"residue={residue}")

        # 4) chaos wedge -> schema-valid black box + clean degradation
        from fgumi_tpu.observe.flight import validate_dump

        flight_dir = os.path.join(tmp, "flight")
        os.mkdir(flight_dir)
        rpt4 = os.path.join(tmp, "wedge.report.json")
        # identical relative argv in two working dirs (the chaos knobs and
        # the report travel via env), so @PG CL provenance bytes match and
        # the degradation's byte-identity contract is actually testable
        wd_ref = os.path.join(tmp, "wedge_ref")
        wd_chaos = os.path.join(tmp, "wedge_chaos")
        os.mkdir(wd_ref)
        os.mkdir(wd_chaos)
        argv4 = ["simplex", "-i", grouped, "-o", "wedge.bam",
                 "--min-reads", "1"]
        out4 = os.path.join(wd_chaos, "wedge.bam")
        ref4 = os.path.join(wd_ref, "wedge.bam")
        p = run(argv4, cwd=wd_ref)
        assert p.returncode == 0, p.stderr
        p = run(argv4, cwd=wd_chaos,
                env={"FGUMI_TPU_HOST_ENGINE": "0",
                     "FGUMI_TPU_ROUTE": "device",
                     "FGUMI_TPU_FLIGHT": flight_dir,
                     "FGUMI_TPU_RUN_REPORT": rpt4,
                     "FGUMI_TPU_DISPATCH_DEADLINE_S": "0.5:1",
                     "FGUMI_TPU_FAULT_HANG_S": "3",
                     "FGUMI_TPU_FAULT": "device.wedge:hang:1.0:1"})
        ok &= check("wedged run degrades cleanly (exit 0)",
                    p.returncode == 0, f"rc={p.returncode}")
        ok &= check("wedged run output byte-identical to clean run",
                    os.path.exists(out4)
                    and open(out4, "rb").read() == open(ref4, "rb").read())
        dumps = sorted(f for f in os.listdir(flight_dir)
                       if f.startswith("flight-"))
        ok &= check("wedge leaves a flight-recorder black box",
                    len(dumps) >= 1, f"dumps={dumps}")
        if dumps:
            obj = json.load(open(os.path.join(flight_dir, dumps[0])))
            derrs = validate_dump(obj)
            ok &= check("black box is schema-valid", not derrs,
                        "; ".join(derrs[:3]))
            ok &= check("black box names the wedged dispatch",
                        obj.get("reason") == "dispatch-deadline"
                        and obj.get("attrs", {})
                        .get("deadline_fallbacks", 0) >= 1
                        and bool((obj.get("device") or {})
                                 .get("timeline_tail")))
            try:
                r4 = json.load(open(rpt4))
            except (OSError, ValueError):
                r4 = {}
            ok &= check("run report carries the dump path",
                        any(os.path.basename(d) in dumps
                            for d in r4.get("flight_dumps", [])),
                        str(r4.get("flight_dumps")))
    finally:
        if opts.keep:
            print("scratch kept at", tmp)
        else:
            shutil.rmtree(tmp, ignore_errors=True)
    print("telemetry smoke:", "OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
