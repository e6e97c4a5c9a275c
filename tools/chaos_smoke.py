#!/usr/bin/env python3
"""Chaos smoke: run one command under each injected fault and check the
exit-code contract, SIGKILL one of two fleet daemons mid-job and check
the balancer-eject + journal-lease-takeover contract (byte-identical
completion, zero double-execution), then SIGKILL a run mid-write and
check crash-safe commit (no partial file under the final output name).

Usage:  python tools/chaos_smoke.py [--keep]

Exit 0 when every scenario holds; prints a one-line PASS/FAIL per
scenario. Used as the fast out-of-pytest resilience gate (ROADMAP: chaos
tooling satellite); the equivalent in-pytest coverage lives in
tests/test_faults.py / tests/test_atomic_output.py.
"""

import argparse
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--keep", action="store_true",
                    help="keep the scratch directory")
    opts = ap.parse_args()
    tmp = tempfile.mkdtemp(prefix="fgumi_chaos_")
    ok = True
    try:
        sim = os.path.join(tmp, "sim.bam")
        p = run(["simulate", "grouped-reads", "-o", sim,
                 "--num-families", "25", "--family-size", "4",
                 "--seed", "11"])
        assert p.returncode == 0, p.stderr

        # clean reference run (device path). Each parity run uses its own
        # cwd with a RELATIVE -o so argv — and hence the @PG CL header
        # line — is byte-identical across runs.
        clean_dir = os.path.join(tmp, "clean")
        os.mkdir(clean_dir)
        p = run(["simplex", "-i", sim, "-o", "out.bam", "--min-reads", "1"],
                env={"FGUMI_TPU_HOST_ENGINE": "0"}, cwd=clean_dir)
        assert p.returncode == 0, p.stderr
        clean = open(os.path.join(clean_dir, "out.bam"), "rb").read()

        # 1) host-side faults: clean nonzero exit, no partial final file
        for point in ("reader.decompress", "writer.compress",
                      "native.batch", "pipeline.process"):
            d = os.path.join(tmp, point.replace(".", "_"))
            os.mkdir(d)
            out = os.path.join(d, "out.bam")
            extra = (["--threads", "4"] if point == "pipeline.process"
                     else [])
            p = run(["simplex", "-i", sim, "-o", out, "--min-reads", "1",
                     *extra],
                    env={"FGUMI_TPU_FAULT": f"{point}:raise:1.0:1"})
            failed_clean = p.returncode != 0 and not os.path.exists(out) \
                and "Traceback" not in p.stderr
            completed = p.returncode == 0 and os.path.exists(out)
            ok &= check(f"{point}:raise -> clean error or completion",
                        failed_clean or completed,
                        f"rc={p.returncode}")

        # 2) device retry: two injected failures absorbed, byte-identical
        for spec, name in (
                ("device.dispatch:raise:1.0:2", "retry"),
                ("device.dispatch:raise:1.0", "host-fallback"),
                ("device.dispatch:oom:1.0:1", "oom-split")):
            d = os.path.join(tmp, name)
            os.mkdir(d)
            env = {"FGUMI_TPU_HOST_ENGINE": "0", "FGUMI_TPU_FAULT": spec,
                   "FGUMI_TPU_DEVICE_BACKOFF_S": "0.01"}
            if name == "oom-split":
                env["FGUMI_TPU_HYBRID"] = "0"
            p = run(["simplex", "-i", sim, "-o", "out.bam",
                     "--min-reads", "1"], env=env, cwd=d)
            got = (open(os.path.join(d, "out.bam"), "rb").read()
                   if p.returncode == 0 else b"")
            if name == "oom-split":
                # the wire path (HYBRID=0) has its own clean reference
                d2 = os.path.join(tmp, "oom_clean")
                os.mkdir(d2)
                p2 = run(["simplex", "-i", sim, "-o", "out.bam",
                          "--min-reads", "1"],
                         env={"FGUMI_TPU_HOST_ENGINE": "0",
                              "FGUMI_TPU_HYBRID": "0"}, cwd=d2)
                ref = open(os.path.join(d2, "out.bam"), "rb").read() \
                    if p2.returncode == 0 else b"?"
            else:
                ref = clean
            ok &= check(f"device.dispatch {name} -> byte-identical",
                        p.returncode == 0 and got == ref,
                        f"rc={p.returncode}")

        # 3) device wedge: a dispatch that never returns is abandoned at
        # its deadline, the batch completes byte-identically on the host
        # engine, the whole run costs seconds (bounded by the deadline,
        # not the hang), and the run report records the breaker opening
        # (ISSUE 7 acceptance)
        # relative --run-report keeps argv — and hence @PG CL provenance —
        # byte-identical between the wedged run and its pure-host twin
        wedge_argv = ["--run-report", "report.json", "simplex", "-i", sim,
                      "-o", "out.bam", "--min-reads", "1"]
        d_host = os.path.join(tmp, "wedge_host_ref")
        os.mkdir(d_host)
        p = run(wedge_argv, env={"FGUMI_TPU_HOST_ENGINE": "1"}, cwd=d_host)
        assert p.returncode == 0, p.stderr
        host_ref = open(os.path.join(d_host, "out.bam"), "rb").read()
        d = os.path.join(tmp, "wedge")
        os.mkdir(d)
        rpt = os.path.join(d, "report.json")
        t0 = time.monotonic()
        p = run(wedge_argv,
                env={"FGUMI_TPU_HOST_ENGINE": "0",
                     "FGUMI_TPU_ROUTE": "device",
                     "FGUMI_TPU_FAULT": "device.wedge:hang:1.0:1",
                     "FGUMI_TPU_FAULT_HANG_S": "30",
                     "FGUMI_TPU_DISPATCH_DEADLINE_S": "2:5"},
                cwd=d)
        wedge_wall = time.monotonic() - t0
        got = (open(os.path.join(d, "out.bam"), "rb").read()
               if p.returncode == 0 else b"")
        ok &= check("device.wedge -> degraded (exit 0), byte-identical "
                    "to the pure host-engine run",
                    p.returncode == 0 and got == host_ref,
                    f"rc={p.returncode}")
        # the wedge cost is the deadline, not the 30 s hang (generous
        # bound: pipeline + interpreter startup ride along)
        ok &= check("wedge cost bounded by the deadline",
                    wedge_wall < 25, f"{wedge_wall:.1f}s")
        try:
            report = __import__("json").load(open(rpt))
            dev = report.get("device", {})
            br = dev.get("breaker", {})
            ok &= check(
                "report records deadline fallback + breaker opening",
                dev.get("deadline_fallbacks", 0) >= 1
                and any(t.get("to") == "open"
                        for t in br.get("transitions", [])),
                f"deadline_fallbacks={dev.get('deadline_fallbacks')} "
                f"breaker={br.get('state')}")
        except (OSError, ValueError) as e:
            ok &= check("report records deadline fallback + breaker "
                        "opening", False, str(e))

        # 3b) silent data corruption (ISSUE 14): corrupt-result at
        # device.fetch with the shadow audit at `all` -> the sentinel
        # detects the divergence within the injected dispatch's own
        # audit, the breaker records an `sdc` trip (quarantine), the run
        # degrades to host and still exits 0 with output byte-identical
        # to the pure-host run (the inline audit repairs the corrupt
        # batch with the oracle tuple it just computed), and the report
        # carries the divergence record + both result digests
        d = os.path.join(tmp, "sdc")
        os.mkdir(d)
        rpt = os.path.join(d, "report.json")
        p = run(wedge_argv,
                env={"FGUMI_TPU_HOST_ENGINE": "0",
                     "FGUMI_TPU_ROUTE": "device",
                     "FGUMI_TPU_AUDIT": "all",
                     "FGUMI_TPU_FLIGHT": d,
                     "FGUMI_TPU_FAULT":
                         "device.fetch:corrupt-result:1.0:1"},
                cwd=d)
        got = (open(os.path.join(d, "out.bam"), "rb").read()
               if p.returncode == 0 else b"")
        ok &= check("corrupt-result + audit=all -> detected, degraded "
                    "(exit 0), byte-identical to the pure host-engine run",
                    p.returncode == 0 and got == host_ref,
                    f"rc={p.returncode}")
        try:
            report = __import__("json").load(open(rpt))
            audit = report.get("audit", {})
            br = report.get("device", {}).get("breaker", {})
            dump_ok = any("sdc" in os.path.basename(f)
                          for f in report.get("flight_dumps", []))
            ok &= check(
                "report records the audit divergence + sdc trip + "
                "flight dump",
                audit.get("divergent", 0) >= 1
                and bool(audit.get("divergence"))
                and br.get("sdc_trips", 0) >= 1
                and any("silent data corruption" in t.get("reason", "")
                        for t in br.get("transitions", []))
                and dump_ok,
                f"divergent={audit.get('divergent')} "
                f"sdc_trips={br.get('sdc_trips')} dump={dump_ok}")
        except (OSError, ValueError) as e:
            ok &= check("report records the audit divergence + sdc trip "
                        "+ flight dump", False, str(e))

        # 3c) the same corruption with the audit OFF documents the
        # undetected baseline: the run exits 0 but silently publishes a
        # corrupt output (differs from the clean run) with zero signal in
        # the report — exactly the gap the sentinel closes
        d = os.path.join(tmp, "sdc_off")
        os.mkdir(d)
        rpt = os.path.join(d, "report.json")
        p = run(wedge_argv,
                env={"FGUMI_TPU_HOST_ENGINE": "0",
                     "FGUMI_TPU_ROUTE": "device",
                     "FGUMI_TPU_AUDIT": "off",
                     "FGUMI_TPU_FAULT":
                         "device.fetch:corrupt-result:1.0:1"},
                cwd=d)
        got = (open(os.path.join(d, "out.bam"), "rb").read()
               if p.returncode == 0 else b"")
        try:
            report = __import__("json").load(open(rpt))
        except (OSError, ValueError):
            report = {}
        ok &= check("corrupt-result + audit=off -> corruption published "
                    "UNDETECTED (exit 0, differing bytes, no audit "
                    "section): the documented baseline",
                    p.returncode == 0 and got != host_ref and len(got) > 0
                    and "audit" not in report,
                    f"rc={p.returncode} bytes={len(got)}")

        # 3e) fused-filter SDC (ISSUE 19): the same corrupt-result fault
        # on the ``--device-filter`` route with the audit at `all` -> the
        # stats-row audit detects the divergence inside the fused
        # dispatch, repairs the batch with the oracle columns (the host
        # filter finishes the stage), the breaker records the sdc trip,
        # and the published output stays byte-identical to the clean
        # fused run
        filt_argv = ["--run-report", "report.json", "simplex", "-i", sim,
                     "-o", "out.bam", "--min-reads", "1",
                     "--device-filter", "--filter-min-reads", "2",
                     "--filter-min-mean-base-quality", "30"]
        d_ref = os.path.join(tmp, "sdc_filter_ref")
        os.mkdir(d_ref)
        p = run(filt_argv, env={"FGUMI_TPU_HOST_ENGINE": "0",
                                "FGUMI_TPU_ROUTE": "device"}, cwd=d_ref)
        assert p.returncode == 0, p.stderr
        filt_ref = open(os.path.join(d_ref, "out.bam"), "rb").read()
        d = os.path.join(tmp, "sdc_filter")
        os.mkdir(d)
        rpt = os.path.join(d, "report.json")
        p = run(filt_argv,
                env={"FGUMI_TPU_HOST_ENGINE": "0",
                     "FGUMI_TPU_ROUTE": "device",
                     "FGUMI_TPU_AUDIT": "all",
                     "FGUMI_TPU_FLIGHT": d,
                     "FGUMI_TPU_FAULT":
                         "device.fetch:corrupt-result:1.0:1"},
                cwd=d)
        got = (open(os.path.join(d, "out.bam"), "rb").read()
               if p.returncode == 0 else b"")
        ok &= check("corrupt-result on --device-filter + audit=all -> "
                    "detected, repaired (exit 0), byte-identical to the "
                    "clean fused run",
                    p.returncode == 0 and got == filt_ref,
                    f"rc={p.returncode}")
        try:
            report = __import__("json").load(open(rpt))
            audit = report.get("audit", {})
            br = report.get("device", {}).get("breaker", {})
            dump_ok = any("sdc" in os.path.basename(f)
                          for f in report.get("flight_dumps", []))
            ok &= check(
                "device-filter report records the audit divergence + "
                "sdc trip + flight dump",
                audit.get("divergent", 0) >= 1
                and br.get("sdc_trips", 0) >= 1
                and dump_ok,
                f"divergent={audit.get('divergent')} "
                f"sdc_trips={br.get('sdc_trips')} dump={dump_ok}")
        except (OSError, ValueError) as e:
            ok &= check("device-filter report records the audit "
                        "divergence + sdc trip + flight dump", False,
                        str(e))

        # 3d) --audit-output: corruption injected below the writer's
        # tally (BGZF layer) is refused before the atomic rename — exit
        # 5, no file published
        d = os.path.join(tmp, "audit_output")
        os.mkdir(d)
        p = run(["--audit-output", "simplex", "-i", sim, "-o", "out.bam",
                 "--min-reads", "1"],
                env={"FGUMI_TPU_FAULT":
                     "writer.compress:corrupt-bytes:1.0:1"}, cwd=d)
        leftovers = os.listdir(d)
        ok &= check("--audit-output refuses a corrupted stream -> exit 5, "
                    "nothing published",
                    p.returncode == 5 and not leftovers
                    and "Traceback" not in p.stderr,
                    f"rc={p.returncode} leftovers={leftovers}")

        # 3e) merged-dispatch fault (ISSUE 15): two concurrent jobs on a
        # coalescing daemon with serve.coalesce:raise armed on EVERY
        # merged launch — each partner degrades to the host engine over
        # its OWN rows, outputs stay byte-identical to the fault-free
        # standalone runs, and the daemon exits 0
        sys.path.insert(0, REPO)
        from fgumi_tpu.serve.client import ServeClient, ServeError

        co_dir = os.path.join(tmp, "coalesce_fault")
        co_std = os.path.join(co_dir, "std")
        co_wd = os.path.join(co_dir, "wd")
        for d in (co_std, co_wd):
            os.makedirs(d)
        co_inp = os.path.join(co_dir, "grouped.bam")
        p = run(["simulate", "grouped-reads", "-o", co_inp,
                 "--num-families", "400", "--family-size", "4",
                 "--seed", "31"])
        assert p.returncode == 0, p.stderr
        co_jobs = [["simplex", "-i", co_inp, "-o", f"out_co{i}.bam",
                    "--min-reads", "1", "--batch-groups", "25"]
                   for i in range(2)]
        for argv in co_jobs:
            p = run(argv, cwd=co_std, env={"FGUMI_TPU_HOST_ENGINE": "0"})
            assert p.returncode == 0, p.stderr
        co_sock = os.path.join(co_dir, "serve.sock")
        co_env = {**BASE_ENV, "FGUMI_TPU_HOST_ENGINE": "0",
                  "FGUMI_TPU_ROUTE": "device",
                  "FGUMI_TPU_COALESCE": "1",
                  "FGUMI_TPU_FAULT": "serve.coalesce:raise:1.0",
                  "FGUMI_TPU_DEVICE_BACKOFF_S": "0.01"}
        dproc = subprocess.Popen(
            [sys.executable, "-m", "fgumi_tpu", "serve", "--socket",
             co_sock, "--workers", "2", "--coalesce-window-ms", "50"],
            cwd=co_wd, env=co_env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        try:
            cclient = ServeClient(co_sock, timeout=30)
            deadline = time.monotonic() + 120
            upc = False
            while time.monotonic() < deadline and not upc:
                try:
                    cclient.ping()
                    upc = True
                except ServeError:
                    time.sleep(0.2)
            assert upc, "coalescing daemon never came up"
            argv0 = os.path.join(REPO, "fgumi_tpu", "__main__.py")
            handles = [cclient.submit(argv, argv0=argv0)
                       for argv in co_jobs]
            states = [cclient.wait(h["id"], timeout=240)["state"]
                      for h in handles]
            ident = True
            for i in range(2):
                ref = open(os.path.join(co_std, f"out_co{i}.bam"),
                           "rb").read()
                got_path = os.path.join(co_wd, f"out_co{i}.bam")
                got = open(got_path, "rb").read() \
                    if os.path.exists(got_path) else b""
                ident &= got == ref
            ok &= check("serve.coalesce:raise -> both jobs done, outputs "
                        "byte-identical to fault-free standalone",
                        states == ["done", "done"] and ident,
                        f"states={states} identical={ident}")
            stats = cclient.request({"v": 1, "op": "stats"}).get(
                "stats", {})
            coal = stats.get("coalesce") or {}
            ok &= check("stats record the merged launches that degraded",
                        coal.get("merged_batches", 0) >= 1
                        and coal.get("partners", 0) >= 2,
                        f"merged={coal.get('merged_batches')} "
                        f"partners={coal.get('partners')}")
            cclient.shutdown()
            rc = dproc.wait(timeout=240)
            ok &= check("coalescing daemon exits 0 under merged-dispatch "
                        "faults", rc == 0, f"rc={rc}")
        finally:
            if dproc.poll() is None:
                dproc.kill()
                dproc.wait(timeout=10)

        # 4) disk full (ISSUE 8): injected ENOSPC mid-spill and mid-merge
        # both honor the resource clean-failure contract — exit 4, no
        # partial output, no stale spill temps, and the run report records
        # the resource event (docs/resilience.md "Resource governance")
        big = os.path.join(tmp, "big.bam")
        p = run(["simulate", "grouped-reads", "-o", big,
                 "--num-families", "120", "--family-size", "4",
                 "--seed", "17"])
        assert p.returncode == 0, p.stderr
        for phase, spec in (
                ("mid-spill", "sort.spill:enospc:1.0:1"),
                ("mid-merge", "writer.compress:enospc:1.0:1")):
            d = os.path.join(tmp, f"enospc_{phase.replace('-', '_')}")
            spill = os.path.join(d, "spill")
            os.makedirs(spill)
            out = os.path.join(d, "out.bam")
            rpt = os.path.join(d, "report.json")
            p = run(["--run-report", rpt, "sort", "-i", big, "-o", out,
                     "--max-records-in-ram", "60", "--tmp-dir", spill],
                    env={"FGUMI_TPU_FAULT": spec})
            leftovers = [n for n in os.listdir(d)
                         if n not in ("report.json", "spill")] \
                + os.listdir(spill)
            ok &= check(f"ENOSPC {phase} -> exit 4, no partial output or "
                        "spill temps",
                        p.returncode == 4 and not leftovers
                        and "Traceback" not in p.stderr,
                        f"rc={p.returncode} leftovers={leftovers}")
            try:
                report = __import__("json").load(open(rpt))
                res = report.get("resource", {})
                ok &= check(f"ENOSPC {phase} -> report records the "
                            "resource event",
                            report.get("exit_status") == 4
                            and any(ev.get("kind") == "enospc"
                                    for ev in res.get("events", [])),
                            f"events={res.get('events')}")
            except (OSError, ValueError) as e:
                ok &= check(f"ENOSPC {phase} -> report records the "
                            "resource event", False, str(e))

        # 5) governed vs ungoverned byte-identity: with the governor
        # rebalancing aggressively (tiny starting channel budgets, fast
        # ticks) the pipeline chain's bytes land identically — budgets
        # change WHEN bytes move, never what is written
        gov_sim = os.path.join(tmp, "gov")
        os.mkdir(gov_sim)
        p = run(["simulate", "fastq-reads", "-1", "r1.fq.gz",
                 "-2", "r2.fq.gz", "--num-families", "60",
                 "--family-size", "3", "--read-length", "60",
                 "--seed", "23"], cwd=gov_sim)
        assert p.returncode == 0, p.stderr
        gov_env = {"FGUMI_TPU_CHAIN_BYTES": str(1 << 20),
                   "FGUMI_TPU_GOVERNOR_PERIOD_S": "0.05"}
        for mode, extra in (("fused", []), ("staged", ["--no-fuse"])):
            outs = {}
            for label, env in (("governed", gov_env),
                               ("ungoverned",
                                {**gov_env, "FGUMI_TPU_GOVERNOR": "0"})):
                d = os.path.join(gov_sim, f"{mode}_{label}")
                os.mkdir(d)
                for f in ("r1.fq.gz", "r2.fq.gz"):
                    os.link(os.path.join(gov_sim, f), os.path.join(d, f))
                p = run(["pipeline", "-i", "r1.fq.gz", "r2.fq.gz",
                         "-r", "8M+T", "+T", "-o", "out.bam",
                         "--filter-min-reads", "1", "--threads", "2",
                         "--sample", "s", "--library", "l", *extra],
                        env=env, cwd=d)
                outs[label] = (open(os.path.join(d, "out.bam"), "rb").read()
                               if p.returncode == 0 else label.encode())
            ok &= check(f"{mode} chain: governed run byte-identical to "
                        "FGUMI_TPU_GOVERNOR=0",
                        outs["governed"] == outs["ungoverned"],
                        f"{len(outs['governed'])} bytes")

        # 6) fleet takeover (ISSUE 12): SIGKILL one of two TCP daemons
        # mid-job; the balancer must eject it, the survivor must claim the
        # dead daemon's journal lease and finish the job byte-identically
        # under its original id, and the journal + dedupe audit must show
        # exactly one execution fleet-wide
        sys.path.insert(0, REPO)
        from fgumi_tpu.serve.client import ServeClient, ServeError

        def _free_port():
            import socket as _socket

            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            return port

        fdir = os.path.join(tmp, "fleet")
        fwd = os.path.join(fdir, "wd")
        fstd = os.path.join(fdir, "std")
        jdir = os.path.join(fdir, "journals")
        for d in (fwd, fstd, jdir):
            os.makedirs(d)
        finp = os.path.join(fdir, "grouped.bam")
        p = run(["simulate", "grouped-reads", "-o", finp,
                 "--num-families", "500", "--family-size", "4",
                 "--seed", "29"])
        assert p.returncode == 0, p.stderr
        fleet_job = ["simplex", "-i", finp, "-o", "out_fleet.bam",
                     "--min-reads", "1"]
        p = run(fleet_job, cwd=fstd, env={"FGUMI_TPU_HOST_ENGINE": "0"})
        assert p.returncode == 0, p.stderr
        ports = {"a": _free_port(), "b": _free_port()}
        front = _free_port()
        fleet_env = {**BASE_ENV, "FGUMI_TPU_HOST_ENGINE": "0"}
        daemons = {}
        bal = None
        try:
            for fid in ("a", "b"):
                daemons[fid] = subprocess.Popen(
                    [sys.executable, "-m", "fgumi_tpu", "serve",
                     "--tcp", f"127.0.0.1:{ports[fid]}", "--workers", "1",
                     "--queue-limit", "0", "--journal-dir", jdir,
                     "--fleet-id", fid, "--lease-scan-period", "0.5"],
                    cwd=fwd, env=fleet_env, stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT, text=True)
            bal = subprocess.Popen(
                [sys.executable, "-m", "fgumi_tpu", "balance",
                 "--listen", f"tcp:127.0.0.1:{front}",
                 "--backend", f"tcp:127.0.0.1:{ports['a']}",
                 "--backend", f"tcp:127.0.0.1:{ports['b']}",
                 "--poll-period", "0.3", "--eject-failures", "2",
                 "--cooldown", "1.0"],
                cwd=fdir, env=fleet_env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)
            client = ServeClient(f"tcp:127.0.0.1:{front}", timeout=30)
            deadline = time.monotonic() + 120
            up = False
            while time.monotonic() < deadline and not up:
                try:
                    st = client.stats()
                    up = all(b["state"] == "closed"
                             for b in st["backends"])
                except ServeError:
                    time.sleep(0.2)
            ok &= check("fleet: balancer + both backends up", up)
            # argv0 matching the standalone invocation (python -m
            # fgumi_tpu) so @PG CL provenance bytes agree
            argv0 = os.path.join(REPO, "fgumi_tpu", "__main__.py")
            jk = client.submit(fleet_job, dedupe="chaos-fleet",
                               argv0=argv0)
            victim = jk["id"].split("-j-")[0]
            seen_running = False
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                try:
                    state = client.job(jk["id"])["state"]
                    if state == "running":
                        seen_running = True
                        break
                    if state in ("done", "failed", "cancelled"):
                        break  # finished pre-kill: scenario void
                except ServeError:
                    pass
                time.sleep(0.1)
            ok &= check("fleet: job observed running before SIGKILL",
                        seen_running)
            daemons[victim].kill()
            daemons[victim].wait(timeout=30)
            victim_addr = f"tcp:127.0.0.1:{ports[victim]}"
            ejected = False
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline and not ejected:
                try:
                    st = client.stats()
                    ejected = any(b["address"] == victim_addr
                                  and b["state"] == "open"
                                  for b in st["backends"])
                except ServeError:
                    pass
                time.sleep(0.2)
            ok &= check("fleet: balancer ejects the SIGKILL'd backend",
                        ejected)
            final = None
            deadline = time.monotonic() + 240
            while time.monotonic() < deadline:
                try:
                    j = client.job(jk["id"])
                    if j["state"] in ("done", "failed", "cancelled"):
                        final = j
                        break
                except ServeError:
                    pass
                time.sleep(0.25)
            ok &= check("fleet: job finishes under its original id via "
                        "lease takeover",
                        final is not None and final["state"] == "done"
                        and final["id"] == jk["id"],
                        str(final and final["state"]))
            ref = open(os.path.join(fstd, "out_fleet.bam"), "rb").read()
            got_path = os.path.join(fwd, "out_fleet.bam")
            got = open(got_path, "rb").read() \
                if os.path.exists(got_path) else b""
            ok &= check("fleet: takeover output byte-identical",
                        ref == got, f"{len(ref)} vs {len(got)} bytes")
            # audit: one done event fleet-wide; dedupe resubmit answers
            # with the finished job instead of running a second copy
            done_events = 0
            for name in os.listdir(jdir):
                if ".journal" not in name:
                    continue
                for line in open(os.path.join(jdir, name)):
                    try:
                        rec = __import__("json").loads(line)
                    except ValueError:
                        continue
                    if rec.get("id") == jk["id"] \
                            and rec.get("state") == "done":
                        done_events += 1
            jk2 = client.submit(fleet_job, dedupe="chaos-fleet",
                                argv0=argv0)
            ok &= check("fleet: no job ran twice (journal + dedupe audit)",
                        done_events == 1 and jk2["id"] == jk["id"]
                        and jk2["state"] == "done",
                        f"done_events={done_events} resubmit={jk2['id']}")
        finally:
            for proc in list(daemons.values()) + ([bal] if bal else []):
                if proc is not None and proc.poll() is None:
                    proc.kill()
                    proc.wait(timeout=10)

        # 7) SIGKILL mid-write: no partial file under the final name
        victim = os.path.join(tmp, "victim.bam")
        code = (
            "import sys, time\n"
            f"sys.path.insert(0, {REPO!r})\n"
            "from fgumi_tpu.io.bam import BamHeader, BamWriter\n"
            "hdr = BamHeader(text='@HD\\tVN:1.6\\n@SQ\\tSN:c\\tLN:9\\n',\n"
            "                ref_names=['c'], ref_lengths=[9])\n"
            f"w = BamWriter({victim!r}, hdr, level=0)\n"
            "print('WRITING', flush=True)\n"
            "while True:\n"
            "    w.write_record_bytes(b'\\x00' * 4096)\n"
            "    w._w.flush(); w._w._f.flush()\n"
            "    time.sleep(0.002)\n")
        child = subprocess.Popen([sys.executable, "-c", code],
                                 stdout=subprocess.PIPE, text=True,
                                 env=BASE_ENV)
        child.stdout.readline()
        time.sleep(0.5)
        child.send_signal(signal.SIGKILL)
        child.wait(timeout=10)
        ok &= check("SIGKILL mid-write -> no partial final file",
                    not os.path.exists(victim))
    finally:
        if opts.keep:
            print("scratch kept at", tmp)
        else:
            shutil.rmtree(tmp, ignore_errors=True)
    print("chaos smoke:", "OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
