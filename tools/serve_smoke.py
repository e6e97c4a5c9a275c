#!/usr/bin/env python3
"""Serve smoke: daemon round-trip parity, warm-kernel reuse, capacity
rejection, and SIGTERM drain — the CI gate for the job-service subsystem.

Scenarios (exit 0 when every check holds, one PASS/FAIL line each):

1. Two jobs submitted concurrently to a 2-worker daemon produce outputs
   byte-identical to the same commands run standalone (the daemon resolves
   relative paths against its own working directory, so both runs use the
   same literal argv — provenance lines included — and land in different
   directories).
2. One submission over capacity (workers + queue-limit) is rejected with an
   explicit reason while the admitted jobs complete.
3. Every admitted job leaves a schema-valid per-job run report.
4. Warm-kernel serving: the first device-kernel job reports real XLA
   compilations (``device.backend_compiles``); resubmitting the identical
   command on the warm daemon reports none (and the persistent compile
   cache gained no new entries).
5. SIGTERM drain: a running job finishes and commits its output, new
   submissions are refused, and the daemon exits 0.
6. SIGKILL + journal-driven restart (crash recovery): a daemon with
   --journal is SIGKILL'd mid-job; the restarted daemon replaces the stale
   socket, replays the journal, requeues the job under its ORIGINAL id,
   and the output is byte-identical to the standalone run; an idempotent
   resubmit with the same dedupe key returns the finished job instead of
   running it twice.
7. Live introspection (ISSUE 9): the ``stats`` protocol op and a
   ``--metrics-port`` Prometheus ``/metrics`` scrape return CONSISTENT
   live snapshots (job counts, histogram counts), the scrape parses as
   text format 0.0.4, ``/healthz`` answers 200 on a healthy daemon, the
   ``fgumi-tpu stats`` CLI verb round-trips the same payload, and job
   outputs stay byte-identical to standalone (checks 1/4 above run on the
   same daemon).

Usage:  python tools/serve_smoke.py [--keep]
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BASE_ENV = {
    **os.environ,
    "PYTHONPATH": REPO,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "",
    # force the device kernel AND the device route so warm-vs-cold compile
    # evidence exists even on a CPU-only host (the adaptive offload policy
    # would price these tiny jobs host-side and dispatch nothing)
    "FGUMI_TPU_HOST_ENGINE": "0",
    "FGUMI_TPU_ROUTE": "device",
}


def run(args, cwd, env=None, timeout=300):
    return subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", *args], cwd=cwd,
        env={**BASE_ENV, **(env or {})}, capture_output=True, text=True,
        timeout=timeout)


def check(name, ok, detail=""):
    print(f"{'PASS' if ok else 'FAIL'}  {name}" + (f"  ({detail})"
                                                   if detail else ""))
    return ok


def wait_for_socket(path, timeout=60):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if os.path.exists(path):
            return True
        time.sleep(0.1)
    return False


def wait_for_ping(client, timeout=120):
    """Socket-file existence is not enough after a SIGKILL restart (the
    stale file lingers until the new daemon claims it); ping instead."""
    from fgumi_tpu.serve.client import ServeError

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            client.ping()
            return True
        except ServeError:
            time.sleep(0.2)
    return False


def cache_entries(d):
    if not os.path.isdir(d):
        return 0
    return sum(len(files) for _, _, files in os.walk(d))


def free_port():
    import socket as _socket

    s = _socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def parse_prometheus(body):
    """Minimal text-format 0.0.4 parser: {series_with_labels: float}.
    Raises ValueError on any malformed sample line or duplicate series
    (a real Prometheus server rejects the whole scrape on duplicates)."""
    out = {}
    for line in body.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        if not name or not name[0].isalpha():
            raise ValueError(f"malformed sample line: {line!r}")
        if name in out:
            raise ValueError(f"duplicate series: {name}")
        out[name] = float(value)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--keep", action="store_true",
                    help="keep the scratch directory")
    opts = ap.parse_args()
    from fgumi_tpu.observe.report import validate_report
    from fgumi_tpu.serve.client import ServeClient, ServeError

    tmp = tempfile.mkdtemp(prefix="fgumi_serve_")
    ok = True
    daemon = None
    try:
        wd_std = os.path.join(tmp, "standalone")
        wd_srv = os.path.join(tmp, "daemon")
        rpt = os.path.join(tmp, "reports")
        cache = os.path.join(tmp, "xla_cache")
        for d in (wd_std, wd_srv, rpt):
            os.makedirs(d)
        inp = os.path.join(tmp, "grouped.bam")
        p = run(["simulate", "grouped-reads", "-o", inp,
                 "--num-families", "600", "--family-size", "4",
                 "--seed", "7"], cwd=tmp)
        assert p.returncode == 0, p.stderr

        # job argvs use relative outputs: same literal command line in both
        # worlds (provenance bytes included); directories keep them apart
        job1 = ["simplex", "-i", inp, "-o", "out1.bam", "--min-reads", "1"]
        job2 = ["sort", "-i", inp, "-o", "out2.bam",
                "--order", "template-coordinate"]

        # --- standalone references -------------------------------------
        for argv in (job1, job2):
            p = run(argv, cwd=wd_std)
            assert p.returncode == 0, p.stderr

        # --- daemon up --------------------------------------------------
        sock = os.path.join(tmp, "serve.sock")
        metrics_port = free_port()
        daemon = subprocess.Popen(
            [sys.executable, "-m", "fgumi_tpu", "serve", "--socket", sock,
             "--workers", "2", "--queue-limit", "0", "--report-dir", rpt,
             "--compile-cache", cache, "--metrics-port",
             str(metrics_port)],
            cwd=wd_srv, env=BASE_ENV, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        ok &= check("daemon socket appears", wait_for_socket(sock))
        client = ServeClient(sock, timeout=30)

        # argv0 matching the standalone invocations (python -m fgumi_tpu)
        argv0 = os.path.join(REPO, "fgumi_tpu", "__main__.py")

        # --- two concurrent jobs + one rejected over capacity -----------
        j1 = client.submit(job1, argv0=argv0)
        j2 = client.submit(job2, argv0=argv0)
        over_reason = None
        try:
            client.submit(job1, argv0=argv0)
        except ServeError as e:
            over_reason = str(e)
        ok &= check("over-capacity submission rejected with reason",
                    over_reason is not None and "queue full" in over_reason,
                    over_reason or "admitted!")
        j1 = client.wait(j1["id"], timeout=240)
        j2 = client.wait(j2["id"], timeout=240)
        ok &= check("both concurrent jobs done",
                    j1["state"] == "done" and j2["state"] == "done",
                    f"{j1['state']}/{j2['state']} "
                    f"{j1.get('error')}/{j2.get('error')}")

        for name in ("out1.bam", "out2.bam"):
            a = open(os.path.join(wd_std, name), "rb").read()
            b = open(os.path.join(wd_srv, name), "rb").read()
            ok &= check(f"{name} byte-identical to standalone", a == b,
                        f"{len(a)} vs {len(b)} bytes")

        # --- per-job run reports ----------------------------------------
        reports = {}
        for j in (j1, j2):
            try:
                reports[j["id"]] = json.load(open(j["report_path"]))
            except (OSError, ValueError, TypeError):
                reports[j["id"]] = None
            errs = (validate_report(reports[j["id"]])
                    if reports[j["id"]] else ["unreadable"])
            ok &= check(f"job {j['id']} run report schema-valid", not errs,
                        "; ".join(errs[:3]))

        # --- warm-kernel evidence ---------------------------------------
        r1 = reports.get(j1["id"]) or {}
        cold_compiles = r1.get("metrics", {}).get("device.backend_compiles",
                                                  0)
        ok &= check("cold job reports XLA compilations",
                    cold_compiles > 0, f"compiles={cold_compiles}")
        entries_before = cache_entries(cache)
        j3 = client.submit(job1, argv0=argv0)  # identical shapes, warm now
        j3 = client.wait(j3["id"], timeout=240)
        ok &= check("warm resubmission done", j3["state"] == "done",
                    str(j3.get("error")))
        r3 = json.load(open(j3["report_path"]))
        warm_compiles = r3.get("metrics", {}).get("device.backend_compiles",
                                                  0)
        ok &= check("warm job skips recompilation",
                    warm_compiles == 0 and r3.get("device", {})
                    .get("dispatches", 0) > 0,
                    f"compiles={warm_compiles} "
                    f"dispatches={r3.get('device', {}).get('dispatches')}")
        ok &= check("compile cache gained no entries on the warm job",
                    cache_entries(cache) == entries_before,
                    f"{entries_before} -> {cache_entries(cache)}")
        a = open(os.path.join(wd_std, "out1.bam"), "rb").read()
        b = open(os.path.join(wd_srv, "out1.bam"), "rb").read()
        ok &= check("warm rerun output still byte-identical", a == b)

        # --- live introspection: stats op + /metrics + /healthz ---------
        import urllib.request

        stats = client.request({"v": 1, "op": "stats"})
        ok &= check("stats op answers ok", stats.get("ok") is True)
        stats = stats.get("stats", {})
        ok &= check("stats carries scheduler/jobs/latency sections",
                    stats.get("scheduler", {}).get("workers") == 2
                    and "latency" in stats and "jobs" in stats)
        done_jobs = stats.get("jobs", {}).get("done", 0)
        ok &= check("stats counts the finished jobs", done_jobs >= 3,
                    f"done={done_jobs}")
        lat = stats.get("latency", {})
        ok &= check("stats carries serve job latency histograms",
                    lat.get("serve.job.run_s", {}).get("count", 0) >= 3
                    and lat.get("serve.job.queue_wait_s", {})
                    .get("count", 0) >= 3,
                    f"latency keys={sorted(lat)[:8]}")
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{metrics_port}/metrics",
                timeout=10).read().decode()
            series = parse_prometheus(body)
            perr = None
        except (OSError, ValueError) as e:
            body, series, perr = "", {}, str(e)
        ok &= check("/metrics parses as Prometheus text format",
                    perr is None and bool(series),
                    perr or f"{len(series)} series")
        # the scrape and the stats op must agree on live state: job counts
        # and histogram sample counts come from the same snapshot source
        scraped_done = series.get('fgumi_tpu_serve_jobs{state="done"}')
        ok &= check("/metrics agrees with stats (job counts)",
                    scraped_done == stats.get("jobs", {}).get("done"),
                    f"scrape={scraped_done} "
                    f"stats={stats.get('jobs', {}).get('done')}")
        hist_ok = all(
            series.get(f"fgumi_tpu_{name.replace('.', '_')}_count")
            == summ["count"] for name, summ in lat.items())
        ok &= check("/metrics agrees with stats (histogram counts)",
                    bool(lat) and hist_ok)
        try:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{metrics_port}/healthz", timeout=10)
            hz = json.loads(resp.read().decode())
            hz_status = resp.status
        except OSError as e:
            hz, hz_status = {"error": str(e)}, 0
        ok &= check("/healthz answers 200 ok on a healthy daemon",
                    hz_status == 200 and hz.get("status") == "ok",
                    f"{hz_status} {hz}")
        # the CLI verb round-trips the same payload
        p = run(["stats", "--socket", sock, "--section", "scheduler"],
                cwd=tmp)
        try:
            verb = json.loads(p.stdout)
        except ValueError:
            verb = {}
        ok &= check("fgumi-tpu stats verb round-trips",
                    p.returncode == 0
                    and verb.get("scheduler", {}).get("workers") == 2,
                    p.stdout[:120])

        # --- SIGTERM drain ----------------------------------------------
        j4 = client.submit(job1, argv0=argv0)
        daemon.send_signal(signal.SIGTERM)
        # admission must close; allow for signal-delivery latency (a submit
        # racing the handler may still be admitted — it just runs to
        # completion during the drain, which is the documented contract)
        refused = None
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                client.submit(job2, argv0=argv0)
                time.sleep(0.1)
            except ServeError as e:
                refused = str(e)
                # require the DRAIN refusal (or the daemon already gone):
                # accepting any rejection would let a "queue full" bounce
                # satisfy this check without drain ever engaging
                if "draining" in refused or "cannot reach" in refused:
                    break
        ok &= check("post-SIGTERM submission refused by drain",
                    refused is not None
                    and ("draining" in refused or "cannot reach" in refused),
                    refused or "still admitting")
        daemon_rc = daemon.wait(timeout=240)
        ok &= check("daemon exits 0 after drain", daemon_rc == 0,
                    f"rc={daemon_rc}")
        daemon = None
        j4_report = os.path.join(rpt, f"{j4['id']}.report.json")
        r4 = json.load(open(j4_report))
        ok &= check("in-flight job finished during drain",
                    r4["exit_status"] == 0 and not validate_report(r4))
        ok &= check("drained job committed its output",
                    open(os.path.join(wd_srv, "out1.bam"), "rb").read()
                    == open(os.path.join(wd_std, "out1.bam"), "rb").read())
        ok &= check("socket removed on exit", not os.path.exists(sock))

        # --- SIGKILL + journal-driven restart (crash recovery) ----------
        kill_job = ["simplex", "-i", inp, "-o", "out_kill.bam",
                    "--min-reads", "1"]
        p = run(kill_job, cwd=wd_std)
        assert p.returncode == 0, p.stderr
        wd_kill = os.path.join(tmp, "daemon_kill")
        os.makedirs(wd_kill)
        jr = os.path.join(tmp, "journal.jsonl")
        sock2 = os.path.join(tmp, "serve2.sock")
        serve_argv = [sys.executable, "-m", "fgumi_tpu", "serve",
                      "--socket", sock2, "--workers", "1",
                      "--report-dir", rpt, "--compile-cache", cache,
                      "--journal", jr]
        daemon = subprocess.Popen(serve_argv, cwd=wd_kill, env=BASE_ENV,
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True)
        client2 = ServeClient(sock2, timeout=30)
        ok &= check("journaled daemon up", wait_for_ping(client2))
        jk = client2.submit(kill_job, argv0=argv0, dedupe="kill-restart")
        # kill mid-job: wait until the journal records it running
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if client2.job(jk["id"])["state"] == "running":
                break
            time.sleep(0.05)
        daemon.kill()  # SIGKILL: no drain, no cleanup, socket left behind
        daemon.wait(timeout=30)
        ok &= check("SIGKILL leaves the stale socket behind",
                    os.path.exists(sock2))
        ok &= check("killed job never published output",
                    not os.path.exists(os.path.join(wd_kill,
                                                    "out_kill.bam")))
        # restart: stale socket replaced, journal replayed, job requeued
        daemon = subprocess.Popen(serve_argv, cwd=wd_kill, env=BASE_ENV,
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True)
        ok &= check("restarted daemon claims the stale socket",
                    wait_for_ping(client2))
        try:
            jk2 = client2.wait(jk["id"], timeout=240)
        except ServeError as e:
            jk2 = {"state": f"lost ({e})"}
        ok &= check("requeued job finishes under its original id",
                    jk2.get("state") == "done", str(jk2.get("state")))
        a = open(os.path.join(wd_std, "out_kill.bam"), "rb").read()
        b_path = os.path.join(wd_kill, "out_kill.bam")
        b = open(b_path, "rb").read() if os.path.exists(b_path) else b""
        ok &= check("recovered output byte-identical to standalone",
                    a == b, f"{len(a)} vs {len(b)} bytes")
        leftovers = [n for n in os.listdir(wd_kill) if ".tmp." in n]
        ok &= check("no temp leftovers after recovery", not leftovers,
                    ",".join(leftovers))
        # idempotent resubmit: the dedupe key survived the restart
        jk3 = client2.submit(kill_job, argv0=argv0, dedupe="kill-restart")
        ok &= check("dedupe key resolves to the recovered job",
                    jk3["id"] == jk["id"] and jk3["state"] == "done",
                    f"{jk3['id']} ({jk3['state']})")
        client2.shutdown()
        rc2 = daemon.wait(timeout=240)
        ok &= check("journaled daemon exits 0", rc2 == 0, f"rc={rc2}")
        daemon = None

        # --- cross-job dispatch coalescing (ISSUE 15) -------------------
        # 4 concurrent small submit jobs on a 4-worker daemon with the
        # merge window armed: per-job outputs byte-identical to
        # standalone (coalesce off), merged_batches > 0 evidence in the
        # stats op, and aggregate wall reported for the throughput story.
        wd_std_c = os.path.join(tmp, "standalone_coalesce")
        wd_srv_c = os.path.join(tmp, "daemon_coalesce")
        for d in (wd_std_c, wd_srv_c):
            os.makedirs(d)
        co_jobs = [["simplex", "-i", inp, "-o", f"outc{i}.bam",
                    "--min-reads", "1", "--batch-groups", "40"]
                   for i in range(4)]
        t0 = time.monotonic()
        for argv in co_jobs:
            p = run(argv, cwd=wd_std_c, env={"FGUMI_TPU_COALESCE": "0"})
            assert p.returncode == 0, p.stderr
        serial_wall = time.monotonic() - t0
        sock3 = os.path.join(tmp, "serve3.sock")
        daemon = subprocess.Popen(
            [sys.executable, "-m", "fgumi_tpu", "serve", "--socket",
             sock3, "--workers", "4", "--queue-limit", "0",
             "--compile-cache", cache, "--coalesce-window-ms", "50"],
            cwd=wd_srv_c, env={**BASE_ENV, "FGUMI_TPU_COALESCE": "1"},
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        client3 = ServeClient(sock3, timeout=30)
        ok &= check("coalescing daemon up", wait_for_ping(client3))
        t0 = time.monotonic()
        handles = [client3.submit(argv, argv0=argv0) for argv in co_jobs]
        done = [client3.wait(h["id"], timeout=240) for h in handles]
        merged_wall = time.monotonic() - t0
        ok &= check("4 concurrent coalesced jobs done",
                    all(j["state"] == "done" for j in done),
                    ",".join(j["state"] for j in done))
        ident = True
        for i in range(4):
            a = open(os.path.join(wd_std_c, f"outc{i}.bam"), "rb").read()
            bp = os.path.join(wd_srv_c, f"outc{i}.bam")
            b = open(bp, "rb").read() if os.path.exists(bp) else b""
            ident &= a == b
        ok &= check("coalesced outputs byte-identical to standalone "
                    "(coalesce off)", ident)
        st = client3.request({"v": 1, "op": "stats"}).get("stats", {})
        coal = st.get("coalesce") or {}
        ok &= check("stats op records merged cross-job batches",
                    coal.get("merged_batches", 0) > 0
                    and coal.get("partners", 0) >= 2,
                    f"merged={coal.get('merged_batches')} "
                    f"partners={coal.get('partners')}")
        # informational (not gated: shared-CI hosts are too noisy for a
        # wall-clock assertion): 4 concurrent merged jobs vs 4 serial
        # standalone runs
        print(f"INFO  coalesce aggregate: 4 jobs {merged_wall:.1f}s "
              f"concurrent+merged vs {serial_wall:.1f}s serial "
              f"standalone ({serial_wall / max(merged_wall, 1e-9):.2f}x)")
        client3.shutdown()
        rc3 = daemon.wait(timeout=240)
        ok &= check("coalescing daemon exits 0", rc3 == 0, f"rc={rc3}")
        daemon = None

        # --- forced host route: identity with the window armed ----------
        # coalescing only engages on device dispatches; a ROUTE=host
        # daemon with the window armed must stay byte-identical too
        wd_std_h = os.path.join(tmp, "standalone_host")
        wd_srv_h = os.path.join(tmp, "daemon_host")
        for d in (wd_std_h, wd_srv_h):
            os.makedirs(d)
        host_env = {"FGUMI_TPU_ROUTE": "host", "FGUMI_TPU_HOST_ENGINE": ""}
        for argv in co_jobs[:2]:
            p = run(argv, cwd=wd_std_h, env=host_env)
            assert p.returncode == 0, p.stderr
        sock4 = os.path.join(tmp, "serve4.sock")
        daemon = subprocess.Popen(
            [sys.executable, "-m", "fgumi_tpu", "serve", "--socket",
             sock4, "--workers", "2", "--queue-limit", "0",
             "--coalesce-window-ms", "50"],
            cwd=wd_srv_h,
            env={**BASE_ENV, **host_env, "FGUMI_TPU_COALESCE": "1"},
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        client4 = ServeClient(sock4, timeout=30)
        ok &= check("host-route daemon up", wait_for_ping(client4))
        handles = [client4.submit(argv, argv0=argv0)
                   for argv in co_jobs[:2]]
        done = [client4.wait(h["id"], timeout=240) for h in handles]
        ident = all(
            open(os.path.join(wd_std_h, f"outc{i}.bam"), "rb").read()
            == open(os.path.join(wd_srv_h, f"outc{i}.bam"), "rb").read()
            for i in range(2)
            if os.path.exists(os.path.join(wd_srv_h, f"outc{i}.bam")))
        ok &= check("host-route outputs byte-identical with the window "
                    "armed",
                    all(j["state"] == "done" for j in done) and ident
                    and all(os.path.exists(os.path.join(
                        wd_srv_h, f"outc{i}.bam")) for i in range(2)))
        client4.shutdown()
        rc4 = daemon.wait(timeout=240)
        ok &= check("host-route daemon exits 0", rc4 == 0, f"rc={rc4}")
        daemon = None
    finally:
        if daemon is not None and daemon.poll() is None:
            daemon.kill()
            daemon.wait(timeout=10)
        if opts.keep:
            print("scratch kept at", tmp)
        else:
            shutil.rmtree(tmp, ignore_errors=True)
    print("serve smoke:", "OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
