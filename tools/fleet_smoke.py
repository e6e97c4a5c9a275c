#!/usr/bin/env python3
"""Fleet smoke: 2 TCP daemons + a health-routed balancer — the CI gate
for the fleet resilience tier (ISSUE 12).

Scenarios (exit 0 when every check holds, one PASS/FAIL line each):

1. Fleet up: both daemons answer through the balancer's front end
   (handshake token enforced end to end), 2/2 backends healthy.
2. Spillover on over-capacity: with workers=1 / queue-limit=0 per
   daemon, two concurrent submits land on DIFFERENT backends (job-id
   fleet prefixes prove it), both outputs byte-identical to standalone
   runs; a third concurrent submit is refused with an explicit reason.
3. Kill-one-mid-job takeover: SIGKILL the daemon RUNNING a job. The
   balancer ejects it (breaker open in the balancer's stats), the
   survivor claims the dead daemon's journal lease and requeues the job
   under its ORIGINAL id, the job completes byte-identically to a
   standalone run, and the journal audit shows exactly ONE done event
   fleet-wide (zero double-executions); an idempotent resubmit with the
   same dedupe key answers with the finished job.
4. Warm survivor: the post-takeover job on the surviving daemon reports
   zero XLA recompilations (device.backend_compiles == 0) — scale-out
   keeps the warm-serving economics.
5. Eject -> re-admit: restarting the killed daemon (fresh, its journal
   was consumed) brings its backend closed again through the balancer's
   half-open probes.
6. Fleet tracing + aggregated metrics (ISSUE 17): a traced submit
   through the balancer leaves per-process trace files whose
   `fgumi-tpu trace-merge` stitches into ONE timeline with spans from
   >=3 processes under one trace-id; the balancer's --metrics-port
   /metrics endpoint re-exports both backends' labeled series and
   agrees with the `stats` op's fleet_metrics section; the per-backend
   end-to-end submit-to-done latency summary is surfaced fleet-side.
7. Whale scatter/gather (ISSUE 18): a fresh 2-backend fleet behind
   `balance --scatter 2`. Submitted pipeline/simplex/duplex jobs come
   back as whales (`w-...` ids) whose gathered outputs are
   byte-identical to standalone runs; SIGKILLing the backend running a
   shard mid-flight completes the whale through the journal-lease
   takeover with a fleet-wide audit of exactly one done event per
   shard (zero double-execution, no coordinator requeue); the same
   whale on both backends beats the one-backend fleet by >=1.6x
   aggregate reads/s (enforced when >=3 CPU cores are visible; loudly
   skipped on smaller hosts where shards must timeshare one core); the
   stats op carries schema v3 with the scatter section and /metrics
   exports the fleet.scatter.* gauges from the same snapshot.

Usage:  python tools/fleet_smoke.py [--keep]
"""

import argparse
import json
import os
import re
import shutil
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TOKEN = "fleet-smoke-secret"

BASE_ENV = {
    **os.environ,
    "PYTHONPATH": REPO,
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "",
    # force the device kernel AND the device route so warm-vs-cold compile
    # evidence exists even on a CPU-only host
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


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wait_for_ping(client, timeout=120):
    from fgumi_tpu.serve.client import ServeError

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            return client.ping()
        except ServeError:
            time.sleep(0.2)
    return None


def wait_job_tolerant(client, job_id, timeout=240):
    """Poll a job through the balancer, tolerating the takeover window
    (the dead backend's job is briefly unknown fleet-wide until the
    survivor's lease scan adopts it)."""
    from fgumi_tpu.serve.client import ServeError
    from fgumi_tpu.serve.jobs import TERMINAL

    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            job = client.job(job_id)
            last = job
            if job["state"] in TERMINAL:
                return job
        except ServeError as e:
            last = {"state": f"unresolved ({e})"}
        time.sleep(0.25)
    return last


def backend_states(client):
    stats = client.stats()
    return {b["address"]: b["state"] for b in stats["backends"]}


def wait_backend_state(client, address, state, timeout=60):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            if backend_states(client).get(address) == state:
                return True
        except Exception:  # noqa: BLE001 - balancer may be briefly busy
            pass
        time.sleep(0.2)
    return False


def journal_events(jdir):
    """Every record from every journal artifact in the fleet dir."""
    out = []
    for name in sorted(os.listdir(jdir)):
        if ".journal" not in name:
            continue
        with open(os.path.join(jdir, name)) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                rec["_file"] = name
                out.append(rec)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--keep", action="store_true",
                    help="keep the scratch directory")
    opts = ap.parse_args()
    from fgumi_tpu.serve.client import ServeClient, ServeError

    tmp = tempfile.mkdtemp(prefix="fgumi_fleet_")
    ok = True
    procs = {}
    balancer = None
    try:
        wd_std = os.path.join(tmp, "standalone")
        wd_fleet = os.path.join(tmp, "fleet_wd")   # BOTH daemons' cwd:
        # relative job outputs land here no matter which daemon runs the
        # job — the property takeover relies on
        rpt = os.path.join(tmp, "reports")
        jdir = os.path.join(tmp, "journals")
        cache = os.path.join(tmp, "xla_cache")
        for d in (wd_std, wd_fleet, rpt, jdir):
            os.makedirs(d)
        tok = os.path.join(tmp, "token")
        with open(tok, "w") as f:
            f.write(TOKEN + "\n")
        inp = os.path.join(tmp, "grouped.bam")
        p = run(["simulate", "grouped-reads", "-o", inp,
                 "--num-families", "600", "--family-size", "4",
                 "--seed", "7"], cwd=tmp)
        assert p.returncode == 0, p.stderr
        # the kill job gets a much larger input: by the time it runs the
        # daemons are WARM (earlier scenarios compiled its shapes), and a
        # sub-second job would finish before the SIGKILL lands — voiding
        # the mid-job takeover scenario (the observed-running check below
        # enforces this stays true)
        inp_big = os.path.join(tmp, "grouped_big.bam")
        p = run(["simulate", "grouped-reads", "-o", inp_big,
                 "--num-families", "8000", "--family-size", "4",
                 "--seed", "8"], cwd=tmp)
        assert p.returncode == 0, p.stderr

        job1 = ["simplex", "-i", inp, "-o", "out1.bam", "--min-reads", "1"]
        job2 = ["simplex", "-i", inp, "-o", "out2.bam", "--min-reads", "1"]
        kill_job = ["simplex", "-i", inp_big, "-o", "out_kill.bam",
                    "--min-reads", "1"]
        warm_job = ["simplex", "-i", inp, "-o", "out_warm.bam",
                    "--min-reads", "1"]

        # --- standalone references --------------------------------------
        for argv in (job1, job2, kill_job, warm_job):
            p = run(argv, cwd=wd_std)
            assert p.returncode == 0, p.stderr

        # --- fleet up: 2 daemons + balancer, all TCP + token -------------
        ports = {"a": free_port(), "b": free_port()}
        front = free_port()
        metrics_port = free_port()
        bal_trace = os.path.join(tmp, "balancer_trace.json")

        def start_daemon(fid):
            argv = [sys.executable, "-m", "fgumi_tpu", "serve",
                    "--tcp", f"127.0.0.1:{ports[fid]}",
                    "--workers", "1", "--queue-limit", "0",
                    "--journal-dir", jdir, "--fleet-id", fid,
                    "--lease-scan-period", "0.5",
                    "--report-dir", rpt, "--compile-cache", cache,
                    "--token-file", tok]
            return subprocess.Popen(argv, cwd=wd_fleet, env=BASE_ENV,
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.STDOUT, text=True)

        procs["a"] = start_daemon("a")
        procs["b"] = start_daemon("b")
        balancer = subprocess.Popen(
            [sys.executable, "-m", "fgumi_tpu", "--trace", bal_trace,
             "balance",
             "--listen", f"tcp:127.0.0.1:{front}",
             "--backend", f"tcp:127.0.0.1:{ports['a']}",
             "--backend", f"tcp:127.0.0.1:{ports['b']}",
             "--token-file", tok, "--poll-period", "0.3",
             "--eject-failures", "2", "--cooldown", "1.0",
             "--probes", "2", "--metrics-port", str(metrics_port)],
            cwd=tmp, env=BASE_ENV, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        client = ServeClient(f"tcp:127.0.0.1:{front}", timeout=30,
                             token=TOKEN)
        ping = wait_for_ping(client)
        ok &= check("balancer front end answers through the token "
                    "handshake", ping is not None
                    and ping.get("tool") == "fgumi-tpu-balance",
                    str(ping))
        addr_a = f"tcp:127.0.0.1:{ports['a']}"
        addr_b = f"tcp:127.0.0.1:{ports['b']}"
        ok &= check("both backends healthy",
                    wait_backend_state(client, addr_a, "closed")
                    and wait_backend_state(client, addr_b, "closed"))

        # --- spillover on over-capacity ---------------------------------
        argv0 = os.path.join(REPO, "fgumi_tpu", "__main__.py")
        j1 = client.submit(job1, argv0=argv0)
        j2 = client.submit(job2, argv0=argv0)
        prefixes = {j1["id"].split("-j-")[0], j2["id"].split("-j-")[0]}
        ok &= check("concurrent submits spill across BOTH backends",
                    prefixes == {"a", "b"},
                    f"{j1['id']} / {j2['id']}")
        over_reason = None
        try:
            client.submit(job1, argv0=argv0)
        except ServeError as e:
            over_reason = str(e)
        ok &= check("over-capacity submit refused with an explicit reason",
                    over_reason is not None
                    and "no backend admitted" in over_reason,
                    over_reason or "admitted!")
        j1 = wait_job_tolerant(client, j1["id"])
        j2 = wait_job_tolerant(client, j2["id"])
        ok &= check("both spillover jobs done",
                    j1 and j2 and j1.get("state") == "done"
                    and j2.get("state") == "done",
                    f"{j1 and j1.get('state')}/{j2 and j2.get('state')}")
        for name in ("out1.bam", "out2.bam"):
            a = open(os.path.join(wd_std, name), "rb").read()
            b = open(os.path.join(wd_fleet, name), "rb").read()
            ok &= check(f"{name} byte-identical to standalone", a == b,
                        f"{len(a)} vs {len(b)} bytes")

        # --- kill-one-mid-job takeover ----------------------------------
        jk = client.submit(kill_job, argv0=argv0, dedupe="kill-fleet")
        victim_id = jk["id"].split("-j-")[0]
        survivor_id = "b" if victim_id == "a" else "a"
        victim_addr = addr_a if victim_id == "a" else addr_b
        observed_running = False
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            state = wait_job_tolerant(client, jk["id"], timeout=1)
            s = state.get("state") if state else None
            if s == "running":
                observed_running = True
                break
            if s in ("done", "failed", "cancelled"):
                break  # finished before the kill: the scenario is void
        # the takeover scenario is only exercised if the SIGKILL lands
        # MID-JOB — a pre-kill completion must fail the gate loudly, not
        # let the later checks pass vacuously
        ok &= check("kill job observed running before SIGKILL",
                    observed_running,
                    str(state and state.get("state")))
        procs[victim_id].kill()   # SIGKILL: no drain, lease dies with it
        procs[victim_id].wait(timeout=30)
        ok &= check("balancer ejects the killed backend",
                    wait_backend_state(client, victim_addr, "open"),
                    json.dumps(backend_states(client)))
        jk_final = wait_job_tolerant(client, jk["id"], timeout=240)
        ok &= check("killed daemon's job finishes under its ORIGINAL id "
                    "via lease takeover",
                    jk_final and jk_final.get("state") == "done"
                    and jk_final.get("id", jk["id"]) == jk["id"],
                    str(jk_final and jk_final.get("state")))
        a = open(os.path.join(wd_std, "out_kill.bam"), "rb").read()
        b_path = os.path.join(wd_fleet, "out_kill.bam")
        b = open(b_path, "rb").read() if os.path.exists(b_path) else b""
        ok &= check("takeover output byte-identical to standalone",
                    a == b, f"{len(a)} vs {len(b)} bytes")
        leftovers = [n for n in os.listdir(wd_fleet) if ".tmp." in n]
        ok &= check("no temp leftovers after takeover", not leftovers,
                    ",".join(leftovers))
        # zero double-execution: exactly one `done` event fleet-wide for
        # the job, and the consumed journal was renamed .claimed
        events = journal_events(jdir)
        done_events = [e for e in events if e.get("id") == jk["id"]
                       and e.get("state") == "done"]
        ok &= check("journal audit: exactly one done event fleet-wide",
                    len(done_events) == 1,
                    f"{len(done_events)} done events")
        claimed = [n for n in os.listdir(jdir)
                   if n == f"{victim_id}.journal.claimed"]
        ok &= check("dead daemon's journal consumed exactly once "
                    "(renamed .claimed)", len(claimed) == 1,
                    ",".join(sorted(os.listdir(jdir))))
        # dedupe audit: the idempotent resubmit answers with the SAME
        # (finished) job instead of executing a second copy
        jk_again = client.submit(kill_job, argv0=argv0,
                                 dedupe="kill-fleet")
        ok &= check("dedupe resubmit answers with the recovered job",
                    jk_again["id"] == jk["id"]
                    and jk_again["state"] == "done",
                    f"{jk_again['id']} ({jk_again['state']})")

        # --- warm survivor: zero recompiles -----------------------------
        jw = client.submit(warm_job, argv0=argv0)
        ok &= check("warm job routed to the survivor",
                    jw["id"].startswith(survivor_id + "-"), jw["id"])
        jw = wait_job_tolerant(client, jw["id"])
        ok &= check("warm job done", jw and jw.get("state") == "done",
                    str(jw and (jw.get("error") or jw.get("state"))))
        try:
            r = json.load(open(os.path.join(rpt,
                                            f"{jw['id']}.report.json")))
        except (OSError, ValueError):
            r = {}
        # absent metric = zero observed compiles (the compile watcher
        # only counts real backend-compile events; serve_smoke reads the
        # same way) — dispatches > 0 proves the device path actually ran
        compiles = r.get("metrics", {}).get("device.backend_compiles", 0)
        dispatches = r.get("device", {}).get("dispatches", 0)
        ok &= check("warm survivor reports zero XLA recompilations",
                    bool(r) and compiles == 0 and dispatches > 0,
                    f"compiles={compiles} dispatches={dispatches}")
        a = open(os.path.join(wd_std, "out_warm.bam"), "rb").read()
        b = open(os.path.join(wd_fleet, "out_warm.bam"), "rb").read()
        ok &= check("warm output byte-identical to standalone", a == b)

        # --- eject -> re-admit after restart ----------------------------
        procs[victim_id] = start_daemon(victim_id)
        ok &= check("restarted backend re-admitted via half-open probes",
                    wait_backend_state(client, victim_addr, "closed",
                                       timeout=90),
                    json.dumps(backend_states(client)))

        # --- fleet tracing + aggregated metrics (ISSUE 17) ---------------
        client_trace = os.path.join(tmp, "client_trace.json")
        before_traces = set(os.listdir(rpt))
        p = run(["--trace", client_trace, "submit",
                 "--socket", f"tcp:127.0.0.1:{front}",
                 "--token-file", tok, "--job-trace", "--",
                 "simplex", "-i", inp, "-o", "out_traced.bam",
                 "--min-reads", "1"], cwd=wd_fleet)
        ok &= check("traced submit through the balancer succeeds",
                    p.returncode == 0, (p.stdout + p.stderr)[-300:])
        backend_traces = [n for n in os.listdir(rpt)
                          if n.endswith(".trace.json")
                          and n not in before_traces]
        ok &= check("backend wrote a per-job trace",
                    len(backend_traces) == 1, ",".join(backend_traces))
        client_ctx = {}
        try:
            client_ctx = json.load(open(client_trace))["otherData"].get(
                "trace_context") or {}
        except (OSError, ValueError, KeyError):
            pass
        tid = client_ctx.get("trace_id")
        ok &= check("client trace carries the fleet trace id", bool(tid),
                    json.dumps(client_ctx))
        # the traced job's run report carries the v5 end-to-end
        # attribution: trace context + a decomposition whose components
        # never sum past the total (capped shares, see observe/report.py)
        job_report = {}
        if backend_traces:
            rpt_name = backend_traces[0].replace(".trace.json",
                                                 ".report.json")
            try:
                job_report = json.load(open(os.path.join(rpt, rpt_name)))
            except (OSError, ValueError):
                pass
        dec = job_report.get("latency_decomposition") or {}
        comp = sum(v for k, v in dec.items() if k != "total_s")
        ok &= check("run report carries the fleet latency decomposition",
                    job_report.get("trace_context", {}).get("trace_id")
                    == tid and "client_to_balancer_s" in dec
                    and "queue_s" in dec and "host_complete_s" in dec
                    and comp <= dec.get("total_s", 0) + 0.005,
                    json.dumps(dec)[:220])
        # the balancer cache needs one poll after the job finished before
        # the e2e summaries appear fleet-side
        fm = {}
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            fm = client.stats().get("fleet_metrics") or {}
            if any(e.get("submit_to_done_s")
                   for e in fm.get("per_backend", [])):
                break
            time.sleep(0.3)
        ok &= check("fleet p99 submit-to-bytes-published surfaced per "
                    "backend (stats op fleet_metrics)",
                    any(e.get("submit_to_done_s", {}).get("p99")
                        is not None for e in fm.get("per_backend", [])),
                    json.dumps(fm.get("per_backend"))[:200])
        metrics_body = urllib.request.urlopen(
            f"http://127.0.0.1:{metrics_port}/metrics",
            timeout=10).read().decode()
        for addr in (addr_a, addr_b):
            ok &= check(f"/metrics exports labeled series for {addr}",
                        f'fgumi_tpu_fleet_backend_up{{backend="{addr}"}} 1'
                        in metrics_body)
        e2e_series = set(re.findall(
            r'fgumi_tpu_serve_job_e2e_submit_to_done_s\{backend="([^"]+)"',
            metrics_body))
        ok &= check("backend e2e latency summaries re-exported on /metrics",
                    len(e2e_series) >= 1, ",".join(sorted(e2e_series)))
        ok &= check("/metrics consistent with the stats op "
                    "(same-snapshot rule)",
                    fm.get("backends_total") == 2
                    and f"fgumi_tpu_fleet_backends_total 2" in metrics_body
                    and f"fgumi_tpu_fleet_backends_healthy "
                        f"{fm.get('backends_healthy')}" in metrics_body,
                    json.dumps({k: fm.get(k) for k in
                                ("backends_total", "backends_healthy")}))

        # --- clean shutdown ---------------------------------------------
        client.shutdown()  # drains the balancer
        rc = balancer.wait(timeout=60)
        ok &= check("balancer exits 0 on shutdown", rc == 0, f"rc={rc}")
        balancer = None

        # --- merged fleet timeline (balancer trace flushed on exit) ------
        merged = os.path.join(tmp, "merged_trace.json")
        p = run(["trace-merge", client_trace, bal_trace,
                 os.path.join(rpt, backend_traces[0]), "-o", merged,
                 "--trace-id", tid or "0" * 32], cwd=tmp)
        ok &= check("trace-merge stitches the fleet timeline",
                    p.returncode == 0, (p.stdout + p.stderr)[-300:])
        try:
            m = json.load(open(merged))
        except (OSError, ValueError):
            m = {"traceEvents": [], "otherData": {}}
        span_pids = {e["pid"] for e in m["traceEvents"]
                     if e.get("ph") == "X"}
        ok &= check("merged trace has spans from >=3 processes",
                    len(span_pids) >= 3, str(sorted(span_pids)))
        names = {e["name"] for e in m["traceEvents"] if e.get("ph") == "X"}
        ok &= check("client, balancer and backend spans all present",
                    "serve.submit" in names and "serve.forward" in names
                    and "pipeline.process" in names,
                    ",".join(sorted(names))[:200])
        ok &= check("merged under ONE trace id",
                    m["otherData"].get("trace_context", {}).get("trace_id")
                    == tid and len(m["otherData"].get("merged_from", []))
                    == 3, json.dumps(m.get("otherData", {}))[:200])
        for fid, proc in procs.items():
            direct = ServeClient(f"tcp:127.0.0.1:{ports[fid]}",
                                 timeout=30, token=TOKEN)
            try:
                direct.shutdown()
            except ServeError:
                pass
            rc = proc.wait(timeout=120)
            ok &= check(f"daemon {fid} exits 0", rc == 0, f"rc={rc}")
        procs.clear()

        # ================================================================
        # Whale scatter/gather (ISSUE 18): a FRESH fleet behind
        # `balance --scatter 2`.
        # ================================================================
        wd_sstd = os.path.join(tmp, "scatter_standalone")
        wd_sfleet = os.path.join(tmp, "scatter_fleet")  # daemons AND the
        # scatter balancers share this cwd: the gather stage resolves the
        # shards' relative output paths against the balancer's own cwd
        # (the documented shared-filesystem assumption)
        jdir2 = os.path.join(tmp, "journals_scatter")
        for d in (wd_sstd, wd_sfleet, jdir2):
            os.makedirs(d)
        fq1 = os.path.join(tmp, "sc_r1.fq.gz")
        fq2 = os.path.join(tmp, "sc_r2.fq.gz")
        p = run(["simulate", "fastq-reads", "-1", fq1, "-2", fq2,
                 "--num-families", "120", "--family-size", "3",
                 "--read-length", "60", "--seed", "23"], cwd=tmp)
        assert p.returncode == 0, p.stderr
        dup = os.path.join(tmp, "sc_duplex.bam")
        p = run(["simulate", "duplex-reads", "-o", dup,
                 "--num-molecules", "180", "--reads-per-strand", "3",
                 "--read-length", "80", "--seed", "11"], cwd=tmp)
        assert p.returncode == 0, p.stderr
        # the kill/perf whale is big on purpose: a shard must run for
        # seconds so the SIGKILL lands mid-shard, and the >=1.6x scaling
        # gate must dwarf the ~1.5s fixed gather+detection overhead
        whale_fams = 30000
        whale_reads = whale_fams * 6
        inp_whale = os.path.join(tmp, "sc_whale.bam")
        p = run(["simulate", "grouped-reads", "-o", inp_whale,
                 "--num-families", str(whale_fams), "--family-size", "6",
                 "--seed", "9"], cwd=tmp, timeout=600)
        assert p.returncode == 0, p.stderr

        sc_jobs = {
            "simplex": ["simplex", "-i", inp, "-o", "out_sc_simplex.bam",
                        "--min-reads", "1"],
            "pipeline": ["pipeline", "-i", fq1, fq2, "-r", "8M+T", "+T",
                         "-o", "out_sc_pipeline.bam",
                         "--filter-min-reads", "1", "--threads", "2",
                         "--sample", "s", "--library", "l"],
            "duplex": ["duplex", "-i", dup, "-o", "out_sc_duplex.bam",
                       "--min-reads", "1"],
        }
        sc_kill = ["simplex", "-i", inp_whale, "-o", "out_sc_kill.bam",
                   "--min-reads", "1"]
        for argv in list(sc_jobs.values()) + [sc_kill]:
            p = run(argv, cwd=wd_sstd, timeout=600)
            assert p.returncode == 0, p.stderr

        # --- scatter fleet up: 2 daemons + `balance --scatter 2` --------
        ports2 = {"c": free_port(), "d": free_port()}
        front2 = free_port()
        mport2 = free_port()

        def start_scatter_daemon(fid):
            argv = [sys.executable, "-m", "fgumi_tpu", "serve",
                    "--tcp", f"127.0.0.1:{ports2[fid]}",
                    "--workers", "1", "--queue-limit", "4",
                    "--journal-dir", jdir2, "--fleet-id", fid,
                    "--lease-scan-period", "0.5",
                    "--compile-cache", cache, "--token-file", tok]
            return subprocess.Popen(argv, cwd=wd_sfleet, env=BASE_ENV,
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.STDOUT, text=True)

        def start_scatter_balancer(port, fids, metrics_port=None):
            argv = [sys.executable, "-m", "fgumi_tpu", "balance",
                    "--listen", f"tcp:127.0.0.1:{port}"]
            for fid in fids:
                argv += ["--backend", f"tcp:127.0.0.1:{ports2[fid]}"]
            argv += ["--token-file", tok, "--poll-period", "0.3",
                     "--scatter", "2",
                     "--scatter-wal", os.path.join(tmp, f"sc_{port}.wal")]
            if metrics_port:
                argv += ["--metrics-port", str(metrics_port)]
            return subprocess.Popen(argv, cwd=wd_sfleet, env=BASE_ENV,
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.STDOUT, text=True)

        procs["c"] = start_scatter_daemon("c")
        procs["d"] = start_scatter_daemon("d")
        procs["bal_sc"] = start_scatter_balancer(front2, ("c", "d"),
                                                 metrics_port=mport2)
        sclient = ServeClient(f"tcp:127.0.0.1:{front2}", timeout=30,
                              token=TOKEN)
        ping = wait_for_ping(sclient)
        ok &= check("scatter balancer front end answers",
                    ping is not None
                    and ping.get("tool") == "fgumi-tpu-balance", str(ping))
        addr_c = f"tcp:127.0.0.1:{ports2['c']}"
        addr_d = f"tcp:127.0.0.1:{ports2['d']}"
        # both backends must be HEALTHY before any whale goes in: an
        # unknown-depth backend sorts last in routing, so a premature
        # fan-out would stack both shards on the already-polled daemon
        ok &= check("scatter fleet: both backends healthy",
                    wait_backend_state(sclient, addr_c, "closed")
                    and wait_backend_state(sclient, addr_d, "closed"))

        # --- byte-identity: pipeline / simplex / duplex whales ----------
        for name, argv in sc_jobs.items():
            j = sclient.submit(argv, argv0=argv0)
            is_whale = j["id"].startswith("w-")
            rec = sclient.scatter(j["id"]) if is_whale else {}
            nshards = len(rec.get("scatter", {}).get("shards", []))
            j = wait_job_tolerant(sclient, j["id"], timeout=300)
            a = open(os.path.join(wd_sstd, f"out_sc_{name}.bam"),
                     "rb").read()
            bp = os.path.join(wd_sfleet, f"out_sc_{name}.bam")
            b = open(bp, "rb").read() if os.path.exists(bp) else b""
            ok &= check(f"{name} whale scattered 2-way, gathered "
                        "byte-identical to standalone",
                        is_whale and nshards == 2 and j
                        and j.get("state") == "done" and a == b,
                        f"whale={is_whale} shards={nshards} "
                        f"state={j and j.get('state')} "
                        f"{len(a)} vs {len(b)} bytes")
        leftovers = [n for n in os.listdir(wd_sfleet) if ".scatter" in n]
        ok &= check("no shard leftovers after gathers", not leftovers,
                    ",".join(leftovers))

        # --- kill one backend MID-SHARD ---------------------------------
        jk = sclient.submit(sc_kill, argv0=argv0, dedupe="whale-kill")
        ok &= check("kill job accepted as a whale",
                    jk["id"].startswith("w-"), jk["id"])
        victim = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            rec = sclient.scatter(jk["id"]) or {}
            running = [s for s in rec.get("scatter", {}).get("shards", [])
                       if s["state"] == "running" and s["job_id"]]
            if running:
                victim = running[0]["job_id"].split("-j-")[0]
                break
            if rec.get("state") in ("done", "failed", "cancelled"):
                break  # finished before the kill: the scenario is void
            time.sleep(0.1)
        ok &= check("a shard observed running before SIGKILL",
                    victim in ("c", "d"),
                    f"victim={victim} whale={rec.get('state')}")
        if victim in ("c", "d"):
            procs[victim].kill()  # no drain: the shard dies mid-flight
            procs[victim].wait(timeout=30)
        jk_final = wait_job_tolerant(sclient, jk["id"], timeout=300)
        ok &= check("whale completes through the shard-level takeover",
                    jk_final and jk_final.get("state") == "done",
                    str(jk_final and (jk_final.get("error")
                                      or jk_final.get("state"))))
        a = open(os.path.join(wd_sstd, "out_sc_kill.bam"), "rb").read()
        bp = os.path.join(wd_sfleet, "out_sc_kill.bam")
        b = open(bp, "rb").read() if os.path.exists(bp) else b""
        ok &= check("takeover whale output byte-identical to standalone",
                    a == b, f"{len(a)} vs {len(b)} bytes")
        # zero double-execution: the dead daemon's shard finished under
        # its ORIGINAL job id via the journal-lease takeover (attempt
        # stays 0 — the coordinator's requeue grace never expired) and
        # the fleet journals carry exactly one done event per shard
        rec = sclient.scatter(jk["id"]) or {}
        shard_recs = rec.get("scatter", {}).get("shards", [])
        shard_ids = [s["job_id"] for s in shard_recs]
        ok &= check("takeover kept the ORIGINAL shard ids "
                    "(no coordinator requeue)",
                    len(shard_ids) == 2 and all(shard_ids)
                    and all(s["attempt"] == 0 for s in shard_recs),
                    json.dumps(shard_recs))
        events = journal_events(jdir2)
        per_shard = {sid: sum(1 for e in events if e.get("id") == sid
                              and e.get("state") == "done")
                     for sid in shard_ids}
        ok &= check("journal audit: exactly one done event per shard "
                    "(zero double-execution)",
                    bool(per_shard)
                    and all(v == 1 for v in per_shard.values()),
                    json.dumps(per_shard))

        # --- restart the victim, then the scaling gate ------------------
        if victim in ("c", "d"):
            procs[victim] = start_scatter_daemon(victim)
        victim_addr = addr_c if victim == "c" else addr_d
        ok &= check("killed scatter backend re-admitted",
                    wait_backend_state(sclient, victim_addr, "closed",
                                       timeout=90),
                    json.dumps(backend_states(sclient)))
        # warm round: the restarted daemon re-loads the whale shard
        # shapes from the shared compile cache; keep that out of the
        # timed comparison
        jw = sclient.submit(["simplex", "-i", inp_whale, "-o",
                             "out_sc_warm.bam", "--min-reads", "1"],
                            argv0=argv0)
        jw = wait_job_tolerant(sclient, jw["id"], timeout=300)
        ok &= check("warm whale done", jw and jw.get("state") == "done",
                    str(jw and (jw.get("error") or jw.get("state"))))
        t0 = time.monotonic()
        j2 = sclient.submit(["simplex", "-i", inp_whale, "-o",
                             "out_sc_t2.bam", "--min-reads", "1"],
                            argv0=argv0)
        j2 = wait_job_tolerant(sclient, j2["id"], timeout=300)
        t_two = time.monotonic() - t0
        ok &= check("timed 2-backend whale done",
                    j2 and j2.get("state") == "done", f"{t_two:.2f}s")
        # the SAME whale behind a 1-backend scatter balancer: the
        # fairness cap (healthy // whales = 1) strictly serializes the
        # shards, so this measures one backend doing all the work
        front1 = free_port()
        procs["bal_sc1"] = start_scatter_balancer(front1, ("c",))
        sclient1 = ServeClient(f"tcp:127.0.0.1:{front1}", timeout=30,
                               token=TOKEN)
        wait_for_ping(sclient1)
        ok &= check("1-backend scatter balancer up, backend healthy",
                    wait_backend_state(sclient1, addr_c, "closed"))
        t0 = time.monotonic()
        j1 = sclient1.submit(["simplex", "-i", inp_whale, "-o",
                              "out_sc_t1.bam", "--min-reads", "1"],
                             argv0=argv0)
        j1 = wait_job_tolerant(sclient1, j1["id"], timeout=600)
        t_one = time.monotonic() - t0
        ok &= check("timed 1-backend whale done",
                    j1 and j1.get("state") == "done", f"{t_one:.2f}s")
        rps_two = whale_reads / t_two
        rps_one = whale_reads / t_one
        shard_fids = {s["job_id"].split("-j-")[0]
                      for s in (sclient.scatter(j2["id"]) or {})
                      .get("scatter", {}).get("shards", [])
                      if s["job_id"]}
        ok &= check("timed whale spread one shard to EACH backend",
                    shard_fids == {"c", "d"}, str(sorted(shard_fids)))
        cores = len(os.sched_getaffinity(0))
        scaling = (f"{rps_two:,.0f} vs {rps_one:,.0f} reads/s "
                   f"({t_one:.2f}s / {t_two:.2f}s = "
                   f"{t_one / t_two:.2f}x, {cores} core(s))")
        if cores >= 3:
            ok &= check("2-backend fleet beats 1 backend by >=1.6x "
                        "aggregate reads/s on the scatter workload",
                        rps_two >= 1.6 * rps_one, scaling)
        else:
            # the >=1.6x gate needs parallel hardware: pinned to fewer
            # than 3 cores (2 daemons + balancer) the shard processes
            # timeshare ONE cpu and wall-clock cannot improve. Loud
            # skip, never a silent pass — the spread check above still
            # proves both backends did the work, and the bound below
            # that timesharing overhead stays small
            print(f"SKIP  2-backend >=1.6x scaling gate: only {cores} "
                  f"CPU core(s) visible, shards timeshare one core  "
                  f"({scaling})")
            ok &= check("scatter overhead bounded on a timesharing "
                        "host", t_two <= 1.5 * t_one + 1.0, scaling)

        # --- scatter observability --------------------------------------
        snap = sclient.stats()
        sc = snap.get("scatter") or {}
        ok &= check("balancer stats v3 carries the scatter section",
                    snap.get("schema_version") == 3
                    and sc.get("enabled") is True and sc.get("shards") == 2
                    and sc.get("whales", {}).get("done", 0) >= 5,
                    json.dumps({k: sc.get(k) for k in
                                ("enabled", "shards", "whales")}))
        metrics_body = urllib.request.urlopen(
            f"http://127.0.0.1:{mport2}/metrics", timeout=10
        ).read().decode()
        ok &= check("/metrics exports the fleet.scatter.* gauges",
                    "fgumi_tpu_fleet_scatter_enabled 1" in metrics_body
                    and "fgumi_tpu_fleet_scatter_shards_per_whale 2"
                    in metrics_body
                    and 'fgumi_tpu_fleet_scatter_whales_state'
                        '{state="done"}' in metrics_body,
                    "\n".join(ln for ln in metrics_body.splitlines()
                              if "scatter" in ln)[:300])

        # --- scatter fleet clean shutdown -------------------------------
        sclient1.shutdown()
        rc = procs.pop("bal_sc1").wait(timeout=60)
        ok &= check("1-backend scatter balancer exits 0", rc == 0,
                    f"rc={rc}")
        sclient.shutdown()
        rc = procs.pop("bal_sc").wait(timeout=60)
        ok &= check("scatter balancer exits 0 on shutdown", rc == 0,
                    f"rc={rc}")
        for fid in ("c", "d"):
            direct = ServeClient(f"tcp:127.0.0.1:{ports2[fid]}",
                                 timeout=30, token=TOKEN)
            try:
                direct.shutdown()
            except ServeError:
                pass
            rc = procs[fid].wait(timeout=120)
            ok &= check(f"daemon {fid} exits 0", rc == 0, f"rc={rc}")
        procs.clear()
    finally:
        for proc in list(procs.values()) + ([balancer] if balancer
                                            else []):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
        if opts.keep:
            print("scratch kept at", tmp)
        else:
            shutil.rmtree(tmp, ignore_errors=True)
    print("fleet smoke:", "OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
