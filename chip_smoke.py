#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that fgumi-tpu still starts on the chip.

Drives the main path once through the entry points a user would call, on
the attached accelerator, with no routing, kernel or engine variable set
(legs A, B, D), and checks every leg's records against the same command on
the native f64 host engine (``JAX_PLATFORMS=cpu``), by ``compare bams``:

  A  eval config 1 at its stated scale: 91,000 lognormal families (about
     1M reads) through ``simplex`` — twice; the second run must find every
     executable in the persistent compile cache
  B  eval config 5's chain: paired FASTQ -> ``pipeline`` -> filtered BAM
  C  the other kernels, ``FGUMI_TPU_ROUTE=device`` so each is certain to
     run: ``duplex``, ``codec``, ``simplex --device-filter``, and one
     library call each for the >63-quality packed kernels and the device
     Hamming kernel, which no command above reaches
  D  ``serve`` on a Unix socket, two ``submit``s, ``jobs --shutdown``

A chip belongs to one process at a time, so this parent never imports jax:
each leg is one child, run in turn (leg D: the daemon owns the chip, the
clients stay off it). Every leg writes ``--run-report``; the report's
``device`` section, not this script, says what ran where.

Exit 0 and a last stdout line ``{"ok": true, "device": {...}}`` only when
every leg passed. No accelerator, a failed native build, a leg that fell
back, retried, timed out or disagreed with the host engine: non-zero, no
result line. Sizes are the driver's eval configs (BASELINE.json); each cut
taken to fit the time limit is printed.
"""

import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
#: fixed and git-ignored; under chiprun_out/ so reports survive a chip call
WORK = os.path.join(REPO, "chiprun_out", "chip_smoke")
CLI = [sys.executable, "-m", "fgumi_tpu"]
#: children that must never touch the chip (data, references, clients)
OFF_CHIP = {"JAX_PLATFORMS": "cpu"}
ROUTE_VARS = ("FGUMI_TPU_ROUTE", "FGUMI_TPU_KERNEL", "FGUMI_TPU_HOST_ENGINE",
              "FGUMI_TPU_HYBRID", "FGUMI_TPU_MESH", "FGUMI_TPU_MAX_INFLIGHT")

_PROBE = ("import json, jax; d = jax.devices(); print(json.dumps("
          "{'platform': d[0].platform, 'kind': d[0].device_kind, "
          "'count': len(d)}))")

# Library leg: kernels no CLI leg reaches. Each runs once on the device
# and must equal its host twin exactly.
_LIBRARY_LEG = r"""
import json, sys
import numpy as np
from fgumi_tpu.ops.kernel import (DEVICE_STATS, ConsensusKernel,
                                  device_identity, pad_segments)
from fgumi_tpu.ops.tables import quality_tables
from fgumi_tpu.umi import assigners

rng = np.random.default_rng(7)
kern = ConsensusKernel(quality_tables(45, 40))
kern.set_force_device()
n_fam, fam, L = 2000, 5, 100
template = rng.integers(0, 4, size=(n_fam, 1, L), dtype=np.uint8)
codes = np.repeat(template, fam, axis=1).reshape(n_fam * fam, L)
err = rng.random(codes.shape) < 0.01
codes[err] = (codes[err] + rng.integers(1, 4, size=int(err.sum()))) % 4
quals = rng.integers(2, 94, size=codes.shape, dtype=np.uint8)  # 92 values
counts = np.full(n_fam, fam, dtype=np.int64)
starts = (np.arange(n_fam + 1) * fam).astype(np.int64)
host = kern._host().call_segments(codes, quals, starts)
for full in (True, False):  # the segp2f and segp2 kernels
    cd, qd, seg, _st, f_pad = pad_segments(codes, quals, counts)
    ticket = kern.device_call_segments_wire(cd, qd, seg, f_pad, n_fam,
                                            full=full)
    got = kern.resolve_segments_wire(ticket, codes, quals, starts)
    for name, g, h in zip(("winner", "qual", "depth", "errors"), got, host):
        assert np.array_equal(np.asarray(g), np.asarray(h)), \
            f"packed2 full={full}: {name} differs from the host engine"
umis = np.frombuffer(b"ACGT", dtype=np.uint8)[
    rng.integers(0, 4, size=(2048, 8))]
umis[1::2] = umis[::2]  # neighbours to find: every odd row one edit away
umis[1::2, 3] = ord("A")
bits = assigners._device_within_bits(umis, umis, 1)
assert bits.shape == (2048, 256), "the device sends back a bit a pair"
got = assigners._unpack_within(bits, 2048, 2048)
want = (umis[:, None, :] != umis[None, :, :]).sum(-1) <= 1
assert want.sum() > 2 * 2048 and np.array_equal(got, want), \
    "device Hamming differs"
snap = DEVICE_STATS.snapshot()
# the two wire kernels; the Hamming executable is a dispatch, not a wire kernel
assert snap.get("kernel_xla", 0) == 2 and not snap.get("host_fallbacks")
assert snap["dispatches"] == 3 and snap["bytes_fetched"] >= bits.nbytes
print(json.dumps({"device": device_identity(), "stats": snap}))
"""


#: Leg A's device-dispatch floor. The input is 13 batches; with a chip and
#: the host engine both attached the cost model overflows to the host when
#: enough dispatches are unresolved, so the device gets most of them, not
#: all (8 of 13 in both runs of PR 21's first chip run).
A_FLOOR = 6


class SmokeFailure(Exception):
    pass


FAILURES = []


def fail(leg, what):
    FAILURES.append(f"{leg}: {what}")
    print(f"FAIL  {leg}: {what}", flush=True)


def child_env(extra=None):
    """The parent's environment minus every routing, kernel or engine
    variable, plus the checkout on PYTHONPATH."""
    env = {k: v for k, v in os.environ.items() if k not in ROUTE_VARS}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def run(argv, env_extra=None, timeout=300, leg="", tolerate=False):
    """One child, to completion (the slowest leg takes about 30 s; a hung
    one must not eat the smoke's time limit)."""
    env = child_env(env_extra)
    t0 = time.monotonic()
    proc = subprocess.run(argv, cwd=WORK, env=env, capture_output=True,
                          text=True, timeout=timeout)
    dt = time.monotonic() - t0
    if proc.returncode != 0 and not tolerate:
        fail(leg, f"{' '.join(argv[2:6])} ... exited {proc.returncode} "
                  f"after {dt:.0f}s:\n{proc.stderr[-4000:]}")
    return proc, dt


def cli(args, **kw):
    return run(CLI + args, **kw)


def w(name):
    return os.path.join(WORK, name)


def load_report(path, leg):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        fail(leg, f"no run report at {path}: {e}")
        return None


def check_report(leg, report, probe, *, floor, want_route, kernel=None):
    """The device section must show the leg ran on the chip, unaided."""
    if report is None:
        return {}
    dev = report.get("device") or {}
    ident = {"platform": dev.get("platform"), "kind": dev.get("device_kind"),
             "count": dev.get("device_count")}
    if ident != probe:
        fail(leg, f"report says it ran on {ident}, the probe saw {probe}")
    if dev.get("dispatches", 0) < floor:
        fail(leg, f"{dev.get('dispatches', 0)} device dispatches, "
                  f"floor is {floor}")
    if want_route and not dev.get("route_device", 0) > 0:
        fail(leg, f"route_device={dev.get('route_device')}: the router "
                  f"sent nothing to the device ({dev.get('routing')})")
    for key in ("host_fallbacks", "deadline_fallbacks", "dispatch_retries",
                "batch_splits"):
        if dev.get(key, 0):
            fail(leg, f"{key}={dev[key]} (must be 0)")
    if "breaker" in dev:
        fail(leg, f"breaker left closed: {dev['breaker']}")
    pallas, xla = dev.get("kernel_pallas", 0), dev.get("kernel_xla", 0)
    if kernel and probe["count"] == 1:
        other = xla if kernel == "pallas" else pallas
        mine = pallas if kernel == "pallas" else xla
        if other or not mine:
            fail(leg, f"selection says {kernel}; report has "
                      f"kernel_pallas={pallas} kernel_xla={xla}")
    metrics = report.get("metrics") or {}
    summary = {k: dev.get(k, 0) for k in (
        "dispatches", "route_device", "route_host", "kernel_pallas",
        "kernel_xla")}
    why = {k.rsplit(".", 1)[1]: v for k, v in metrics.items()
           if k.startswith("device.route.why.")}
    if why:
        summary["route_why"] = why
    summary.update(
        shape_keys=dev.get("shapes", []),
        shape_compiles=metrics.get("device.shape_bucket.recompiles", 0),
        backend_compiles=metrics.get("device.backend_compiles", 0),
        backend_compile_s=round(metrics.get("device.backend_compile_s", 0),
                                2),
        compile_cache_hits=metrics.get("device.compile_cache_hits", 0),
        shapes=metrics.get("device.shape_bucket.shapes", 0),
        wall_s=report.get("wall_s"))
    routing = dev.get("routing") or {}
    if routing:
        summary["routing"] = {k: routing.get(k) for k in (
            "link_mbps", "overhead_s", "dispatch_wall_s",
            "host_mcells_per_s", "last_decision")}
    if "mesh" in dev:
        summary["mesh"] = dev["mesh"]
    shown = {k: v for k, v in summary.items() if k != "shape_keys"}
    print(f"  {leg}: {json.dumps(shown)}", flush=True)
    return summary


def same_records(leg, a, b):
    proc, _ = cli(["compare", "bams", "-a", a, "-b", b], env_extra=OFF_CHIP,
                  leg=leg, tolerate=True)
    if proc.returncode != 0:
        fail(leg, f"records differ from the host-engine run "
                  f"({os.path.basename(a)} vs {os.path.basename(b)}): "
                  f"{(proc.stdout + proc.stderr)[-1500:]}")


def device_and_reference(leg, args_for, probe, env_extra=None, **checks):
    """Run ``args_for(tag)`` on the chip, then on the host engine, and
    compare the records. Returns the device run's summary."""
    cli(["--run-report", w(f"{leg}.report.json")] + args_for(f"{leg}.dev"),
        env_extra=env_extra, leg=leg)
    summary = check_report(leg, load_report(w(f"{leg}.report.json"), leg),
                           probe, **checks)
    cli(args_for(f"{leg}.ref"), env_extra=OFF_CHIP, leg=f"{leg} reference")
    same_records(leg, w(f"{leg}.dev.bam"), w(f"{leg}.ref.bam"))
    return summary


def build_native():
    """Step 1: the checkout ships no .so (``*.so`` is git-ignored)."""
    sys.path.insert(0, REPO)
    try:
        from fgumi_tpu import native
    except ImportError as e:
        raise SmokeFailure(f"not a checkout of fgumi-tpu: {e}")
    if not native.build():  # warns with g++'s stderr
        raise SmokeFailure("building libfgumi_native.so failed")
    if native.get_lib() is None:
        raise SmokeFailure("libfgumi_native.so was built but does not load")


def platform_check():
    """Step 2: what does jax see? One short-lived child; the parent stays
    off jax. No accelerator ends the smoke here."""
    proc, _ = run([sys.executable, "-c", _PROBE], leg="platform check",
                  tolerate=True, timeout=300)
    if proc.returncode != 0:
        raise SmokeFailure(f"jax did not start:\n{proc.stderr[-3000:]}")
    probe = json.loads(proc.stdout.strip().splitlines()[-1])
    if probe["platform"] == "cpu":
        raise SmokeFailure(f"jax found no accelerator: {probe}")
    print(f"platform check: {probe}", flush=True)
    return probe


def simulate_inputs():
    """All data from ``simulate --seed``; CPU-only children, in parallel."""
    jobs = {
        "A": ["simulate", "grouped-reads", "-o", w("a.bam"),
              "--num-families", "91000", "--family-size", "5",
              "--family-size-distribution", "lognormal",
              "--read-length", "100", "--seed", "7"],
        "B": ["simulate", "fastq-reads", "-1", w("r1.fq.gz"),
              "-2", w("r2.fq.gz"), "--num-families", "40000",
              "--family-size", "5", "--read-length", "100", "--seed", "7"],
        "D": ["simulate", "grouped-reads", "-o", w("d.bam"),
              "--num-families", "40000", "--family-size", "5",
              "--family-size-distribution", "lognormal",
              "--read-length", "100", "--seed", "7"],
        "C-duplex": ["simulate", "duplex-reads", "-o", w("duplex.bam"),
                     "--num-molecules", "17000", "--reads-per-strand", "3",
                     "--read-length", "100", "--seed", "7"],
        "C-codec": ["simulate", "codec-reads", "-o", w("codec.bam"),
                    "--num-molecules", "50000", "--pairs-per-molecule", "2",
                    "--read-length", "100", "--seed", "7"],
    }
    env = child_env(OFF_CHIP)
    procs = {k: subprocess.Popen(CLI + a, cwd=WORK, env=env,
                                 stdout=subprocess.DEVNULL,
                                 stderr=subprocess.PIPE, text=True)
             for k, a in jobs.items()}
    try:
        for k, p in procs.items():
            _, err = p.communicate(timeout=300)
            if p.returncode != 0:
                raise SmokeFailure(
                    f"simulate for leg {k} failed:\n{err[-2000:]}")
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()
    print("inputs: A 91,000 families (config 1 at its stated scale); "
          "B 40,000 families of read pairs (README's 400k-read analog of "
          "config 5); C duplex 17,000 molecules, codec 50,000 molecules, "
          "device-filter and D on 40,000 families (cuts: B-D below their "
          "configs' scale to fit the time limit)", flush=True)


def leg_a(probe):
    def args(tag):
        return ["simplex", "-i", w("a.bam"), "-o", w(f"{tag}.bam"),
                "--min-reads", "1", "--threads", "4"]

    cold = device_and_reference("A", args, probe, floor=A_FLOOR,
                                want_route=True, kernel="pallas")
    if probe["count"] > 1:
        mesh = cold.get("mesh") or {}
        if mesh.get("devices") != probe["count"]:
            fail("A", f"{probe['count']} chips visible but device.mesh is "
                      f"{mesh or 'absent'}: not every device got a shard")
    # again, in a new process: the cache must have been written and found.
    # The router may split the batches differently this time, so the rule
    # is per shape: nothing the first run dispatched is compiled again.
    cli(["--run-report", w("A2.report.json")] + args("A2.dev"), leg="A2")
    warm = check_report("A2", load_report(w("A2.report.json"), "A2"), probe,
                        floor=A_FLOOR, want_route=True, kernel="pallas")
    if warm:
        unseen = sorted(set(warm["shape_keys"]) - set(cold["shape_keys"]))
        if not warm["compile_cache_hits"]:
            fail("A2", "no executable was loaded from the persistent "
                       "cache: it was not written or not found")
        if warm["backend_compiles"] and not unseen:
            fail("A2", f"second run compiled {warm['backend_compiles']} "
                       "executable(s) for shapes the first run had "
                       "already dispatched")
        if unseen:
            print(f"  A2: the router sent {len(unseen)} shape(s) to the "
                  f"device that the first run kept on the host: {unseen}",
                  flush=True)
    same_records("A2", w("A2.dev.bam"), w("A.ref.bam"))


def leg_b(probe):
    def args(tag):
        return ["pipeline", "-i", w("r1.fq.gz"), w("r2.fq.gz"),
                "-r", "8M+T", "+T", "-o", w(f"{tag}.bam"), "--sample", "s",
                "--library", "l", "--threads", "4", "--filter-min-reads", "3"]

    device_and_reference("B", args, probe, floor=2, want_route=True,
                         kernel="pallas")


def leg_c(probe):
    forced = {"FGUMI_TPU_ROUTE": "device"}
    legs = {
        # the resident strand-combine route stays on the XLA kernels
        "C-duplex": (["duplex", "-i", w("duplex.bam"), "--min-reads", "1",
                      "--threads", "4"], "xla"),
        "C-codec": (["codec", "-i", w("codec.bam"), "--min-reads", "1",
                     "--threads", "4"], "pallas"),
        "C-filter": (["simplex", "-i", w("d.bam"), "--min-reads", "1",
                      "--threads", "4", "--device-filter",
                      "--filter-min-reads", "3",
                      "--filter-min-mean-base-quality", "30",
                      "--filter-min-base-quality", "20"], "pallas"),
    }
    for leg, (argv, kernel) in legs.items():
        device_and_reference(
            leg, lambda tag, a=argv: a + ["-o", w(f"{tag}.bam")], probe,
            env_extra=forced, floor=2, want_route=False, kernel=kernel)
    proc, _ = run([sys.executable, "-c", _LIBRARY_LEG], leg="C-library")
    if proc.returncode == 0:
        got = json.loads(proc.stdout.strip().splitlines()[-1])
        ident = got["device"]
        if (ident["platform"], ident["device_kind"]) != (probe["platform"],
                                                        probe["kind"]):
            fail("C-library", f"ran on {ident}")
        print(f"  C-library: packed2 (full + split) and device Hamming "
              f"match their host twins; {got['stats'].get('dispatches')} "
              "dispatches", flush=True)


def leg_d(probe):
    sock, reports = w("serve.sock"), w("serve_reports")
    log = open(w("serve.log"), "w")
    daemon = subprocess.Popen(
        CLI + ["serve", "--socket", sock, "--workers", "1",
               "--report-dir", reports], cwd=WORK, env=child_env(),
        stdout=log,
        stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 180
        while not os.path.exists(sock):
            if daemon.poll() is not None or time.monotonic() > deadline:
                fail("D", "serve did not come up:\n"
                          + open(w("serve.log")).read()[-3000:])
                return
            time.sleep(0.2)
        for n in (1, 2):
            cli(["submit", "--socket", sock, "--timeout", "240", "simplex",
                 "-i", w("d.bam"), "-o", w(f"D{n}.dev.bam"), "--min-reads",
                 "1", "--threads", "4"], env_extra=OFF_CHIP, leg=f"D{n}")
        cli(["jobs", "--socket", sock, "--shutdown"], env_extra=OFF_CHIP,
            leg="D shutdown")
        try:
            rc = daemon.wait(timeout=120)
            if rc != 0:
                fail("D", f"serve exited {rc}")
        except subprocess.TimeoutExpired:
            fail("D", "serve did not exit after jobs --shutdown")
    finally:
        if daemon.poll() is None:
            daemon.kill()
            daemon.wait()
        log.close()
    found = []
    if os.path.isdir(reports):
        for name in os.listdir(reports):
            if name.endswith(".report.json"):
                with open(os.path.join(reports, name)) as f:
                    found.append(json.load(f))
    found.sort(key=lambda r: r["started_unix"])
    if len(found) != 2:
        fail("D", f"expected 2 job reports, found {len(found)}")
        return
    for n, report in enumerate(found, 1):
        summary = check_report(f"D{n}", report, probe, floor=2,
                               want_route=True, kernel="pallas")
        # an identical second job compiles nothing the daemon has seen:
        # every compile it reports belongs to a first-sight shape (one the
        # router kept on the host during job 1)
        if n == 2 and (summary.get("backend_compiles", 0)
                       != summary.get("shape_compiles", 0)):
            fail("D2", f"the warm daemon compiled "
                       f"{summary['backend_compiles']} executable(s), "
                       f"{summary['shape_compiles']} of them for shapes "
                       "new to it")
    cli(["simplex", "-i", w("d.bam"), "-o", w("D.ref.bam"), "--min-reads",
         "1", "--threads", "4"], env_extra=OFF_CHIP, leg="D reference")
    for n in (1, 2):
        same_records(f"D{n}", w(f"D{n}.dev.bam"), w("D.ref.bam"))


def main():
    t0 = time.monotonic()
    try:
        build_native()
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    try:
        probe = platform_check()
        simulate_inputs()
        for leg in (leg_a, leg_b, leg_c, leg_d):
            try:
                leg(probe)
            except subprocess.TimeoutExpired as e:
                fail(leg.__name__, f"timed out: {e}")
            print(f"{leg.__name__} done at +{time.monotonic() - t0:.0f}s",
                  flush=True)
    except (SmokeFailure, subprocess.TimeoutExpired) as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    finally:
        for name in os.listdir(WORK):  # keep the reports, drop the data
            if name.endswith((".bam", ".gz")):
                os.unlink(os.path.join(WORK, name))
    if FAILURES:
        print(f"chip_smoke: {len(FAILURES)} failure(s):\n  "
              + "\n  ".join(f.splitlines()[0] for f in FAILURES),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": probe}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
