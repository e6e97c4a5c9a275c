#!/usr/bin/env python3
"""Fused-chain smoke: gate the in-memory FastqToConsensus handoff.

Checks (exit 0 when every scenario holds, one PASS/FAIL line each):

1. **Byte parity**: the fused ``pipeline`` run is byte-identical to the
   staged (``--no-fuse``) run — both executed in ONE python process so the
   @PG CL provenance lines agree, exactly like the serve daemon's parity
   contract. Also at ``--threads 2``.
2. **No intermediate BAMs**: a filesystem watcher polls the work tree for
   the whole fused run; the only BAM that may ever exist is the final
   output (the staged run, by contrast, must be seen writing
   intermediates — proving the watcher actually watches).
3. **Run report**: the fused run's report carries ``pipeline.chain.*``
   channel metrics, per-stage ``wall_s`` entries, and a smaller
   ``io.bytes_written`` than the staged run (the four intermediate
   encode/decode passes are gone).
4. **Chaos**: an armed ``chain.handoff`` raise exits 3, commits no final
   output, and leaves no temp files behind.

Sibling of tools/telemetry_smoke.py / serve_smoke.py / chaos_smoke.py /
perf_smoke.py in the verify flow (.claude/skills/verify).

Usage:  python tools/chain_smoke.py [--keep]
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


def check(name, ok, detail=""):
    print(f"{'PASS' if ok else 'FAIL'}  {name}" + (f"  ({detail})"
                                                   if detail else ""))
    return ok


# Runs fused + staged in one interpreter (identical sys.argv -> identical
# @PG CL lines) while a watcher thread records every *.bam path that ever
# appears under the work dir.
_PARITY = r"""
import glob, json, os, sys, threading, time
sys.path.insert(0, %(repo)r)
from fgumi_tpu.cli import main as cli_main

work = %(work)r
os.chdir(work)

seen = set()
stop = threading.Event()
def watch():
    # the staged driver may put its temp dir on tmpfs (/dev/shm) instead of
    # next to the output; watch both, so "no intermediate BAMs" means
    # nowhere, not just not-here
    pats = [os.path.join(work, "**", "*.bam"),
            "/dev/shm/fgumi_pipeline_*/*.bam"]
    while not stop.is_set():
        for pat in pats:
            for p in glob.glob(pat, recursive=True):
                if p.startswith(work):
                    seen.add(os.path.relpath(p, work))
                else:
                    seen.add(os.path.basename(p))
        time.sleep(0.005)

def run(argv):
    return cli_main(argv)

base = ["pipeline", "-i", "r1.fq.gz", "r2.fq.gz", "-r", "8M+T", "+T",
        "--sample", "s", "--library", "l", "--filter-min-reads", "2"]

t = threading.Thread(target=watch, daemon=True)
t.start()
rc_f = run(["--run-report", "fused.json"] + base + ["-o", "fused.bam"])
stop.set(); t.join()
fused_seen = sorted(seen)

rc_t2 = run(base + ["-o", "fused_t2.bam", "--threads", "2"])

seen.clear(); stop.clear()
t = threading.Thread(target=watch, daemon=True)
t.start()
rc_s = run(["--run-report", "staged.json"] + base
           + ["-o", "staged.bam", "--no-fuse"])
stop.set(); t.join()
staged_seen = sorted(p for p in seen if p not in
                     ("fused.bam", "fused_t2.bam", "staged.bam"))

out = {
    "rc_fused": rc_f, "rc_threads2": rc_t2, "rc_staged": rc_s,
    "fused_seen": fused_seen, "staged_seen": staged_seen,
    "fused_eq_staged": open("fused.bam", "rb").read()
                       == open("staged.bam", "rb").read(),
    "t2_eq_staged": open("fused_t2.bam", "rb").read()
                    == open("staged.bam", "rb").read(),
}
print("RESULT " + json.dumps(out))
"""

_CHAOS = r"""
import glob, json, os, sys
sys.path.insert(0, %(repo)r)
os.environ["FGUMI_TPU_FAULT"] = "chain.handoff:raise:1.0:1"
from fgumi_tpu.cli import main as cli_main

work = %(work)r
os.chdir(work)
rc = cli_main(["pipeline", "-i", "r1.fq.gz", "r2.fq.gz", "-r", "8M+T",
               "+T", "--sample", "s", "--library", "l",
               "--filter-min-reads", "2", "-o", "chaos.bam"])
left = sorted(os.path.basename(p) for p in
              glob.glob(os.path.join(work, "*"))
              if os.path.basename(p) not in
              ("r1.fq.gz", "r2.fq.gz", "truth.tsv", "fused.bam",
               "fused_t2.bam", "staged.bam", "fused.json", "staged.json"))
print("RESULT " + json.dumps({
    "rc": rc, "output_exists": os.path.exists("chaos.bam"),
    "leftovers": left}))
"""


def run_py(script, timeout=600):
    p = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                       env=BASE_ENV, capture_output=True, text=True,
                       timeout=timeout)
    result = None
    for line in p.stdout.splitlines():
        if line.startswith("RESULT "):
            result = json.loads(line[len("RESULT "):])
    return p, result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--keep", action="store_true",
                    help="keep the work dir for inspection")
    opts = ap.parse_args()

    from fgumi_tpu.native import batch as nb

    if not nb.available():
        print("SKIP  chain smoke: native batch engine unavailable "
              "(the fused path is gated on it)")
        return 0

    work = tempfile.mkdtemp(prefix="fgumi_chain_smoke_")
    ok = True
    try:
        sim = subprocess.run(
            [sys.executable, "-m", "fgumi_tpu", "simulate", "fastq-reads",
             "-1", "r1.fq.gz", "-2", "r2.fq.gz", "--truth", "truth.tsv",
             "--num-families", "120", "--family-size", "4",
             "--read-length", "80", "--error-rate", "0.005",
             "--seed", "31"],
            cwd=work, env=BASE_ENV, capture_output=True, text=True,
            timeout=300)
        if sim.returncode != 0:
            print(sim.stderr)
            return 1

        p, res = run_py(_PARITY % {"repo": REPO, "work": work})
        if not check("parity run completed", res is not None
                     and res["rc_fused"] == res["rc_staged"]
                     == res["rc_threads2"] == 0,
                     (p.stderr or "")[-300:] if res is None else ""):
            return 1
        ok &= check("fused output byte-identical to staged",
                    res["fused_eq_staged"])
        ok &= check("fused --threads 2 byte-identical to staged",
                    res["t2_eq_staged"])
        ok &= check("fused run created no intermediate BAMs",
                    set(res["fused_seen"]) <= {"fused.bam"},
                    f"saw {res['fused_seen']}")
        ok &= check("watcher sanity: staged run's intermediates were seen",
                    len(res["staged_seen"]) >= 1,
                    f"saw {res['staged_seen']}")

        rep_f = json.load(open(os.path.join(work, "fused.json")))
        rep_s = json.load(open(os.path.join(work, "staged.json")))
        m = rep_f["metrics"]
        chain_keys = [k for k in m if k.startswith("pipeline.chain.")]
        ok &= check("report carries pipeline.chain.* metrics",
                    m.get("pipeline.chain.fused") == 1
                    and any(k.endswith(".batches") for k in chain_keys),
                    f"{len(chain_keys)} keys")
        stages = rep_f.get("stages", {})
        ok &= check("report folds per-stage wall times",
                    all("wall_s" in stages.get(s, {}) for s in
                        ("extract", "sort", "group", "simplex", "filter")))
        wf = m.get("io.bytes_written", 0)
        ws = rep_s["metrics"].get("io.bytes_written", 1 << 60)
        ok &= check("io.bytes_written drops without intermediates",
                    0 < wf < ws, f"fused {wf} vs staged {ws}")

        p, res = run_py(_CHAOS % {"repo": REPO, "work": work})
        if not check("chaos run completed", res is not None,
                     (p.stderr or "")[-300:] if res is None else ""):
            return 1
        ok &= check("chain.handoff fault exits 3", res["rc"] == 3)
        ok &= check("chaos run committed no output and left no temps",
                    not res["output_exists"] and res["leftovers"] == [],
                    f"leftovers {res['leftovers']}")
    finally:
        if opts.keep:
            print(f"work dir kept: {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)
    print("chain smoke:", "OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
