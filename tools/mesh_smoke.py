#!/usr/bin/env python3
"""Mesh smoke: gate the production dp x sp compile path on 8 virtual CPU
devices (fast, runs anywhere — the same virtual-mesh trick as the dryrun).

Checks (exit 0 when every scenario holds, one PASS/FAIL line each):

1. **Three-engine byte-identity**: `simplex`, `duplex`, and `codec` run
   single-device and with ``FGUMI_TPU_MESH=dp4xsp2`` and ``dp8`` — the
   sharded outputs' records are byte-identical to the single-device run
   (headers differ only by the recorded command line). The duplex and
   codec runs also force their device combine stages so the sharded
   resident / elementwise combine kernels are exercised, not just priced.
2. **Mesh observability**: the sharded run's report carries
   ``device.mesh`` = {dp, sp, devices}, the ``device.mesh.*`` gauges, and
   per-dispatch ``shards`` / ``psums`` timeline stamps.
3. **1-device fallback**: ``--mesh off`` (and a 1-device mesh) is the
   bit-for-bit legacy path — same records, and the report carries NO mesh
   section.
4. **Loud misconfiguration**: an oversized ``--mesh`` exits 2 with a
   one-line diagnostic, never a silently smaller mesh.

Sibling of tools/perf_smoke.py / tools/serve_smoke.py in the verify flow
(.claude/skills/verify); docs/multi-chip.md explains the compile path.

Usage:  python tools/mesh_smoke.py [--keep]
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
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "FGUMI_TPU_HOST_ENGINE": "0",
    "FGUMI_TPU_HYBRID": "0",
}


def run_cli(args, env=None, timeout=420):
    return subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", *args], cwd=REPO,
        env={**BASE_ENV, **(env or {})}, capture_output=True, text=True,
        timeout=timeout)


def last_err(p):
    """Last stderr line of a failed subprocess, or a rc note (a SIGKILLed
    child has empty stderr — never IndexError inside a FAIL report)."""
    lines = p.stderr.strip().splitlines()
    return lines[-1] if lines else f"rc={p.returncode}, no stderr"


def check(name, ok, detail=""):
    print(f"{'PASS' if ok else 'FAIL'}  {name}" + (f"  ({detail})"
                                                   if detail else ""))
    return ok


def records(path):
    """All record bytes of a BAM (header excluded — it carries the argv)."""
    from fgumi_tpu.io.bam import BamReader

    with BamReader(path) as r:
        return [rec.data for rec in r]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--keep", action="store_true")
    opts = ap.parse_args()
    tmp = tempfile.mkdtemp(prefix="fgumi_mesh_smoke_")
    ok = True
    try:
        j = lambda *p: os.path.join(tmp, *p)  # noqa: E731

        # inputs for the three engines
        for args in (
            ["simulate", "grouped-reads", "-o", j("sim.bam"),
             "--num-families", "500", "--family-size", "6",
             "--read-length", "80", "--error-rate", "0.02", "--seed", "11"],
            ["simulate", "duplex-reads", "-o", j("dup.bam"),
             "--num-molecules", "180", "--reads-per-strand", "3",
             "--read-length", "80", "--seed", "11"],
            ["simulate", "codec-reads", "-o", j("codec.bam"),
             "--num-molecules", "220", "--pairs-per-molecule", "2",
             "--read-length", "80", "--seed", "11"],
        ):
            p = run_cli(args)
            ok &= check(f"simulate {args[1]}", p.returncode == 0,
                        last_err(p) if p.returncode else "")

        engines = (
            ("simplex", j("sim.bam"), {}),
            ("duplex", j("dup.bam"), {"FGUMI_TPU_DUPLEX_COMBINE": "device"}),
            ("codec", j("codec.bam"), {"FGUMI_TPU_CODEC_COMBINE": "device"}),
        )
        single = {}
        for cmd, inp, env in engines:
            out = j(f"{cmd}_single.bam")
            p = run_cli(["--mesh", "off", cmd, "-i", inp, "-o", out,
                         "--min-reads", "1"], env=env)
            ok &= check(f"{cmd} single-device run", p.returncode == 0,
                        last_err(p) if p.returncode else "")
            if p.returncode == 0:
                single[cmd] = records(out)

        for mesh in ("dp4xsp2", "dp8"):
            for cmd, inp, env in engines:
                if cmd not in single:
                    continue
                out = j(f"{cmd}_{mesh}.bam")
                p = run_cli([cmd, "-i", inp, "-o", out, "--min-reads", "1"],
                            env={**env, "FGUMI_TPU_MESH": mesh})
                good = p.returncode == 0 and records(out) == single[cmd]
                ok &= check(f"{cmd} {mesh} byte-identity", good,
                            "" if good else (last_err(p) if p.returncode
                                             else "records differ"))

        # mesh observability: report section + gauges + timeline stamps
        rep = j("mesh_report.json")
        p = run_cli(["--mesh", "dp4xsp2", "--run-report", rep, "simplex",
                     "-i", j("sim.bam"), "-o", j("obs.bam"),
                     "--min-reads", "1", "--stats"])
        good = p.returncode == 0
        mesh_sec = gauges = False
        if good:
            r = json.load(open(rep))
            dev = r.get("device", {})
            mesh_sec = dev.get("mesh") == {"dp": 4, "sp": 2, "devices": 8,
                                           "platform": "cpu"}
            m = r.get("metrics", {})
            gauges = (m.get("device.mesh.dp") == 4
                      and m.get("device.mesh.sp") == 2
                      and m.get("device.mesh.devices") == 8)
        # the per-mesh routing EWMAs are not checked here: this job is one
        # dispatch, a shape's first dispatch is a compile and feeds the
        # router nothing; tests/test_mesh_cell.py reads them from a job of
        # several dispatches
        ok &= check("report device.mesh section", good and mesh_sec)
        ok &= check("report device.mesh.* gauges", good and gauges)

        # timeline shard stamps (in-process: the subprocess report has no
        # timeline; assert via a short library run)
        p = subprocess.run(
            [sys.executable, "-c", _TIMELINE_SCRIPT % {"repo": REPO}],
            cwd=REPO, env=BASE_ENV, capture_output=True, text=True,
            timeout=300)
        good = p.returncode == 0 and p.stdout.strip().endswith("OK")
        ok &= check("timeline shards/psums stamps", good,
                    "" if good else last_err(p))

        # 1-device fallback: no mesh section in the report
        rep1 = j("single_report.json")
        p = run_cli(["--mesh", "off", "--run-report", rep1, "simplex",
                     "-i", j("sim.bam"), "-o", j("fb.bam"),
                     "--min-reads", "1"])
        good = p.returncode == 0
        if good:
            r = json.load(open(rep1))
            good = ("mesh" not in r.get("device", {})
                    and "device.mesh.dp" not in r.get("metrics", {})
                    and records(j("fb.bam")) == single.get("simplex"))
        ok &= check("1-device fallback (no mesh section, same bytes)", good)

        # loud misconfiguration
        p = run_cli(["--mesh", "dp64xsp2", "simplex", "-i", j("sim.bam"),
                     "-o", j("bad.bam"), "--min-reads", "1"])
        good = p.returncode == 2 and "needs 128 devices" in p.stderr
        ok &= check("oversized --mesh exits 2 with loud error", good,
                    f"rc={p.returncode}")
        p = run_cli(["--mesh", "banana", "simplex", "-i", j("sim.bam"),
                     "-o", j("bad.bam"), "--min-reads", "1"])
        ok &= check("malformed --mesh rejected at parse",
                    p.returncode == 2, f"rc={p.returncode}")
    finally:
        if opts.keep:
            print(f"kept: {tmp}")
        else:
            shutil.rmtree(tmp, ignore_errors=True)
    print("mesh_smoke:", "OK" if ok else "FAILED")
    return 0 if ok else 1


_TIMELINE_SCRIPT = r"""
import sys
import numpy as np
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
from fgumi_tpu.ops.tables import quality_tables
from fgumi_tpu.ops.kernel import (ConsensusKernel, DEVICE_STATS,
                                  pad_segments_mesh)
from fgumi_tpu.parallel.mesh import resolve_mesh

kernel = ConsensusKernel(quality_tables(45, 40))
kernel.set_force_device()
rng = np.random.default_rng(3)
counts = rng.integers(2, 8, size=64).astype(np.int64)
codes = rng.integers(0, 4, size=(int(counts.sum()), 32)).astype(np.uint8)
quals = rng.integers(10, 40, size=codes.shape).astype(np.uint8)
starts = np.concatenate(([0], np.cumsum(counts)))
mesh = resolve_mesh(jax.devices(), (4, 2))
cg, qg, sg, _st, F_loc, gather = pad_segments_mesh(codes, quals, counts,
                                                   mesh)
t = kernel.device_call_segments_wire(cg, qg, sg, F_loc, len(counts),
                                     full=True, mesh=mesh,
                                     mesh_gather=gather)
kernel.resolve_segments_wire(t, codes, quals, starts)
tl = [e for e in DEVICE_STATS.timeline_snapshot() if "shards" in e]
assert tl, "no mesh timeline entries"
e = tl[0]
assert e["shards"] == 8 and e["psums"] == 2 and e["shard_up_bytes"] > 0, e
print("OK")
"""


if __name__ == "__main__":
    sys.exit(main())
