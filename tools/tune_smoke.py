#!/usr/bin/env python3
"""Deployment-profile smoke (ISSUE 20): gate the self-tuning loop end to
end on the CPU platform (fast, runs anywhere).

Checks (exit 0 when every scenario holds, one PASS/FAIL line each):

1. **Quick tune**: ``fgumi-tpu tune --quick`` exits 0 and commits a
   schema-valid deployment profile plus a crossover atlas whose cells
   carry positive measured rates for both routes.
2. **Byte identity**: a ``simplex`` run with the freshly tuned profile
   loaded produces record bytes identical to the defaults run — a
   profile tunes throughput, never output.
3. **No slower**: the profile-loaded run's wall clock is within a
   generous CI-noise envelope of the defaults run (the profile must
   never make a run pathologically slower).
4. **Prior-seeded routing**: with the profile applied, the router's very
   first fam-3 batch routes to the side the atlas measured as the winner
   for that workload cell, with ``prior_source == "profile"`` and a cost
   (not probe) decision; the run report carries the ``profile`` section
   and ``tune.*`` gauges.
5. **Precedence + diagnostics**: an explicit env knob survives profile
   application (skipped_explicit), and a malformed profile is a clean
   exit-2 diagnostic.
6. **Replay**: ``tune --replay`` over the quick run's atlas-backing
   microbench cells derives a schema-valid ``source: replay`` profile.

Sibling of tools/perf_smoke.py / tools/serve_smoke.py in the verify
flow (.claude/skills/verify).

Usage:  python tools/tune_smoke.py [--keep]
"""

import argparse
import json
import os
import shutil
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
}
# a stray deployed profile must not leak into the smoke's baseline
BASE_ENV.pop("FGUMI_TPU_PROFILE", None)


def run_cli(args, env=None, timeout=600):
    return subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", *args], cwd=REPO,
        env={**BASE_ENV, **(env or {})}, capture_output=True, text=True,
        timeout=timeout)


def check(name, ok, detail=""):
    print(f"{'PASS' if ok else 'FAIL'}  {name}" + (f"  ({detail})"
                                                   if detail else ""))
    return ok


def record_bytes(path):
    from fgumi_tpu.io.bam import BamReader

    with BamReader(path) as rd:
        return b"".join(r.data for r in rd)


def tune_scenario(tmp):
    prof = os.path.join(tmp, "deploy_profile.json")
    atlas = os.path.join(tmp, "TUNE_ATLAS.json")
    p = run_cli(["tune", "--quick", "-o", prof, "--atlas", atlas])
    ok = check("tune --quick exits 0", p.returncode == 0,
               (p.stderr.strip().splitlines() or ["no stderr"])[-1]
               if p.returncode else "")
    if not ok:
        return False, None, None
    from fgumi_tpu.tune.profile import load_profile, validate_profile

    profile = load_profile(prof)
    validate_profile(profile)  # raises on schema violations
    ok &= check("profile schema-valid", True)
    ok &= check("profile carries router priors",
                bool(profile.get("priors", {}).get("router")))
    doc = json.load(open(atlas))
    cells = doc.get("cells", [])
    ok &= check("atlas carries measured cells", len(cells) >= 3,
                f"{len(cells)} cells")
    ok &= check("atlas cells carry positive rates on both routes",
                all(c.get("device_rows_per_sec", 0) > 0
                    and c.get("host_rows_per_sec", 0) > 0 for c in cells))
    return ok, prof, doc


def identity_scenario(tmp, prof):
    bam = os.path.join(tmp, "grouped.bam")
    p = run_cli(["simulate", "grouped-reads", "-o", bam,
                 "--num-families", "200", "--family-size", "3",
                 "--seed", "7"])
    if not check("simulate exits 0", p.returncode == 0,
                 p.stderr.strip().splitlines()[-1] if p.returncode else ""):
        return False
    cold = os.path.join(tmp, "cold.bam")
    warm = os.path.join(tmp, "warm.bam")
    t0 = time.monotonic()
    p1 = run_cli(["simplex", "-i", bam, "-o", cold, "--min-reads", "1"])
    t_cold = time.monotonic() - t0
    t0 = time.monotonic()
    p2 = run_cli(["--profile", prof, "simplex", "-i", bam, "-o", warm,
                  "--min-reads", "1"])
    t_warm = time.monotonic() - t0
    ok = check("defaults + profile runs exit 0",
               p1.returncode == 0 and p2.returncode == 0,
               (p1.stderr or p2.stderr).strip().splitlines()[-1]
               if p1.returncode or p2.returncode else "")
    if not ok:
        return False
    ok &= check("profile run byte-identical to defaults",
                record_bytes(cold) == record_bytes(warm))
    # generous envelope: a profile must never be pathologically slower
    # (2x + 2s absorbs CI noise on tiny inputs where wall is dominated
    # by interpreter startup, not the tuned path)
    ok &= check("profile run no slower (2x + 2s envelope)",
                t_warm <= 2.0 * t_cold + 2.0,
                f"cold {t_cold:.2f}s warm {t_warm:.2f}s")
    return ok


_ROUTE_PAYLOAD = r"""
import json, sys
sys.path.insert(0, %(repo)r)
from fgumi_tpu.tune import profile as profmod
from fgumi_tpu.ops.router import ROUTER
from fgumi_tpu.native import batch as nb

profile = profmod.load_profile(%(prof)r)
rec = profmod.apply_profile(profile, path=%(prof)r)

class K:
    @staticmethod
    def hybrid_mode():
        return True

# the quick atlas' fam-3 L100 cell: 4000 families x 3 reads
decision = ROUTER.decide_batch(K(), n_rows=12000, n_segments=4000, L=100)
snap = ROUTER.snapshot()
print(json.dumps({
    "native": nb.available(),
    "decision": decision,
    "prior_source": snap["prior_source"],
    "why": (snap.get("last_decision") or {}).get("why"),
    "applied": rec["applied"],
}))
"""


def routing_scenario(tmp, prof, atlas_doc):
    cell = next((c for c in atlas_doc["cells"]
                 if c.get("mean_depth") == 3 and c.get("read_length") == 100),
                None)
    if cell is None:
        return check("atlas carries the fam-3 L100 cell", False)
    p = subprocess.run(
        [sys.executable, "-c",
         _ROUTE_PAYLOAD % {"repo": REPO, "prof": prof}], cwd=REPO,
        env=BASE_ENV, capture_output=True, text=True, timeout=300)
    ok = check("routing payload exits 0", p.returncode == 0,
               (p.stderr.strip().splitlines() or ["no stderr"])[-1]
               if p.returncode else "")
    if not ok:
        return False
    out = json.loads(p.stdout.strip().splitlines()[-1])
    ok &= check("profile seeds the router (prior_source=profile)",
                out["prior_source"] == "profile", out["prior_source"])
    if out["native"]:
        ok &= check("first-batch route matches the atlas winner",
                    out["decision"] == cell["winner"],
                    f"routed {out['decision']}, atlas says {cell['winner']}")
        ok &= check("decision is cost-based, not a probe",
                    out["why"] == "cost", str(out["why"]))
    else:
        check("first-batch route matches the atlas winner",
              out["decision"] == "device",
              "native engine unavailable: device-only"),
    # the profile section rides the run report of a profile-loaded run
    rpt = os.path.join(tmp, "report.json")
    bam = os.path.join(tmp, "grouped.bam")
    out_bam = os.path.join(tmp, "rpt.bam")
    p = run_cli(["--profile", prof, "--run-report", rpt, "simplex",
                 "-i", bam, "-o", out_bam, "--min-reads", "1"])
    ok &= check("profile-loaded run-report run exits 0", p.returncode == 0)
    if p.returncode == 0:
        report = json.load(open(rpt))
        sec = report.get("profile") or {}
        ok &= check("run report carries the profile section",
                    sec.get("path") == prof)
        ok &= check("run report carries tune.* gauges",
                    report.get("metrics", {}).get(
                        "tune.profile.loaded") == 1)
        routing = (report.get("device") or {}).get("routing") or {}
        ok &= check("device.routing stamps prior_source",
                    routing.get("prior_source") in
                    ("profile", "cold", "snapshot"),
                    str(routing.get("prior_source")))
    return ok


def precedence_scenario(tmp, prof):
    rpt = os.path.join(tmp, "prec_report.json")
    bam = os.path.join(tmp, "grouped.bam")
    out_bam = os.path.join(tmp, "prec.bam")
    p = run_cli(["--profile", prof, "--run-report", rpt, "simplex",
                 "-i", bam, "-o", out_bam, "--min-reads", "1"],
                env={"FGUMI_TPU_COALESCE_WINDOW_MS": "9"})
    ok = check("explicit-env run exits 0", p.returncode == 0)
    if p.returncode == 0:
        sec = json.load(open(rpt)).get("profile") or {}
        ok &= check("explicit env knob wins over the profile",
                    "coalesce_window_ms" in
                    sec.get("knobs_skipped_explicit", []),
                    str(sec.get("knobs_skipped_explicit")))
    bad = os.path.join(tmp, "bad_profile.json")
    with open(bad, "w") as fh:
        json.dump({"schema_version": 1, "source": "manual"}, fh)
    p = run_cli(["--profile", bad, "simplex", "-i", bam, "-o", out_bam,
                 "--min-reads", "1"])
    ok &= check("malformed profile is a clean exit-2 diagnostic",
                p.returncode == 2 and "expected" in p.stderr,
                f"rc={p.returncode}")
    return ok


def replay_scenario(tmp):
    micro = os.path.join(tmp, "micro.json")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "microbench.py"), REPO,
         "--tune-cells-only"], cwd=REPO, env=BASE_ENV,
        capture_output=True, text=True, timeout=600)
    if not check("microbench --tune-cells-only exits 0", p.returncode == 0,
                 (p.stderr.strip().splitlines() or ["?"])[-1]
                 if p.returncode else ""):
        return False
    with open(micro, "w") as fh:
        fh.write(p.stdout.strip().splitlines()[-1])
    prof2 = os.path.join(tmp, "replay_profile.json")
    atlas2 = os.path.join(tmp, "replay_atlas.json")
    p = run_cli(["tune", "--replay", micro, "-o", prof2,
                 "--atlas", atlas2])
    ok = check("tune --replay exits 0", p.returncode == 0,
               (p.stderr.strip().splitlines() or ["?"])[-1]
               if p.returncode else "")
    if not ok:
        return False
    from fgumi_tpu.tune.profile import load_profile, validate_profile

    profile = load_profile(prof2)
    validate_profile(profile)
    ok &= check("replay profile schema-valid, source=replay",
                profile["source"] == "replay", profile["source"])
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--keep", action="store_true",
                    help="keep the scratch dir")
    args = ap.parse_args()
    tmp = tempfile.mkdtemp(prefix="tune_smoke_")
    ok = True
    try:
        ok, prof, atlas_doc = tune_scenario(tmp)
        if ok:
            ok &= identity_scenario(tmp, prof)
            ok &= routing_scenario(tmp, prof, atlas_doc)
            ok &= precedence_scenario(tmp, prof)
            ok &= replay_scenario(tmp)
    finally:
        if args.keep:
            print(f"scratch kept: {tmp}")
        else:
            shutil.rmtree(tmp, ignore_errors=True)
    print("OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
