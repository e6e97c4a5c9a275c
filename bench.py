"""Benchmark: `simplex` end to end on the attached accelerator.

A stop-gap until the cell benchmark (ROADMAP S1) replaces it. It measures
input reads consumed per second, grouped BAM in -> consensus BAM out, for
two simulated inputs:

- ``config1``: BASELINE.json eval config 1's shape (lognormal family size 5,
  100 bp) at ``BENCH_FAMILIES`` families (default 40,000, about 441k reads);
- ``mixed``: the long-tail analog of eval config 2 (``MIXED_SIM_KWARGS``).

Prints ONE JSON line, last on stdout. The headline ``value`` is a device
number or nothing: when jax finds no accelerator, or any run fails, the
script prints the error and exits non-zero — it never reports a CPU timing
under the device metric. The native f64 host engine on the same inputs is
reported beside it under its own name (``host_engine_reads_per_sec``).

One process per chip: this parent never imports jax. Each side is one child
that warms up, takes ``BENCH_RUNS`` timed runs in-process and reports their
median; the device child exits before the host-engine child starts.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
#: fixed, git-ignored work directory (nothing named by a pid or a time)
WORK = os.path.join(REPO, "chiprun_out", "bench_work")

#: BASELINE eval config 2 analog: long-tail family sizes, ragged read
#: lengths, 3' quality decay — the ragged-batch economics the fixed-size
#: config hides (ROADMAP R4 promotes this to a cell).
MIXED_SIM_KWARGS = dict(family_size=4, family_size_distribution="longtail",
                        read_length=100, read_length_jitter=30,
                        qual_slope=0.05, error_rate=0.01, seed=43)

_WORKER = r"""
import json, os, statistics, sys, time
sys.path.insert(0, sys.argv[1])
from fgumi_tpu.cli import main
from fgumi_tpu.ops.kernel import DEVICE_STATS, device_identity

threads, runs, out_dir = sys.argv[2], int(sys.argv[3]), sys.argv[4]
result = {"inputs": {}}
for path in sys.argv[5:]:
    name = os.path.basename(path).split(".")[0]
    argv = ["simplex", "-i", path, "--min-reads", "1", "--threads", threads,
            "-o", os.path.join(out_dir, name + ".cons.bam")]
    t0 = time.monotonic()
    assert main(argv) == 0, "warm-up run failed"
    warm_s = time.monotonic() - t0
    walls = []
    for _ in range(runs):
        DEVICE_STATS.reset()
        t0 = time.monotonic()
        assert main(argv) == 0, "timed run failed"
        walls.append(time.monotonic() - t0)
    result["inputs"][name] = {
        "warm_s": round(warm_s, 3), "wall_s": [round(w, 4) for w in walls],
        "median_wall_s": statistics.median(walls),
        "device_stats": DEVICE_STATS.snapshot()}
result["device"] = device_identity()
print(json.dumps(result))
"""


def _child(env_overrides, threads, runs, inputs):
    env = {**os.environ, **env_overrides}
    proc = subprocess.run(
        [sys.executable, "-c", _WORKER, REPO, str(threads), str(runs), WORK,
         *inputs], env=env, capture_output=True, text=True, timeout=3000)
    if proc.returncode != 0:
        raise RuntimeError(f"bench child failed rc={proc.returncode}:\n"
                           f"{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _count_records(path):
    from fgumi_tpu.io.batch_reader import BamBatchReader

    with BamBatchReader(path) as reader:
        return sum(batch.n for batch in reader)


def main():
    sys.path.insert(0, REPO)
    from fgumi_tpu.simulate import simulate_grouped_bam

    n_families = int(os.environ.get("BENCH_FAMILIES", "40000"))
    threads = int(os.environ.get("BENCH_THREADS", "4"))
    runs = int(os.environ.get("BENCH_RUNS", "5"))
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    try:
        config1 = os.path.join(WORK, "config1.bam")
        simulate_grouped_bam(config1, num_families=n_families, family_size=5,
                             family_size_distribution="lognormal",
                             read_length=100, error_rate=0.01, seed=42)
        mixed = os.path.join(WORK, "mixed.bam")
        simulate_grouped_bam(mixed, num_families=max(n_families // 2, 1000),
                             **MIXED_SIM_KWARGS)
        inputs = (config1, mixed)
        n_reads = {"config1": _count_records(config1),
                   "mixed": _count_records(mixed)}

        device = _child({}, threads, runs, inputs)
        ident = device["device"]
        if ident["platform"] == "cpu":
            print(f"bench: jax found no accelerator ({ident}); no device "
                  "number is reported", file=sys.stderr)
            return 1
        host = _child({"JAX_PLATFORMS": "cpu"}, threads, runs, inputs)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)

    def rate(side, name):
        return n_reads[name] / side["inputs"][name]["median_wall_s"]

    out = {"metric": "simplex_reads_per_sec", "unit": "reads/s",
           "value": rate(device, "config1"), "device": ident,
           "threads": threads, "timed_runs": runs, "input_reads": n_reads,
           "mixed_reads_per_sec": rate(device, "mixed"),
           "host_engine_reads_per_sec": rate(host, "config1"),
           "host_engine_mixed_reads_per_sec": rate(host, "mixed"),
           "device_runs": device["inputs"], "host_engine_runs": host["inputs"]}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
