#!/usr/bin/env python3
"""One run of one benchmark cell: set-up, a window of whole jobs, then the
comparison with the plain reference. See benchmark/README.md.

    python benchmark/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

One process, which holds the chip. The last line of standard output is the
result object; every earlier line is for whoever has to diagnose a noisy run.
"""

import os
import sys
import time

T0 = time.monotonic()  # process start, as near as Python can read it

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402

ROOT = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(ROOT)
WORK = os.path.join(CHECKOUT, ".benchwork")  # git-ignored, fixed
sys.path[:0] = [ROOT, CHECKOUT]

import compare  # noqa: E402
import traffic  # noqa: E402


def say(*parts):
    print(*parts, flush=True)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload):
    """The cell's entry of BENCHMARK.json with its configuration (JSON and
    reference module) and traffic parameters, each found by name."""
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    with open(os.path.join(ROOT, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    reference = load_module(
        os.path.join(ROOT, "configs", cell["config"] + ".py"),
        "config_reference")
    return bench, cell, config, reference, traffic.load(cell["traffic"], ROOT)


def run_window(run_job, seconds, clock=time.monotonic,
               maxrss=lambda: resource.getrusage(
                   resource.RUSAGE_SELF).ru_maxrss):
    """Start jobs while less than ``seconds`` have passed since the first
    instant; never start one after, never abandon one. Returns the clock at
    every job boundary (first instant, then each job's return) and the
    ``ru_maxrss`` read at each. Between two jobs nothing else happens."""
    marks = [clock()]
    rss = [maxrss()]
    k = 0
    while marks[-1] - marks[0] < seconds:
        run_job(k)
        marks.append(clock())
        rss.append(maxrss())
        k += 1
    return marks, rss


def window_rate(marks, reads_per_job):
    """All reads of the window's jobs over first instant -> last return."""
    jobs = len(marks) - 1
    window_s = marks[-1] - marks[0]
    return jobs * reads_per_job / window_s, window_s


def device_block(chips, rehearse):
    """What jax sees; no accelerator (or too few chips) ends the run unless
    this is a rehearsal, which then says ``cpu``."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform == "cpu" and not rehearse:
        raise SystemExit("jax found no accelerator: no result is printed "
                         "(use --rehearse for a CPU rehearsal)")
    if len(devs) < chips and not rehearse:
        raise SystemExit(f"the cell asks for {chips} chip(s), jax sees "
                         f"{len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak():
    import jax

    peaks = []
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def read_per_layer(bench, cell_name, run):
    """Every per-layer metric of this cell, each by its own reader file."""
    out = {}
    for metric in bench["per_layer"]:
        if "workloads" in metric and cell_name not in metric["workloads"]:
            continue
        reader = load_module(
            os.path.join(ROOT, "metrics", metric["name"] + ".py"),
            "metric_" + metric["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny input, CPU allowed; the result says so")
    args = ap.parse_args(argv)

    bench, cell, config, reference, params = load_cell(args.workload)
    if args.rehearse:
        params["num_families"] = max(200, params["num_families"] // 50)

    # ---------------------------------------------------------------- set-up
    work = os.path.join(WORK, args.workload + ("-rehearse" if args.rehearse
                                               else ""), str(args.seed))
    out_dir = os.path.join(work, "out")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    # The input is made anew in every run, by a process of its own that never
    # touches jax: its arrays stay out of the heap the program's jobs run in
    # (the program's pace follows its heap's history, PERF.md), and it works
    # while this process builds the library and starts the backend.
    maker = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "traffic.py"), "--traffic",
         cell["traffic"], "--seed", str(args.seed), "--prefix",
         os.path.join(work, "input"), "--families",
         str(params["num_families"])], stdout=subprocess.PIPE, text=True)
    try:
        return measure(args, bench, cell, config, reference, params, maker,
                       work, out_dir)
    finally:
        if maker.poll() is None:
            maker.kill()
        maker.wait()
        for name in os.listdir(work):  # the reference's digest stays, for the
            if name != "expected.json":  # seed's next run
                path = os.path.join(work, name)
                (shutil.rmtree if os.path.isdir(path) else os.remove)(path)


def measure(args, bench, cell, config, reference, params, maker, work,
            out_dir):
    from fgumi_tpu import native

    t = time.monotonic()
    if native.get_lib() is None:  # builds it where the checkout has none
        raise SystemExit("libfgumi_native.so could not be built or loaded")
    native_s = time.monotonic() - t

    from fgumi_tpu.cli import main as cli_main
    from fgumi_tpu.ops import kernel

    t = time.monotonic()
    kernel._ensure_jax()  # the program's own first use of jax (cache, watch)
    device = device_block(cell["chips"], args.rehearse)
    backend_s = time.monotonic() - t

    t = time.monotonic()
    made, _ = maker.communicate()
    if maker.returncode != 0:
        raise SystemExit(f"the input maker exited {maker.returncode}")
    meta = json.loads(made.strip().splitlines()[-1])
    inputs_s = meta["seconds"]  # the maker's own clock, import to last byte
    inputs_wait_s = time.monotonic() - t  # what set-up still waited for it
    reads = meta["reads"]

    def job_argv(tag, report=None):
        subst = {f"in{i}": p for i, p in enumerate(meta["inputs"])}
        subst["out"] = os.path.join(out_dir, f"{tag}.bam")
        argv = [a.format(**subst) for a in config["command"]]
        return (["--run-report", report] if report else []) + argv

    t = time.monotonic()
    for k in range(config.get("warm_jobs", 2)):
        rc = cli_main(job_argv(f"warm{k}"))
        if rc != 0:
            raise SystemExit(f"warm job {k} exited {rc}")
        os.remove(os.path.join(out_dir, f"warm{k}.bam"))
    warm_s = time.monotonic() - t

    # ---------------------------------------------------------------- window
    traced = {"stats": [], "timeline": [], "reports": [], "on": False}
    trace_dir = os.path.join(work, "trace")
    rcs = []

    def stop_trace():
        import jax

        jax.profiler.stop_trace()
        traced["on"] = False

    def run_job(k):
        """One job of the window. Untraced, the branches below are all it
        adds to the program's own work."""
        report = None
        if args.trace:
            import jax

            report = os.path.join(out_dir, f"job{k}.report.json")
            if k == 0:
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(trace_dir)
                traced["on"] = True
            if k == TRACED_JOBS:  # its seconds fall to a job no reader reads
                stop_trace()
        rcs.append(cli_main(job_argv(f"job{k}", report)))
        if args.trace:
            traced["stats"].append(kernel.DEVICE_STATS.snapshot())
            traced["timeline"].append(kernel.DEVICE_STATS.timeline_snapshot())
            traced["reports"].append(report)

    setup_s = time.monotonic() - T0
    marks, rss = run_window(run_job, args.seconds)
    if traced["on"]:  # a window of three jobs or fewer
        stop_trace()

    # ------------------------------------------------------ after the window
    rate, window_s = window_rate(marks, reads)
    jobs = len(marks) - 1
    device["memory_peak_bytes"] = memory_peak()
    last_stats = kernel.DEVICE_STATS.snapshot()
    walls = [b - a for a, b in zip(marks, marks[1:])]
    for k, wall in enumerate(walls):
        line = {"job": k, "wall_s": round(wall, 4), "reads": reads,
                "rc": rcs[k], "maxrss_kb": rss[k + 1]}
        if args.trace and k == TRACED_JOBS:
            line["holds_trace_stop"] = True  # the profiler's, not the job's
        if traced["stats"]:
            packs = sorted(e.get("pack_s", 0) for e in traced["timeline"][k])
            line.update(device_batches=traced["stats"][k].get("route_device"),
                        host_batches=traced["stats"][k].get("route_host"),
                        dispatches=traced["stats"][k].get("dispatches"),
                        pack_ms_p50=(round(packs[len(packs) // 2] * 1e3, 1)
                                     if packs else None))
        say("job", json.dumps(line))
    say("window", json.dumps({
        "jobs": jobs, "window_s": round(window_s, 4), "asked_s": args.seconds,
        "reads_per_s": rate,
        "last_job_device_batches": last_stats.get("route_device"),
        "last_job_host_batches": last_stats.get("route_host"),
        "last_job_dispatches": last_stats.get("dispatches")}))
    say("setup", json.dumps({
        "setup_s": round(setup_s, 3), "native_s": round(native_s, 3),
        "inputs_s": round(inputs_s, 3),
        "inputs_wait_s": round(inputs_wait_s, 3),
        "backend_s": round(backend_s, 3),
        "warm_jobs_s": round(warm_s, 3)}))

    run = None
    if args.trace:
        import tracered

        reports = []
        for path in traced["reports"]:
            with open(path) as f:
                reports.append(json.load(f))
        trace = tracered.reduce_dir(trace_dir, config)
        run = {"setup": {"backend_s": backend_s, "warm_jobs_s": warm_s,
                         "inputs_s": inputs_s, "native_s": native_s},
               "walls": walls, "reads_per_job": reads, "window_s": window_s,
               "maxrss_kb": rss, "stats": traced["stats"],
               "timeline": traced["timeline"], "reports": reports,
               "trace": trace, "traced_jobs": min(TRACED_JOBS, jobs),
               "device": device, "params": params, "config": config,
               "consensus_reads_per_row": 2 * params["num_families"] / reads}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]

    t = time.monotonic()
    outputs = [os.path.join(out_dir, f"job{k}.bam") for k in range(jobs)]
    verdict = compare.judge(
        outputs, rcs, out_dir, os.path.join(work, "expected.json"),
        lambda dtype: reference.expected(
            traffic.generate(params, args.seed), config, dtype))
    say("harness", json.dumps({"compare_s": round(time.monotonic() - t, 3),
                               "reference_cached": verdict["cached"]}))

    if args.trace:
        metrics = read_per_layer(bench, args.workload, run)
    else:
        metrics = {"reads_per_s": {"value": rate, "unit": "reads/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    result = {"correct": verdict["correct"], "attempted": jobs,
              "failed": sum(1 for rc in rcs if rc != 0), "metrics": metrics,
              "device": device}
    if args.rehearse:
        result["rehearsal"] = True
    if run is not None and run["trace"].get("breakdown"):
        result["breakdown"] = run["trace"]["breakdown"]
    result["compared"] = verdict["compared"]
    for name, entry in verdict["compared"].items():
        print(f"compared {name} = {entry['value']} (limit {entry['limit']})",
              file=sys.stderr, flush=True)
    say(json.dumps(result))
    return 0


TRACED_JOBS = 3

if __name__ == "__main__":
    sys.exit(main())
