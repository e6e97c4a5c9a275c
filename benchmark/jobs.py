"""What the job readers share: the process record's ``jobs`` entries (run
report schema 11) of the jobs after the profiler's stop, each beside its own
report, and the device's idle time cut against the command thread's ``job.*``
spans in the traced run's xplane.

The program keeps one entry a finished top-level invocation in the process
record that every report carries (``observe/process.py JobClock``):
``at_s`` (process start to ``cli.main``'s entry), ``wall_s`` from there, and
the seconds of its phases ``before_arm_s`` (to where spans are armed),
``open_s`` (to the command thread's wait for the pipeline's first item),
``fill_s`` (that wait), ``drain_s`` (the pipeline's last item to its stage
threads joined), ``close_s`` (to the command's return) and ``report_s`` (the
run report's own making). The pipeline's run is no phase. The phases from
``open`` to ``close`` are also spans ``job.*`` of the command thread, and
its pipeline's roots cover the run, so whatever of a job's wall neither
covers is time no span names.

A job's entry is made after its own report, so each is read from the last
report of the window, which holds every job but the last; the jobs are those
``threads.reports_read`` gives (after the profiler's stop: its Python tracer
stretches what it watches). A report without ``process.jobs``, or a trace
without a ``job.*`` span (a program from before them), makes every reader
here return ``None``.
"""

import spans
import threads
import tracered

#: a report's ``started_unix`` is kept to the millisecond
_SLACK_S = 0.001


def _entry_of(proc, report):
    """The entry of the job that wrote ``report``: the latest that began at
    or before the report's start and ended after it."""
    started = report["started_unix"] - proc["start_unix"]
    found = None
    for entry in proc["jobs"]:
        if entry["at_s"] <= started + _SLACK_S \
                and entry["at_s"] + entry["wall_s"] >= started - _SLACK_S:
            found = entry
    return found


def jobs_read(run):
    """``(report, entry)`` of each job read that a later report holds the
    entry of; ``None`` where there is none."""
    proc = spans.process_record(run, last=True)
    if proc is None or "jobs" not in proc:
        return None
    pairs = []
    for report in threads.reports_read(run):
        entry = _entry_of(proc, report)
        if entry is not None:
            pairs.append((report, entry))
    return pairs or None


def phase_s_per_mread(run, fields):
    """The ``fields`` of an entry summed, mean of the jobs read, per million
    input reads."""
    pairs = jobs_read(run)
    if pairs is None:
        return None
    total = sum(entry[f] for _report, entry in pairs for f in fields)
    return total / len(pairs) / (run["reads_per_job"] / 1e6)


def command_thread(report):
    """The ``threads`` record of the thread whose root spans hold
    ``job.open``, else ``None``."""
    for rec in report.get("threads", {}).values():
        if "job.open" in rec.get("roots", {}):
            return rec
    return None


def unnamed_share(run):
    """Of the jobs' walls less their reports' making, the share in percent
    that neither ``before_arm_s`` nor the command thread's root spans (the
    phases, the pipeline's wrappers and waits) cover."""
    pairs = jobs_read(run)
    if pairs is None:
        return None
    unnamed = whole = 0.0
    for report, entry in pairs:
        cmd = command_thread(report)
        if cmd is None:
            return None
        wall = entry["wall_s"] - entry["report_s"]
        unnamed += wall - entry["before_arm_s"] - cmd["root_wall_s"]
        whole += wall
    return 100.0 * unnamed / whole if whole > 0 else None


def worker_starved_share(run):
    """``queue.worker_get`` over the resolve workers' root wall, in percent,
    summed over the workers and the jobs read."""
    jobs = threads.role_threads(run, "worker")
    if jobs is None:
        return None
    waited = wall = 0.0
    seen = False
    for records in jobs:
        for rec in records:
            got = rec.get("self_s", {}).get("queue.worker_get")
            if got is not None:
                seen = True
                waited += got
            wall += rec["root_wall_s"]
    return 100.0 * waited / wall if seen and wall > 0 else None


_edges_cache = {}


def idle_job_edges(run):
    """Of the device's idle time in the traced window (cut as
    ``spans.idle_attribution`` cuts it, which keeps no intervals to share),
    the seconds and those inside a ``job.*`` span of the command thread.
    ``None`` on a CPU rehearsal, with no xplane, or with no ``job.*`` span
    in it."""
    if run["device"]["platform"] == "cpu":
        return None
    path = spans.xplane_path(run)
    if path is None:
        return None
    if path not in _edges_cache:
        device, host = tracered.load(path)
        busy = []
        for plane in device.values():
            busy = spans._merge((s, s + d) for _n, s, d in plane["ops"]
                                if d > 0)
            if busy:
                break
        starts = [s for evs in host.values() for _n, s, d, _m in evs if d > 0]
        ends = [s + d for evs in host.values() for _n, s, d, _m in evs]
        if busy:
            starts.append(busy[0][0])
            ends.append(busy[-1][1])
        edges = spans._merge((s, s + d) for evs in host.values()
                             for name, s, d, _m in evs
                             if d > 0 and name.startswith("job."))
        result = None
        if starts and edges:
            idle = spans.idle_intervals(busy, (min(starts), max(ends)))
            result = {"idle_s": spans._length(idle),
                      "in_jobs_s": spans._length(spans.intersect(idle,
                                                                 edges))}
        _edges_cache[path] = result
    return _edges_cache[path]
