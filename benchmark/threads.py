"""What the thread readers share: the ``threads`` and ``alloc`` sections of
the run reports (schema 10) of the jobs that ran without the profiler.

The program keeps, per thread of a job, the wall of its *root* spans (those
with no parent on that thread), the waits it declared inside them, and the
thread's own CPU clock over them (``getrusage(RUSAGE_THREAD)``):
``work_s = root_wall_s - wait_s`` and ``offcpu_s = work_s - utime_s -
stime_s`` are what it worked and what of that it was on no CPU. A thread's
role is found by the names of its root spans, never by the thread's name.

**Which jobs.** Every job of a traced run writes a run report, but the
first ``traced_jobs`` run under ``jax.profiler``, whose Python tracer slows
pure Python 1.6-2.5x and can change which thread paces a job (PERF.md,
Findings, PR 25; ROADMAP D2.2), and job ``traced_jobs`` holds the
profiler's stop. These readers read the jobs after it, and say nothing
where a window has fewer than two such: the traced jobs are no stand-in.

A report without the section (a program from before it) makes every reader
here return ``None``.
"""

#: role -> the root span a thread of that role has
ROLES = {"process": "pipeline.process", "worker": "pipeline.resolve",
         "reader": "pipeline.read"}
#: a thread that worked this long on a ticking clock has CPU time to show
CLOCK_FLOOR_S = 0.1


def reports_read(run):
    """The reports of the jobs after the profiler's stop; none where fewer
    than two came after it."""
    after = run["reports"][run["traced_jobs"] + 1:]
    return after if len(after) >= 2 else []


def role_threads(run, role):
    """Per job read, the ``threads`` records of the threads with the role's
    root span. ``None`` where a job's report has no ``threads`` section or
    no thread of the role."""
    jobs = []
    for report in reports_read(run):
        records = [t for t in report.get("threads", {}).values()
                   if ROLES[role] in t.get("roots", {})]
        if not records:
            return None
        jobs.append(records)
    return jobs or None


def work_s_per_mread(run, role):
    """``work_s`` of the role's busiest thread per million input reads, mean
    of the jobs read: the rate such a thread alone allows is ``1e6 /`` it."""
    jobs = role_threads(run, role)
    if jobs is None:
        return None
    busiest = [max(t["work_s"] for t in records) for records in jobs]
    return sum(busiest) / len(busiest) / (run["reads_per_job"] / 1e6)


def share_of_work(run, role, field):
    """``field`` over ``work_s``, in percent, summed over the role's threads
    and the jobs read. ``None`` where a thread has no clock reading, or
    worked over ``CLOCK_FLOOR_S`` with ``utime_s + stime_s == 0``: a host
    whose thread clock does not tick says nothing about where time went."""
    jobs = role_threads(run, role)
    if jobs is None:
        return None
    part = work = 0.0
    for records in jobs:
        for t in records:
            if "utime_s" not in t or (
                    t["work_s"] > CLOCK_FLOOR_S
                    and t["utime_s"] + t["stime_s"] == 0):
                return None
            part += t[field]
            work += t["work_s"]
    return 100.0 * part / work if work > 0 else None


def alloc_ends(run, key):
    """``key`` of the ``alloc`` record at the end of each job read, in the
    jobs' order (``None``: no job read, a job without the record, or a C
    library that does not give the key)."""
    ends = [report.get("alloc", {}).get("end", {}).get(key)
            for report in reports_read(run)]
    return ends if ends and None not in ends else None
