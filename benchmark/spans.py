"""What the span readers share: the traced jobs' ``spans`` sections, the
process-level record, and the traced run's xplane cut against the program's
own annotations.

The program records a span aggregate per job (run report ``spans``, schema 9)
and mirrors every live span onto the profiler's clock, so the same names
appear as host events in the xplane. A report without the section (a program
from before the spans) makes every reader here return ``None``.
"""

import os
import statistics

import tracered

#: spans in which a thread only waits: never evidence of what it does
WAIT_SPANS = frozenset((
    "pipeline.wait_in", "pipeline.wait_out", "chain.put", "chain.get",
    "chain.header", "resolve.wait", "feeder.queue_wait"))
#: spans that cover a thread by construction: a stage thread's whole life,
#: and the run_stages wrappers round work and waits alike
COVER_ALL = frozenset((
    "chain.extract", "chain.sort", "chain.group", "chain.simplex",
    "chain.filter", "pipeline.read", "pipeline.process", "pipeline.resolve",
    "pipeline.sink"))
#: first dotted part of every span name the program has
PROGRAM_PREFIXES = frozenset((
    "process", "engine", "router", "feeder", "device", "resolve", "sink",
    "reader", "chain", "pipeline", "group", "extract", "sort", "filter",
    "bgzf", "io", "startup"))


def traced_reports(run):
    return run["reports"][:run["traced_jobs"]]


def span_records(run, name):
    """The aggregate record of ``name`` in each traced job that has one;
    ``None`` when no traced job's report has a ``spans`` section at all."""
    sections = [r["spans"]["by_name"] for r in traced_reports(run)
                if "spans" in r]
    if not sections:
        return None
    return [s[name] for s in sections if name in s]


def span_sum(run, names, field):
    """``field`` summed over ``names`` and the traced jobs (``None``: no
    ``spans`` section; 0.0: the section has none of the names)."""
    total = 0.0
    for name in names:
        records = span_records(run, name)
        if records is None:
            return None
        total += sum(rec.get(field, 0) for rec in records)
    return total


def span_p50_ms(run, name):
    """Median over the traced jobs of the job's own median, in ms."""
    records = span_records(run, name)
    if not records:
        return None
    return statistics.median(rec["p50_s"] for rec in records) * 1e3


def stage_busy_s(run, stage):
    """Seconds a job's ``chain.<stage>`` thread works: the wall of its
    stage-life span minus the waits below it on that thread (channel puts
    and gets, queue waits), mean of the traced jobs."""
    records = span_records(run, "chain." + stage)
    if not records:
        return None
    return sum(r["wall_s"] - r["wait_s"] for r in records) / len(records)


def mreads(run):
    """Million input reads of the traced jobs."""
    return run["traced_jobs"] * run["reads_per_job"] / 1e6


def process_record(run, last=False):
    """The process-level record as the first traced job's report has it
    (``last``: as the window's last job has it, with everything since)."""
    reports = run["reports"] if last else traced_reports(run)
    for report in (reversed(reports) if last else reports):
        if "process" in report:
            return report["process"]
    return None


def compile_seconds(run, in_window):
    """Seconds of backend compiles and persistent-cache loads that ended
    before the first traced job started (start-up: the warm jobs'), or from
    then on (``in_window``: inside the window's jobs, every one of them)."""
    proc = process_record(run, last=in_window)
    if proc is None or not run["reports"]:
        return None
    first = run["reports"][0]["started_unix"]
    total = 0.0
    for rec in proc["compiles"]:
        inside = proc["start_unix"] + rec["at_s"] >= first
        if inside == in_window:
            total += rec["s"]
    return total


# ------------------------------------------------------------------ xplane


def _merge(intervals):
    return tracered._union(intervals)[0]


def _length(merged):
    return sum(e - s for s, e in merged)


def intersect(a, b):
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b):
    """``a`` minus ``b``, both merged and sorted."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, cur = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append([cur, end])
    return out


def idle_intervals(busy, window):
    """The window minus the device's merged busy intervals."""
    return subtract([[window[0], window[1]]], busy)


def work_cover(host, names=None):
    """Merged intervals in which some thread was inside a program span that
    says what it does: per thread, the spans named ``names`` (default: every
    program span but the waits and the cover-all ones) minus that thread's
    wait spans, then the union over threads."""
    cover = []
    for events in host.values():
        work, waits = [], []
        for name, start, dur, _mod in events:
            if dur <= 0:
                continue
            if name in WAIT_SPANS:
                waits.append((start, start + dur))
            elif names is not None:
                if name in names:
                    work.append((start, start + dur))
            elif name not in COVER_ALL \
                    and name.split(".", 1)[0] in PROGRAM_PREFIXES \
                    and "." in name and " " not in name:
                work.append((start, start + dur))
        if work:
            cover += subtract(_merge(work), _merge(waits))
    return _merge(cover)


def xplane_path(run):
    """The traced run's xplane, found from the first traced job's own report:
    its ``argv`` holds ``-o <work>/out/job0.bam`` and the harness writes the
    trace under ``<work>/trace``."""
    for report in traced_reports(run):
        argv = report.get("argv", [])
        if "-o" in argv:
            out = argv[argv.index("-o") + 1]
            trace_dir = os.path.join(os.path.dirname(os.path.dirname(out)),
                                     "trace")
            try:
                return tracered.find_xplane(trace_dir)
            except FileNotFoundError:
                return None
    return None


_idle_cache = {}


def idle_attribution(run):
    """Of the device's idle time in the traced window: the seconds, those an
    ``engine.pack`` annotation covers, and those any program work span
    covers. ``None`` with no device plane, no xplane, or an xplane without a
    single program annotation (a program from before the spans)."""
    if run["device"]["platform"] == "cpu":
        return None
    path = xplane_path(run)
    if path is None:
        return None
    if path not in _idle_cache:
        device, host = tracered.load(path)
        busy = []
        for plane in device.values():
            busy = _merge((s, s + d) for _n, s, d in plane["ops"] if d > 0)
            if busy:
                break
        starts = [s for evs in host.values() for _n, s, d, _m in evs if d > 0]
        ends = [s + d for evs in host.values() for _n, s, d, _m in evs]
        if busy:
            starts.append(busy[0][0])
            ends.append(busy[-1][1])
        result = None
        everything = work_cover(host)
        if starts and everything:
            idle = idle_intervals(busy, (min(starts), max(ends)))
            result = {
                "idle_s": _length(idle),
                "under_pack_s": _length(intersect(
                    idle, work_cover(host, ("engine.pack",)))),
                "under_work_s": _length(intersect(idle, everything))}
        _idle_cache[path] = result
    return _idle_cache[path]
