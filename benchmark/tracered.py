"""Reduction of a jax profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, the consensus kernel's executions, the top
device operations and the longest idle gaps by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone. Device planes are named
``/device:...``; their ``XLA Ops`` line holds one event per operation run and
``XLA Modules`` one per executable run. A CPU rehearsal has no device plane:
there the operations are the host events that carry an ``hlo_module`` stat,
and nothing it yields is a device number.
"""

import glob
import os
import re
from collections import defaultdict


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Merged, sorted intervals and their total length."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged, sum(e - s for s, e in merged)


def _stat(event, key):
    for name, value in event.stats:
        if name == key:
            return value
    return None


def load(path):
    """Events of one trace as plain tuples: device operations and module
    runs per device plane, and host events per host thread, in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = {}, {}
    planes = list(data.planes)
    on_device = any(p.name.startswith("/device:TPU") for p in planes)
    for plane in planes:
        if plane.name.startswith("/device:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(e.name, e.start_ns * 1e-9,
                                e.duration_ns * 1e-9) for e in line.events]
            if ops or modules:
                device[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                thread = re.sub(r"/-?\d+$", "", line.name) or "thread"
                events = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                           None if on_device else _stat(e, "hlo_module"))
                          for e in line.events]
                host.setdefault(thread, []).extend(events)
    if not device:  # CPU rehearsal: operations run on host threads
        ops = [(name, s, d) for evs in host.values()
               for name, s, d, mod in evs if mod is not None]
        runs = defaultdict(list)
        for evs in host.values():
            for name, s, d, mod in evs:
                if mod is not None:
                    runs[mod].append((s, s + d))
        modules = [(mod, min(s for s, _ in iv), sum(e - s for s, e in iv))
                   for mod, iv in runs.items()]
        device["/host:CPU (rehearsal)"] = {"ops": ops, "modules": modules}
    return device, host


def _short(name):
    """An event name fit for a key: a python frame without its file, a
    module without its fingerprint, an HLO instruction as ``name_shape``."""
    name = re.sub(r"^\$\S+:\d+ ", "", name)
    hlo = re.match(r"%(\S+) = \(?(\w+\[[\d,]*\])", name)
    if hlo:
        name = f"{hlo.group(1)}_{hlo.group(2)}"
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"[^A-Za-z0-9_.\-/]+", "_", name).strip("_")[:80]


def _gap_labels(gaps, host, top=10):
    """For each idle gap, what the host was doing: the longest host event
    that lies inside it (the biggest single thing that happened while the
    device waited), else the shortest event that spans it. Gap seconds are
    summed by that label."""
    by_label = defaultdict(float)
    for g0, g1 in gaps:
        slack = 0.02 * (g1 - g0)
        inside, inside_d = None, 0.0
        around, around_d = "nothing_recorded", float("inf")
        for thread, events in host.items():
            for name, s, d, _mod in events:
                if d <= 0 or s >= g1 or s + d <= g0:
                    continue
                if s >= g0 - slack and s + d <= g1 + slack:
                    if d > inside_d:
                        inside, inside_d = f"{_short(name)}@{thread}", d
                elif s <= g0 and s + d >= g1 and d < around_d:
                    around, around_d = f"{_short(name)}@{thread}", d
        by_label[inside or around] += g1 - g0
    ranked = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return [[name, seconds] for name, seconds in ranked if seconds >= 1e-6]


def reduce_file(path, config, window=None, gaps_labelled=40):
    """The trace as the metrics need it. ``window`` (start, end in trace
    seconds) defaults to the trace's own extent: first to last event of any
    host thread or device."""
    device, host = load(path)
    pattern = re.compile(config.get("kernel_modules", "consensus"))
    busy, spans, kernel_runs = [], [], []
    op_totals = defaultdict(float)
    merged_first = None
    for plane in device.values():
        intervals = [(s, s + d) for _n, s, d in plane["ops"] if d > 0]
        merged, total = _union(intervals)
        busy.append(total)
        if merged:
            spans.append((merged[0][0], merged[-1][1]))
            if merged_first is None:
                merged_first = merged
        for name, _s, d in plane["ops"]:
            op_totals[_short(name)] += d
        kernel_runs += [d for name, _s, d in plane["modules"]
                        if pattern.search(name)]
    if window is None:
        starts = [s for evs in host.values() for _n, s, d, _m in evs if d > 0]
        ends = [s + d for evs in host.values() for _n, s, d, _m in evs]
        starts += [s for s, _ in spans]
        ends += [e for _, e in spans]
        window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    gaps = []
    if merged_first:
        edges = [window[0]] + [x for iv in merged_first for x in iv] \
            + [window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:gaps_labelled]
    ranked = sorted(op_totals.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": window[1] - window[0],
        "kernel_runs_s": sorted(kernel_runs),
        "module_names": sorted({_short(n) for p in device.values()
                                for n, _s, _d in p["modules"]}),
        "planes": sorted(device),
        "breakdown": {"device_ops": [[n, s] for n, s in ranked],
                      "idle_gaps": _gap_labels(gaps, host)}}


def reduce_dir(trace_dir, config):
    return reduce_file(find_xplane(trace_dir), config)


def summarise(path, top=12):
    """Planes, lines and the heaviest event names of a trace, as text: what
    to look at before writing a matcher against it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            totals, count = defaultdict(float), 0
            for e in line.events:
                totals[e.name] += e.duration_ns * 1e-9
                count += 1
            out.append(f"  LINE {line.name}: {count} events")
            for name, sec in sorted(totals.items(),
                                    key=lambda kv: -kv[1])[:top]:
                out.append(f"      {sec:.6f}s  {name[:120]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(summarise(sys.argv[1]))
