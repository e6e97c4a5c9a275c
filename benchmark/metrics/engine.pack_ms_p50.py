"""Median host pack time per device dispatch (gather, pad, wire build on
the processing thread), from the DeviceStats timeline of the traced jobs."""

import statistics


def read(run):
    packs = [e["pack_s"] for tl in run["timeline"][:run["traced_jobs"]]
             for e in tl if "pack_s" in e]
    return statistics.median(packs) * 1e3 if packs else None
