"""Median wait of a resolver for its dispatch (rest of the compute plus the
download), from the DeviceStats timeline of the traced jobs."""

import statistics


def read(run):
    waits = [e["fetch_wait_s"] for tl in run["timeline"][:run["traced_jobs"]]
             for e in tl if "fetch_wait_s" in e]
    return statistics.median(waits) * 1e3 if waits else None
