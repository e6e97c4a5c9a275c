"""Reads over the median wall of the traced jobs: the statistic that may
not be end-to-end (a stall between jobs does not move it)."""

import statistics


def read(run):
    walls = run["walls"][:run["traced_jobs"]]
    if not walls:
        return None
    return run["reads_per_job"] / statistics.median(walls)
