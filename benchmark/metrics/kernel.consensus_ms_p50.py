"""Median device time of one execution of the consensus executable, from
the profiler trace's device plane."""

import statistics


def read(run):
    runs = run["trace"]["kernel_runs_s"]
    if not runs or run["device"]["platform"] == "cpu":
        return None
    return statistics.median(runs) * 1e3
