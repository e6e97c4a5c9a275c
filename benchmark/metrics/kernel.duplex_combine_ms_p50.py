"""Median device time of one execution of the duplex strand-combine
executable, from the profiler trace's device plane."""

import statistics

import roofline_duplex


def read(run):
    runs = roofline_duplex.combine_runs(run)
    return statistics.median(runs) * 1e3 if runs else None
