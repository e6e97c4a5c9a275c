"""Median device time of one execution of the Hamming executable (one
neighbour search of ``group``), from the profiler trace's device plane."""

import statistics

import roofline_hamming


def read(run):
    runs = roofline_hamming.hamming_runs(run)
    return statistics.median(runs) * 1e3 if runs else None
