"""Seconds the reader thread works per million input reads: ``work_s`` of the
thread whose root spans are ``pipeline.read`` (the busiest where a chain has
one a stage), mean of the jobs read. Read from the jobs after the profiler's
stop, not the traced ones: the profiler's Python tracer slows pure Python
1.6-2.5x (``threads.py``)."""

import threads


def read(run):
    return threads.work_s_per_mread(run, "reader")
