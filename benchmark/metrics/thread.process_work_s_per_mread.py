"""Seconds the processing thread works per million input reads: ``work_s`` of
the thread whose root spans are ``pipeline.process`` (root wall minus the
waits it declared: ``pipeline.wait_in``, ``pipeline.wait_out``), mean of the
jobs read. Read from the jobs after the profiler's stop, not the traced ones:
the profiler's Python tracer slows pure Python 1.6-2.5x (``threads.py``). The
largest of this, the worker's and the reader's is the cell's ceiling:
``reads_per_s`` cannot exceed ``1e6 /`` it."""

import threads


def read(run):
    return threads.work_s_per_mread(run, "process")
