"""Seconds the busier resolve worker works per million input reads: the
largest ``work_s`` among a job's threads whose root spans are
``pipeline.resolve`` (``resolve.wait`` and the wait for the fetch thread are
declared waits and no part of it), mean of the jobs read. Read from the jobs
after the profiler's stop, not the traced ones: the profiler's Python tracer
slows pure Python 1.6-2.5x (``threads.py``)."""

import threads


def read(run):
    return threads.work_s_per_mread(run, "worker")
