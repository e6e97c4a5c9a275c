"""Of the resolve workers' work seconds, the percent their threads spent in
the kernel (``stime_s / work_s`` over the ``pipeline.resolve`` threads, the
workers and the jobs read summed): page faults on fresh mappings, ``mmap`` /
``munmap`` of an arena's large blocks. Read from the jobs after the
profiler's stop, not the traced ones: the profiler's Python tracer slows pure
Python 1.6-2.5x (``threads.py``). ``None`` on a host whose thread clock does
not tick."""

import threads


def read(run):
    return threads.share_of_work(run, "worker", "stime_s")
