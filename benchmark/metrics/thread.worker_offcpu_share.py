"""Of the resolve workers' work seconds, the percent they were on no CPU
having declared no wait (``offcpu_s / work_s`` over the ``pipeline.resolve``
threads, the workers and the jobs read summed): waiting for the interpreter
lock, or blocked in the kernel. Read from the jobs after the profiler's stop,
not the traced ones: the profiler's Python tracer slows pure Python 1.6-2.5x
and changes who holds the lock (``threads.py``). ``None`` on a host whose
thread clock does not tick."""

import threads


def read(run):
    return threads.share_of_work(run, "worker", "offcpu_s")
