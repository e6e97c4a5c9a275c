"""Of the processing thread's work seconds, the percent it was on no CPU
having declared no wait (``offcpu_s / work_s`` of the ``pipeline.process``
thread, summed over the jobs read): waiting for the interpreter lock, or
blocked in the kernel. Read from the jobs after the profiler's stop, not the
traced ones: the profiler's Python tracer slows pure Python 1.6-2.5x and
changes who holds the lock (``threads.py``). ``None`` on a host whose thread
clock does not tick."""

import threads


def read(run):
    return threads.share_of_work(run, "process", "offcpu_s")
