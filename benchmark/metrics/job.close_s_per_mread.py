"""Seconds from the pipeline's return to the command's, per million input
reads: the process record's ``close_s`` (the carried group's flush, the
writer's close with the BGZF end, flush, ``fsync``, rename and the
directory's ``fsync``, the rejects and the logs), mean of the jobs read after
the profiler's stop, not the traced ones: the profiler's Python tracer
stretches what it watches (``jobs.py``)."""

import jobs


def read(run):
    return jobs.phase_s_per_mread(run, ("close_s",))
