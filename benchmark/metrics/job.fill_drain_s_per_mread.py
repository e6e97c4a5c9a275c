"""Seconds the command thread waits at its pipeline's two ends, per million
input reads: the process record's ``fill_s`` (the first item, as the reader
makes it) plus ``drain_s`` (the last item's round trip through the workers
and the writer, to the stage threads' join), mean of the jobs read after the
profiler's stop, not the traced ones: the profiler's Python tracer stretches
what it watches (``jobs.py``). The fused chain runs no pipeline on its
command thread and reads 0."""

import jobs


def read(run):
    return jobs.phase_s_per_mread(run, ("fill_s", "drain_s"))
