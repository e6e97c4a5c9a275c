"""Seconds a job spends before its pipeline's first item is waited for, per
million input reads: the process record's ``before_arm_s`` (``cli.main``'s
entry, the parser, the telemetry set-up) plus ``open_s`` (the command's
set-up: the mesh, the reader's open and header, the callers, the writer's
open), mean of the jobs read. Read from the jobs after the profiler's stop,
not the traced ones: the profiler's Python tracer stretches what it watches
(``jobs.py``). Serial work of the command thread, outside every stage."""

import jobs


def read(run):
    return jobs.phase_s_per_mread(run, ("before_arm_s", "open_s"))
