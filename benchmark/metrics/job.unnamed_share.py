"""Share of a job's wall, less the run report's own making, that no span and
no phase names: the process record's ``wall_s - report_s - before_arm_s``
less the command thread's root wall (its ``job.*`` phases, the pipeline's
``pipeline.*`` wrappers and waits), over the jobs read after the profiler's
stop, not the traced ones: the profiler's Python tracer stretches what it
watches (``jobs.py``). Near 0 where the phases and the pipeline tile the
job; the fused chain's command thread waits for its stage threads in no
span."""

import jobs


def read(run):
    return jobs.unnamed_share(run)
