"""Growth a job of the bytes glibc's arenas hold free (``mallinfo2().fordblks``,
every arena summed), in GB: memory the process keeps mapped and no object
uses, from the end of the first job read to the end of the last, over the jobs
between (as ``host.rss_growth_gb_per_job`` reads the resident set). The level
itself is the process's so far and rises with every job a window holds, so a
faster program would read a higher one; the growth a job does not. Read from
the jobs after the profiler's stop (``threads.py``)."""

import threads


def read(run):
    free = threads.alloc_ends(run, "arena_free_bytes")
    if free is None:
        return None
    return (free[-1] - free[0]) / 1e9 / (len(free) - 1)
