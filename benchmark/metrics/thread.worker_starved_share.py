"""Share of the resolve workers' root wall spent waiting for the next output
(``queue.worker_get``): low where the workers pace, high where something
ahead of them does. Summed over the threads whose root spans are
``pipeline.resolve`` and the jobs read after the profiler's stop, not the
traced ones: the profiler's Python tracer stretches what it watches
(``threads.py``)."""

import jobs


def read(run):
    return jobs.worker_starved_share(run)
