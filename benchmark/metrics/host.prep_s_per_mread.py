"""Self time of the processing thread's host stages before routing
(``process.decode``, ``.group``, ``.overlap``, ``.prep``) per million input
reads, from the traced jobs' span aggregates."""

import spans

NAMES = ("process.decode", "process.group", "process.overlap", "process.prep")


def read(run):
    own = spans.span_sum(run, NAMES, "self_s")
    return None if own is None else own / spans.mreads(run)
