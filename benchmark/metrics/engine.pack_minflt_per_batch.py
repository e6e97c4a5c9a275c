"""Minor page faults of the processing thread inside ``engine.pack`` per
packed batch (``getrusage(RUSAGE_THREAD)`` deltas in the traced jobs' span
aggregates): thousands when glibc maps the pack's temporaries anew, near
zero when it serves them from resident heap."""

import spans


def read(run):
    batches = spans.span_sum(run, ("engine.pack",), "count")
    if not batches:
        return None
    return spans.span_sum(run, ("engine.pack",), "minflt") / batches
