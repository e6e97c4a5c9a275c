"""Seconds per million input reads the assigning thread waits for a
distance matrix to come back over the device link: the wall of
``device.fetch`` in a job whose neighbour graphs went to the device
(``group.hamming.dispatch`` is there), from the traced jobs' span aggregates.
``group`` fetches nothing else from the device, so every such span lies under
``group.assign.graph``; the aggregate has spans by name, which is why the
entry lists the group cell alone."""

import spans


def read(run):
    if not spans.span_records(run, "group.hamming.dispatch"):
        return None  # no spans section, or no graph went to the device
    return spans.span_sum(run, ("device.fetch",), "wall_s") / spans.mreads(run)
