"""Seconds ``group`` spends assigning molecules per million input reads:
the wall of ``group.assign`` (UMI strings to molecule ids, a position group
at a time: uniques, the neighbour graph with its device round trip, the
compare with ``edits``, the BFS, the ids) less the waits declared below it,
from the traced jobs' span aggregates."""

import spans


def read(run):
    records = spans.span_records(run, "group.assign")
    if not records:
        return None  # no spans section, or a command that assigns nothing
    return sum(r["wall_s"] - r["wait_s"] for r in records) / spans.mreads(run)
