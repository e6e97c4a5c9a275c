"""Self time of the assigner's host work round the neighbour search per
million input reads: the unique UMIs and their byte matrix
(``group.assign.umis``), the compare of the distances with ``edits`` and the
neighbour lists (``group.assign.threshold``), the directed BFS
(``group.assign.bfs``) and the ids (``group.assign.ids``), from the traced
jobs' span aggregates."""

import spans

NAMES = ("group.assign.umis", "group.assign.threshold", "group.assign.bfs",
         "group.assign.ids")


def read(run):
    if not spans.span_records(run, "group.assign.threshold"):
        return None  # no spans section, or a program without these spans
    return spans.span_sum(run, NAMES, "self_s") / spans.mreads(run)
