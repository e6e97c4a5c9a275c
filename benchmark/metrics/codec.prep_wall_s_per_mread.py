"""Seconds of wall the processing thread spends in ``codec``'s batch prepare
per million input reads: the wall of ``process.prep``, children included
(the closed forms over a span's groups and the per-molecule ``prepare()`` of
its groups with a CIGAR alike; the molecule a batch boundary cuts is prepared
outside it), over the traced jobs' span aggregates. Read only where the
traced jobs are codec's (run-report counter ``codec.molecules``)."""

import spans


def read(run):
    if not any("codec.molecules" in r.get("metrics", {})
               for r in spans.traced_reports(run)):
        return None
    records = spans.span_records(run, "process.prep")
    if not records:
        return None  # no spans section
    return sum(r["wall_s"] for r in records) / spans.mreads(run)
