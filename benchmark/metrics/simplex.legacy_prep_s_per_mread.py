"""Seconds the processing thread spends preparing groups one at a time per
million input reads: the wall of ``process.prep.legacy`` (one span a batch
round the per-group scan: the decode, simplify, reverse and truncate of each
read's CIGAR and the most-common-alignment filter) over the traced jobs'
span aggregates. ``host.prep_s_per_mread`` sums ``process.prep``'s self time
and so does not hold these seconds."""

import spans


def read(run):
    records = spans.span_records(run, "process.prep.legacy")
    if not records:
        return None  # no spans section, or no group on the per-group path
    return sum(r["wall_s"] for r in records) / spans.mreads(run)
