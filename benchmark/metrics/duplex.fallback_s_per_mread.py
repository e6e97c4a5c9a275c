"""Seconds the processing thread spends calling molecules one at a time per
million input reads: the wall of ``engine.duplex.fallback`` (one span a
batch round the loop that sends the span's fallback set through the
per-molecule caller: the X/Y most-common-alignment filter, the four
single-strand consensuses and the combine of each molecule) over the traced
jobs' span aggregates. The molecule a batch boundary cuts is not in it."""

import spans


def read(run):
    records = spans.span_records(run, "engine.duplex.fallback")
    if not records:
        return None  # no spans section, or no molecule in a fallback set
    return sum(r["wall_s"] for r in records) / spans.mreads(run)
