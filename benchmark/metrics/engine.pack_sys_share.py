"""System CPU seconds of the processing thread inside ``engine.pack`` over
the span's wall, in percent (traced jobs' span aggregates)."""

import spans


def read(run):
    wall = spans.span_sum(run, ("engine.pack",), "wall_s")
    if not wall:
        return None
    return 100.0 * spans.span_sum(run, ("engine.pack",), "stime_s") / wall
