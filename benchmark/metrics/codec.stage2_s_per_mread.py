"""Self time of codec's stage 2 per million input reads: the single-read
strands' table pass, the orient-and-pad placement, the strand combine, the
gates and masks, and the record build (``engine.codec.single``, ``.place``,
``.combine``, ``.gates`` and ``resolve.serialize``), from the traced jobs'
span aggregates, on whichever thread ran them."""

import spans

NAMES = ("engine.codec.single", "engine.codec.place", "engine.codec.combine",
         "engine.codec.gates", "resolve.serialize")


def read(run):
    if not spans.span_records(run, "engine.codec.place"):
        return None  # no spans section, or a program without codec's spans
    return spans.span_sum(run, NAMES, "self_s") / spans.mreads(run)
