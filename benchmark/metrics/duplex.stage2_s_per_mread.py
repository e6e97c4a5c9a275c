"""Self time of duplex's stage 2 on the processing thread per million input
reads: the per-molecule classification loop, the strand combine, the RX
consensus and the record build (``engine.duplex.classify``, ``.combine``,
``.rx`` and ``resolve.serialize``), from the traced jobs' span aggregates."""

import spans

NAMES = ("engine.duplex.classify", "engine.duplex.combine",
         "engine.duplex.rx", "resolve.serialize")


def read(run):
    if not spans.span_records(run, "engine.duplex.classify"):
        return None  # no spans section, or a program without duplex's spans
    return spans.span_sum(run, NAMES, "self_s") / spans.mreads(run)
