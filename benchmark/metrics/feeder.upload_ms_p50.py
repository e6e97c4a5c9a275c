"""Median wall of a dispatch's ``device_put`` on the feeder thread, from the
traced jobs' span aggregates."""

import spans


def read(run):
    return spans.span_p50_ms(run, "feeder.upload")
