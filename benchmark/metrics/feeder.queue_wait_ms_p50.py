"""Median wait of a dispatch between ``DeviceFeeder.submit`` and the feeder
thread taking its ticket (queue and depth/byte gate), from the traced jobs'
span aggregates."""

import spans


def read(run):
    return spans.span_p50_ms(run, "feeder.queue_wait")
