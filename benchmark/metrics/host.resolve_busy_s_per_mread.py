"""Seconds the resolve workers spend per million input reads unpacking
device results, serializing consensus records and inside ``device.fetch``
(span walls of the traced jobs)."""

import spans

NAMES = ("resolve.unpack", "resolve.serialize", "device.fetch")


def read(run):
    wall = spans.span_sum(run, NAMES, "wall_s")
    return None if wall is None else wall / spans.mreads(run)
