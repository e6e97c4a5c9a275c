"""Seconds a job's ``sort`` stage thread works (``chain.sort``: wall minus
the waits below it on that thread), mean of the traced jobs."""

import spans


def read(run):
    return spans.stage_busy_s(run, "sort")
