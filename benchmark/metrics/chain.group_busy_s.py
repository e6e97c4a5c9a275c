"""Seconds a job's ``group`` stage thread works (``chain.group``: wall minus
the waits below it on that thread), mean of the traced jobs."""

import spans


def read(run):
    return spans.stage_busy_s(run, "group")
