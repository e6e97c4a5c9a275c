"""Seconds a job's ``extract`` stage thread works (``chain.extract``: wall minus
the waits below it on that thread), mean of the traced jobs."""

import spans


def read(run):
    return spans.stage_busy_s(run, "extract")
