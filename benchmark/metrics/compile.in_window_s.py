"""Seconds of backend compiles and persistent-cache loads inside the
window's jobs, every one of them (process-level record of the last job's
run report): a shape first sent to the device in the window pays here."""

import spans


def read(run):
    return spans.compile_seconds(run, in_window=True)
