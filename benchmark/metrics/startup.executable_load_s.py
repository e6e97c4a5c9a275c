"""Seconds of backend compiles and persistent-cache loads that ended before
the first traced job started: what the warm jobs spent getting executables
(process-level record of the run reports)."""

import spans


def read(run):
    return spans.compile_seconds(run, in_window=False)
