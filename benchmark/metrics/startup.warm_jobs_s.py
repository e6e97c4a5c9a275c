"""Seconds the warm jobs before the window took: executable loads or, in a
checkout's first run, compilation (harness clock; inside ``setup_s``)."""


def read(run):
    return run["setup"]["warm_jobs_s"]
