"""Seconds of the process's first job before its pipeline's first item is
waited for: ``before_arm_s + open_s`` of the first entry of the process
record (the first warm job: the lazy imports, ``startup.pallas_import``, the
callers' constructors), what a one-job CLI user pays before the pipeline."""

import spans


def read(run):
    proc = spans.process_record(run)
    if proc is None or not proc.get("jobs"):
        return None
    first = proc["jobs"][0]
    return first["before_arm_s"] + first["open_s"]
