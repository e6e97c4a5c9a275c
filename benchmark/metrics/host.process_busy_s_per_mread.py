"""Busy seconds of the ``process`` stage thread (decode, group, pack,
dispatch) per million input reads, from the traced jobs' run reports."""


def read(run):
    busy = [r["stages"]["process"]["busy_s"] for r in run["reports"][:run["traced_jobs"]]
            if "process" in r.get("stages", {})]
    if not busy:
        return None
    return sum(busy) / (len(busy) * run["reads_per_job"] / 1e6)
