"""Share of the device's idle time in the traced window that falls inside the
command thread's ``job.open``, ``job.fill``, ``job.drain`` and ``job.close``
spans (xplane: device plane against the host plane's job phases): what of
the idle time the jobs' serial ends hold."""

import jobs


def read(run):
    cut = jobs.idle_job_edges(run)
    if not cut or not cut["idle_s"]:
        return None
    return 100.0 * cut["in_jobs_s"] / cut["idle_s"]
