"""Share of the processing thread's busy seconds that no span names: the
``pipeline.process`` pulls' self time (what runs between the layer spans)
and whatever of ``stages.process.busy_s`` lies outside the pulls and the
waits on the output queue. Traced jobs' reports; one ``run_stages`` a job."""

import spans


def read(run):
    pulls = spans.span_sum(run, ("pipeline.process",), "wall_s")
    if pulls is None:
        return None
    named = pulls - spans.span_sum(run, ("pipeline.process",), "self_s") \
        + spans.span_sum(run, ("pipeline.wait_out",), "wall_s")
    busy = sum(r["stages"]["process"]["busy_s"]
               for r in spans.traced_reports(run)
               if "process" in r.get("stages", {}))
    if not busy:
        return None
    return 100.0 * max(1.0 - named / busy, 0.0)
