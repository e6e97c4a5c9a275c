"""Share of single-strand consensus calls made from one read, which resolve
from tables on the host and never reach the device by design, in percent,
over every job of the traced run's window (run-report counters
``codec.single_strands`` / ``codec.strands``)."""


def read(run):
    counted = [r["metrics"] for r in run["reports"]
               if "codec.strands" in r.get("metrics", {})]
    strands = sum(m["codec.strands"] for m in counted)
    if not strands:
        return None
    return 100.0 * sum(m.get("codec.single_strands", 0)
                       for m in counted) / strands
