"""Share of ``simplex``'s input reads that lie in groups prepared one at a
time (see ``simplex.legacy_group_share``), in percent, over every job of the
traced run's window: run-report counters ``simplex.reads.legacy`` /
``simplex.reads``."""


def read(run):
    counted = [r["metrics"] for r in run["reports"]
               if "simplex.reads" in r.get("metrics", {})]
    reads = sum(m["simplex.reads"] for m in counted)
    if not reads:
        return None  # a program from before the counters
    return 100.0 * sum(m.get("simplex.reads.legacy", 0)
                       for m in counted) / reads
