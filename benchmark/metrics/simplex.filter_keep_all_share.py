"""Share of the most-common-alignment filter's runs (one a family's reads of
one type) that kept every read, in percent, over every job of the traced
run's window: what a proof over whole arrays could skip. Run-report counters
``simplex.filter.segments_kept_all`` / ``simplex.filter.segments``."""


def read(run):
    counted = [r["metrics"] for r in run["reports"]
               if "simplex.filter.segments" in r.get("metrics", {})]
    segments = sum(m["simplex.filter.segments"] for m in counted)
    if not segments:
        return None  # no counters, or the filter never ran
    return 100.0 * sum(m.get("simplex.filter.segments_kept_all", 0)
                       for m in counted) / segments
