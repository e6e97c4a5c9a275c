"""Share of codec's per-strand most-common-alignment filter runs (one the
R1s or the R2s of a molecule, two reads or more) that kept every read, in
percent, over every job of the traced run's window: what a keep mask over
whole arrays could leave on the batch path. Run-report counters
``codec.filter.sets_kept_all`` / ``codec.filter.sets``."""


def read(run):
    counted = [r["metrics"] for r in run["reports"]
               if "codec.filter.sets" in r.get("metrics", {})]
    sets = sum(m["codec.filter.sets"] for m in counted)
    if not sets:
        return None  # no counters, or the filter never ran
    return 100.0 * sum(m.get("codec.filter.sets_kept_all", 0)
                       for m in counted) / sets
