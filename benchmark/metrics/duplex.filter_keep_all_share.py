"""Share of the X/Y most-common-alignment filter's runs (one an X or Y set of
two reads or more of a molecule on the per-molecule caller) that kept every
read, in percent, over every job of the traced run's window: what a keep
mask over whole arrays could leave on the batch path. Run-report counters
``duplex.filter.sets_kept_all`` / ``duplex.filter.sets``."""


def read(run):
    counted = [r["metrics"] for r in run["reports"]
               if "duplex.filter.sets" in r.get("metrics", {})]
    sets = sum(m["duplex.filter.sets"] for m in counted)
    if not sets:
        return None  # no counters, or the filter never ran
    return 100.0 * sum(m.get("duplex.filter.sets_kept_all", 0)
                       for m in counted) / sets
