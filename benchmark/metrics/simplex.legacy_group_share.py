"""Share of ``simplex``'s MI groups that left the whole-array preparation for
the per-group scan (differing CIGARs that need the most-common-alignment
filter, mixed strands over a non-palindromic CIGAR, a downsample), in
percent, over every job of the traced run's window: run-report counters
``simplex.groups.legacy`` / ``simplex.groups``."""


def read(run):
    counted = [r["metrics"] for r in run["reports"]
               if "simplex.groups" in r.get("metrics", {})]
    groups = sum(m["simplex.groups"] for m in counted)
    if not groups:
        return None  # a program from before the counters
    return 100.0 * sum(m.get("simplex.groups.legacy", 0)
                       for m in counted) / groups
