"""Share of molecules that went down the per-molecule ``prepare()`` (the one
a batch boundary cuts, and whatever the closed forms cannot express), in
percent, over every job of the traced run's window (run-report counters
``codec.slow_molecules`` / ``codec.molecules``)."""


def read(run):
    counted = [r["metrics"] for r in run["reports"]
               if "codec.molecules" in r.get("metrics", {})]
    molecules = sum(m["codec.molecules"] for m in counted)
    if not molecules:
        return None
    return 100.0 * sum(m.get("codec.slow_molecules", 0)
                       for m in counted) / molecules
