"""Share of molecules that went down the per-molecule caller (the one a
batch boundary cuts, and whatever the vectorized engine cannot express), in
percent, over every job of the traced run's window (run-report counters
``duplex.slow_molecules`` / ``duplex.molecules``)."""


def read(run):
    counted = [r["metrics"] for r in run["reports"]
               if "duplex.molecules" in r.get("metrics", {})]
    molecules = sum(m["duplex.molecules"] for m in counted)
    if not molecules:
        return None
    return 100.0 * sum(m.get("duplex.slow_molecules", 0)
                       for m in counted) / molecules
