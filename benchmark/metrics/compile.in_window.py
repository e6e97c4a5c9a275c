"""Backend compilations inside the window (every job's run report);
expected 0. Cache loads are not compilations."""


def read(run):
    if not run["reports"]:
        return None
    return sum(r.get("metrics", {}).get("device.backend_compiles", 0)
               for r in run["reports"])
