"""What the mesh readers share: the ``mesh.*`` counters the program folds
into the ``engine.pack`` span's record (one update a mesh dispatch), summed
over every job of the traced run's window. A program without the counters
(from before them, or a job that never dispatched on a mesh) gives ``None``.
"""

#: the counters a reader reads; ``mesh.dispatches``, ``mesh.families`` and
#: ``mesh.psums`` stay in the report for whoever diagnoses a run
READ = ("mesh.rows", "mesh.rows_padded", "mesh.shard_rows_max")


def counters(run):
    packs = [r["spans"]["by_name"].get("engine.pack", {})
             for r in run["reports"] if "spans" in r]
    packs = [p for p in packs if "mesh.rows" in p]
    if not packs:
        return None
    return {key: sum(p[key] for p in packs) for key in READ}


def dp(run):
    """The mesh's family axis as the jobs' reports state it."""
    for report in run["reports"]:
        mesh = report.get("device", {}).get("mesh")
        if mesh:
            return mesh["dp"]
    return None
