"""How much more the fullest dp shard holds than an even split would give
it, in percent: the shards run side by side, so the fullest one sets a
dispatch's device time (counters ``mesh.shard_rows_max``, summed over the
dispatches, and ``mesh.rows`` on ``engine.pack``; dp from the report's
``device.mesh``; every job of the traced run's window)."""

import meshpack


def read(run):
    c = meshpack.counters(run)
    dp = meshpack.dp(run)
    if not c or not c["mesh.rows"] or not dp:
        return None
    return 100.0 * (dp * c["mesh.shard_rows_max"] / c["mesh.rows"] - 1.0)
