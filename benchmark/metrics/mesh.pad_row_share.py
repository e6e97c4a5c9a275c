"""Share of the rows uploaded to the mesh that are padding, in percent:
every chunk is padded to the common ladder bucket of the fullest one
(counters ``mesh.rows`` / ``mesh.rows_padded`` on ``engine.pack``, every
job of the traced run's window)."""

import meshpack


def read(run):
    c = meshpack.counters(run)
    if not c or not c["mesh.rows_padded"]:
        return None
    return 100.0 * (1.0 - c["mesh.rows"] / c["mesh.rows_padded"])
