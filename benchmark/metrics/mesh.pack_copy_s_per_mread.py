"""Seconds per million input reads the pacing thread spends copying a mesh
batch's rows before the wire is built: the caller's dense row copies (self
time of ``engine.pack.gather``) and the chunked dp x sp layout
(``engine.pack.mesh_layout``, ``pad_segments_mesh``), from the traced jobs'
span aggregates. One device's ragged pack makes neither copy."""

import spans

NAMES = ("engine.pack.gather", "engine.pack.mesh_layout")


def read(run):
    if not spans.span_records(run, "engine.pack.mesh_layout"):
        return None  # no spans section, or a program without the mesh's span
    return spans.span_sum(run, NAMES, "self_s") / spans.mreads(run)
