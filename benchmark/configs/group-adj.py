"""Plain reference of configuration ``group-adj``: what ``group --strategy
adjacency --edits 1`` must write for an ``amplicon_bam`` input. The
configuration computes no float; ``np.float32`` (``benchmark/control.py``'s
"one precision below") asks for the control, the same reference at zero
mismatches (``reference_group``'s docstring)."""

import numpy as np

import reference_group
import traffic

HEADER = traffic.kind_module("amplicon_bam").HEADER.splitlines()


def expected(data, config, dtype):
    edits = 0 if np.dtype(dtype) == np.float32 \
        else config["assumed"]["group"]["edits"]
    flat, n_records, _counted = reference_group.group(data, edits)
    return {"records": flat, "n_records": n_records, "header": HEADER}
