"""The work one duplex strand combine cannot avoid, whatever implements it,
and the device time the program's combine executable took in a traced run.

Per combined output column (one position of one duplex consensus read): both
strands' consensus base and quality in (4 bytes), the combined base, quality
and error count out (1 + 1 + 2 bytes), and about 20 integer operations for
the agreement / better-base / mask selects. The errors are recounted against
the combined base from every source read of both strands, so every such
observation is read once more (1 byte, base and quality packed as the
consensus kernel's wire has them) and costs a compare and an add:

    operations = 20 * K * L + 2 * O
    bytes      =  8 * K * L + 1 * O

for K combined reads of L positions whose two single-strand segments hold O
source bases. ``roofline.PEAKS`` gives the chip's peaks; on a v5e the bytes
set the bound.
"""

import re

import spans
import tracered
from roofline import peaks

COMBINE_MODULES = re.compile(r"duplex_combine")


def combine_work(rows, length, observations):
    """(operations, bytes) of ``rows`` combined reads of ``length`` positions
    recounted over ``observations`` source bases."""
    return (20 * rows * length + 2 * observations,
            8 * rows * length + observations)


def least_seconds(device_kind, rows, length, observations):
    pk = peaks(device_kind)
    operations, moved = combine_work(rows, length, observations)
    return max(operations / pk["flops_per_s"], moved / pk["bytes_per_s"])


_runs_cache = {}


def combine_runs(run):
    """Device seconds of every execution of the combine executable in the
    traced window (``XLA Modules`` of the device plane); ``None`` with no
    device plane or no xplane, ``[]`` where it never ran."""
    if run["device"]["platform"] == "cpu":
        return None
    path = spans.xplane_path(run)
    if path is None:
        return None
    if path not in _runs_cache:
        device, _host = tracered.load(path)
        _runs_cache[path] = sorted(
            d for plane in device.values() for name, _s, d in plane["modules"]
            if COMBINE_MODULES.search(name))
    return _runs_cache[path]

