"""The work one neighbour search of ``group`` cannot avoid, whatever
implements it, and the device time the program's Hamming executable took in a
traced run.

A neighbour search takes ``n`` UMIs against ``m`` (a position sub-group's
unique UMIs against themselves: ``n = m``) of ``L`` bases and says of each of
the ``n x m`` pairs whether it lies within ``edits`` mismatches. Counted from
the **unpadded** sizes the program reports (``group.hamming.rows`` = the sum
of ``n + m``, ``group.hamming.cells`` = the sum of ``n x m``):

    operations = 2 * cells * L        (a compare and an add a base of a pair)
    bytes      = rows * L  +  cells / 8   (a byte a base in; one bit a pair out)

``roofline.PEAKS`` gives the chip's peaks; on a v5e the bytes set the bound
(2 L = 16 operations a pair against an eighth of a byte: 128 operations a
byte, under the ridge of 240). An executable that writes a 16-bit distance a
padded pair moves 16 times the bits a pair and the padding besides, which is
what its share of this roofline shows.
"""

import re

import spans
import tracered
from roofline import peaks

#: the jitted ``dist`` of ``fgumi_tpu/umi/assigners.py`` as the device plane's
#: ``XLA Modules`` line names it (``configs/group-adj.json`` ``kernel_modules``)
HAMMING_MODULES = re.compile(r"^jit_dist\(")


def hamming_work(rows, cells, length):
    """(operations, bytes) of neighbour searches over ``rows`` UMIs of
    ``length`` bases in all, ``cells`` pairs in all."""
    return 2 * cells * length, rows * length + cells / 8


def least_seconds(device_kind, rows, cells, length):
    pk = peaks(device_kind)
    operations, moved = hamming_work(rows, cells, length)
    return max(operations / pk["flops_per_s"], moved / pk["bytes_per_s"])


def counted(run, name):
    """Run-report counter ``name`` summed over the traced jobs."""
    return sum(r.get("metrics", {}).get(name, 0)
               for r in spans.traced_reports(run))


_runs_cache = {}


def hamming_runs(run):
    """Device seconds of every execution of the Hamming executable in the
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
            if HAMMING_MODULES.search(name))
    return _runs_cache[path]
