"""Least time the chip could take for the traced jobs' neighbour searches
over the time the Hamming executable took, in percent. Work
(``roofline_hamming.hamming_work``) is counted from the unpadded UMIs and
pairs the program reports searching on the device in the traced jobs
(``group.hamming.rows``, ``.cells``) and the UMI's length: a byte a base in,
one bit a pair out; memory bounds it on a v5e."""

import roofline_hamming


def read(run):
    runs = roofline_hamming.hamming_runs(run)
    rows = roofline_hamming.counted(run, "group.hamming.rows")
    cells = roofline_hamming.counted(run, "group.hamming.cells")
    if not runs or not cells:
        return None
    least = roofline_hamming.least_seconds(
        run["device"]["kind"], rows, cells, run["params"]["umi_length"])
    return 100.0 * least / sum(runs)
