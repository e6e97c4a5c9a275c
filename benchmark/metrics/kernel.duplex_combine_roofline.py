"""Least time the chip could take for the traced jobs' device combines over
the time the combine executable took, in percent. Work
(``roofline_duplex.combine_work``) is counted from the combined reads the
program reports combining on the device in the traced jobs, the read length,
and the source bases of their two single-strand segments at the input's mean
reads per segment; memory bounds it on a v5e."""

import roofline_duplex
import spans


def read(run):
    runs = roofline_duplex.combine_runs(run)
    rows = sum(r.get("metrics", {}).get("duplex.combine_rows_device", 0)
               for r in spans.traced_reports(run))
    if not runs or not rows:
        return None
    length = run["params"]["read_length"]
    observations = 2 * rows * length / run["consensus_reads_per_row"]
    least = roofline_duplex.least_seconds(run["device"]["kind"], rows, length,
                                          observations)
    return 100.0 * least / sum(runs)
