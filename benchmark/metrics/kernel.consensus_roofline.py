"""Least time the chip could take for the traced dispatches' work over the
time the consensus executable took, in percent. Work is counted from the
unpadded rows the program reports uploading in the traced jobs, the read
length, and the molecules those rows hold at the input's mean reads per
consensus read (``roofline.consensus_work``); memory bounds it on a v5e."""

import roofline


def read(run):
    runs = run["trace"]["kernel_runs_s"]
    if not runs or run["device"]["platform"] == "cpu":
        return None
    rows = sum(s.get("pad_rows_real", 0)
               for s in run["stats"][:run["traced_jobs"]])
    if not rows:
        return None
    least, _bound = roofline.least_seconds(
        run["device"]["kind"], rows, run["params"]["read_length"],
        rows * run["consensus_reads_per_row"])
    return 100.0 * least / sum(runs)
