"""Share of the distance cells the device computed and sent back that no
pair of real UMIs asked for, in percent: 1 - ``group.hamming.cells`` /
``group.hamming.cells_padded`` (both sides of a search padded to a power of
two), over every job of the traced run's window (run-report counters)."""


def read(run):
    real = padded = 0
    for report in run["reports"]:
        metrics = report.get("metrics", {})
        real += metrics.get("group.hamming.cells", 0)
        padded += metrics.get("group.hamming.cells_padded", 0)
    return 100.0 * (1.0 - real / padded) if padded else None
