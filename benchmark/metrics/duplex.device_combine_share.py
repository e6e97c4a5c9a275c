"""Share of combined duplex reads whose strand combine ran on the device
(the ``AdaptiveChooser``'s pick, a batch at a time), in percent, over every
job of the traced run's window (run-report counters
``duplex.combine_rows_device`` / ``_host``)."""


def read(run):
    dev = host = 0
    for report in run["reports"]:
        metrics = report.get("metrics", {})
        dev += metrics.get("duplex.combine_rows_device", 0)
        host += metrics.get("duplex.combine_rows_host", 0)
    return 100.0 * dev / (dev + host) if dev + host else None
