"""Share of fragment positions whose strand combine ran on the device (the
``AdaptiveChooser``'s pick, a batch at a time), in percent, over every job
of the traced run's window (run-report counters
``codec.combine_cells_device`` / ``_host``)."""


def read(run):
    dev = host = 0
    for report in run["reports"]:
        metrics = report.get("metrics", {})
        dev += metrics.get("codec.combine_cells_device", 0)
        host += metrics.get("codec.combine_cells_host", 0)
    return 100.0 * dev / (dev + host) if dev + host else None
