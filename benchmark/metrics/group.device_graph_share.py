"""Share of the neighbour graphs (one a position sub-group of two or more
unique UMIs) built on the device route, in percent, over every job of the
traced run's window: run-report counter ``group.graph.device`` over
``.dense_host`` + ``.device`` + ``.sparse_native``."""

ROUTES = ("dense_host", "device", "sparse_native")


def read(run):
    by_route = dict.fromkeys(ROUTES, 0)
    for report in run["reports"]:
        metrics = report.get("metrics", {})
        for route in ROUTES:
            by_route[route] += metrics.get("group.graph." + route, 0)
    total = sum(by_route.values())
    return 100.0 * by_route["device"] / total if total else None
