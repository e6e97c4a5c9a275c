"""Share of consensus batches the router sent to the device, over
every job of the traced run's window."""


def read(run):
    dev = sum(s.get("route_device", 0) for s in run["stats"])
    host = sum(s.get("route_host", 0) for s in run["stats"])
    return 100.0 * dev / (dev + host) if dev + host else None
