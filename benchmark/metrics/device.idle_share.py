"""Share of the traced window in which no operation ran on the device."""


def read(run):
    trace = run["trace"]
    if not trace["window_s"] or run["device"]["platform"] == "cpu":
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
