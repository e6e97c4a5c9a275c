"""Growth of the process's peak resident set per traced job, in GB
(``ru_maxrss`` at job boundaries; a peak only rises)."""


def read(run):
    rss = run["maxrss_kb"][:run["traced_jobs"] + 1]
    if len(rss) < 2:
        return None
    return (rss[-1] - rss[0]) * 1024 / 1e9 / (len(rss) - 1)
