"""Share of the templates handed to the assigner that took its integer path
(a sub-group's UMIs as 2-bit codes, molecule ids as int64, no Python
string), in percent, over every job of the traced run's window: run-report
counter ``group.templates.coded`` over ``group.templates``. None where the
program has no such counter."""


def read(run):
    coded = templates = 0
    seen = False
    for report in run["reports"]:
        metrics = report.get("metrics", {})
        seen = seen or "group.templates.coded" in metrics
        coded += metrics.get("group.templates.coded", 0)
        templates += metrics.get("group.templates", 0)
    return 100.0 * coded / templates if seen and templates else None
