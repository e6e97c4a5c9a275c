"""Share of the device's idle time in the traced window in which no thread
is inside a program span that says what it does: waits, the stage-life
``chain.<stage>`` spans and the ``run_stages`` wrappers do not count."""

import spans


def read(run):
    cut = spans.idle_attribution(run)
    if not cut or not cut["idle_s"]:
        return None
    return 100.0 * (1.0 - cut["under_work_s"] / cut["idle_s"])
