"""Share of the device's idle time in the traced window that an
``engine.pack`` annotation of the program covers (xplane: device plane
against the host plane's program spans)."""

import spans


def read(run):
    cut = spans.idle_attribution(run)
    if not cut or not cut["idle_s"]:
        return None
    return 100.0 * cut["under_pack_s"] / cut["idle_s"]
