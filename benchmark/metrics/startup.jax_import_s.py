"""Seconds of the program's first jax import (import, compile-cache
placement, compile watch), from the process-level record that every run
report carries: the Python part of ``startup.backend_s``."""

import spans


def read(run):
    proc = spans.process_record(run)
    if proc is None or "startup.jax_import" not in proc["spans"]:
        return None
    return proc["spans"]["startup.jax_import"]["s"]
