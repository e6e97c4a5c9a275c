"""Seconds from the program's first use of jax to a listed device (harness
clock; inside ``setup_s``)."""


def read(run):
    return run["setup"]["backend_s"]
