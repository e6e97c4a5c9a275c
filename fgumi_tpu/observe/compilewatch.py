"""Fold XLA compilation activity into the metrics registry.

The serve daemon's whole value proposition is that the second job on a warm
process *does not compile anything* — but "it felt faster" is not evidence.
jax publishes monitoring events for exactly this: every request that gets
past the in-memory jit cache records
``/jax/core/compile/backend_compile_duration`` when it returns — whether
the executable was compiled or loaded from the persistent cache — and a
persistent-cache load records ``/jax/compilation_cache/cache_hits`` first,
on the same thread. A process-wide listener (installed once, at first jax
use) pairs the two and forwards them into ``METRICS`` under::

    device.backend_compiles      count of real XLA compilations
    device.backend_compile_s     seconds spent in them
    device.compile_cache_hits    executables loaded from the persistent cache
    device.compile_cache_load_s  seconds spent loading them

and keeps one record of each in the process-level record
(``observe/process.py``: kind, seconds, the bucketed shape key of the open
dispatch, seconds since process start), which every run report carries.

so a fresh process that found every executable on disk reports
``backend_compiles == 0`` (the chip smoke's second leg asserts exactly that).

``METRICS`` is the scope-resolving proxy, and the listener fires on the
thread that triggered the compile (the job thread or its context-carrying
device feeder), so in the daemon these counters land in the *owning job's*
registry — ``tools/serve_smoke.py`` and the run reports assert warm-kernel
behaviour from them: job 1 reports ``backend_compiles > 0``, the identical
job 2 reports none.
"""

import threading

_installed = False
#: set by a cache-hit event, consumed by the duration event that closes the
#: same compile request (both fire on the requesting thread)
_tls = threading.local()

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _on_duration(event: str, duration: float, fun_name=None, **_kw):
    if event == _BACKEND_COMPILE_EVENT:
        from . import process
        from .metrics import METRICS

        # shape-bucket attribution: the dispatch machinery flags (via a
        # contextvar that rides the feeder's context copy) dispatches
        # whose bucketed shape is new this process, with the shape's key
        shape = None
        try:
            from ..ops.datapath import compile_shape_key

            shape = compile_shape_key()
        except Exception:  # pragma: no cover - attribution is best-effort
            pass
        if getattr(_tls, "cache_hit", False):
            _tls.cache_hit = False  # a disk load, not a compile
            METRICS.inc("device.compile_cache_load_s", round(duration, 4))
            process.note_compile("cache_load", duration, shape, fun_name)
            return
        METRICS.inc("device.backend_compiles")
        METRICS.inc("device.backend_compile_s", round(duration, 4))
        process.note_compile("compile", duration, shape, fun_name)
        # a real backend compile landing inside a shape-miss dispatch is a
        # shape-ladder recompile, which is what
        # device.shape_bucket.recompiles counts (ops/datapath.py)
        if shape:
            METRICS.inc("device.shape_bucket.recompiles")


def _on_event(event: str, **_kw):
    if event == _CACHE_HIT_EVENT:
        _tls.cache_hit = True
        from .metrics import METRICS

        METRICS.inc("device.compile_cache_hits")


def install() -> bool:
    """Register the jax monitoring listeners (idempotent).

    Called from ``ops.kernel._ensure_jax`` so any code path that can compile
    has the watch in place first. Returns True when listening."""
    global _installed
    if _installed:
        return True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _installed = True
    return True
