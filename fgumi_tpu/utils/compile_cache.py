"""Persistent XLA compilation cache across CLI invocations.

The reference is an AOT-compiled Rust binary: its per-invocation startup cost
is process exec only. A JAX-based CLI pays JIT compilation on every fresh
process instead — several seconds across the consensus kernel's size buckets
— which lands on every stage of a best-practice chain
(extract -> group -> simplex -> filter) because each stage is its own
process. The persistent compilation cache makes second and later invocations
load compiled executables from disk, the closest JAX analog of shipping an
AOT binary.

One shared implementation: the CLI enables it up front (so every command's
jits benefit, not just the consensus kernel's), and ConsensusKernel
construction enables it for library users who never go through the CLI.

Where the cache lives (the directory is part of what a deployment pins, so
it is never derived from ``~``, a temporary name, a pid or a time):

  JAX_COMPILATION_CACHE_DIR=..  jax reads it itself; this module sets no
                                directory, only the thresholds below
  unset                         ``<checkout>/.jax_cache`` (git-ignored),
                                or the directory ``serve --compile-cache``
                                names
  FGUMI_TPU_NO_XLA_CACHE=1      no persistent cache at all

Either way the cache-everything thresholds apply: the chain's cost is many
small-to-medium kernels, not one big one, so jax's default entry-size and
compile-time floors would skip exactly the executables worth reusing.
"""

import logging
import os

log = logging.getLogger("fgumi_tpu.compile_cache")

#: ``<checkout>/.jax_cache``: beside the package, inside the tree a
#: deployment copies, so a machine that keeps nothing but the checkout
#: still finds what an earlier process compiled.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_enabled = False
_cache_dir = None


def cache_dir():
    """The directory the persistent cache was enabled with, or None."""
    return _cache_dir


def enable_persistent_cache(path: str = None):
    """Point jax at an on-disk compilation cache (idempotent).

    ``path`` pins an explicit directory (the serve daemon's
    ``--compile-cache DIR``); it is ignored, with a warning, when
    ``JAX_COMPILATION_CACHE_DIR`` is set — the environment owns the
    placement then. Returns the cache dir, or None when disabled.
    """
    global _enabled, _cache_dir
    if _enabled:
        return _cache_dir
    _enabled = True
    if os.environ.get("FGUMI_TPU_NO_XLA_CACHE", "").lower() \
            not in ("", "0", "false"):
        return None
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        if path and os.path.abspath(path) != os.path.abspath(env_dir):
            log.warning("JAX_COMPILATION_CACHE_DIR=%s is set; ignoring the "
                        "requested compile cache directory %s", env_dir,
                        path)
        _cache_dir = env_dir
    else:
        target = path or DEFAULT_CACHE_DIR
        try:
            os.makedirs(target, exist_ok=True)
        except OSError as e:  # read-only checkout: run without reuse
            log.warning("persistent compile cache disabled: cannot create "
                        "%s (%s)", target, e)
            return None
        _cache_dir = target
        jax.config.update("jax_compilation_cache_dir", _cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return _cache_dir
