"""DeploymentProfile: schema-versioned tuned knobs + measured priors.

A profile is one JSON document produced by ``fgumi-tpu tune`` (or its
``--replay`` mode) and loaded at CLI/daemon start via ``--profile`` /
``FGUMI_TPU_PROFILE``. It carries three sections:

- ``fingerprint`` — the hardware the values were measured on (platform,
  visible cores, RAM, and the JAX backend + device count when JAX was
  live at tune time). A mismatch at load is LOUD (one warning naming
  every differing field, counted in ``tune.profile.fingerprint_mismatch``)
  but not fatal: a profile from a same-generation sibling host is still a
  far better prior than the static guesses.
- ``knobs`` — tuned values for the env-var surface
  (:data:`KNOB_ENV`). Precedence is strict and per knob: an explicit env
  var or CLI flag always wins; the profile fills only unset knobs; code
  defaults remain the floor. Applied once per process (daemon jobs
  re-enter the CLI in fresh contexts and must not re-apply).
- ``priors`` — measured starting points for the adaptive machinery:
  the :class:`~fgumi_tpu.ops.router.OffloadRouter` EWMAs (link rate,
  per-dispatch overhead, dispatch wall, host cells/s, fused-filter
  keep rate, per-mesh-size overrides). A ``choosers`` section from before
  PR 42 is ignored with one log line. Seeding is cold-only — live
  measurements always win — and stamps ``prior_source="profile"`` into
  the router snapshot so first-batch routing is attributable in any run
  report.

Schema history:

- v1: initial layout (schema_version, tool, created_unix, source,
  fingerprint, knobs, priors).

Parse/validation failures raise :class:`ProfileError` with the shared
knob-diagnostic grammar (utils/knobs.py); the CLI maps it to exit 2 like
every other knob parse error.
"""

import json
import os
import threading

from ..utils.knobs import knob_error

PROFILE_SCHEMA_VERSION = 1

#: profile knob name -> the env var it fills (when that var is unset)
KNOB_ENV = {
    "feeder_depth": "FGUMI_TPU_FEEDER_DEPTH",
    "feeder_bytes": "FGUMI_TPU_FEEDER_BYTES",
    "shape_buckets": "FGUMI_TPU_SHAPE_BUCKETS",
    "chain_bytes": "FGUMI_TPU_CHAIN_BYTES",
    "coalesce_window_ms": "FGUMI_TPU_COALESCE_WINDOW_MS",
    "mesh": "FGUMI_TPU_MESH",
}

_ROUTER_PRIOR_KEYS = ("link_mbps", "overhead_s", "dispatch_wall_s",
                      "host_mcells_per_s", "filter_keep_rate")


class ProfileError(ValueError):
    """A profile failed to parse or validate (CLI: exit 2)."""


# ---------------------------------------------------------------- schema


def _err(path, token, problem, grammar):
    return ProfileError(knob_error(f"profile:{path}", token, problem,
                                   grammar))


def _check_number(path, v, lo=None, hi=None, integer=False):
    kind = "an integer" if integer else "a number"
    bounds = ""
    if lo is not None:
        bounds += f" >= {lo}"
    if hi is not None:
        bounds += f" <= {hi}"
    grammar = kind + bounds
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise _err(path, v, "wrong type", grammar)
    if integer and not isinstance(v, int):
        raise _err(path, v, "not an integer", grammar)
    if lo is not None and v < lo:
        raise _err(path, v, f"below the {lo} floor", grammar)
    if hi is not None and v > hi:
        raise _err(path, v, f"above the {hi} ceiling", grammar)


def _validate_knobs(knobs):
    if not isinstance(knobs, dict):
        raise _err("knobs", knobs, "wrong type", "an object")
    for k in knobs:
        if k not in KNOB_ENV:
            raise _err("knobs", k, "unknown knob",
                       "one of " + ", ".join(sorted(KNOB_ENV)))
    if "feeder_depth" in knobs:
        _check_number("knobs.feeder_depth", knobs["feeder_depth"],
                      lo=2, hi=64, integer=True)
    if "feeder_bytes" in knobs:
        _check_number("knobs.feeder_bytes", knobs["feeder_bytes"],
                      lo=1 << 20, integer=True)
    if "chain_bytes" in knobs:
        _check_number("knobs.chain_bytes", knobs["chain_bytes"],
                      lo=1 << 16, integer=True)
    if "coalesce_window_ms" in knobs:
        _check_number("knobs.coalesce_window_ms",
                      knobs["coalesce_window_ms"], lo=0.0, hi=1000.0)
    if "shape_buckets" in knobs:
        from ..ops.datapath import parse_shape_buckets

        try:
            parse_shape_buckets(knobs["shape_buckets"])
        except ValueError as e:
            raise ProfileError(f"profile:knobs.shape_buckets: {e}") \
                from None
    if "mesh" in knobs:
        from ..parallel.mesh import MeshConfigError, parse_mesh_spec

        try:
            parse_mesh_spec(knobs["mesh"])
        except MeshConfigError as e:
            raise ProfileError(f"profile:knobs.mesh: {e}") from None


def _validate_priors(priors):
    if not isinstance(priors, dict):
        raise _err("priors", priors, "wrong type", "an object")
    router = priors.get("router", {})
    if not isinstance(router, dict):
        raise _err("priors.router", router, "wrong type", "an object")
    for k in _ROUTER_PRIOR_KEYS:
        if router.get(k) is not None:
            hi = 1.0 if k == "filter_keep_rate" else None
            lo = 0.0 if k in ("overhead_s", "dispatch_wall_s",
                              "filter_keep_rate") else 1e-9
            _check_number(f"priors.router.{k}", router[k], lo=lo, hi=hi)
    mesh = router.get("mesh", {})
    if not isinstance(mesh, dict):
        raise _err("priors.router.mesh", mesh, "wrong type",
                   "an object keyed by device count")
    for n, mp in mesh.items():
        if not str(n).isdigit() or int(n) < 2:
            raise _err("priors.router.mesh", n, "bad device count",
                       "integer keys >= 2")
        if not isinstance(mp, dict):
            raise _err(f"priors.router.mesh.{n}", mp, "wrong type",
                       "an object")
        for k in ("link_mbps", "overhead_s", "dispatch_wall_s"):
            if mp.get(k) is not None:
                _check_number(f"priors.router.mesh.{n}.{k}", mp[k], lo=0.0)
    crossover = priors.get("crossover", [])
    if not isinstance(crossover, list):
        raise _err("priors.crossover", crossover, "wrong type",
                   "a list of atlas cells")


def validate_profile(profile):
    """Structural validation; raises :class:`ProfileError` on the first
    problem (one consistent diagnostic naming token + grammar)."""
    if not isinstance(profile, dict):
        raise _err("", profile, "wrong type", "a JSON object")
    sv = profile.get("schema_version")
    if not isinstance(sv, int) or sv < 1:
        raise _err("schema_version", sv, "missing or malformed",
                   f"an integer >= 1 (current {PROFILE_SCHEMA_VERSION})")
    if sv > PROFILE_SCHEMA_VERSION:
        raise _err("schema_version", sv, "from a newer fgumi-tpu",
                   f"<= {PROFILE_SCHEMA_VERSION}")
    fp = profile.get("fingerprint")
    if not isinstance(fp, dict):
        raise _err("fingerprint", fp, "missing or malformed", "an object")
    src = profile.get("source")
    if src not in ("autotune", "replay", "manual"):
        raise _err("source", src, "unknown source",
                   "'autotune', 'replay', or 'manual'")
    _validate_knobs(profile.get("knobs", {}))
    _validate_priors(profile.get("priors", {}))
    return profile


# --------------------------------------------------------- fingerprinting


def fingerprint_host(probe_jax=False):
    """The identity of THIS host, for stamping into / comparing against a
    profile. Cheap fields always; the JAX backend + device count only when
    JAX is already imported (or ``probe_jax`` forces the import — the tune
    verb does, an ordinary ``--profile`` load must not pay backend init
    for a host-only command)."""
    import platform
    import sys

    fp = {
        "platform": platform.system().lower(),
        "machine": platform.machine(),
        "cpu_count": os.cpu_count(),
    }
    try:
        fp["ram_bytes"] = (os.sysconf("SC_PAGE_SIZE")
                           * os.sysconf("SC_PHYS_PAGES"))
    except (ValueError, OSError, AttributeError):
        fp["ram_bytes"] = None
    jax = sys.modules.get("jax")
    if jax is None and probe_jax:
        import jax
    if jax is not None:
        try:
            fp["jax_backend"] = jax.default_backend()
            fp["device_count"] = jax.device_count()
        except Exception:  # backend init failure: fingerprint stays cheap
            pass
    return fp


def fingerprint_mismatches(profile_fp, host_fp):
    """Fields present in BOTH fingerprints that disagree. RAM compares at
    quarter-granularity (two otherwise-identical hosts rarely report the
    same byte count)."""
    diffs = []
    for k in sorted(set(profile_fp) & set(host_fp)):
        a, b = profile_fp[k], host_fp[k]
        if a is None or b is None:
            continue
        if k == "ram_bytes":
            if abs(a - b) > max(a, b) / 4:
                diffs.append((k, a, b))
        elif a != b:
            diffs.append((k, a, b))
    return diffs


# --------------------------------------------------------------- load/save


def write_profile(path, profile):
    """Validate + atomically write (crash-safe like every other output)."""
    from ..utils.atomic import discard_output, open_output

    validate_profile(profile)
    out = open_output(path, "w")
    try:
        json.dump(profile, out, indent=2, sort_keys=True)
        out.write("\n")
        out.close()
    except BaseException:
        discard_output(out)
        raise
    return path


def load_profile(path):
    """Parse + validate one profile file; :class:`ProfileError` on any
    problem (missing file, bad JSON, schema violation)."""
    try:
        with open(path) as f:
            raw = f.read()
    except OSError as e:
        raise ProfileError(knob_error("FGUMI_TPU_PROFILE", path,
                                      f"unreadable ({e.strerror})",
                                      "a readable profile JSON path")) \
            from None
    try:
        profile = json.loads(raw)
    except ValueError as e:
        raise ProfileError(knob_error("FGUMI_TPU_PROFILE", path,
                                      f"not valid JSON ({e})",
                                      "a fgumi-tpu tune profile document")) \
            from None
    return validate_profile(profile)


# ------------------------------------------------------------ application

_lock = threading.Lock()
#: the one applied-profile record for this process (None until a profile
#: loads). Daemon jobs re-enter cli.main at depth 0 in fresh contexts;
#: the guard keeps application (env mutation, seeding, the mismatch
#: warning) a process-once event while stamp_metrics() re-stamps the
#: outcome into every invocation's scoped registry.
_APPLIED = None


def applied_info():
    return _APPLIED


def reset_applied_for_tests():
    global _APPLIED
    with _lock:
        _APPLIED = None


def apply_profile(profile, path="<inline>"):
    """Apply a validated profile to this process, once.

    Env knobs: filled only when the env var is unset (explicit env/flags
    win — CLI flags act later and override the env either way). Router
    priors: seeded cold-only. A ``priors.choosers`` section (written
    before the device strand combines went) is ignored with one log line.
    Returns the application record (also stored for
    :func:`stamp_metrics`)."""
    global _APPLIED
    import logging

    log = logging.getLogger("fgumi_tpu")
    with _lock:
        if _APPLIED is not None:
            return _APPLIED
        record = {"path": path, "applied": [], "skipped_explicit": [],
                  "fingerprint_mismatch": [], "seeded_router": False}
        host_fp = fingerprint_host()
        diffs = fingerprint_mismatches(profile.get("fingerprint", {}),
                                       host_fp)
        if diffs:
            record["fingerprint_mismatch"] = [
                {"field": k, "profile": a, "host": b} for k, a, b in diffs]
            log.warning(
                "profile %s was tuned on DIFFERENT hardware (%s); loading "
                "anyway — measured priors from a mismatched host can "
                "misroute until live EWMAs converge", path,
                ", ".join(f"{k}: profile={a!r} host={b!r}"
                          for k, a, b in diffs))
        for knob, value in sorted((profile.get("knobs") or {}).items()):
            env = KNOB_ENV[knob]
            if value is None:
                continue
            if os.environ.get(env) is not None:
                record["skipped_explicit"].append(knob)
            else:
                os.environ[env] = str(value)
                record["applied"].append(knob)
        priors = profile.get("priors") or {}
        from ..ops import router as _router

        if _router.ROUTER.seed_priors(priors.get("router") or {},
                                      source="profile"):
            record["seeded_router"] = True
        if "choosers" in priors:
            log.info("profile %s: priors.choosers ignored (the strand "
                     "combines run on the host)", path)
        _APPLIED = record
    log.info("profile %s: %d knob(s) applied (%s), %d explicit override(s)"
             ", router priors %s", path, len(record["applied"]),
             ",".join(record["applied"]) or "none",
             len(record["skipped_explicit"]),
             "seeded" if record["seeded_router"] else "not seeded")
    stamp_metrics()
    return record


def stamp_metrics():
    """Stamp the process's profile-application outcome into the CURRENT
    metrics registry (tune.* gauges). Called once at application and again
    per scoped invocation so every run report carries the facts even
    though application itself is process-once."""
    if _APPLIED is None:
        return
    from ..observe.metrics import METRICS

    METRICS.set("tune.profile.loaded", 1)
    METRICS.set("tune.profile.knobs_applied", len(_APPLIED["applied"]))
    METRICS.set("tune.profile.knobs_skipped_explicit",
                len(_APPLIED["skipped_explicit"]))
    METRICS.set("tune.profile.fingerprint_mismatch",
                len(_APPLIED["fingerprint_mismatch"]))
    METRICS.set("tune.profile.seeded_router",
                1 if _APPLIED["seeded_router"] else 0)


def maybe_apply_from_env(profile_flag=None):
    """CLI entry: load + apply the profile named by ``--profile`` (wins)
    or ``FGUMI_TPU_PROFILE``. No-op when neither is set or one already
    applied. Raises :class:`ProfileError` (exit 2) on a bad profile."""
    if _APPLIED is not None:
        stamp_metrics()
        return _APPLIED
    path = profile_flag or os.environ.get("FGUMI_TPU_PROFILE") or None
    if not path:
        return None
    return apply_profile(load_profile(path), path=path)
