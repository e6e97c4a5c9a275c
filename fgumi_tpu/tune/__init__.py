"""Self-tuning deployment profiles (ROADMAP items 4 + 5).

Closes the telemetry loop PR 9 opened: the offline autotuner
(:mod:`.autotune`, surfaced as ``fgumi-tpu tune``) sweeps a simulated
workload matrix across forced device/host routes, records a crossover
atlas, and derives a schema-versioned :mod:`DeploymentProfile <.profile>`
of measured knob values + router priors; the CLI and serve daemon
load it at start (``--profile`` / ``FGUMI_TPU_PROFILE``) so a cold
process's first batch routes on the measured side of every crossover
instead of the static guesses. Profiles only change scheduling — never
the bytes written — so byte-identity holds on every route by construction.
"""

from .profile import (PROFILE_SCHEMA_VERSION, ProfileError,  # noqa: F401
                      fingerprint_host, load_profile, validate_profile,
                      write_profile)
