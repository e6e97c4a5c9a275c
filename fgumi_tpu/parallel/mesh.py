"""Multi-chip execution: consensus kernel sharded over a device mesh.

Families are embarrassingly parallel (exactly like the reference's per-group Process
step, SURVEY.md §5.7), so the natural mesh is:

- ``dp``: the family axis F — data parallel, no communication;
- ``sp``: the read axis R — "sequence parallel" for very deep families: each shard
  reduces its local reads' likelihood contributions, then a single psum over ``sp``
  combines them (the only collective in the hot path, riding ICI).

This module provides the shard_map-wrapped kernel plus mesh construction helpers
and the production mesh resolution (``FGUMI_TPU_MESH`` / ``--mesh`` / ``--devices``
-> a live jax Mesh, docs/multi-chip.md). The reference has no distributed backend
(single host, SURVEY.md §5.8); this is the TPU-native scale-out design the
reference's thread pool maps to.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.kernel import _call_epilogue, _reduce_contributions

from . import MeshConfigError

#: snapshot of the last production mesh built by resolve_mesh/publish —
#: the run report and flight dumps read it without holding a Mesh reference
LAST_MESH_SNAPSHOT = None

_MESH_RE = re.compile(r"^dp(\d+)(?:xsp(\d+))?$")


def parse_mesh_spec(spec):
    """``FGUMI_TPU_MESH`` / ``--mesh`` value -> ``None`` (off), ``"auto"``,
    or ``(dp, sp)``.

    Accepted forms (loud errors otherwise, same discipline as
    FGUMI_TPU_SHAPE_BUCKETS): empty/``off``/``0`` (mesh disabled, legacy
    single-device path), ``auto`` (dp = all visible devices, sp = 1), or
    ``dpNxspM`` / ``dpN`` (explicit shape; sp defaults to 1).
    """
    if spec is None:
        return None
    s = str(spec).strip().lower()
    if s in ("", "off", "none", "0", "1"):
        return None
    if s == "auto":
        return "auto"
    from ..utils.knobs import knob_error

    grammar = "'auto', 'off', or 'dpNxspM' (e.g. dp4xsp2) with dp, sp >= 1"
    m = _MESH_RE.match(s)
    if not m:
        raise MeshConfigError(knob_error(
            "FGUMI_TPU_MESH", spec, f"unrecognized shape {s!r}", grammar))
    dp = int(m.group(1))
    sp = int(m.group(2)) if m.group(2) else 1
    if dp < 1 or sp < 1:
        raise MeshConfigError(knob_error(
            "FGUMI_TPU_MESH", spec, f"dp={dp} sp={sp} below the >= 1 floor",
            grammar))
    return dp, sp


def resolve_mesh(devices=None, spec=None, sp_default=1):
    """The production (dp, sp) Mesh for this process, or None (single
    device / mesh disabled).

    ``spec`` is a parse_mesh_spec result (or raw string). An explicit
    ``(dp, sp)`` shape is validated against the live device count with a
    loud :class:`MeshConfigError` — a silently smaller mesh would report
    itself as N-way while computing on fewer chips. ``auto`` uses every
    visible device with ``sp_default``. ``None`` disables the mesh.
    """
    if isinstance(spec, str):
        spec = parse_mesh_spec(spec)
    if spec is None:
        return None
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if spec == "auto":
        if n <= 1:
            return None
        sp = sp_default if n % max(sp_default, 1) == 0 else 1
        return make_mesh(devices, sp=sp)
    dp, sp = spec
    if dp * sp > n:
        raise MeshConfigError(
            f"FGUMI_TPU_MESH=dp{dp}xsp{sp} needs {dp * sp} devices but only "
            f"{n} are visible (XLA_FLAGS=--xla_force_host_platform_device_"
            f"count=N forces virtual CPU devices)")
    if dp * sp == 1:
        return None
    return make_mesh(devices[:dp * sp], dp=dp, sp=sp)


def mesh_snapshot(mesh) -> dict:
    """Machine-readable mesh description for reports / artifacts."""
    dp = int(mesh.shape.get("dp", mesh.size))
    sp = int(mesh.shape.get("sp", 1))
    devs = list(mesh.devices.flat)
    return {"dp": dp, "sp": sp, "devices": len(devs),
            "platform": getattr(devs[0], "platform", "unknown")}


def publish_mesh(mesh) -> dict:
    """Record the active production mesh: ``device.mesh.{dp,sp,devices}``
    gauges, a flight-ring note, and the module snapshot the run report
    attaches to its ``device`` section. Returns the snapshot."""
    global LAST_MESH_SNAPSHOT
    snap = mesh_snapshot(mesh)
    LAST_MESH_SNAPSHOT = snap
    from ..observe.flight import FLIGHT
    from ..observe.metrics import METRICS

    METRICS.set("device.mesh.dp", snap["dp"])
    METRICS.set("device.mesh.sp", snap["sp"])
    METRICS.set("device.mesh.devices", snap["devices"])
    FLIGHT.note("device.mesh", **snap)
    return snap


def make_mesh(devices=None, dp: int = None, sp: int = 1) -> Mesh:
    """Build a (dp, sp) mesh over the given (default: all) devices."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if dp is None:
        dp = n // sp
    if dp * sp != n:
        raise ValueError(f"dp*sp ({dp}*{sp}) != device count {n}")
    arr = np.array(devices).reshape(dp, sp)
    return Mesh(arr, axis_names=("dp", "sp"))


def sharded_consensus_fn(mesh: Mesh, correct_tab, err_tab, ln_error_pre_umi):
    """Returns a jitted fn(codes, quals) sharded over the mesh.

    codes/quals: (F, R, L) with F divisible by dp and R divisible by sp.
    Outputs are (F, L) arrays sharded along dp.
    """
    correct_tab = jnp.asarray(correct_tab, dtype=jnp.float32)
    err_tab = jnp.asarray(err_tab, dtype=jnp.float32)
    pre = jnp.float32(ln_error_pre_umi)

    def local(codes, quals):
        contrib, obs = _reduce_contributions(codes, quals, correct_tab, err_tab)
        # Combine partial read-axis reductions across the sp axis — the one
        # collective in the hot path.
        contrib = jax.lax.psum(contrib, "sp")
        obs = jax.lax.psum(obs, "sp")
        return _call_epilogue(contrib, obs, pre)

    mapped = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("dp", "sp", None), P("dp", "sp", None)),
        out_specs=(P("dp"), P("dp"), P("dp"), P("dp"), P("dp")),
    )
    return jax.jit(mapped)


def pad_for_mesh(codes: np.ndarray, quals: np.ndarray, mesh: Mesh):
    """Pad (F, R, L) arrays so F % dp == 0 and R % sp == 0 (pad = N/qual 0)."""
    from ..constants import N_CODE

    dp = mesh.shape["dp"]
    sp = mesh.shape["sp"]
    F, R, L = codes.shape
    Fp = -(-F // dp) * dp
    Rp = -(-R // sp) * sp
    if (Fp, Rp) != (F, R):
        pc = np.full((Fp, Rp, L), N_CODE, dtype=np.uint8)
        pq = np.zeros((Fp, Rp, L), dtype=np.uint8)
        pc[:F, :R] = codes
        pq[:F, :R] = quals
        codes, quals = pc, pq
    return codes, quals, F
