"""Hand-tiled Pallas TPU kernel for the fused wire consensus(+filter) path.

ROADMAP item 3 / ISSUE 19: BENCH_r05 measured ~23 GFLOP/s achieved on a
~200 TFLOP/s chip because the XLA lowering of the wire kernels widens the
1-byte packed observations to f32 one-hots in HBM and round-trips HBM
between the segment reduction, the posterior-Q epilogue, and the PR 11
filter mask. This module re-expresses the same computation as ONE Pallas
kernel that keeps every intermediate in VMEM:

    grid (S_tiles, W) — segment-tile-major, windowed over row tiles

    ┌ wire (R_TILE, L) u8 block ──────────────┐   VMEM, one DMA per
    │ qidx=b>>2, code=b&3, dict select (SMEM) │   in-window row tile
    └──────────────┬──────────────────────────┘
                   │  one-hot matmul  A(S_TILE,R_TILE) @ X(R_TILE,L)
                   ▼  (MXU, precision=HIGHEST — guard-band contract)
    ┌ VMEM scratch: contrib/obs (4,S_TILE,L) f32, poison (S_TILE,L) ┐
    │ accumulated across the w window; epilogue at w == W-1:       │
    │ vote → loser-gap posterior → Phred → suspect guard band      │
    └──────────────┬───────────────────────────────────────────────┘
                   ▼
    winner/qual/depth/errors/suspect (S_TILE, L) i32 output blocks

Windowing: seg_ids are sorted, so the rows of segment tile ``s`` live in
a contiguous row-tile range. The per-tile window base and width ride the
scalar-prefetch channel (SMEM) and the BlockSpec index_map clamps
out-of-window steps to the last in-window block — no DMA is issued for a
revisited block and ``pl.when`` skips the compute, so a skewed ladder
batch pays for the rows it has, not ``S_tiles * n_row_tiles``. ``W`` is
bucketed to powers of two to keep the compile vocabulary bounded
(same philosophy as the shape-bucket ladder feeding it).

Numerics contract (docs/device-datapath.md "Suspect guard band"): the
guard-band derivation in ops/kernel.py holds for summing nonnegative f32
terms in ANY order, so the matmul segment reduction (different order
than XLA's segment_sum) stays inside the band: non-suspect positions are
provably exact in both backends and the backends' CLI bytes agree after
the standard host patching of (possibly different) suspect sets. Q0-class
nonfinite dictionary entries cannot ride the matmul (0 * inf = NaN would
poison the whole segment tile), so they are zeroed per observation and a
poison-count matmul forces ``suspect`` at exactly the (segment, position)
cells XLA's NaN propagation would have flagged.

The small (J, L)-scale epilogues — split-result packing and the PR 11
filter mask + 7-col stats row (``f_emin_tab`` is a 32768-entry table; an
in-kernel one-hot gather of it would blow VMEM) — run as jnp ops inside
the SAME jit around pallas_call: all-integer, bit-exact, and operating on
segment-scale (not row-scale) arrays, so no O(N*L) HBM round-trip is
reintroduced.

Selection: ``FGUMI_TPU_KERNEL=pallas|xla|auto`` (default auto = Pallas on
real TPU backends only; CPU/GPU hosts keep XLA). Forcing ``pallas`` on a
CPU host runs Mosaic interpret mode — the parity-test path; production
CPU runs fall back to XLA so tier-1 latency is unchanged. The XLA kernels
remain the permanent parity oracle. Covered dispatch kinds: the
full-column wire kernel (``segwfp``) and the fused consensus→filter
kernel (``segwxp``) — simplex, duplex, codec and the chain all dispatch
``segwfp`` on one device; mesh, packed2-fallback, and gather dispatches
stay XLA.
"""

import functools
import logging
import os

import numpy as np

from ..constants import MAX_PHRED, MIN_PHRED, N_CODE

log = logging.getLogger("fgumi_tpu")

#: row-tile (matmul contraction dim) and segment-tile (output sublanes)
R_TILE = 128
S_TILE = 8
#: lane width: the kernel's position axis is padded to a multiple of it on
#: the device (a 100-column matmul operand is not lane-aligned)
LANE = 128
#: f32 exponent field — all ones means inf/NaN
_F32_EXP_MASK = 0x7F800000

_IMPORT_OK = None  # cached pallas-import probe
_WARNED = set()    # loud-once keys (bad env value)


# ---------------------------------------------------------------- selection

def kernel_backend() -> str:
    """Parsed ``FGUMI_TPU_KERNEL``: ``"pallas"``, ``"xla"`` or ``"auto"``.

    Invalid values are a LOUD error (logged once per distinct value) and
    fall back to ``auto`` — a typo must never silently pin a production
    fleet to the wrong kernel."""
    v = os.environ.get("FGUMI_TPU_KERNEL", "auto").strip().lower()
    if v in ("", "auto", "default"):
        return "auto"
    if v in ("pallas", "xla"):
        return v
    key = ("badenv", v)
    if key not in _WARNED:
        _WARNED.add(key)
        log.error("FGUMI_TPU_KERNEL=%r: expected pallas, xla or auto; "
                  "using auto", v)
    return "auto"


def available() -> bool:
    """Whether the Pallas lowering can be used in this process.

    ``FGUMI_TPU_PALLAS_UNAVAILABLE=1`` forces False (the test hook that
    simulates a jaxlib built without Mosaic support)."""
    if os.environ.get("FGUMI_TPU_PALLAS_UNAVAILABLE", "").strip().lower() \
            in ("1", "true", "on"):
        return False
    global _IMPORT_OK
    if _IMPORT_OK is None:
        from ..observe.process import startup_span
        from .kernel import _ensure_jax

        # the first jax import of a process goes through _ensure_jax (one
        # thread at a time, cache placed, compiles watched): the first wire
        # dispatch of a CLI run asks this before anything else has
        _ensure_jax()
        try:
            with startup_span("startup.pallas_import"):
                from jax.experimental import pallas as _pl  # noqa: F401
                from jax.experimental.pallas import (  # noqa: F401
                    tpu as _pltpu)

            _IMPORT_OK = True
        except Exception as exc:  # noqa: BLE001 - any import failure
            log.warning("pallas kernels unavailable: %s", exc)
            _IMPORT_OK = False
    return _IMPORT_OK


def interpreted() -> bool:
    """True when Pallas would run in Mosaic interpret mode (no real TPU
    backend) — microbench/report results must carry this flag so CPU CI
    numbers are never mistaken for silicon evidence."""
    from .kernel import _ensure_jax

    jax = _ensure_jax()
    return jax.default_backend() != "tpu"


def selected_backend() -> str:
    """The kernel backend for the next wire dispatch: ``"pallas"`` or
    ``"xla"``.

    - ``xla`` forced: XLA.
    - ``pallas`` forced: Pallas (interpret mode off-TPU — the test
      path). A forced kernel that cannot run ends the run: it raises
      rather than logging and quietly running something else.
    - ``auto``: Pallas only on a real TPU backend; CPU/GPU hosts keep
      the XLA path so production latency never pays interpret mode.
    """
    mode = kernel_backend()
    if mode == "xla":
        return "xla"
    if mode == "pallas":
        if not available():
            raise RuntimeError(
                "FGUMI_TPU_KERNEL=pallas but the Pallas lowering is "
                "unavailable in this jax install")
        return "pallas"
    # auto
    return "pallas" if (available() and not interpreted()) else "xla"


# ------------------------------------------------------------- host prepare

def _bucket_pow2(n: int) -> int:
    v = 1
    while v < n:
        v <<= 1
    return v


class Windows:
    """Per-segment-tile row-tile windows of one Pallas wire dispatch.

    Computed at plan time, on the processing thread, because ``w_tiles``
    is part of what gets compiled: the jitted wrappers are keyed by it, so
    the shape registry has to see it before the dispatch is submitted — a
    batch of a known (rows, L, segments) shape whose window bucket is new
    still compiles."""

    __slots__ = ("base", "cnt", "s_tiles", "w_tiles", "n_rt", "dev")

    def __init__(self, base, cnt, s_tiles, w_tiles, n_rt):
        self.base = base
        self.cnt = cnt
        self.s_tiles = s_tiles
        self.w_tiles = w_tiles
        self.n_rt = n_rt
        self.dev = None  # device handles, set by upload()


def plan_windows(seg_ids: np.ndarray, num_segments: int) -> Windows:
    """seg_ids are sorted, so segment tile s's rows span
    ``searchsorted(s*S_TILE) .. searchsorted((s+1)*S_TILE)``; the widest
    span, bucketed to a power of two, is the grid's window axis."""
    n_rows = len(seg_ids)
    s_tiles = -(-int(num_segments) // S_TILE)
    n_rt = max(-(-n_rows // R_TILE), 1)
    edges = np.arange(s_tiles + 1, dtype=np.int64) * S_TILE
    bounds = np.searchsorted(seg_ids, edges, side="left")
    lo, hi = bounds[:-1], bounds[1:]
    base = (lo // R_TILE).astype(np.int32)
    cnt = np.where(hi > lo, -(-(hi - base.astype(np.int64) * R_TILE)
                              // R_TILE), 0).astype(np.int32)
    base = np.clip(base, 0, n_rt - 1).astype(np.int32)
    w_tiles = min(_bucket_pow2(int(cnt.max()) if len(cnt) else 1) or 1,
                  n_rt)
    return Windows(base, cnt, s_tiles, max(w_tiles, 1), n_rt)


def upload(wire: np.ndarray, seg_ids: np.ndarray, dict32: np.ndarray,
           win: Windows) -> Windows:
    """Row-tile padding + device_put of everything a Pallas wire dispatch
    uploads (called on the feeder thread inside the upload-timing window).

    Pad rows carry a seg id outside every tile's range and WIRE_INVALID
    bytes — double-masked no-ops."""
    from .kernel import _ensure_jax

    jax = _ensure_jax()
    n_rows, L = wire.shape
    n_full = win.n_rt * R_TILE
    if n_full != n_rows:
        from .kernel import WIRE_INVALID

        wire_p = np.full((n_full, L), WIRE_INVALID, dtype=np.uint8)
        wire_p[:n_rows] = wire
        segp = np.full(n_full, win.s_tiles * S_TILE, dtype=np.int32)
        segp[:n_rows] = seg_ids
    else:
        wire_p = wire
        segp = np.ascontiguousarray(seg_ids, dtype=np.int32)
    # (n_rt, 1, R_TILE): the block's last two dims equal the array's, so
    # one row tile of segment ids is a legal Mosaic block (a (1, R_TILE)
    # block of a 2-D (n_rt, R_TILE) array breaks the (8, 128) rule)
    seg3d = segp.reshape(win.n_rt, 1, R_TILE)
    dictbits = np.ascontiguousarray(dict32, dtype=np.float32).view(np.int32)
    win.dev = (jax.device_put(wire_p), jax.device_put(seg3d),
               jax.device_put(win.base), jax.device_put(win.cnt),
               jax.device_put(dictbits))
    return win


# ------------------------------------------------------------ kernel proper

def _consensus_kernel(s_tiles: int, w_tiles: int, last_w: int):
    """The Pallas kernel body factory (closed over static grid dims)."""
    from .kernel import (_EPS32, _LN_4_3_F32, _PHRED_PER_LN,
                         _QUAL_GUARD_FLOOR, _TIE_GUARD_FLOOR, _ensure_jax)

    jax = _ensure_jax()
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    highest = jax.lax.Precision.HIGHEST
    neg_inf = float("-inf")

    def dot(a, b):
        return jax.lax.dot_general(
            a, b, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=highest, preferred_element_type=jnp.float32)

    def kernel(base_ref, cnt_ref, dictbits_ref, prebits_ref, seg_ref,
               wire_ref, win_ref, qual_ref, dep_ref, err_ref, sus_ref,
               contrib_ref, obs_ref, poison_ref):
        s = pl.program_id(0)
        w = pl.program_id(1)

        @pl.when(w == 0)
        def _zero():
            contrib_ref[...] = jnp.zeros_like(contrib_ref)
            obs_ref[...] = jnp.zeros_like(obs_ref)
            poison_ref[...] = jnp.zeros_like(poison_ref)

        @pl.when(w < cnt_ref[s])
        def _accumulate():
            # widen once: Mosaic has no 8-bit vector shifts
            wire = wire_ref[...].astype(jnp.int32)  # (R_TILE, L)
            qidx = wire >> 2
            code = wire & 3
            valid = qidx != 63
            # dictionary select off the SMEM scalar channel: 63 unrolled
            # compare-selects (entry 63 is the invalid sentinel == 0) over
            # the f32 BIT PATTERNS — Mosaic bitcasts vectors, not scalars,
            # so the select runs in int32 and one vector bitcast follows.
            # Nonfinite (Q0-class) entries are zeroed per observation and
            # tracked in `pois` — 0 * inf through the matmul would NaN
            # the whole segment tile, where XLA's segment_sum NaNs only
            # the observation's own segment.
            L = wire.shape[1]
            bits = jnp.zeros((R_TILE, L), jnp.int32)
            pois = jnp.zeros((R_TILE, L), jnp.float32)
            for k in range(63):
                bits_k = dictbits_ref[k]
                fin_k = (bits_k & _F32_EXP_MASK) != _F32_EXP_MASK
                sel = qidx == k
                bits = jnp.where(sel, jnp.where(fin_k, bits_k, 0), bits)
                pois = jnp.where(sel, jnp.where(fin_k, 0.0, 1.0), pois)
            delta = jax.lax.bitcast_convert_type(bits, jnp.float32)
            # local segment one-hot: A[t, r] = [seg[r] == s*S_TILE + t]
            s_local = seg_ref[...] - s * S_TILE  # (1, R_TILE)
            iota_t = jax.lax.broadcasted_iota(jnp.int32,
                                              (S_TILE, R_TILE), 0)
            a = jnp.where(iota_t == s_local, 1.0, 0.0)
            for b in range(4):
                hot = jnp.where((code == b) & valid, 1.0, 0.0)
                contrib_ref[b] += dot(a, delta * hot)
                obs_ref[b] += dot(a, hot)
            poison_ref[...] += dot(a, pois)

        @pl.when(w == last_w)
        def _epilogue():
            c = [contrib_ref[b] for b in range(4)]
            o = [obs_ref[b] for b in range(4)]
            pre = jax.lax.bitcast_convert_type(
                jnp.full(c[0].shape, prebits_ref[0], jnp.int32), jnp.float32)
            depth_f = o[0] + o[1] + o[2] + o[3]
            depth = depth_f.astype(jnp.int32)
            max_c = jnp.maximum(jnp.maximum(c[0], c[1]),
                                jnp.maximum(c[2], c[3]))
            # first-max winner mask (argmax + one_hot twin)
            m = []
            taken = None
            for b in range(4):
                hit = c[b] == max_c
                m.append(hit if taken is None else (hit & ~taken))
                taken = m[b] if taken is None else (taken | m[b])
            winner = (jnp.where(m[1], 1, 0) + jnp.where(m[2], 2, 0)
                      + jnp.where(m[3], 3, 0)).astype(jnp.int32)
            # loser-gap frame (ops/kernel._call_epilogue twin, f32)
            s_sum = jnp.zeros_like(max_c)
            for b in range(4):
                s_sum = s_sum + jnp.where(m[b], 0.0,
                                          jnp.exp(-(max_c - c[b])))
            ln_cons_err = jnp.log(s_sum) - jnp.log1p(s_sum)
            hi = jnp.maximum(pre, ln_cons_err)
            lo = jnp.minimum(pre, ln_cons_err)
            diff = hi - lo
            quick = ~(diff < 6.0)
            safe_diff = jnp.where(quick, 6.0, diff)
            term1 = hi + jnp.log1p(jnp.exp(-safe_diff))
            term2_minus_term1 = (_LN_4_3_F32 + lo
                                 - jnp.log1p(jnp.exp(-safe_diff)))
            full = term1 + jnp.log1p(
                -jnp.exp(jnp.minimum(term2_minus_term1, -_EPS32)))
            ln_final = jnp.where(quick, hi, full)
            phred_f = -ln_final * _PHRED_PER_LN + 0.001
            qual = jnp.clip(jnp.floor(phred_f), MIN_PHRED,
                            MAX_PHRED).astype(jnp.int32)
            # suspect guard band (identical formulas; the band is valid
            # for any nonnegative summation order, so it covers the
            # matmul accumulation too)
            eps_gap = _EPS32 * (depth_f + 2.0) * (1.0 + max_c)
            second = jnp.full_like(max_c, neg_inf)
            for b in range(4):
                second = jnp.maximum(second,
                                     jnp.where(m[b], neg_inf, c[b]))
            margin = max_c - second
            tie_suspect = margin <= (2.0 * eps_gap + _TIE_GUARD_FLOOR)
            took_pre = quick & (ln_cons_err < pre)
            err_phred = jnp.where(took_pre, 0.0,
                                  _PHRED_PER_LN * 2.0 * eps_gap)
            frac = phred_f - jnp.floor(phred_f)
            near_boundary = (jnp.minimum(frac, 1.0 - frac)
                             <= (err_phred + _QUAL_GUARD_FLOOR))
            clamped = ((phred_f <= MIN_PHRED)
                       | (phred_f >= MAX_PHRED + 0.5))
            branch_suspect = jnp.abs(diff - 6.0) <= (2.0 * eps_gap + 1e-4)
            nonfinite = (~jnp.isfinite(max_c)) | (poison_ref[...] > 0.0)
            suspect = (tie_suspect | branch_suspect | nonfinite
                       | (near_boundary & ~clamped))
            no_call = depth == 0
            winner = jnp.where(no_call | tie_suspect, N_CODE, winner)
            qual = jnp.where(no_call | tie_suspect, MIN_PHRED, qual)
            suspect = suspect & ~no_call
            winner_obs = jnp.zeros_like(depth_f)
            for b in range(4):
                winner_obs = winner_obs + jnp.where(m[b], o[b], 0.0)
            errors = depth - jnp.where(winner == N_CODE, 0,
                                       winner_obs.astype(jnp.int32))
            win_ref[...] = winner
            qual_ref[...] = qual
            dep_ref[...] = depth
            err_ref[...] = errors
            sus_ref[...] = jnp.where(suspect, 1, 0)

    return kernel


def _pallas_consensus(wire_p, seg3d, base, cnt, dictbits, prebits,
                      s_tiles: int, w_tiles: int, interpret: bool):
    """pallas_call plumbing: grid/specs/scratch for the windowed kernel.
    Traced inside the jit wrappers below."""
    from .kernel import _ensure_jax

    jax = _ensure_jax()
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .kernel import WIRE_INVALID

    n_rt = seg3d.shape[0]
    L = wire_p.shape[1]
    Lp = -(-L // LANE) * LANE
    if Lp != L:
        # pad columns are invalid observations: depth 0, no-call, sliced
        # off below — padded here, on the device, so the upload stays L
        wire_p = jnp.pad(wire_p, ((0, 0), (0, Lp - L)),
                         constant_values=WIRE_INVALID)
    s_pad = s_tiles * S_TILE

    def _row_tile(s, w, base_ref, cnt_ref):
        wc = jnp.minimum(w, jnp.maximum(cnt_ref[s] - 1, 0))
        return jnp.minimum(base_ref[s] + wc, n_rt - 1)

    out_shape = [jax.ShapeDtypeStruct((s_pad, Lp), jnp.int32)
                 for _ in range(5)]
    out_specs = [pl.BlockSpec((S_TILE, Lp), lambda s, w, *_: (s, 0))
                 for _ in range(5)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_tiles, w_tiles),
        in_specs=[
            pl.BlockSpec((None, 1, R_TILE),           # seg ids
                         lambda s, w, b, c, _db, _pb:
                         (_row_tile(s, w, b, c), 0, 0)),
            pl.BlockSpec((R_TILE, Lp),                # wire
                         lambda s, w, b, c, _db, _pb:
                         (_row_tile(s, w, b, c), 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((4, S_TILE, Lp), jnp.float32),  # contrib
            pltpu.VMEM((4, S_TILE, Lp), jnp.float32),  # obs
            pltpu.VMEM((S_TILE, Lp), jnp.float32),     # poison
        ],
    )
    fn = pl.pallas_call(
        _consensus_kernel(s_tiles, w_tiles, w_tiles - 1),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )
    outs = fn(base, cnt, dictbits, prebits, seg3d, wire_p)
    return [o[:, :L] for o in outs] if Lp != L else outs


# --------------------------------------------------- jitted entry wrappers

def _pack_split(winner, qual, suspect, out_segments: int):
    """jnp twin of ops/kernel._pack_result_split over i32 planes."""
    import jax.numpy as jnp

    qs = (qual | (suspect << 7))[:out_segments]
    w4 = jnp.where(winner > 3, 0, winner)[:out_segments]
    w4 = w4.reshape(out_segments, -1, 4)
    wp = (w4[..., 0] | (w4[..., 1] << 2) | (w4[..., 2] << 4)
          | (w4[..., 3] << 6))
    return qs.astype(jnp.uint8), wp.astype(jnp.uint8)


@functools.lru_cache(maxsize=64)
def _full_jit(out_segments: int, s_tiles: int, w_tiles: int,
              interpret: bool):
    from .kernel import _ensure_jax

    jax = _ensure_jax()
    import jax.numpy as jnp

    def fn(wire_p, seg3d, base, cnt, dictbits, prebits):
        win, qual, dep, err, sus = _pallas_consensus(
            wire_p, seg3d, base, cnt, dictbits, prebits, s_tiles, w_tiles,
            interpret)
        qs, wp = _pack_split(win, qual, sus, out_segments)
        return (qs, wp, dep[:out_segments].astype(jnp.uint16),
                err[:out_segments].astype(jnp.uint16))

    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _filter_jit(out_segments: int, s_tiles: int, w_tiles: int,
                interpret: bool):
    from .kernel import _I16_MAX, _ensure_jax

    jax = _ensure_jax()
    import jax.numpy as jnp

    def fn(wire_p, seg3d, base, cnt, dictbits, prebits, min_reads_c,
           min_qual_c, lens, f_min_reads, f_emin_tab, f_min_base_q,
           f_per_base):
        win, qual, dep, err, sus = _pallas_consensus(
            wire_p, seg3d, base, cnt, dictbits, prebits, s_tiles, w_tiles,
            interpret)
        qs, wp = _pack_split(win, qual, sus, out_segments)
        # filter epilogue — _wire_filter_fn twin over the kernel's
        # (out_segments, L) planes: consensus thresholds, the integer
        # emin-table mask, and the 7-col stats reduction. All-integer →
        # bit-exact vs the XLA kernel; runs at segment scale inside the
        # same jit (the 32768-entry emin gather is why this half stays
        # out of the Pallas body — see the module docstring).
        w = win[:out_segments]
        q = qual[:out_segments]
        d = dep[:out_segments]
        e = err[:out_segments]
        sus_o = sus[:out_segments].astype(jnp.bool_)
        low_depth = d < min_reads_c
        low_qual = q < min_qual_c
        tb = jnp.where(low_depth | low_qual, N_CODE, w)
        tq = jnp.where(low_depth, 0, jnp.where(low_qual, MIN_PHRED, q))
        L = wire_p.shape[1]
        in_len = jnp.arange(L, dtype=jnp.int32)[None, :] < lens[:, None]
        d16 = jnp.minimum(d, _I16_MAX)
        e16 = jnp.minimum(e, _I16_MAX)
        fmask = (f_per_base > 0) & ((d16 < f_min_reads)
                                    | ((d16 > 0) & (e16 >= f_emin_tab[d16])))
        fmask = fmask | ((f_min_base_q >= 0) & (tq < f_min_base_q))
        fmask = fmask & in_len
        fb = jnp.where(fmask, N_CODE, tb)
        fq = jnp.where(fmask, MIN_PHRED, tq)
        z32 = jnp.int32(0)
        stats = jnp.stack([
            jnp.max(jnp.where(in_len, d16, z32), axis=1),
            jnp.sum(jnp.where(in_len, d16, z32), axis=1),
            jnp.sum(jnp.where(in_len, e16, z32), axis=1),
            jnp.sum(jnp.where(in_len, tq, z32), axis=1),
            jnp.sum((in_len & (fb == N_CODE)).astype(jnp.int32), axis=1),
            jnp.sum((fmask & (tb != N_CODE)).astype(jnp.int32), axis=1),
            jnp.any(sus_o & in_len, axis=1).astype(jnp.int32),
        ], axis=1).astype(jnp.int32)
        return (stats, fb.astype(jnp.uint8), fq.astype(jnp.uint8),
                d.astype(jnp.uint16), e.astype(jnp.uint16), qs, wp)

    return jax.jit(fn)


def _prebits(ln_error_pre_umi) -> np.ndarray:
    return np.asarray([np.float32(ln_error_pre_umi)],
                      dtype=np.float32).view(np.int32)


def call_full(prep: Windows, ln_error_pre_umi, out_segments: int):
    """Full-column Pallas dispatch: the _wire_full_fn contract —
    (qs u8, wp u8, depth u16, errors u16), sliced to out_segments."""
    fn = _full_jit(int(out_segments), prep.s_tiles, prep.w_tiles,
                   interpreted())
    return fn(*prep.dev, _prebits(ln_error_pre_umi))


def call_filter(prep: Windows, ln_error_pre_umi, min_reads_c, min_qual_c,
                lens_pad: np.ndarray, fparams, out_segments: int):
    """Fused consensus→filter Pallas dispatch: the
    ``_consensus_segments_wire_filter_jit`` contract —
    (stats i32(J,7), fb, fq, d16, e16, qs, wp)."""
    from .datapath import CONST_CACHE
    from .kernel import _ensure_jax

    jax = _ensure_jax()
    fn = _filter_jit(int(out_segments), prep.s_tiles, prep.w_tiles,
                     interpreted())
    ld = jax.device_put(np.ascontiguousarray(lens_pad, dtype=np.int32))
    etab = CONST_CACHE.put("filter_emin", fparams.emin_tab)
    return fn(*prep.dev, _prebits(ln_error_pre_umi),
              np.int32(min_reads_c), np.int32(min_qual_c), ld,
              fparams.min_reads, etab, fparams.min_base_q,
              np.int32(1 if fparams.per_base else 0))
