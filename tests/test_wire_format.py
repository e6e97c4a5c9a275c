"""Round-5 device wire formats: 1-byte upload dictionary, split packed
output with fetch slicing, refined pad buckets, and hybrid routing.

Parity contract: the wire dispatch path (device_call_segments_wire +
resolve_segments_wire) must reproduce the f64 oracle integer-exactly, same
as resolve_segments (tests/test_kernel_parity.py) — the wire format is a
lossless re-encoding, not an approximation.
"""

import os

import numpy as np
import pytest

from fgumi_tpu.ops import oracle
from fgumi_tpu.ops.kernel import (ConsensusKernel, _pad_out_segments,
                                  _pad_rows, build_wire, pad_segments_gather,
                                  unpack_result_split, DEVICE_STATS,
                                  WIRE_INVALID)
from fgumi_tpu.ops.tables import quality_tables

TABLES = quality_tables(45, 40)


def make_ragged(rng, J, L, max_r=7, err=0.1, n_rate=0.03, qlo=10, qhi=45):
    counts = rng.integers(2, max_r, size=J)
    starts = np.concatenate(([0], np.cumsum(counts))).astype(np.int64)
    N = int(starts[-1])
    truth = rng.integers(0, 4, size=(J, L))
    codes = np.repeat(truth, counts, axis=0)
    errs = rng.random((N, L)) < err
    codes[errs] = rng.integers(0, 4, size=int(errs.sum()))
    ns = rng.random((N, L)) < n_rate
    codes[ns] = 4
    quals = rng.integers(qlo, qhi + 1, size=(N, L)).astype(np.uint8)
    return codes.astype(np.uint8), quals, counts, starts


def wire_roundtrip(kernel, codes, quals, counts):
    """Dispatch via the wire path (forced XLA-CPU) and resolve."""
    rows = np.arange(codes.shape[0], dtype=np.int64)
    L = codes.shape[1]
    cd, qd, seg_ids, starts, F_pad, N = pad_segments_gather(
        codes, quals, rows, L, counts)
    ticket = kernel.device_call_segments_wire(cd, qd, seg_ids, F_pad,
                                              len(counts))
    return kernel.resolve_segments_wire(ticket, cd[:N], qd[:N], starts)


def assert_oracle_parity(codes, quals, starts, w, q, d, e):
    for j in range(len(starts) - 1):
        fam = slice(starts[j], starts[j + 1])
        ow, oq, od, oe = oracle.call_family(codes[fam], quals[fam], TABLES)
        np.testing.assert_array_equal(w[j], ow, err_msg=f"winner fam {j}")
        np.testing.assert_array_equal(q[j], oq, err_msg=f"qual fam {j}")
        np.testing.assert_array_equal(d[j], od, err_msg=f"depth fam {j}")
        np.testing.assert_array_equal(e[j], oe, err_msg=f"errors fam {j}")


@pytest.fixture
def device_kernel(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    k = ConsensusKernel(TABLES)
    k.set_force_device()
    return k


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_wire_parity_ragged(device_kernel, seed):
    rng = np.random.default_rng(seed)
    codes, quals, counts, starts = make_ragged(rng, J=40, L=32)
    w, q, d, e = wire_roundtrip(device_kernel, codes, quals, counts)
    assert_oracle_parity(codes, quals, starts, w, q, d, e)


def test_wire_parity_edge_quals(device_kernel):
    """Q0 (-inf table entries), Q2 floor, very high quals — the suspect /
    nonfinite guard paths through the dictionary encoding."""
    rng = np.random.default_rng(9)
    codes, quals, counts, starts = make_ragged(rng, J=24, L=16, err=0.4,
                                               qlo=0, qhi=8)
    w, q, d, e = wire_roundtrip(device_kernel, codes, quals, counts)
    assert_oracle_parity(codes, quals, starts, w, q, d, e)


def test_wire_fallback_many_quals(device_kernel):
    """>63 distinct quals forces the packed-codes fallback; same parity."""
    rng = np.random.default_rng(5)
    codes, quals, counts, starts = make_ragged(rng, J=40, L=16,
                                               qlo=2, qhi=88)
    assert len(np.unique(quals)) > 63
    w, q, d, e = wire_roundtrip(device_kernel, codes, quals, counts)
    assert_oracle_parity(codes, quals, starts, w, q, d, e)


def test_build_wire_encoding():
    rng = np.random.default_rng(3)
    codes = rng.integers(0, 5, size=(20, 12)).astype(np.uint8)
    quals = rng.choice([2, 11, 25, 37, 40], size=(20, 12)).astype(np.uint8)
    delta94 = np.arange(94, dtype=np.float32) * 0.25
    wire, dict32 = build_wire(codes, quals, delta94)
    # invalid sentinel exactly where codes are N
    np.testing.assert_array_equal(wire == WIRE_INVALID, codes == 4)
    # code bits survive where valid
    valid = codes != 4
    np.testing.assert_array_equal((wire & 3)[valid], codes[valid])
    # the dictionary maps each wire qidx back to the right delta
    qidx = (wire >> 2)[valid]
    np.testing.assert_array_equal(dict32[qidx], delta94[quals[valid]])
    assert dict32[63] == 0.0


def test_build_wire_declines_wide_qual_sets():
    codes = np.zeros((2, 40), dtype=np.uint8)
    quals = np.arange(80, dtype=np.uint8).reshape(2, 40)
    assert build_wire(codes, quals, np.zeros(94, np.float32)) is None


def _numpy_build_wire(codes2d, quals2d, delta94):
    """build_wire's numpy body (the oracle): the library looks absent."""
    from unittest import mock

    from fgumi_tpu.native import batch as nb

    with mock.patch.object(nb, "get_lib", return_value=None):
        return build_wire(codes2d, quals2d, delta94)


def _wire_case(name):
    """(codes (R, stride), quals, rows or None, L_max, counts) of one
    layout the native pass must reproduce byte for byte. Quals are never
    0 unless the case says so: a 0 in the dictionary comes from pad rows."""
    rng = np.random.default_rng(11)
    R, stride = 64, 24
    codes = rng.integers(0, 5, size=(R, stride)).astype(np.uint8)
    quals = rng.choice(np.array([2, 11, 25, 37, 40], np.uint8),
                       size=(R, stride))
    L = stride
    rows = np.sort(rng.choice(R, 27, replace=False)).astype(np.int64)
    counts = [5, 1, 9, 2, 10]
    if name in ("exact_bucket", "63_quals", "64_quals"):
        rows = np.arange(16, 48, dtype=np.int64)     # _pad_rows(32) == 32
        counts = [7, 25]
    if name == "short_L":
        L = 16
    elif name == "unsorted_rows":
        rng.shuffle(rows)
    elif name == "repeated_rows":
        rows[5:9] = rows[4]
        rows[20] = rows[0]
    elif name == "all_N":
        codes[:] = 4
    elif name == "one_row":
        rows, counts = np.array([5], dtype=np.int64), [1]
    elif name in ("63_quals", "64_quals", "63_quals_plus_pad"):
        n = 64 if name == "64_quals" else 63
        quals[rows] = (1 + np.arange(len(rows) * stride) % n).reshape(
            len(rows), stride)
    elif name == "dense":
        rows = None
    return codes, quals, rows, L, counts


@pytest.mark.parametrize("name", [
    "padded", "exact_bucket", "short_L", "unsorted_rows", "repeated_rows",
    "all_N", "one_row", "63_quals", "64_quals", "63_quals_plus_pad",
    "dense"])
def test_native_wire_pass_equals_numpy(name):
    """The one native pass (gather + pad + histogram + LUT + wire) against
    numpy's pad_segments_gather + build_wire, byte for byte: the fused
    ragged form of the single-device route, and the dense form every other
    caller of build_wire takes."""
    from fgumi_tpu.native import batch as nb
    from fgumi_tpu.ops.datapath import STAGING_POOL

    if not nb.available():
        pytest.skip("no native library on this host")
    kernel = ConsensusKernel(TABLES)
    delta94 = kernel._delta94
    codes, quals, rows, L, counts = _wire_case(name)
    if rows is None:
        # already the dense (N_pad, L) layout (mesh route, coalescer)
        cd, qd = codes, quals
        N = N_pad = len(codes)
    else:
        cd, qd, seg, starts, F_pad, N = pad_segments_gather(
            codes, quals, rows, L, counts)
        N_pad = _pad_rows(N)
        held0 = STAGING_POOL.snapshot()["held_bytes"]
        got = kernel.pack_segments_wire(codes, quals, rows, L, counts)
    want = _numpy_build_wire(cd, qd, delta94)
    declines = name in ("64_quals", "63_quals_plus_pad")
    assert (want is None) == declines
    assert (N == N_pad) == (name in ("exact_bucket", "63_quals",
                                     "64_quals", "dense"))
    if name == "padded":
        assert want[1][0] == delta94[0] and (want[0][N:] == WIRE_INVALID).all()
    if name == "63_quals":
        assert np.count_nonzero(want[1]) == 63

    # dense form, into a caller's buffer and into a fresh one
    out = np.full(cd.shape, 0xA5, dtype=np.uint8)
    for w in (build_wire(cd, qd, delta94, out=out),
              build_wire(cd, qd, delta94)):
        if declines:
            assert w is None
        else:
            np.testing.assert_array_equal(w[0], want[0])
            np.testing.assert_array_equal(w[1], want[1])
            assert w[0].dtype == np.uint8 and w[1].dtype == np.float32
    assert declines == bool((out == 0xA5).all())  # declined: nothing written
    if rows is None:
        return

    # ragged form: the dense views, the bookkeeping and the wire in one pass
    cd2, qd2, seg2, starts2, F_pad2, N2, prebuilt = got
    np.testing.assert_array_equal(cd2, cd)
    np.testing.assert_array_equal(qd2, qd)
    np.testing.assert_array_equal(seg2, seg)
    np.testing.assert_array_equal(starts2, starts)
    assert (F_pad2, N2, seg2.dtype) == (F_pad, N, seg.dtype)
    if declines:
        # the packed-codes layout takes over, and the staging buffer the
        # pass had taken is back in the pool for it
        assert prebuilt is None
        assert STAGING_POOL.snapshot()["held_bytes"] >= held0
        return
    np.testing.assert_array_equal(prebuilt[0], want[0])
    np.testing.assert_array_equal(prebuilt[1], want[1])
    STAGING_POOL.release(prebuilt[0])


@pytest.mark.parametrize("native", [True, False])
def test_wire_counters_say_which_build_ran(device_kernel, monkeypatch,
                                           native):
    """``engine.pack`` counts one ``wire_native`` a dispatch on a host with
    the library and one ``wire_numpy`` under FGUMI_TPU_NO_NATIVE=1, and
    the results are the oracle's either way."""
    import fgumi_tpu.native as native_mod
    from fgumi_tpu.observe import trace

    if not native:
        # what a fresh process does with the variable set
        monkeypatch.setenv("FGUMI_TPU_NO_NATIVE", "1")
        monkeypatch.setattr(native_mod, "_lib", None)
        monkeypatch.setattr(native_mod, "_lib_failed", False)
    elif native_mod.get_lib() is None:
        pytest.skip("no native library on this host")
    rng = np.random.default_rng(6)
    trace.stop_trace()
    trace.arm_spans()
    try:
        for _ in range(2):
            codes, quals, counts, starts = make_ragged(rng, J=24, L=16)
            rows = np.arange(len(codes), dtype=np.int64)
            with trace.span("engine.pack"):
                cd, qd, seg, st, F_pad, N, prebuilt = \
                    device_kernel.pack_segments_wire(codes, quals, rows, 16,
                                                     counts)
                assert (prebuilt is not None) == native
                ticket = device_kernel.device_call_segments_wire(
                    cd, qd, seg, F_pad, len(counts), prebuilt=prebuilt)
            w, q, d, e = device_kernel.resolve_segments_wire(
                ticket, cd[:N], qd[:N], st)
            assert_oracle_parity(codes, quals, starts, w, q, d, e)
        pack = trace.current_aggregate().snapshot()["by_name"]["engine.pack"]
    finally:
        trace.stop_trace()
    ran, other = (("wire_native", "wire_numpy") if native
                  else ("wire_numpy", "wire_native"))
    assert pack[ran] == pack["count"] == 2
    assert other not in pack


def test_pack_codes2_roundtrip():
    from fgumi_tpu.ops.kernel import QUAL_INVALID, pack_codes2

    rng = np.random.default_rng(4)
    codes = rng.integers(0, 5, size=(9, 24)).astype(np.uint8)
    quals = rng.integers(0, 94, size=(9, 24)).astype(np.uint8)
    cp, q = pack_codes2(codes, quals)
    assert cp.shape == (9, 6)
    shifts = np.arange(0, 8, 2, dtype=np.uint8)
    un = ((cp[:, :, None] >> shifts) & 3).reshape(9, 24)
    valid = codes != 4
    np.testing.assert_array_equal(un[valid], codes[valid])
    np.testing.assert_array_equal(q == QUAL_INVALID, ~valid)
    np.testing.assert_array_equal(q[valid], quals[valid])


def test_unpack_result_split_roundtrip():
    rng = np.random.default_rng(1)
    J, L = 7, 16
    winner = rng.integers(0, 4, size=(J, L)).astype(np.int64)
    qual = rng.integers(2, 94, size=(J, L)).astype(np.int64)
    suspect = rng.random((J, L)) < 0.2
    qs = (qual | suspect.astype(np.int64) << 7).astype(np.uint8)
    w4 = winner.reshape(J, L // 4, 4)
    wp = (w4[..., 0] | w4[..., 1] << 2 | w4[..., 2] << 4
          | w4[..., 3] << 6).astype(np.uint8)
    w2, q2, s2 = unpack_result_split(qs, wp, J)
    np.testing.assert_array_equal(w2, winner)
    np.testing.assert_array_equal(q2, qual)
    np.testing.assert_array_equal(s2, suspect)


def test_pad_rows_buckets():
    # monotonic, >= n, 16-aligned, and waste within one geometric ladder
    # step (ops/datapath.py ShapeBucketRegistry; default growth 1.0625)
    from fgumi_tpu.ops.datapath import DEFAULT_GROWTH

    prev = 0
    for n in [1, 16, 17, 100, 8192, 8193, 20000, 65536, 65537, 100000,
              300000, 441242]:
        p = _pad_rows(n)
        assert p >= n
        assert p >= prev
        assert p % 16 == 0
        prev = p
        assert p - n <= (DEFAULT_GROWTH - 1.0) * n + 16


def test_pad_out_segments():
    for f_pad in [1, 8, 64, 1024, 65536]:
        for j in [1, f_pad // 3 + 1, f_pad - 1, f_pad]:
            out = _pad_out_segments(j, f_pad)
            assert j <= out <= f_pad
            # waste <= 1/8 of the pow2 ceiling
            assert out - j <= max(f_pad // 8, 1)


def ragged_layout(rng, codes, quals, stride_extra=8, spare_rows=5):
    """The rows of a dense (N, L) batch scattered over a wider, longer
    packed array as the engines hold them: (codes_pk, quals_pk, rows, L)."""
    N, L = codes.shape
    rows = np.sort(rng.choice(N + spare_rows, N, replace=False)).astype(
        np.int64)
    codes_pk = rng.integers(0, 5, size=(N + spare_rows, L + stride_extra))
    quals_pk = rng.integers(2, 40, size=codes_pk.shape)
    codes_pk, quals_pk = codes_pk.astype(np.uint8), quals_pk.astype(np.uint8)
    codes_pk[rows, :L] = codes
    quals_pk[rows, :L] = quals
    return codes_pk, quals_pk, rows, L


def submit_and_resolve(kernel, entry, codes, quals, counts, route="device",
                       rng=None, **kwargs):
    """One batch through ConsensusKernel.submit_ragged / submit_dense and
    its PendingSegments.resolve()."""
    if entry == "ragged":
        codes_pk, quals_pk, rows, L = ragged_layout(
            rng or np.random.default_rng(0), codes, quals)
        pending = kernel.submit_ragged(codes_pk, quals_pk, rows, L, counts,
                                       route, **kwargs)
    else:
        pending = kernel.submit_dense(lambda: (codes, quals), counts, route,
                                      **kwargs)
    return pending.resolve()


# every route a submitted batch can take x the entry that takes it there;
# the pair that does not exist (a mesh dispatch starts from dense rows
# only) is left out
_SUBMIT_CASES = [
    ("host", "ragged"), ("host", "dense"),
    ("wire", "ragged"), ("wire", "dense"),
    ("packed2", "ragged"), ("packed2", "dense"), ("mesh", "dense"),
]


@pytest.mark.parametrize("route,entry", _SUBMIT_CASES)
def test_submit_resolve_parity(device_kernel, route, entry):
    """submit -> PendingSegments.resolve against the f64 oracle on the
    host route, the XLA wire kernel, the packed2 fallback (> 63 distinct
    quals) and a mesh of the 8 virtual CPU devices."""
    rng = np.random.default_rng(13)
    wide = route == "packed2"
    codes, quals, counts, starts = make_ragged(
        rng, J=37, L=32, err=0.15, qlo=2, qhi=88 if wide else 45)
    assert (len(np.unique(quals)) > 63) == wide
    kwargs = {}
    if route == "mesh":
        import jax

        from fgumi_tpu.parallel.mesh import make_mesh

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 virtual devices")
        kwargs["mesh"] = make_mesh(jax.devices()[:8], dp=4, sp=2)
    before = DEVICE_STATS.snapshot()["dispatches"]
    out = submit_and_resolve(
        device_kernel, entry, codes, quals, counts,
        route="host" if route == "host" else "device", rng=rng, **kwargs)
    assert DEVICE_STATS.snapshot()["dispatches"] - before == \
        (0 if route == "host" else 1)
    assert_oracle_parity(codes, quals, starts, *out)
    assert DEVICE_STATS.in_flight_count() == 0


@pytest.mark.parametrize("case", ["native", "numpy", "wide_quals"])
def test_submit_ragged_full_equals_dense(device_kernel, monkeypatch, case):
    """The duplex request (the full-column kernel, nothing kept on the
    device) through submit_ragged is the one through submit_dense on the
    same rows: the same kernel on the same shapes, equal columns. Also
    where the native pass declines its inputs (numpy packs instead), and
    with more than 63 distinct quals, where both entries dispatch the
    packed-codes layout."""
    from fgumi_tpu.native import batch as nb
    from fgumi_tpu.ops.datapath import SHAPE_REGISTRY

    if case == "numpy":
        monkeypatch.setattr(nb, "wire_inputs_ok", lambda *a, **kw: False)
    elif nb.get_lib() is None:
        pytest.skip("no native library on this host")
    rng = np.random.default_rng(29)
    wide = case == "wide_quals"
    codes, quals, counts, starts = make_ragged(
        rng, J=37, L=32, err=0.15, qlo=2, qhi=88 if wide else 45)
    codes_pk, quals_pk, rows, L = ragged_layout(rng, codes, quals)
    shapes = []
    observe = SHAPE_REGISTRY.observe
    monkeypatch.setattr(
        SHAPE_REGISTRY, "observe",
        lambda kind, *dims: shapes.append((kind,) + dims) or observe(kind,
                                                                     *dims))
    got = {}
    for entry in ("dense", "ragged"):
        if entry == "dense":
            pending = device_kernel.submit_dense(
                lambda: (codes, quals), counts, "device")
        else:
            pending = device_kernel.submit_ragged(
                codes_pk, quals_pk, rows, L, counts, "device")
        got[entry] = pending.resolve()
    assert shapes[0] == shapes[1] and len(shapes) == 2
    assert shapes[0][0] == ("segp2f" if wide else "segwf")
    for a, b in zip(got["dense"], got["ragged"], strict=True):
        np.testing.assert_array_equal(a, b)
    assert got["ragged"][0].shape == (len(counts), L)
    assert_oracle_parity(codes, quals, starts, *got["ragged"])
    assert DEVICE_STATS.in_flight_count() == 0
    assert DEVICE_STATS.resident_bytes == 0


@pytest.mark.parametrize("entry", ["ragged", "dense"])
def test_submit_deep_family(device_kernel, entry):
    """One deep family (256 reads) among shallow ones: depth-class
    bucketing in the suspect patch, saturation on the deep column."""
    rng = np.random.default_rng(5)
    counts = np.array([256, 3, 5, 2])
    starts = np.concatenate(([0], np.cumsum(counts))).astype(np.int64)
    N = int(starts[-1])
    L = 12
    truth = rng.integers(0, 4, size=(4, L))
    codes = np.repeat(truth, counts, axis=0)
    errs = rng.random((N, L)) < 0.3
    codes[errs] = rng.integers(0, 4, size=int(errs.sum()))
    codes = codes.astype(np.uint8)
    quals = rng.integers(5, 45, size=(N, L)).astype(np.uint8)
    w, q, d, e = submit_and_resolve(device_kernel, entry, codes, quals,
                                    counts, rng=rng)
    assert d[0].max() == 256
    assert_oracle_parity(codes, quals, starts, w, q, d, e)


@pytest.mark.parametrize("entry", ["ragged", "dense"])
def test_submit_clean_unanimous(device_kernel, entry):
    """A clean unanimous pileup (no errors, no N, high quals): every
    column saturates, none is an error, and the device's answer stands."""
    rng = np.random.default_rng(2)
    codes, quals, counts, starts = make_ragged(rng, J=16, L=20, err=0.0,
                                               n_rate=0.0, qlo=30, qhi=40)
    w, q, d, e = submit_and_resolve(device_kernel, entry, codes, quals,
                                    counts, rng=rng)
    assert not e.any()
    np.testing.assert_array_equal(d, np.repeat(counts, 20).reshape(-1, 20))
    assert_oracle_parity(codes, quals, starts, w, q, d, e)


def test_hybrid_routes_overflow_to_host(monkeypatch):
    """When in-flight dispatches exceed the cap, _dispatch_jobs must route
    the batch to the host f64 engine (a pending round HOST_DISPATCH)."""
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    monkeypatch.setenv("FGUMI_TPU_HYBRID", "1")
    from fgumi_tpu.ops.kernel import HOST_DISPATCH

    k = ConsensusKernel(TABLES)
    k.set_force_device()
    assert k.hybrid_mode()
    # simulate a saturated device pipe
    monkeypatch.setattr(DEVICE_STATS, "in_flight", 99)
    assert DEVICE_STATS.in_flight_count() == 99

    class FakeFast:
        max_inflight = 3
        mesh = None

    # distill the routing condition _dispatch_jobs applies
    route_host = k.host_mode() or (
        k.hybrid_mode()
        and DEVICE_STATS.in_flight_count() >= FakeFast.max_inflight)
    assert route_host
    monkeypatch.setattr(DEVICE_STATS, "in_flight", 0)
    route_host = k.host_mode() or (
        k.hybrid_mode()
        and DEVICE_STATS.in_flight_count() >= FakeFast.max_inflight)
    assert not route_host
    assert HOST_DISPATCH is not None


def test_fast_simplex_hybrid_cli_bytes(tmp_path):
    """Threaded hybrid run (device pipe cap 0 => everything routes host;
    cap huge => everything routes device/XLA) produce identical bytes."""
    import subprocess
    import sys

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sim = tmp_path / "grouped.bam"
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", "simulate", "grouped-reads",
         "-o", str(sim), "--num-families", "400",
         "--family-size-distribution", "longtail",
         "--read-length", "60", "--seed", "23"],
        check=True, cwd=REPO, env={**os.environ, "PYTHONPATH": REPO})
    outs = {}
    for label, env in (
            ("host", {"FGUMI_TPU_MAX_INFLIGHT": "0",
                      "FGUMI_TPU_HOST_ENGINE": "0"}),
            ("device", {"FGUMI_TPU_MAX_INFLIGHT": "1000000",
                        "FGUMI_TPU_HOST_ENGINE": "0"}),
            ("mixed", {"FGUMI_TPU_MAX_INFLIGHT": "1",
                       "FGUMI_TPU_HOST_ENGINE": "0"}),
            ("wholebatch", {"FGUMI_TPU_HYBRID": "0",
                            "FGUMI_TPU_HOST_ENGINE": "0"})):
        d = tmp_path / label
        d.mkdir()
        subprocess.run(
            [sys.executable, "-m", "fgumi_tpu", "simplex", "-i", str(sim),
             "-o", "cons.bam", "--min-reads", "1", "--allow-unmapped",
             "--threads", "4"],
            check=True, cwd=d,
            env={**os.environ, "PYTHONPATH": REPO, **env})
        outs[label] = (d / "cons.bam").read_bytes()
    assert outs["host"] == outs["device"]
    assert outs["host"] == outs["mixed"]
    assert outs["host"] == outs["wholebatch"]


def test_feeder_error_propagates_cleanly(tmp_path):
    """A device dispatch failure inside the feeder thread must surface as a
    command error (no hang, no leaked in-flight count silently disabling
    the device for later batches)."""
    import subprocess
    import sys

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sim = tmp_path / "g.bam"
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", "simulate", "grouped-reads",
         "-o", str(sim), "--num-families", "200", "--read-length", "50",
         "--error-rate", "0.2", "--seed", "3"],
        check=True, cwd=REPO, env={**os.environ, "PYTHONPATH": REPO})
    code = r"""
import sys
sys.path.insert(0, %(repo)r)
import numpy as np
from fgumi_tpu.ops import kernel as K

def boom(*a, **kw):
    raise RuntimeError("injected device failure")

# break every whole-batch device kernel the engines can route to
K._consensus_segments_wire_jit = boom
K._consensus_segments_wire_full_jit = boom
K._consensus_segments_packed2_jit = boom
K._consensus_segments_packed2_full_jit = boom
from fgumi_tpu.cli import main
try:
    rc = main(["simplex", "-i", %(sim)r, "-o", %(out)r, "--min-reads", "1",
               "--allow-unmapped", "--threads", "4"])
    print("RC", rc)
except RuntimeError as e:
    print("RAISED", e)
# the in-flight accounting must be balanced no matter how the command died
assert K.DEVICE_STATS.in_flight_count() == 0, "in-flight leak"
print("INFLIGHT-OK")
""" % {"repo": REPO, "sim": str(sim), "out": str(tmp_path / "o.bam")}
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300,
        env={**os.environ, "PYTHONPATH": REPO,
             "FGUMI_TPU_HOST_ENGINE": "0", "JAX_PLATFORMS": "cpu",
             # force the device route: the adaptive cost model would price
             # this tiny workload host-side and never hit the broken kernels
             "FGUMI_TPU_ROUTE": "device",
             # conftest exports an 8-device XLA_FLAGS: without clearing it
             # the CLI auto-meshes and takes the sharded (unpatched) path
             "XLA_FLAGS": ""})
    out = proc.stdout + proc.stderr
    assert "INFLIGHT-OK" in out, out
    assert "in-flight leak" not in out, out
    # the failure must have been VISIBLE — raised, nonzero rc, or (since
    # the resilience layer) loudly recovered onto the host f64 engine with
    # a warning — never silently swallowed into an unexplained success
    recovered = "host engine" in out and "failed" in out
    assert "RAISED" in out or "RC 0" not in out or recovered, out


def test_duplex_deferred_hybrid_cli_bytes(tmp_path):
    """Every duplex span is a pending chunk (fast_duplex._DuplexPending):
    inline (threads 0) it resolves in the double-buffer window, behind the
    next batch's dispatch or (FGUMI_TPU_INLINE_FLIGHT=1) at once; at
    threads 4 on the resolve workers. All hybrid configurations must
    produce byte-identical output — including the MI/ordinal numbering of
    classic-fallback molecules, called and numbered at process time."""
    import subprocess
    import sys

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sim = tmp_path / "dup.bam"
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", "simulate", "duplex-reads",
         "-o", str(sim), "--num-molecules", "300", "--reads-per-strand", "3",
         "--seed", "11"],
        check=True, cwd=REPO, env={**os.environ, "PYTHONPATH": REPO})
    outs = {}
    # pin every knob that could collapse the configs into one path: an
    # ambient FGUMI_TPU_HYBRID=0 or leftover FGUMI_TPU_INLINE_FLIGHT would
    # otherwise make all four runs synchronous and the test vacuous
    base_env = {k: v for k, v in os.environ.items()
                if k not in ("FGUMI_TPU_HYBRID", "FGUMI_TPU_INLINE_FLIGHT",
                             "FGUMI_TPU_HOST_ENGINE",
                             "FGUMI_TPU_MAX_INFLIGHT")}
    for label, threads, env in (
            ("inline_deferred", "0", {"FGUMI_TPU_HOST_ENGINE": "0"}),
            ("inline_serial", "0", {"FGUMI_TPU_HOST_ENGINE": "0",
                                    "FGUMI_TPU_INLINE_FLIGHT": "1"}),
            ("threaded_pool", "4", {"FGUMI_TPU_HOST_ENGINE": "0"}),
            ("host_engine", "0", {"FGUMI_TPU_HOST_ENGINE": "1"})):
        d = tmp_path / label
        d.mkdir()
        subprocess.run(
            [sys.executable, "-m", "fgumi_tpu", "duplex", "-i", str(sim),
             "-o", "cons.bam", "--min-reads", "1", "--threads", threads],
            check=True, cwd=d,
            env={**base_env, "PYTHONPATH": REPO, **env})
        outs[label] = (d / "cons.bam").read_bytes()
    # same write path -> compressed bytes identical
    assert outs["inline_deferred"] == outs["inline_serial"]

    def records(raw):
        """Decoded record stream, header stripped (the @PG CL field records
        the differing --threads value)."""
        import gzip
        import io
        import struct as st

        data = gzip.GzipFile(fileobj=io.BytesIO(raw)).read()
        assert data[:4] == b"BAM\x01"
        l_text = st.unpack("<I", data[4:8])[0]
        o = 8 + l_text
        n_ref = st.unpack("<I", data[o:o + 4])[0]
        o += 4
        for _ in range(n_ref):
            l_name = st.unpack("<I", data[o:o + 4])[0]
            o += 4 + l_name + 4
        return data[o:]

    # threaded mode delivers different chunk sizes to the writer (BGZF
    # framing differs) and a different @PG CL — the record stream itself
    # must still be byte-identical
    assert records(outs["inline_deferred"]) == records(outs["threaded_pool"])
    assert records(outs["inline_deferred"]) == records(outs["host_engine"])
