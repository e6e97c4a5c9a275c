"""Full-column device consensus + adaptive offload policy (ISSUE 6).

Byte-identity of the full-column wire path (device-computed winner/qual/
depth/errors per column) against the native f64 host engine across
simplex/duplex/codec, at bucket-edge shapes and through the >63-distinct-
qual fallback; forced-route parity (FGUMI_TPU_ROUTE=device|host produce
identical bytes); fused duplex-combine and CODEC-concordance device stages
vs their numpy twins; OffloadRouter policy unit tests.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from fgumi_tpu.native import batch as nb  # noqa: E402
from fgumi_tpu.ops import router as R  # noqa: E402
from fgumi_tpu.ops.host_kernel import HostConsensusEngine  # noqa: E402
from fgumi_tpu.ops.kernel import (ConsensusKernel, build_wire,  # noqa: E402
                                  codec_combine_device, pad_segments_gather)
from fgumi_tpu.ops.tables import quality_tables  # noqa: E402


def _device_kernel(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    k = ConsensusKernel(quality_tables(45, 40))
    k.set_force_device()
    return k


def _ragged_pileup(rng, counts, L, qual_lo=2, qual_hi=41):
    """Family-consistent ragged rows: a shared template per family plus
    ~2% errors and some N positions (exercises winner/depth/error paths)."""
    N = int(counts.sum())
    starts = np.concatenate(([0], np.cumsum(counts))).astype(np.int64)
    codes = np.empty((N, L), dtype=np.uint8)
    for j, (lo, hi) in enumerate(zip(starts[:-1], starts[1:])):
        tmpl = rng.integers(0, 4, size=L, dtype=np.uint8)
        fam = np.repeat(tmpl[None, :], hi - lo, axis=0)
        err = rng.random(fam.shape) < 0.02
        fam[err] = (fam[err] + rng.integers(1, 4, size=int(err.sum()))) % 4
        fam[rng.random(fam.shape) < 0.01] = 4  # N observations
        codes[lo:hi] = fam
    quals = rng.integers(qual_lo, qual_hi, size=(N, L), dtype=np.uint8)
    return codes, quals, starts


def _full_column_resolve(kernel, codes, quals, counts, starts, L, J):
    rows = np.arange(int(counts.sum()))
    cd, qd, seg, _st, F_pad, N = pad_segments_gather(
        codes, quals, rows, L, counts)
    ticket = kernel.device_call_segments_wire(cd, qd, seg, F_pad, J,
                                              full=True)
    return kernel.resolve_segments_wire(ticket, cd[:N], qd[:N], starts)


@pytest.mark.skipif(not nb.available(), reason="native library required")
@pytest.mark.parametrize("n_fam,fam,L", [
    (16, 4, 32),        # N=64: exactly a small ladder bucket
    (37, 3, 36),        # ragged-ish J, odd sizes
    (128, 5, 64),       # J at a segment-bucket edge
])
def test_full_column_matches_host_engine(monkeypatch, n_fam, fam, L):
    """Device full-column results (incl. device depth/errors) are integer-
    exact vs the native f64 host engine at bucket-edge shapes."""
    kernel = _device_kernel(monkeypatch)
    host = HostConsensusEngine(quality_tables(45, 40))
    rng = np.random.default_rng(n_fam)
    counts = rng.integers(2, fam + 2, size=n_fam).astype(np.int64)
    codes, quals, starts = _ragged_pileup(rng, counts, L)
    w, q, d, e = _full_column_resolve(kernel, codes, quals, counts, starts,
                                      L, n_fam)
    wh, qh, dh, eh = host.call_segments(codes, quals, starts)
    np.testing.assert_array_equal(w, wh)
    np.testing.assert_array_equal(q, qh)
    np.testing.assert_array_equal(np.asarray(d, np.int64),
                                  np.asarray(dh, np.int64))
    np.testing.assert_array_equal(np.asarray(e, np.int64),
                                  np.asarray(eh, np.int64))


@pytest.mark.skipif(not nb.available(), reason="native library required")
def test_full_column_qual_dict_fallback(monkeypatch):
    """>63 distinct quals forces the 1.25 B packed2 full kernel; results
    stay integer-exact vs the host engine."""
    kernel = _device_kernel(monkeypatch)
    host = HostConsensusEngine(quality_tables(45, 40))
    rng = np.random.default_rng(7)
    counts = np.full(24, 4, dtype=np.int64)
    codes, quals, starts = _ragged_pileup(rng, counts, 40,
                                          qual_lo=1, qual_hi=94)
    assert len(np.unique(quals)) > 63
    assert build_wire(codes, quals,
                      kernel._delta94) is None  # fallback layout engaged
    w, q, d, e = _full_column_resolve(kernel, codes, quals, counts, starts,
                                      40, 24)
    wh, qh, dh, eh = host.call_segments(codes, quals, starts)
    np.testing.assert_array_equal(w, wh)
    np.testing.assert_array_equal(q, qh)
    np.testing.assert_array_equal(np.asarray(d, np.int64),
                                  np.asarray(dh, np.int64))
    np.testing.assert_array_equal(np.asarray(e, np.int64),
                                  np.asarray(eh, np.int64))


def test_codec_combine_device_matches_numpy(monkeypatch):
    """The CODEC concordance device stage is bit-identical to
    combine_arrays on adversarial inputs (N bases both cases, ties,
    single-strand, Q2 floors)."""
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    from fgumi_tpu.consensus.codec import combine_arrays

    rng = np.random.default_rng(5)
    T = 1000
    bases = np.frombuffer(b"ACGTNacgtn", np.uint8)
    ba = rng.choice(bases, size=T)
    bb = rng.choice(bases, size=T)
    qa = rng.integers(0, 94, size=T).astype(np.uint8)
    qb = rng.integers(0, 94, size=T).astype(np.uint8)
    qa[rng.random(T) < 0.2] = 2  # MIN_PHRED floors
    qb[rng.random(T) < 0.2] = 2
    da = rng.integers(0, 40000, size=T).astype(np.int32)
    db = rng.integers(0, 40000, size=T).astype(np.int32)
    ea = rng.integers(0, 33000, size=T).astype(np.int32)
    eb = rng.integers(0, 33000, size=T).astype(np.int32)
    ref = combine_arrays(ba, bb, qa, qb, da, db, ea, eb)
    got = codec_combine_device(ba, bb, qa, qb, da, db, ea, eb)
    for i, (g, r) in enumerate(zip(got, ref)):
        np.testing.assert_array_equal(np.asarray(g, np.int64),
                                      np.asarray(r, np.int64), err_msg=str(i))


# --------------------------------------------------------------- CLI parity

def _simulate(tmp_path, what, args):
    out = tmp_path / f"{what}.bam"
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", "simulate", what, "-o",
         str(out), *args],
        check=True, cwd=REPO, env={**os.environ, "PYTHONPATH": REPO})
    return out


def _cli_bytes(tmp_path, label, cmd, sim, env):
    d = tmp_path / label
    d.mkdir()
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", cmd, "-i", str(sim),
         "-o", "cons.bam", "--min-reads", "1", "--threads", "2"],
        check=True, cwd=d,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "", **env})
    return (d / "cons.bam").read_bytes()


@pytest.mark.slow
@pytest.mark.skipif(not nb.available(), reason="native library required")
def test_forced_routes_byte_identical_simplex(tmp_path):
    """FGUMI_TPU_ROUTE=device, =host, and =auto (the policy's own choice)
    produce identical simplex bytes — the forced-route acceptance gate."""
    sim = _simulate(tmp_path, "grouped-reads",
                    ["--num-families", "300", "--family-size-distribution",
                     "longtail", "--read-length", "60", "--seed", "29"])
    outs = {label: _cli_bytes(
        tmp_path, label, "simplex", sim,
        {"FGUMI_TPU_HOST_ENGINE": "0", **env})
        for label, env in (("device", {"FGUMI_TPU_ROUTE": "device"}),
                           ("host", {"FGUMI_TPU_ROUTE": "host"}),
                           ("auto", {}))}
    assert outs["device"] == outs["host"]
    assert outs["device"] == outs["auto"]


@pytest.mark.slow
@pytest.mark.skipif(not nb.available(), reason="native library required")
def test_forced_routes_byte_identical_duplex(tmp_path):
    """Duplex: forced routes AND both strand-combine sides (fused device
    stage vs numpy) are byte-identical."""
    sim = _simulate(tmp_path, "duplex-reads",
                    ["--num-molecules", "150", "--reads-per-strand", "3",
                     "--seed", "31"])
    outs = {label: _cli_bytes(
        tmp_path, label, "duplex", sim,
        {"FGUMI_TPU_HOST_ENGINE": "0", **env})
        for label, env in (
            ("device", {"FGUMI_TPU_ROUTE": "device",
                        "FGUMI_TPU_DUPLEX_COMBINE": "device"}),
            ("devhost", {"FGUMI_TPU_ROUTE": "device",
                         "FGUMI_TPU_DUPLEX_COMBINE": "host"}),
            ("host", {"FGUMI_TPU_ROUTE": "host"}))}
    assert outs["device"] == outs["host"]
    assert outs["device"] == outs["devhost"]


@pytest.mark.slow
@pytest.mark.skipif(not nb.available(), reason="native library required")
def test_forced_routes_byte_identical_codec(tmp_path):
    """CODEC: forced routes and the concordance device stage are
    byte-identical."""
    sim = _simulate(tmp_path, "codec-reads",
                    ["--num-molecules", "200", "--pairs-per-molecule", "2",
                     "--read-length", "80", "--seed", "37"])
    outs = {label: _cli_bytes(
        tmp_path, label, "codec", sim,
        {"FGUMI_TPU_HOST_ENGINE": "0", **env})
        for label, env in (
            ("device", {"FGUMI_TPU_ROUTE": "device",
                        "FGUMI_TPU_CODEC_COMBINE": "device"}),
            ("host", {"FGUMI_TPU_ROUTE": "host"}))}
    assert outs["device"] == outs["host"]


# ------------------------------------------------------------------- router

class _FakeKernel:
    def __init__(self, hybrid=True):
        self._hybrid = hybrid

    def hybrid_mode(self):
        return self._hybrid


def _fresh_router():
    r = R.OffloadRouter()
    r.reset()
    return r


@pytest.mark.skipif(not nb.available(), reason="native library required")
def test_router_env_forcing(monkeypatch):
    monkeypatch.delenv("FGUMI_TPU_MAX_INFLIGHT", raising=False)
    r = _fresh_router()
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "device")
    assert r.decide(_FakeKernel(), 1, 1, 10**9) == "device"
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "host")
    assert r.decide(_FakeKernel(), 1, 1, 1) == "host"
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "auto")
    # no host engine available -> device regardless of cost
    assert r.decide(_FakeKernel(hybrid=False), 10**12, 10**12, 1) == "device"


@pytest.mark.skipif(not nb.available(), reason="native library required")
def test_router_legacy_max_inflight(monkeypatch):
    monkeypatch.delenv("FGUMI_TPU_ROUTE", raising=False)
    r = _fresh_router()
    monkeypatch.setenv("FGUMI_TPU_MAX_INFLIGHT", "0")
    assert r.decide(_FakeKernel(), 1, 1, 1) == "host"
    monkeypatch.setenv("FGUMI_TPU_MAX_INFLIGHT", "1000000")
    assert r.decide(_FakeKernel(), 10**12, 10**12, 1) == "device"


@pytest.mark.skipif(not nb.available(), reason="native library required")
def test_router_cost_model(monkeypatch):
    monkeypatch.delenv("FGUMI_TPU_ROUTE", raising=False)
    monkeypatch.delenv("FGUMI_TPU_MAX_INFLIGHT", raising=False)
    monkeypatch.setenv("FGUMI_TPU_ROUTE_PROBE", "0")  # no refresh probes
    r = _fresh_router()
    # measured: fast link + tiny overhead, slow host
    for _ in range(4):
        r.observe_device(10_000_000, 1_000_000, 0.01, 0.001, 0.011)
        r.observe_host(1_000_000, 1.0)  # 1M cells/s: very slow host
    assert r.decide(_FakeKernel(), 1_000_000, 100_000,
                    50_000_000) == "device"
    # now a very slow link and a fast host
    r2 = _fresh_router()
    for _ in range(4):
        r2.observe_device(1_000_000, 100_000, 10.0, 0.5, 10.5)
        r2.observe_host(100_000_000, 0.1)  # 1G cells/s
    assert r2.decide(_FakeKernel(), 10_000_000, 1_000_000,
                     1_000_000) == "host"
    snap = r2.snapshot()
    assert snap["host_samples"] == 4 and snap["link_samples"] == 4
    assert "last_decision" in snap


@pytest.mark.skipif(not nb.available(), reason="native library required")
def test_router_probes_unmeasured_host(monkeypatch):
    """With the device measured and the host never sampled, the router
    eventually sends a probe batch host-side so the EWMA goes live."""
    monkeypatch.delenv("FGUMI_TPU_ROUTE", raising=False)
    monkeypatch.delenv("FGUMI_TPU_MAX_INFLIGHT", raising=False)
    r = _fresh_router()
    for _ in range(3):
        r.observe_device(10_000_000, 1_000_000, 0.01, 0.001, 0.011)
    sides = {r.decide(_FakeKernel(), 1000, 1000, 1000) for _ in range(4)}
    assert "host" in sides


def test_adaptive_chooser_alternates_then_settles(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_ROUTE_PROBE", "0")
    c = R.AdaptiveChooser("test_chooser")
    # both sides unmeasured: probes alternate (each decide is followed by
    # an observe of the chosen side, as the engines do)
    first = []
    for _ in range(4):
        side = c.decide(1000)
        first.append(side)
        c.observe(side, 1000, 0.5 if side == "device" else 0.001)
    assert set(first) == {"device", "host"}
    for _ in range(3):
        c.observe("device", 1000, 0.5)
        c.observe("host", 1000, 0.001)
    assert c.decide(1000) == "host"
    assert c.decide(1000, override="device") == "device"
    snap = c.snapshot()
    assert snap["host"]["samples"] >= 2
