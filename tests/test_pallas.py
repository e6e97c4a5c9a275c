"""Pallas TPU kernel tests (ISSUE 19, ops/pallas_kernel.py).

Covers: ``FGUMI_TPU_KERNEL`` parsing (invalid values are a loud error,
never a silent pin), the loud XLA fallback when the Pallas lowering is
unavailable, byte-exact parity of the Pallas kernels (Mosaic interpret
mode on this CPU platform) against the XLA reference on the full-column
and fused-filter wire routes at segment-bucket edges, the >63-distinct-
quals packed2 fallback under a forced ``pallas`` selection, the
``kernel_pallas``/``kernel_xla`` backend counters + timeline stamp, and
the fused-filter sentinel audit (clean verdict and injected-corruption
repair)."""

import logging

import numpy as np
import pytest

from fgumi_tpu.consensus.device_filter import (S_SUSPECT, FilterConfig,
                                               SimplexFilterStage)
from fgumi_tpu.native import batch as nb
from fgumi_tpu.ops import pallas_kernel as pk
from fgumi_tpu.ops.breaker import BREAKER
from fgumi_tpu.ops.kernel import DEVICE_STATS, ConsensusKernel, pad_segments
from fgumi_tpu.ops.sentinel import SENTINEL
from fgumi_tpu.ops.tables import quality_tables
from fgumi_tpu.utils import faults

needs_native = pytest.mark.skipif(not nb.available(),
                                  reason="native library unavailable")
needs_pallas = pytest.mark.skipif(not pk.available(),
                                  reason="pallas lowering unavailable")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("FGUMI_TPU_KERNEL", "FGUMI_TPU_PALLAS_UNAVAILABLE",
                "FGUMI_TPU_AUDIT", "FGUMI_TPU_FAULT"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "device")
    faults.reset()
    SENTINEL.reset()
    BREAKER.reset()
    yield
    SENTINEL.drain(timeout=10)
    SENTINEL.reset()
    faults.reset()
    BREAKER.reset()


# ------------------------------------------------------------ env selection


def test_kernel_backend_parse(monkeypatch):
    for v, want in (("", "auto"), ("auto", "auto"), ("default", "auto"),
                    ("  PALLAS ", "pallas"), ("xla", "xla"),
                    ("Xla", "xla")):
        monkeypatch.setenv("FGUMI_TPU_KERNEL", v)
        assert pk.kernel_backend() == want, v
    monkeypatch.delenv("FGUMI_TPU_KERNEL")
    assert pk.kernel_backend() == "auto"


def test_invalid_kernel_value_is_loud_once(monkeypatch, caplog):
    monkeypatch.setattr(pk, "_WARNED", set())
    monkeypatch.setenv("FGUMI_TPU_KERNEL", "mosaic")
    with caplog.at_level(logging.ERROR, logger="fgumi_tpu"):
        assert pk.kernel_backend() == "auto"
        assert pk.kernel_backend() == "auto"
    errs = [r for r in caplog.records if "FGUMI_TPU_KERNEL" in r.message]
    assert len(errs) == 1  # loud, but once per distinct bad value


def test_forced_pallas_unavailable_raises(monkeypatch):
    """A forced kernel that cannot run ends the run — no log-and-use-XLA."""
    monkeypatch.setenv("FGUMI_TPU_KERNEL", "pallas")
    monkeypatch.setenv("FGUMI_TPU_PALLAS_UNAVAILABLE", "1")
    assert pk.available() is False
    with pytest.raises(RuntimeError, match="unavailable"):
        pk.selected_backend()


def test_auto_keeps_xla_off_tpu(monkeypatch):
    """``auto`` must never pay Mosaic interpret mode on a CPU host."""
    monkeypatch.setenv("FGUMI_TPU_KERNEL", "auto")
    if pk.interpreted():
        assert pk.selected_backend() == "xla"
    monkeypatch.setenv("FGUMI_TPU_KERNEL", "xla")
    assert pk.selected_backend() == "xla"


# ------------------------------------------------------------------- parity


class _Opts:
    min_reads = 1
    min_consensus_base_quality = 40
    produce_per_base_tags = True


def _family_batch(n_fam, fam, L, seed=None, qhi=41):
    rng = np.random.default_rng(n_fam * 7 + fam + L if seed is None
                                else seed)
    codes = rng.integers(0, 5, size=(n_fam * fam, L), dtype=np.uint8)
    quals = rng.integers(2, qhi, size=(n_fam * fam, L), dtype=np.uint8)
    counts = np.full(n_fam, fam, dtype=np.int64)
    starts = (np.arange(n_fam + 1) * fam).astype(np.int64)
    return codes, quals, counts, starts


def _run_full(backend, monkeypatch, codes, quals, counts, starts):
    monkeypatch.setenv("FGUMI_TPU_KERNEL", backend)
    kernel = ConsensusKernel(quality_tables(45, 40))
    kernel.set_force_device()
    cd, qd, seg, _st, F = pad_segments(codes, quals, counts)
    t = kernel.device_call_segments_wire(cd, qd, seg, F, len(counts),
                                         full=True)
    out = kernel.resolve_segments_wire(t, codes, quals, starts)
    return tuple(np.array(a, copy=True) for a in out)


@needs_native
@needs_pallas
@pytest.mark.parametrize("n_fam,fam,L", [(7, 3, 48), (65, 3, 100),
                                         (129, 2, 48), (4, 40, 32)])
def test_full_column_parity_and_counters(monkeypatch, n_fam, fam, L):
    """Forced pallas vs forced xla on the full-column wire route:
    byte-identical resolved planes at shapes straddling the row-tile
    (128) and segment-tile (8) bucket edges, with the backend counter
    and timeline stamp recording which kernel ran."""
    batch = _family_batch(n_fam, fam, L)
    ref = _run_full("xla", monkeypatch, *batch)
    px0, xx0 = DEVICE_STATS.kernel_pallas, DEVICE_STATS.kernel_xla
    got = _run_full("pallas", monkeypatch, *batch)
    for name, a, b in zip("wqde", ref, got):
        np.testing.assert_array_equal(a, b, err_msg=f"plane {name}")
    assert DEVICE_STATS.kernel_pallas == px0 + 1
    assert DEVICE_STATS.kernel_xla == xx0
    stamps = [t.get("kernel_backend")
              for t in DEVICE_STATS.timeline_snapshot()]
    assert stamps and stamps[-1] == "pallas" and "xla" in stamps
    snap = DEVICE_STATS.snapshot()
    assert snap["kernel_pallas"] >= 1 and snap["kernel_xla"] >= 1


@needs_native
@needs_pallas
@pytest.mark.parametrize("n_fam,fam,L", [(8, 4, 48), (9, 5, 100)])
def test_fused_filter_parity(monkeypatch, n_fam, fam, L):
    """Forced pallas vs forced xla on the fused consensus->filter route:
    non-suspect stats rows and gathered survivor columns bit-identical;
    suspect rows (either backend's) host-resolve to the same columns, so
    published records are byte-identical regardless of which guard fired."""
    codes, quals, counts, starts = _family_batch(n_fam, fam, L)
    rng = np.random.default_rng(L)
    lens = rng.integers(L - 7, L + 1, size=n_fam).astype(np.int32)
    cfg = FilterConfig.new([fam], [0.025], [0.08], min_base_quality=25,
                           min_mean_base_quality=25.0)
    stage = SimplexFilterStage(cfg, _Opts())

    def run(backend):
        monkeypatch.setenv("FGUMI_TPU_KERNEL", backend)
        kernel = ConsensusKernel(quality_tables(45, 40))
        kernel.set_force_device()
        cd, qd, seg, _st, F = pad_segments(codes, quals, counts)
        t = kernel.device_call_segments_wire(
            cd, qd, seg, F, n_fam, full=True,
            filter_params=(np.int32(1), np.int32(40), lens,
                           stage.dev_params))
        got = kernel.resolve_segments_wire_filtered(t, codes, quals,
                                                    starts)
        assert got[0] == "stats"
        _, stats, resident = got
        rows = np.arange(n_fam, dtype=np.int64)
        fb, fq, d32, e32 = kernel.filter_gather_filtered(resident, rows)
        sus = kernel.filter_resolve_suspect_rows(resident, rows, starts,
                                                 codes, quals)
        resident.release()
        return (stats.copy(), fb.copy(), fq.copy(),
                tuple(np.array(a, copy=True) for a in sus))

    sa, fba, fqa, susa = run("xla")
    sb, fbb, fqb, susb = run("pallas")
    in_len = np.arange(L)[None, :] < lens[:, None]
    clean = (sa[:, S_SUSPECT] == 0) & (sb[:, S_SUSPECT] == 0)
    assert clean.any()
    np.testing.assert_array_equal(sa[clean, :S_SUSPECT],
                                  sb[clean, :S_SUSPECT])
    np.testing.assert_array_equal(np.where(in_len[clean], fba[clean], 0),
                                  np.where(in_len[clean], fbb[clean], 0))
    np.testing.assert_array_equal(np.where(in_len[clean], fqa[clean], 0),
                                  np.where(in_len[clean], fqb[clean], 0))
    for a, b in zip(susa, susb):
        np.testing.assert_array_equal(a, b)


@needs_native
@needs_pallas
def test_wide_qual_set_falls_back_to_packed2(monkeypatch):
    """>63 distinct quals decline the wire dictionary, so a forced
    ``pallas`` selection takes the packed2 XLA path — counted as an XLA
    dispatch, with identical output to a forced ``xla`` run."""
    batch = _family_batch(12, 3, 40, seed=5, qhi=90)
    assert len(np.unique(batch[1])) > 63
    ref = _run_full("xla", monkeypatch, *batch)
    px0, xx0 = DEVICE_STATS.kernel_pallas, DEVICE_STATS.kernel_xla
    got = _run_full("pallas", monkeypatch, *batch)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    assert DEVICE_STATS.kernel_pallas == px0
    assert DEVICE_STATS.kernel_xla == xx0 + 1


# -------------------------------------------------- fused-filter audit tap


def _filter_dispatch(kernel, codes, quals, counts, starts, lens, stage):
    cd, qd, seg, _st, F = pad_segments(codes, quals, counts)
    t = kernel.device_call_segments_wire(
        cd, qd, seg, F, len(counts), full=True,
        filter_params=(np.int32(1), np.int32(40), lens, stage.dev_params))
    return kernel.resolve_segments_wire_filtered(t, codes, quals, starts)


@needs_native
def test_filter_audit_clean_counts(monkeypatch):
    """AUDIT=all on the fused-filter route: the stats row and the
    survivor gather both check out against the f64 host oracle, the
    dispatch proceeds on the stats fast path, and the sentinel counts a
    clean verdict."""
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "all")
    kernel = ConsensusKernel(quality_tables(45, 40))
    kernel.set_force_device()
    codes, quals, counts, starts = _family_batch(6, 3, 48, seed=8)
    lens = np.full(6, 48, dtype=np.int32)
    cfg = FilterConfig.new([3], [0.025], [0.08], min_base_quality=25,
                           min_mean_base_quality=25.0)
    got = _filter_dispatch(kernel, codes, quals, counts, starts, lens,
                           SimplexFilterStage(cfg, _Opts()))
    assert got[0] == "stats"
    got[2].release()
    snap = SENTINEL.snapshot()
    assert snap["sampled"] >= 1 and snap["clean"] >= 1
    assert snap["divergent"] == 0
    assert BREAKER.snapshot()["state"] == "closed"


@needs_native
def test_filter_audit_divergence_repairs_and_trips(monkeypatch):
    """Injected corrupt-result on the fused-filter stats fetch: the
    inline audit detects the divergence, returns the oracle columns (the
    run degrades to the host filter for this batch, byte-identically),
    and the breaker records the sdc trip."""
    kernel = ConsensusKernel(quality_tables(45, 40))
    kernel.set_force_device()
    codes, quals, counts, starts = _family_batch(6, 3, 48, seed=9)
    lens = np.full(6, 48, dtype=np.int32)
    cfg = FilterConfig.new([3], [0.025], [0.08], min_base_quality=25,
                           min_mean_base_quality=25.0)
    stage = SimplexFilterStage(cfg, _Opts())

    # unfaulted full-column reference for the repair tuple
    from fgumi_tpu.ops.kernel import route_and_call_segments
    ref = route_and_call_segments(kernel, codes, quals, counts)

    base_resident = DEVICE_STATS.resident_bytes
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "all")
    monkeypatch.setenv("FGUMI_TPU_FAULT",
                       "device.fetch:corrupt-result:1.0:1")
    got = _filter_dispatch(kernel, codes, quals, counts, starts, lens,
                           stage)
    assert got[0] == "columns"
    for name, a, b in zip("wqde", ref, got[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"plane {name}")
    snap = SENTINEL.snapshot()
    assert snap["divergent"] >= 1
    assert snap["divergence"][0]["route"] == "device-filter"
    bs = BREAKER.snapshot()
    assert bs["sdc_trips"] >= 1
    assert any("silent data corruption" in t["reason"]
               for t in bs["transitions"])
    # the divergent resolve released its resident handles before repair
    assert DEVICE_STATS.resident_bytes == base_resident
