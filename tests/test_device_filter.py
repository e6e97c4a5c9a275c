"""Fused consensus→filter route (ISSUE 11).

Covers: the exact integer reformulation of the per-base error-rate mask,
fused-mask-kernel parity against the host twin at bucket-edge shapes,
CLI forced-route parity for all three engines (`--device-filter` output
record-identical to <engine> | filter), upload byte-identity under
retry and OOM batch-halving, staging-pool reuse, and resident-byte
release on the deadline/abandon path (PR 7 wedge machinery).
"""

import threading
import time

import numpy as np
import pytest

from fgumi_tpu.cli import main as cli_main
from fgumi_tpu.consensus.device_filter import S_SUSPECT, SimplexFilterStage
from fgumi_tpu.consensus.filter import (FilterConfig, FilterThresholds,
                                        R_ERROR_RATE, R_INSUFFICIENT,
                                        R_LOW_QUALITY, R_NO_CALLS, R_PASS,
                                        base_error_rate_table,
                                        simplex_read_verdicts)
from fgumi_tpu.io.bam import BamReader
from fgumi_tpu.native import batch as nb
from fgumi_tpu.ops import oracle
from fgumi_tpu.ops.kernel import (DEVICE_FEEDER, DEVICE_STATS,
                                  ConsensusKernel, DeadlineExceeded,
                                  ResidentHandles, pad_segments)
from fgumi_tpu.ops.tables import quality_tables
from fgumi_tpu.utils import faults

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library unavailable")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for var in ("FGUMI_TPU_FAULT", "FGUMI_TPU_DEVICE_FILTER",
                "FGUMI_TPU_ROUTE"):
        monkeypatch.delenv(var, raising=False)
    faults.reset()
    from fgumi_tpu.ops import breaker as breaker_mod
    from fgumi_tpu.ops.router import ROUTER

    breaker_mod.BREAKER.reset()
    yield
    faults.reset()
    breaker_mod.BREAKER.reset()
    ROUTER.reset()


def _records(path):
    with BamReader(path) as r:
        return [bytes(rec.data) for rec in r]


# ------------------------------------------------------------ exact tables

def test_base_error_rate_table_matches_f64_division():
    rng = np.random.default_rng(5)
    for rate in (0.0, 0.025, 0.1, 1 / 3, 0.5, 1.0, rng.uniform(), 0.0999999):
        tab = base_error_rate_table(rate, size=512)
        c = np.arange(1, 512, dtype=np.int64)
        for e in range(0, 64):
            host = e / c > rate            # the f64 reference comparison
            dev = e >= tab[c]              # the device's integer compare
            assert (host == dev).all(), (rate, e)


def test_simplex_read_verdict_precedence():
    t = FilterThresholds(3, 0.1, 0.1)
    # depth outranks error rate; later checks only touch passing reads
    v = simplex_read_verdicts(
        np.array([2, 5, 5, 5, 5]), np.float32([0.5, 0.5, 0.0, 0.0, 0.0]),
        np.array([0, 0, 10, 400, 400]), np.array([0, 0, 0, 0, 9]),
        np.array([10, 10, 10, 10, 10]), t, 30.0, 0.2)
    assert list(v) == [R_INSUFFICIENT, R_ERROR_RATE, R_LOW_QUALITY,
                       R_PASS, R_NO_CALLS]


# ------------------------------------------------- fused kernel vs host twin

@pytest.mark.parametrize("n_fam,fam", [(7, 3), (8, 4), (9, 5), (65, 3)])
def test_fused_kernel_matches_host_twin(n_fam, fam):
    """The device mask kernel and the host column twin must agree on every
    stat and masked column for non-suspect rows, at shapes straddling the
    8-aligned segment-bucket edges and with ragged consensus lengths."""
    kernel = ConsensusKernel(quality_tables(45, 40))
    kernel.set_force_device()
    cfg = FilterConfig.new([fam], [0.025], [0.08], min_base_quality=25,
                           min_mean_base_quality=25.0)

    class _Opts:
        min_reads = 1
        min_consensus_base_quality = 40
        produce_per_base_tags = True

    stage = SimplexFilterStage(cfg, _Opts())
    rng = np.random.default_rng(n_fam * 7 + fam)
    L = 48
    codes = rng.integers(0, 5, size=(n_fam * fam, L), dtype=np.uint8)
    quals = rng.integers(15, 41, size=(n_fam * fam, L), dtype=np.uint8)
    counts = np.full(n_fam, fam, dtype=np.int64)
    starts = (np.arange(n_fam + 1) * fam).astype(np.int64)
    lens = rng.integers(L - 7, L + 1, size=n_fam).astype(np.int32)

    cd, qd, seg, _st, F = pad_segments(codes, quals, counts)
    ticket = kernel.device_call_segments_wire(
        cd, qd, seg, F, n_fam, full=True,
        filter_params=(np.int32(1), np.int32(40), lens, stage.dev_params))
    got = kernel.resolve_segments_wire_filtered(ticket, codes, quals, starts)
    assert got[0] == "stats"
    _, dev_stats, resident = got
    dev_stats = dev_stats.astype(np.int64)

    # host twin over the standard full resolve
    cd, qd, seg, _st, F = pad_segments(codes, quals, counts)
    t2 = kernel.device_call_segments_wire(cd, qd, seg, F, n_fam, full=True)
    w, q, d, e = kernel.resolve_segments_wire(t2, codes, quals, starts)
    b, qq = oracle.apply_consensus_thresholds(w, q, d, 1, 40)
    fb_h, fq_h, stats_h = stage.host_filter_columns(b, qq, d, e, lens)

    clean = dev_stats[:, S_SUSPECT] == 0
    assert clean.any()
    assert (dev_stats[clean, :6] == stats_h[clean, :6]).all()
    rows = np.nonzero(clean)[0]
    fb_d, fq_d, d32, e32 = kernel.filter_gather_filtered(resident, rows)
    in_len = np.arange(L)[None, :] < lens[rows, None]
    assert (np.where(in_len, fb_d, 0) == np.where(in_len, fb_h[rows], 0)).all()
    assert (np.where(in_len, fq_d, 0) == np.where(in_len, fq_h[rows], 0)).all()
    assert (np.where(in_len, d32, 0)
            == np.where(in_len, d[rows].astype(np.int32), 0)).all()
    # suspect rows complete through the ordinary host path
    if (~clean).any():
        sus_rows = np.nonzero(~clean)[0]
        ws, qs_, ds, es = kernel.filter_resolve_suspect_rows(
            resident, sus_rows, starts, codes, quals)
        assert (ws == w[sus_rows]).all()
        assert (qs_ == q[sus_rows]).all()
        assert (ds == d[sus_rows].astype(np.int32)).all()
        assert (es == e[sus_rows].astype(np.int32)).all()
    resident.release()
    assert DEVICE_STATS.snapshot().get("resident_bytes", 0) == 0


# ------------------------------------------------------------- CLI parity

@pytest.fixture(scope="module")
def grouped_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("devfilt") / "grouped.bam")
    rc = cli_main(["simulate", "grouped-reads", "-o", path,
                   "--num-families", "90", "--family-size", "4",
                   "--family-size-distribution", "longtail", "--seed", "21"])
    assert rc == 0
    return path


_FILT = ["--filter-min-reads", "3", "--filter-min-mean-base-quality", "30",
         "--filter-min-base-quality", "20"]


def _two_stage_simplex(grouped_bam, tmp_path):
    cons = str(tmp_path / "cons.bam")
    ref = str(tmp_path / "ref.bam")
    assert cli_main(["simplex", "-i", grouped_bam, "-o", cons,
                     "--min-reads", "1"]) == 0
    assert cli_main(["filter", "-i", cons, "-o", ref, "-M", "3", "-q", "30",
                     "-N", "20"]) == 0
    return ref


@pytest.mark.parametrize("env", [
    {"FGUMI_TPU_ROUTE": "device", "FGUMI_TPU_HOST_ENGINE": "0"},
    {"FGUMI_TPU_ROUTE": "device", "FGUMI_TPU_HOST_ENGINE": "0",
     "FGUMI_TPU_DEVICE_FILTER": "0"},
    {"FGUMI_TPU_ROUTE": "host", "FGUMI_TPU_HOST_ENGINE": "0",
     "FGUMI_TPU_HYBRID": "1"},
])
def test_cli_simplex_parity(grouped_bam, tmp_path, monkeypatch, env):
    ref = _two_stage_simplex(grouped_bam, tmp_path)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    fused = str(tmp_path / "fused.bam")
    assert cli_main(["simplex", "-i", grouped_bam, "-o", fused,
                     "--min-reads", "1", "--device-filter"] + _FILT) == 0
    assert _records(fused) == _records(ref)


def test_cli_simplex_parity_mesh(grouped_bam, tmp_path, monkeypatch):
    """--device-filter + a >1-device mesh: the fused stage resolves the
    standard mesh ticket and filters host-side — records identical."""
    ref = _two_stage_simplex(grouped_bam, tmp_path)
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "device")
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    fused = str(tmp_path / "mesh_fused.bam")
    assert cli_main(["simplex", "-i", grouped_bam, "-o", fused,
                     "--min-reads", "1", "--devices", "2",
                     "--device-filter"] + _FILT) == 0
    assert _records(fused) == _records(ref)


def test_cli_simplex_parity_classic_engine(grouped_bam, tmp_path):
    ref = _two_stage_simplex(grouped_bam, tmp_path)
    fused = str(tmp_path / "fused_classic.bam")
    assert cli_main(["simplex", "-i", grouped_bam, "-o", fused,
                     "--min-reads", "1", "--classic",
                     "--device-filter"] + _FILT) == 0
    assert _records(fused) == _records(ref)


def test_cli_simplex_parity_under_wedge(grouped_bam, tmp_path, monkeypatch):
    """The deadline/abandon fallback (PR 7) must keep the fused route
    byte-identical: wedged dispatches complete on the host engine."""
    ref = _two_stage_simplex(grouped_bam, tmp_path)
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "device")
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    monkeypatch.setenv("FGUMI_TPU_HYBRID", "1")
    monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", "0.2:1")
    monkeypatch.setenv("FGUMI_TPU_FAULT", "device.wedge:hang:1.0")
    monkeypatch.setenv("FGUMI_TPU_FAULT_HANG_S", "3")
    fused = str(tmp_path / "wedged.bam")
    assert cli_main(["simplex", "-i", grouped_bam, "-o", fused,
                     "--min-reads", "1", "--device-filter"] + _FILT) == 0
    assert _records(fused) == _records(ref)
    # the abandoned dispatch is still hanging on the feeder thread (the
    # CLI returned at its deadline, not the hang's end): wait it out, or
    # the stale item wakes mid-NEXT-test and fires whatever fault spec
    # that test armed — consuming a count-limited budget meant for the
    # dispatch the test is actually measuring
    from fgumi_tpu.ops.kernel import DEVICE_FEEDER

    assert DEVICE_FEEDER.drain(timeout=15)


def test_cli_duplex_parity(tmp_path):
    dup = str(tmp_path / "dup.bam")
    assert cli_main(["simulate", "duplex-reads", "-o", dup,
                     "--num-molecules", "40", "--reads-per-strand", "3",
                     "--seed", "3"]) == 0
    cons = str(tmp_path / "dcons.bam")
    ref = str(tmp_path / "dref.bam")
    assert cli_main(["duplex", "-i", dup, "-o", cons,
                     "--min-reads", "1"]) == 0
    assert cli_main(["filter", "-i", cons, "-o", ref, "-M", "4,2,2",
                     "-q", "30"]) == 0
    fused = str(tmp_path / "dfused.bam")
    assert cli_main(["duplex", "-i", dup, "-o", fused, "--min-reads", "1",
                     "--device-filter", "--filter-min-reads", "4,2,2",
                     "--filter-min-mean-base-quality", "30"]) == 0
    assert _records(fused) == _records(ref)
    # duplex resident accounting drains by command exit
    assert DEVICE_STATS.snapshot().get("resident_bytes", 0) == 0


def test_cli_codec_parity(tmp_path):
    codec = str(tmp_path / "codec.bam")
    assert cli_main(["simulate", "codec-reads", "-o", codec,
                     "--seed", "8"]) == 0
    cons = str(tmp_path / "ccons.bam")
    ref = str(tmp_path / "cref.bam")
    assert cli_main(["codec", "-i", codec, "-o", cons]) == 0
    assert cli_main(["filter", "-i", cons, "-o", ref, "-M", "1,1,0"]) == 0
    fused = str(tmp_path / "cfused.bam")
    assert cli_main(["codec", "-i", codec, "-o", fused, "--device-filter",
                     "--filter-min-reads", "1,1,0"]) == 0
    assert _records(fused) == _records(ref)


# ----------------------------------------------- upload under retry/halving

def test_upload_identity_under_retry(grouped_bam, tmp_path, monkeypatch):
    """An upload whose dispatch fails transiently must be RE-UPLOADED on
    retry (the device buffers died with the failed dispatch; the host
    staging buffer survives) — output identical to a clean run."""
    ref = _two_stage_simplex(grouped_bam, tmp_path)
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "device")
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    monkeypatch.setenv("FGUMI_TPU_DEVICE_BACKOFF_S", "0.01")
    monkeypatch.setenv("FGUMI_TPU_FAULT", "device.dispatch:raise:1.0:1")
    # deadlines off: on a slow shared-core host the deadline-abandon path
    # can preempt the retry this test exists to observe (the batch then
    # completes via host fallback with retries == 0 — a different,
    # separately-tested degrade path)
    monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", "0")
    out = str(tmp_path / "upload_retry.bam")
    assert cli_main(["simplex", "-i", grouped_bam, "-o", out,
                     "--min-reads", "1", "--device-filter"] + _FILT) == 0
    assert _records(out) == _records(ref)
    assert DEVICE_STATS.retries >= 1


def test_upload_identity_under_oom_halving(grouped_bam, tmp_path,
                                           monkeypatch):
    """An injected RESOURCE_EXHAUSTED halves the batch and re-dispatches
    both halves; the output bytes cannot change."""
    ref = _two_stage_simplex(grouped_bam, tmp_path)
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "device")
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    monkeypatch.setenv("FGUMI_TPU_FAULT", "device.dispatch:oom:1.0:1")
    out = str(tmp_path / "upload_oom.bam")
    assert cli_main(["simplex", "-i", grouped_bam, "-o", out,
                     "--min-reads", "1", "--device-filter"] + _FILT) == 0
    assert _records(out) == _records(ref)
    assert DEVICE_STATS.batch_splits >= 1


def test_staging_pool_reuses_after_warmup():
    from fgumi_tpu.ops.datapath import STAGING_POOL

    kernel = ConsensusKernel(quality_tables(45, 40))
    kernel.set_force_device()
    rng = np.random.default_rng(2)
    codes = rng.integers(0, 4, size=(96, 32), dtype=np.uint8)
    quals = rng.integers(20, 40, size=(96, 32), dtype=np.uint8)
    counts = np.full(24, 4, dtype=np.int64)
    starts = (np.arange(25) * 4).astype(np.int64)

    def once():
        cd, qd, seg, _st, F = pad_segments(codes, quals, counts)
        t = kernel.device_call_segments_wire(cd, qd, seg, F, 24, full=True)
        kernel.resolve_segments_wire(t, codes, quals, starts)

    once()
    allocs0 = STAGING_POOL.allocs
    for _ in range(3):
        once()
    assert STAGING_POOL.allocs == allocs0  # zero per-dispatch staging allocs


# --------------------------------------------------- resident-byte release

def test_resident_handles_release_idempotent():
    arrays = (np.zeros((8, 16), np.uint8), np.zeros((8, 16), np.uint16))
    base = DEVICE_STATS.resident_bytes
    h = ResidentHandles(arrays)
    assert DEVICE_STATS.resident_bytes == base + h.nbytes
    h.release()
    h.release()
    assert DEVICE_STATS.resident_bytes == base
    assert h.arrays is None


def test_resident_release_on_abandoned_late_dispatch():
    """A fused dispatch abandoned at its deadline (PR 7 path) must release
    its resident-byte accounting when the late result is discarded."""
    release = threading.Event()
    base = DEVICE_STATS.resident_bytes

    def _late_dispatch():
        release.wait(10)
        return ("stats", ResidentHandles((np.zeros(1024, np.uint8),)))

    ticket = DEVICE_FEEDER.submit(_late_dispatch, upload_bytes=1)
    with pytest.raises(DeadlineExceeded):
        ticket.wait(0.05)
    DEVICE_FEEDER.abandon(ticket)
    release.set()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and \
            (DEVICE_FEEDER._inflight or
             DEVICE_STATS.resident_bytes != base):
        time.sleep(0.01)
    assert DEVICE_STATS.resident_bytes == base
    assert DEVICE_FEEDER._inflight == 0


def test_router_prices_filtered_fetch(monkeypatch):
    """decide_batch(filtered=True) prices the fused fetch with the
    keep-rate EWMA: a measured low keep rate shrinks the down-bytes term
    and the routing snapshot exposes the rate."""
    from fgumi_tpu.ops.router import ROUTER

    ROUTER.reset()
    ROUTER.observe_filter_keep(5, 100)
    snap = ROUTER.snapshot()
    assert snap["filter_keep_rate"] == pytest.approx(0.05)
