"""Silent-corruption sentinel tests (ISSUE 14, ops/sentinel.py).

Covers: deterministic counter-based sampling, clean-audit no-op,
injected-divergence detection (breaker ``sdc`` trip, quarantine without
automatic half-open, flight evidence), audited re-admission, staging-pool
release on both verdicts, mesh per-device attribution, the
``--audit-output`` pre-commit file verification, and byte-identity of
audited vs unaudited CLI runs.
"""

import glob
import json
import os
import struct

import numpy as np
import pytest

from fgumi_tpu.cli import main as cli_main
from fgumi_tpu.io.bam import (BamHeader, BamWriter, audit_output_enabled,
                              set_audit_output)
from fgumi_tpu.io.errors import OutputIntegrityError
from fgumi_tpu.ops import kernel as K
from fgumi_tpu.ops.breaker import BREAKER, DeviceBreaker
from fgumi_tpu.ops.datapath import STAGING_POOL
from fgumi_tpu.ops.sentinel import SENTINEL, AuditSentinel, audit_rate
from fgumi_tpu.ops.tables import quality_tables


@pytest.fixture(autouse=True)
def _device_route(monkeypatch):
    """Force the adaptive layers onto the XLA device path (the sentinel
    only taps device resolves) and keep audits quiet by default."""
    from fgumi_tpu.utils import faults

    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "0")
    monkeypatch.setenv("FGUMI_TPU_ROUTE", "device")
    monkeypatch.delenv("FGUMI_TPU_AUDIT", raising=False)
    monkeypatch.delenv("FGUMI_TPU_FAULT", raising=False)
    faults.reset()  # identical FGUMI_TPU_FAULT values re-arm per test
    SENTINEL.reset()
    yield
    SENTINEL.drain(timeout=10)
    SENTINEL.reset()
    faults.reset()


def _kernel():
    return K.ConsensusKernel(quality_tables(45, 40))


def _batch(seed=0, n_fam=4, fam=3, L=48):
    rng = np.random.default_rng(seed)
    counts = np.full(n_fam, fam, dtype=np.int64)
    N = int(counts.sum())
    codes = rng.integers(0, 4, size=(N, L)).astype(np.uint8)
    quals = rng.integers(2, 40, size=(N, L)).astype(np.uint8)
    starts = np.zeros(n_fam + 1, dtype=np.int64)
    starts[1:] = np.cumsum(counts)
    return codes, quals, counts, starts


def _resolve(kern, codes, quals, counts, starts):
    return K.route_and_call_segments(kern, codes, quals, counts)


# ---------------------------------------------------------------------------
# sampling


def test_audit_rate_parse(monkeypatch):
    for v, want in (("off", 0), ("0", 0), ("false", 0), ("all", 1),
                    ("1", 1), ("16", 16), ("", 64), ("bogus", 64)):
        monkeypatch.setenv("FGUMI_TPU_AUDIT", v)
        assert audit_rate() == want, v


def test_sampling_is_deterministic(monkeypatch):
    """Same rate -> the same set of sampled dispatch ordinals, run to
    run: counter-based sampling has no randomness to drift."""
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "3")
    kern = _kernel()
    batch = _batch(seed=1)
    runs = []
    for _ in range(2):
        SENTINEL.reset()
        for _i in range(7):
            _resolve(kern, *batch)
        SENTINEL.drain()
        runs.append((list(SENTINEL.sampled_ordinals), SENTINEL.sampled))
    assert runs[0] == runs[1]
    # 1-in-3 of 7 dispatches -> ordinals 3 and 6
    assert runs[0][0] == [3, 6] and runs[0][1] == 2


def test_audit_off_is_a_no_op(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "off")
    kern = _kernel()
    out = _resolve(kern, *_batch(seed=2))
    assert out[0].shape[0] == 4
    snap = SENTINEL.snapshot()
    assert snap["sampled"] == 0 and snap["clean"] == 0


# ---------------------------------------------------------------------------
# clean audit


def test_clean_audit_counts_and_keeps_breaker_closed(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "all")
    kern = _kernel()
    out = _resolve(kern, *_batch(seed=3))
    SENTINEL.drain()
    snap = SENTINEL.snapshot()
    assert snap["sampled"] == 1 and snap["clean"] == 1
    assert snap["divergent"] == 0
    assert snap["devices"]["0"] == {"sampled": 1, "clean": 1,
                                    "divergent": 0}
    assert BREAKER.snapshot()["state"] == "closed"
    assert out[2].dtype == np.int32


def test_staging_pool_released_on_clean_verdict(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "all")
    kern = _kernel()
    batch = _batch(seed=4)
    _resolve(kern, *batch)
    SENTINEL.drain()
    before = STAGING_POOL.snapshot()
    _resolve(kern, *batch)
    SENTINEL.drain()
    after = STAGING_POOL.snapshot()
    # the second audit's input copies reuse the first audit's released
    # buffers: no fresh allocations for the audit shapes
    assert after["reuses"] > before["reuses"]
    assert SENTINEL.snapshot()["pending"] == 0


# ---------------------------------------------------------------------------
# divergence


def test_injected_divergence_trips_sdc_and_repairs(monkeypatch, tmp_path):
    from fgumi_tpu.observe.flight import FLIGHT

    FLIGHT.configure(str(tmp_path))
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "all")
    kern = _kernel()
    batch = _batch(seed=5)
    clean = _resolve(kern, *batch)
    monkeypatch.setenv("FGUMI_TPU_FAULT",
                       "device.fetch:corrupt-result:1.0:1")
    corrupted_run = _resolve(kern, *batch)
    snap = SENTINEL.snapshot()
    assert snap["divergent"] == 1
    rec = snap["divergence"][0]
    assert rec["families"] >= 1 and rec["fields"]
    assert rec["device_digest"] != rec["host_digest"]
    bs = BREAKER.snapshot()
    assert bs["state"] == "open"
    assert bs["sdc_trips"] == 1 and bs["sdc_quarantined"] is True
    assert any("silent data corruption" in t["reason"]
               for t in bs["transitions"])
    # inline (`all`) audit repaired the batch with the oracle tuple
    for a, b in zip(clean, corrupted_run):
        assert np.array_equal(a, b)
    # the black box carries both digests
    dumps = glob.glob(str(tmp_path / "flight-*-sdc-divergence.json"))
    assert dumps
    box = json.load(open(dumps[0]))
    assert box["attrs"]["device_digest"] == rec["device_digest"]
    assert box["attrs"]["host_digest"] == rec["host_digest"]


def test_staging_pool_released_on_divergent_verdict(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "all")
    kern = _kernel()
    batch = _batch(seed=6)
    monkeypatch.setenv("FGUMI_TPU_FAULT",
                       "device.fetch:corrupt-result:1.0:1")
    _resolve(kern, *batch)
    snap = SENTINEL.snapshot()
    assert snap["divergent"] == 1 and snap["pending"] == 0
    # divergent audit released its retained inputs back to the pool: the
    # next clean audit reuses them instead of allocating
    monkeypatch.delenv("FGUMI_TPU_FAULT")
    BREAKER.reset()  # lift the quarantine so the batch routes device again
    before = STAGING_POOL.snapshot()
    _resolve(kern, *batch)
    SENTINEL.drain()
    assert STAGING_POOL.snapshot()["reuses"] > before["reuses"]


def test_post_divergence_batches_route_host_byte_identically(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "all")
    kern = _kernel()
    batch = _batch(seed=7)
    clean = _resolve(kern, *batch)
    monkeypatch.setenv("FGUMI_TPU_FAULT",
                       "device.fetch:corrupt-result:1.0:1")
    _resolve(kern, *batch)
    monkeypatch.delenv("FGUMI_TPU_FAULT")
    # breaker open (sdc): the forced-device route is overridden to host
    from fgumi_tpu.ops.router import ROUTER

    after = _resolve(kern, *batch)
    for a, b in zip(clean, after):
        assert np.array_equal(a, b)
    assert ROUTER.snapshot()["last_decision"]["why"] == "sdc-quarantine"


# ---------------------------------------------------------------------------
# quarantine + audited re-admission (breaker units, injectable clock)


@pytest.fixture
def clock():
    state = {"t": 1000.0}

    def now():
        return state["t"]

    now.advance = lambda dt: state.__setitem__("t", state["t"] + dt)
    return now


def test_sdc_trip_does_not_half_open_when_readmit_disabled(clock,
                                                           monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_AUDIT_READMIT", "0")
    b = DeviceBreaker(now=clock)
    b.record_sdc("test")
    assert b.state == "open"
    clock.advance(3600.0)
    assert b.state == "open"  # cooldown elapsed; quarantine holds
    assert not b.allow()


def test_sdc_readmission_requires_audited_probes(clock, monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_AUDIT_READMIT", "2")
    monkeypatch.setenv("FGUMI_TPU_BREAKER_COOLDOWN_S", "5")
    b = DeviceBreaker(now=clock)
    b.record_sdc("test")
    assert b.state == "open" and b.audit_required()
    clock.advance(6.0)
    assert b.state == "half-open"
    # probe 1: ordinary resolve success releases the slot but must NOT
    # count toward closing — the device answered, not proved honest
    assert b.allow()
    b.record_success()
    assert b.state == "half-open"
    b.record_audit_clean()
    assert b.state == "half-open"  # 1 of 2 audited probes
    assert b.allow()
    b.record_success()
    b.record_audit_clean()
    assert b.state == "closed"
    assert not b.audit_required()
    snap = b.snapshot()
    assert any("quarantine lifted" in t["reason"]
               for t in snap["transitions"])


def test_sdc_redivergence_while_probing_reopens(clock, monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_AUDIT_READMIT", "2")
    monkeypatch.setenv("FGUMI_TPU_BREAKER_COOLDOWN_S", "5")
    b = DeviceBreaker(now=clock)
    b.record_sdc("first")
    clock.advance(6.0)
    assert b.state == "half-open"
    assert b.allow()
    b.record_sdc("probe diverged too")
    assert b.state == "open"
    assert b.snapshot()["sdc_trips"] == 2
    # hysteresis: the second trip doubled the cooldown
    clock.advance(6.0)
    assert b.state == "open"
    clock.advance(6.0)
    assert b.state == "half-open"


def test_stale_background_clean_audit_cannot_lift_quarantine(monkeypatch):
    """A background sample taken BEFORE the SDC trip whose clean verdict
    lands during the half-open window must NOT count as a re-admission
    probe — only force-audited (inline) probe dispatches may."""
    monkeypatch.setenv("FGUMI_TPU_AUDIT_READMIT", "1")
    monkeypatch.setenv("FGUMI_TPU_BREAKER_COOLDOWN_S", "0.1")
    s = AuditSentinel()
    kern = _kernel()
    codes, quals, counts, starts = _batch(seed=12)
    engine = kern._host()
    w, q, d, e, _ = engine.call_segments_counted(codes, quals, starts)
    BREAKER.record_sdc("test")
    import time

    time.sleep(0.2)
    assert BREAKER.state == "half-open" and BREAKER.audit_required()
    # simulate the stale pre-trip item reaching its verdict now: it was
    # retained UNFORCED, so its clean verdict must not close the breaker
    item = s._retain(kern, codes, quals, starts, w, q, d, e, 1, None,
                     None, -1, 1)
    item["forced"] = False
    assert s._audit_one(item) is None  # clean
    assert BREAKER.state == "half-open"
    assert BREAKER.audit_required()
    # whereas a forced probe verdict does lift it
    item = s._retain(kern, codes, quals, starts, w, q, d, e, 1, None,
                     None, -1, 2)
    item["forced"] = True
    assert s._audit_one(item) is None
    assert BREAKER.state == "closed" and not BREAKER.audit_required()


def test_queue_overflow_drops_before_retaining(monkeypatch):
    """Overflowed samples are dropped before the input copies are made:
    the staging pool sees no traffic for them."""
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "1")  # every tap sampled...
    s = AuditSentinel()
    kern = _kernel()
    codes, quals, counts, starts = _batch(seed=13)
    engine = kern._host()
    w, q, d, e, _ = engine.call_segments_counted(codes, quals, starts)
    # ...but routed to the background queue (bypass the inline branch by
    # pre-filling the queue past its cap and using rate N)
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "2")
    monkeypatch.setenv("FGUMI_TPU_AUDIT_QUEUE", "1")
    with s._lock:
        s._q.append((None, None))  # synthetic backlog; never executed
    before = STAGING_POOL.snapshot()
    assert s.maybe_audit(kern, codes, quals, starts, w, q, d, e) is None
    assert s.maybe_audit(kern, codes, quals, starts, w, q, d, e) is None
    snap = s.snapshot()  # ordinal 2 sampled (1-in-2) and dropped
    assert snap["dropped"] == 1 and snap["sampled"] == 1
    after = STAGING_POOL.snapshot()
    assert after["allocs"] == before["allocs"]
    assert after["reuses"] == before["reuses"]
    with s._lock:  # drop the synthetic backlog before the worker sees it
        s._q.clear()


def test_audited_readmission_end_to_end(monkeypatch):
    """Sentinel + breaker together: divergence -> quarantine -> audited
    probes lift it."""
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "off")  # only forced audits
    monkeypatch.setenv("FGUMI_TPU_AUDIT_READMIT", "1")
    monkeypatch.setenv("FGUMI_TPU_BREAKER_COOLDOWN_S", "0.1")
    kern = _kernel()
    batch = _batch(seed=8)
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "all")
    monkeypatch.setenv("FGUMI_TPU_FAULT",
                       "device.fetch:corrupt-result:1.0:1")
    _resolve(kern, *batch)
    monkeypatch.delenv("FGUMI_TPU_FAULT")
    monkeypatch.setenv("FGUMI_TPU_AUDIT", "off")
    assert BREAKER.snapshot()["state"] == "open"
    import time

    time.sleep(0.2)  # cooldown -> half-open (quarantined)
    assert BREAKER.audit_required()
    # the probe dispatch is force-audited inline despite FGUMI_TPU_AUDIT=off
    before = SENTINEL.snapshot()["sampled"]
    _resolve(kern, *batch)
    snap = SENTINEL.snapshot()
    assert snap["sampled"] == before + 1
    assert BREAKER.snapshot()["state"] == "closed"
    assert not BREAKER.audit_required()


# ---------------------------------------------------------------------------
# mesh per-device attribution


def test_mesh_divergence_attributes_to_the_corrupt_shard():
    """Divergent rows name the shard device that computed them via the
    ticket's (gather, F_loc) mapping."""
    s = AuditSentinel()
    kern = _kernel()
    codes, quals, counts, starts = _batch(seed=9, n_fam=4)
    engine = kern._host()
    w, q, d, e, _ = engine.call_segments_counted(codes, quals, starts)
    # family order j came from shard position gather[j]; F_loc = 2 ->
    # families 0,1 on device 0 and 2,3 on device 1
    gather = np.array([0, 1, 2, 3])
    bad_w = w.copy()
    bad_w[3, :4] ^= 1  # corrupt a family computed on shard 1
    os.environ["FGUMI_TPU_AUDIT"] = "all"
    try:
        repaired = s.maybe_audit(kern, codes, quals, starts,
                                 bad_w, q.copy(), d.copy(), e.copy(),
                                 devices=2, gather=gather, f_loc=2, slot=7)
    finally:
        os.environ.pop("FGUMI_TPU_AUDIT")
        BREAKER.reset()
    assert repaired is not None
    assert np.array_equal(repaired[0], w)
    snap = s.snapshot()
    rec = snap["divergence"][0]
    assert rec["devices"] == [1]
    assert snap["devices"]["1"]["divergent"] == 1
    assert snap["devices"]["0"]["divergent"] == 0
    assert snap["devices"]["0"]["clean"] == 1


# ---------------------------------------------------------------------------
# --audit-output


def _hdr():
    return BamHeader(
        text="@HD\tVN:1.6\tSO:coordinate\n@SQ\tSN:chr1\tLN:1000\n",
        ref_names=["chr1"], ref_lengths=[1000])


def _record(refid, pos, i):
    name = f"r{i}".encode() + b"\x00"
    data = bytearray()
    data += struct.pack("<iiBBHHHiiii", refid, pos, len(name), 30, 4680,
                        0, 4, 4, -1, -1, 0)
    data += name + bytes([0x12, 0x48]) + bytes([30, 30, 30, 30])
    return bytes(data)


@pytest.fixture
def audit_output():
    set_audit_output(True)
    assert audit_output_enabled()
    yield
    set_audit_output(False)


def _write_bam(path, n=40):
    w = BamWriter(str(path), _hdr())
    for i in range(n):
        w.write_record_bytes(_record(0, 10 + i, i))
    return w


def test_audit_output_clean_commit(tmp_path, audit_output):
    out = tmp_path / "ok.bam"
    w = _write_bam(out)
    w.close()
    assert out.exists()
    rec = SENTINEL.snapshot()["output"][-1]
    assert rec["ok"] and rec["records"] == 40 and rec["members"] >= 2


def test_audit_output_refuses_bitflipped_member(tmp_path, audit_output):
    out = tmp_path / "flip.bam"
    w = _write_bam(out)
    w._w.flush()
    w._w._f.flush()
    tmp = w._w._f._tmp
    with open(tmp, "r+b") as f:
        f.seek(60)
        byte = f.read(1)
        f.seek(60)
        f.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(OutputIntegrityError):
        w.close()
    # no partial file published, no temp residue
    assert not out.exists()
    assert not os.path.exists(tmp)
    assert SENTINEL.snapshot()["output"][-1]["ok"] is False


def test_audit_output_refuses_truncated_member(tmp_path, audit_output):
    out = tmp_path / "trunc.bam"
    w = _write_bam(out)
    # finish the stream manually so the EOF sentinel is on disk, then
    # chop the tail — a torn page-cache writeback signature
    w._w.flush()
    from fgumi_tpu.io.bgzf import BGZF_EOF

    w._w._f.write(BGZF_EOF)
    w._w._f.flush()
    tmp = w._w._f._tmp
    size = os.path.getsize(tmp)
    fobj = w._w._f
    with open(tmp, "r+b") as f:
        f.truncate(size - 9)
    with pytest.raises(OutputIntegrityError):
        fobj.close()
    assert not out.exists()
    assert not os.path.exists(tmp)


def test_audit_output_catches_in_stream_corruption(tmp_path, audit_output,
                                                   monkeypatch):
    """Corruption injected AFTER the writer's tally (the writer.compress
    fault point corrupts inside the BGZF layer) decompresses consistently
    — only the record/header digests can catch it."""
    out = tmp_path / "stream.bam"
    monkeypatch.setenv("FGUMI_TPU_FAULT",
                       "writer.compress:corrupt-bytes:1.0:1")
    w = _write_bam(out)
    with pytest.raises(OutputIntegrityError):
        w.close()
    assert not out.exists()


def test_audit_output_accepts_pos_minus_one_first(tmp_path, audit_output):
    """The sorter's coordinate key is pos+1: a mapped-reference record
    with pos=-1 (RNAME set, POS 0) legally sorts FIRST within its
    reference — the audit's order check must use the same semantics
    instead of rejecting the sorter's own correct output."""
    out = tmp_path / "posm1.bam"
    w = BamWriter(str(out), _hdr())
    w.write_record_bytes(_record(0, -1, 0))
    for i in range(3):
        w.write_record_bytes(_record(0, 10 + i, 1 + i))
    w.write_record_bytes(_record(-1, -1, 9))  # unmapped tail
    w.close()
    assert out.exists()
    assert SENTINEL.snapshot()["output"][-1]["ok"]


def test_audit_output_skips_without_atomic_commit(tmp_path, audit_output):
    from fgumi_tpu.utils.atomic import set_atomic_enabled

    set_atomic_enabled(False)
    try:
        out = tmp_path / "plain.bam"
        w = _write_bam(out)
        w.close()  # no pre-rename window: audit skipped, not failed
        assert out.exists()
    finally:
        set_atomic_enabled(True)


# ---------------------------------------------------------------------------
# CLI end-to-end


@pytest.fixture(scope="module")
def grouped_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("sentinel") / "grouped.bam")
    assert cli_main(["simulate", "grouped-reads", "-o", path,
                     "--num-families", "24", "--family-size", "3",
                     "--seed", "77"]) == 0
    return path


def _simplex(grouped_bam, cwd, env, report=None, extra_global=()):
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    prev = os.getcwd()
    os.chdir(cwd)
    try:
        argv = [*extra_global, "simplex", "-i", grouped_bam, "-o",
                "out.bam", "--min-reads", "1"]
        if report:
            argv = ["--run-report", report] + argv
        rc = cli_main(argv)
    finally:
        os.chdir(prev)
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return rc


def test_cli_byte_identity_audited_vs_unaudited(grouped_bam, tmp_path):
    outs = {}
    for label, audit in (("off", "off"), ("all", "all"),
                         ("sampled", "2")):
        d = tmp_path / label
        d.mkdir()
        rc = _simplex(grouped_bam, d,
                      {"FGUMI_TPU_HOST_ENGINE": "0",
                       "FGUMI_TPU_AUDIT": audit})
        assert rc == 0
        outs[label] = (d / "out.bam").read_bytes()
    assert outs["off"] == outs["all"] == outs["sampled"]


def test_cli_divergence_lands_in_run_report(grouped_bam, tmp_path):
    from fgumi_tpu.observe.report import validate_report

    d = tmp_path / "sdc"
    d.mkdir()
    rc = _simplex(
        grouped_bam, d,
        {"FGUMI_TPU_HOST_ENGINE": "0", "FGUMI_TPU_ROUTE": "device",
         "FGUMI_TPU_AUDIT": "all",
         "FGUMI_TPU_FAULT": "device.fetch:corrupt-result:1.0:1"},
        report="report.json")
    assert rc == 0
    report = json.load(open(d / "report.json"))
    assert validate_report(report) == []
    audit = report["audit"]
    assert audit["divergent"] >= 1 and audit["divergence"]
    breaker = report["device"]["breaker"]
    assert breaker["sdc_trips"] >= 1
    assert report["metrics"].get("device.audit.divergent", 0) >= 1


def test_cli_audit_output_exit_5_on_corruption(grouped_bam, tmp_path):
    d = tmp_path / "out5"
    d.mkdir()
    rc = _simplex(
        grouped_bam, d,
        {"FGUMI_TPU_FAULT": "writer.compress:corrupt-bytes:1.0:1"},
        extra_global=("--audit-output",))
    assert rc == 5
    assert not (d / "out.bam").exists()
    assert not glob.glob(str(d / ".out.bam.tmp.*"))
