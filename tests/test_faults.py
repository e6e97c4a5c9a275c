"""Fault-injection registry + chaos tests.

The resilience contract (docs/resilience.md): under any single injected
fault, a command either fails with a clean diagnostic and a nonzero exit
code, or completes with byte-identical output to a fault-free run (after
retry / batch split / host fallback). These tests arm each fault point and
assert exactly that — deterministically, via FGUMI_TPU_FAULT_SEED.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from fgumi_tpu.cli import main as cli_main
from fgumi_tpu.utils import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("FGUMI_TPU_FAULT", raising=False)
    faults.reset()
    yield
    faults.reset()


def _arm(monkeypatch, spec):
    monkeypatch.setenv("FGUMI_TPU_FAULT", spec)
    faults.reset()


# ---------------------------------------------------------------- registry

def test_parse_rejects_unknown_point(monkeypatch):
    _arm(monkeypatch, "no.such.point:raise:1.0")
    with pytest.raises(ValueError, match="unknown fault point"):
        faults.fire("reader.decompress")


def test_parse_rejects_unknown_kind(monkeypatch):
    _arm(monkeypatch, "reader.decompress:explode:1.0")
    with pytest.raises(ValueError, match="unknown kind"):
        faults.fire("reader.decompress")


def test_count_budget(monkeypatch):
    _arm(monkeypatch, "pipeline.process:raise:1.0:2")
    for _ in range(2):
        with pytest.raises(faults.InjectedFault):
            faults.fire("pipeline.process")
    # budget exhausted: every later fire is a no-op
    assert faults.fire("pipeline.process", b"x") == b"x"
    assert not faults.armed("pipeline.process")


def test_probability_deterministic(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_FAULT_SEED", "7")

    def pattern():
        _arm(monkeypatch, "pipeline.process:raise:0.5")
        hits = []
        for _ in range(32):
            try:
                faults.fire("pipeline.process")
                hits.append(0)
            except faults.InjectedFault:
                hits.append(1)
        return hits

    a, b = pattern(), pattern()
    assert a == b
    assert 0 < sum(a) < 32  # the coin actually flips both ways


def test_corrupt_bytes_deterministic(monkeypatch):
    _arm(monkeypatch, "reader.decompress:corrupt-bytes:1.0")
    data = bytes(range(256)) * 8
    c1 = faults.fire("reader.decompress", data)
    faults.reset()
    c2 = faults.fire("reader.decompress", data)
    assert c1 == c2
    assert c1 != data and len(c1) == len(data)


def test_oom_message_carries_resource_exhausted(monkeypatch):
    _arm(monkeypatch, "device.dispatch:oom:1.0")
    with pytest.raises(faults.InjectedOom, match="RESOURCE_EXHAUSTED"):
        faults.fire("device.dispatch")


def test_disarmed_is_noop():
    assert faults.fire("reader.decompress", b"abc") == b"abc"
    assert not faults.armed("reader.decompress")


# ------------------------------------------------------------- chaos (CLI)

@pytest.fixture(scope="module")
def grouped_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("chaos") / "sim.bam")
    rc = cli_main(["simulate", "grouped-reads", "-o", path,
                   "--num-families", "25", "--family-size", "4",
                   "--error-rate", "0.02", "--seed", "11"])
    assert rc == 0
    return path


def _simplex(inp, out, extra=()):
    return cli_main(["simplex", "-i", inp, "-o", out, "--min-reads", "1",
                     *extra])


@pytest.mark.parametrize("point", ["reader.decompress", "writer.compress",
                                   "native.batch", "pipeline.process"])
def test_chaos_raise_is_clean_failure(grouped_bam, tmp_path, monkeypatch,
                                      point):
    """An injected raise at each host-side point exits nonzero without
    leaving a partial file under the final output name."""
    out = str(tmp_path / "out.bam")
    extra = ("--threads", "4") if point == "pipeline.process" else ()
    _arm(monkeypatch, f"{point}:raise:1.0:1")
    rc = _simplex(grouped_bam, out, extra)
    monkeypatch.delenv("FGUMI_TPU_FAULT")
    faults.reset()
    if rc == 0:
        # the fault landed off the consensus path (e.g. a native.batch call
        # before any data flowed) or was absorbed; output must then be
        # byte-identical to a clean run written under the same argv
        clean = str(tmp_path / "clean") ; os.mkdir(clean)
        rc2 = cli_main(["simplex", "-i", grouped_bam,
                        "-o", os.path.join(clean, "out.bam"),
                        "--min-reads", "1", *extra])
        assert rc2 == 0
        with open(out, "rb") as a, \
                open(os.path.join(clean, "out.bam"), "rb") as b:
            da, db = a.read(), b.read()
        # records must match; headers differ only in the @PG CL line
        from fgumi_tpu.io.bam import BamReader
        ra = [r.data for r in BamReader(out)]
        rb = [r.data for r in BamReader(os.path.join(clean, "out.bam"))]
        assert ra == rb
    else:
        assert rc != 0
        # crash-safe commit: no partial file under the final name
        assert not os.path.exists(out), \
            f"partial output left under final name after rc={rc}"


def test_chaos_corrupt_input_is_clean_failure(grouped_bam, tmp_path,
                                              monkeypatch, caplog):
    """corrupt-bytes at reader.decompress must surface as a diagnosed input
    error (rc=2) — never a silent success or a partial output."""
    out = str(tmp_path / "out.bam")
    _arm(monkeypatch, "reader.decompress:corrupt-bytes:1.0")
    rc = _simplex(grouped_bam, out)
    assert rc != 0
    assert not os.path.exists(out)


def _run_cli(args, env, timeout=240):
    return subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", *args], cwd=REPO,
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "", **env})


@pytest.fixture(scope="module")
def device_parity_runs(grouped_bam, tmp_path_factory):
    """One clean device-path run, reused by the retry/oom parity tests.

    Runs in subprocesses (fresh jax, forced device path) from identical
    working directories so argv — and therefore the @PG CL header line —
    matches byte-for-byte."""
    base = tmp_path_factory.mktemp("parity")
    d = base / "clean"
    d.mkdir()
    # FGUMI_TPU_ROUTE=device: the adaptive cost model would price these
    # small workloads host-side and the device fault points would not fire
    env = {"FGUMI_TPU_HOST_ENGINE": "0", "FGUMI_TPU_ROUTE": "device"}
    p = _run_cli(["simplex", "-i", grouped_bam, "-o", str(d / "out.bam"),
                  "--min-reads", "1"], env)
    assert p.returncode == 0, p.stderr
    return base, grouped_bam, (d / "out.bam").read_bytes()


def test_device_dispatch_retry_byte_identical(device_parity_runs):
    """Acceptance: FGUMI_TPU_FAULT=device.dispatch:raise:1.0:2 completes
    with byte-identical output (bounded retry absorbs both failures)."""
    base, inp, clean = device_parity_runs
    d = base / "retry"
    d.mkdir()
    p = _run_cli(["simplex", "-i", inp, "-o", str(d / "out.bam"),
                  "--min-reads", "1"],
                 {"FGUMI_TPU_HOST_ENGINE": "0", "FGUMI_TPU_ROUTE": "device",
                  "FGUMI_TPU_FAULT": "device.dispatch:raise:1.0:2"})
    assert p.returncode == 0, p.stderr
    assert "retry" in p.stderr  # the retry path actually engaged
    got = (d / "out.bam").read_bytes()
    # same basename but different directory: normalize the @PG CL line by
    # comparing decoded records + all non-CL header lines
    _assert_same_bam(base / "clean" / "out.bam", d / "out.bam")
    assert len(got) > 0 and clean  # both runs produced data


def test_device_dispatch_exhausted_falls_back_to_host(device_parity_runs):
    """A permanently-failing dispatch (count unbounded) degrades to the
    native f64 host engine and still matches the clean run exactly."""
    base, inp, _clean = device_parity_runs
    d = base / "fallback"
    d.mkdir()
    p = _run_cli(["simplex", "-i", inp, "-o", str(d / "out.bam"),
                  "--min-reads", "1"],
                 {"FGUMI_TPU_HOST_ENGINE": "0", "FGUMI_TPU_ROUTE": "device",
                  "FGUMI_TPU_DEVICE_BACKOFF_S": "0.01",
                  "FGUMI_TPU_FAULT": "device.dispatch:raise:1.0"})
    assert p.returncode == 0, p.stderr
    assert "host engine" in p.stderr  # fallback engaged, loudly
    _assert_same_bam(base / "clean" / "out.bam", d / "out.bam")


def test_device_dispatch_oom_splits_batch(device_parity_runs):
    """RESOURCE_EXHAUSTED halves the batch and re-dispatches; output is
    identical (order preserved). Wire path forced via FGUMI_TPU_HYBRID=0."""
    base, inp, _clean = device_parity_runs
    d0 = base / "wire_clean"
    d1 = base / "wire_oom"
    d0.mkdir()
    d1.mkdir()
    env = {"FGUMI_TPU_HOST_ENGINE": "0", "FGUMI_TPU_HYBRID": "0"}
    p0 = _run_cli(["simplex", "-i", inp, "-o", str(d0 / "out.bam"),
                   "--min-reads", "1"], env)
    assert p0.returncode == 0, p0.stderr
    p1 = _run_cli(["simplex", "-i", inp, "-o", str(d1 / "out.bam"),
                   "--min-reads", "1"],
                  {**env, "FGUMI_TPU_FAULT": "device.dispatch:oom:1.0:1"})
    assert p1.returncode == 0, p1.stderr
    assert "halving" in p1.stderr  # the split path actually engaged
    _assert_same_bam(d0 / "out.bam", d1 / "out.bam")


@pytest.fixture(scope="module")
def deep_grouped_bam(tmp_path_factory):
    """A larger grouped BAM so the threaded wire path keeps the upload
    pipeline occupied (multiple dispatches in flight at depth 2)."""
    path = str(tmp_path_factory.mktemp("chaos_deep") / "sim.bam")
    rc = cli_main(["simulate", "grouped-reads", "-o", path,
                   "--num-families", "300",
                   "--family-size-distribution", "longtail",
                   "--read-length", "60", "--error-rate", "0.02",
                   "--seed", "13"])
    assert rc == 0
    return path


@pytest.mark.parametrize("fault,marker", [
    ("device.dispatch:raise:1.0:2", "retry"),
    ("device.dispatch:oom:1.0:1", "halving"),
    ("device.dispatch:raise:1.0", "host engine"),
])
def test_pipelined_dispatch_faults_byte_identical(deep_grouped_bam,
                                                  tmp_path, fault, marker):
    """Depth-2 upload pipeline (FGUMI_TPU_FEEDER_DEPTH=2, wire path,
    threaded resolve): injected device.dispatch faults still retry / halve
    / fall back per dispatch, and the output never reorders or drops a
    batch — byte-identical to the clean run."""
    env = {"FGUMI_TPU_HOST_ENGINE": "0", "FGUMI_TPU_HYBRID": "0",
           "FGUMI_TPU_FEEDER_DEPTH": "2",
           "FGUMI_TPU_DEVICE_BACKOFF_S": "0.01"}
    clean = tmp_path / "clean"
    clean.mkdir()
    p = _run_cli(["simplex", "-i", deep_grouped_bam,
                  "-o", str(clean / "out.bam"), "--min-reads", "1",
                  "--threads", "4"], env)
    assert p.returncode == 0, p.stderr
    faulty = tmp_path / "faulty"
    faulty.mkdir()
    p = _run_cli(["simplex", "-i", deep_grouped_bam,
                  "-o", str(faulty / "out.bam"), "--min-reads", "1",
                  "--threads", "4"], {**env, "FGUMI_TPU_FAULT": fault})
    assert p.returncode == 0, p.stderr
    assert marker in p.stderr  # the targeted degradation path engaged
    _assert_same_bam(clean / "out.bam", faulty / "out.bam")


def _assert_same_bam(path_a, path_b):
    """Byte-identical records + header (modulo the @PG CL argv line, which
    legitimately embeds each run's own -o path)."""
    from fgumi_tpu.io.bam import BamReader

    with BamReader(str(path_a)) as a, BamReader(str(path_b)) as b:
        ha = [ln for ln in a.header.text.splitlines()
              if not ln.startswith("@PG")]
        hb = [ln for ln in b.header.text.splitlines()
              if not ln.startswith("@PG")]
        assert ha == hb
        ra = [r.data for r in a]
        rb = [r.data for r in b]
    assert ra == rb


@pytest.mark.slow
def test_chaos_hang_diagnosed_by_watchdog(grouped_bam, tmp_path,
                                          monkeypatch, caplog):
    """An injected hang in the process stage stalls the threaded pipeline
    long enough for the watchdog to log a stall snapshot; the run still
    completes once the hang releases."""
    import logging

    out = str(tmp_path / "out.bam")
    # host engine: the hang targets the host pipeline, and the in-process
    # 8-virtual-device auto-mesh path is unrelated to this test
    monkeypatch.setenv("FGUMI_TPU_HOST_ENGINE", "1")
    monkeypatch.setenv("FGUMI_TPU_FAULT_HANG_S", "3")
    _arm(monkeypatch, "pipeline.process:hang:1.0:1")
    with caplog.at_level(logging.WARNING, logger="fgumi_tpu"):
        rc = cli_main(["simplex", "-i", grouped_bam, "-o", out,
                       "--min-reads", "1", "--threads", "4",
                       "--devices", "1", "--deadlock-timeout", "1"])
    assert rc == 0
    assert os.path.exists(out)
    assert any("stalled" in r.message for r in caplog.records), \
        "watchdog never diagnosed the injected hang"
