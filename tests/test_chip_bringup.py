"""What must hold before chip budget is spent (ISSUE 21, CPU only).

Each test here fails at the commit before the chip bring-up: the Pallas
kernel did not lower for TPU, no real device error was ever retried (and a
naive repair would have retried compiler failures), the compile cache could
not be placed from outside, a cold router never tried the device, a cold
compile was on the deadline's clock, and the fused chain could die in a
jax import race.
"""

import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------ (a) Pallas lowers for TPU


@pytest.mark.parametrize("L", [100, 152])
@pytest.mark.parametrize("kind", ["full", "filter"])
def test_pallas_kernels_lower_for_tpu(kind, L):
    """``jax.export`` runs the Pallas TPU lowering and Mosaic's verifier
    without a chip: a block shape or op the lowering refuses fails here."""
    import jax
    import jax.numpy as jnp

    from fgumi_tpu.ops import pallas_kernel as pk

    n_rows, n_seg = 1024, 200
    s_tiles = -(-n_seg // pk.S_TILE)
    n_rt = n_rows // pk.R_TILE
    S = jax.ShapeDtypeStruct
    args = [S((n_rows, L), jnp.uint8), S((n_rt, 1, pk.R_TILE), jnp.int32),
            S((s_tiles,), jnp.int32), S((s_tiles,), jnp.int32),
            S((64,), jnp.int32), S((1,), jnp.int32)]
    if kind == "full":
        fn = pk._full_jit(n_seg, s_tiles, 2, False)
    else:
        fn = pk._filter_jit(n_seg, s_tiles, 2, False)
        i32 = S((), jnp.int32)
        args += [i32, i32, S((n_seg,), jnp.int32), i32,
                 S((32768,), jnp.int32), i32, i32]
    exported = jax.export.export(fn, platforms=("tpu",))(*args)
    assert "tpu_custom_call" in exported.mlir_module()


# ------------------------------------------- (b) which errors may be retried


def test_transient_classifies_real_jax_runtime_errors():
    import jax

    from fgumi_tpu.ops.kernel import _is_oom, _is_transient

    err = jax.errors.JaxRuntimeError
    assert _is_transient(err("UNAVAILABLE: TPU runtime restarting"))
    assert _is_transient(err("ABORTED: preempted"))
    # compiler failures never retry and never degrade, whatever the code
    for msg in ("INTERNAL: Mosaic failed to compile TPU kernel: bad tile",
                "UNKNOWN: XLA:TPU compile permanent error. Ran out of "
                "memory in memory space vmem",
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran "
                "out of memory in memory space vmem",
                "INVALID_ARGUMENT: shapes do not match",
                "INTERNAL: Core halted unexpectedly"):
        e = err(msg)
        assert not _is_transient(e), msg
        assert not _is_oom(e), msg
    # a run-time HBM exhaustion is the halving case, not a retry
    oom = err("RESOURCE_EXHAUSTED: Error allocating device buffer: "
              "Attempting to allocate 12.00G")
    assert _is_oom(oom) and not _is_transient(oom)
    # anything that is not a device runtime error (a lowering ValueError,
    # a bug) is never device weather
    assert not _is_transient(ValueError("UNAVAILABLE: looks like one"))
    assert not _is_transient(RuntimeError("UNAVAILABLE: but not jax's"))


def test_compile_failure_ends_the_dispatch(monkeypatch):
    """A compile failure raised inside a wire dispatch propagates out of
    resolve: no retry, no host-engine completion."""
    import jax

    from fgumi_tpu.ops import kernel as K
    from fgumi_tpu.ops.tables import quality_tables

    kern = K.ConsensusKernel(quality_tables(45, 40))
    kern.set_force_device()
    rng = np.random.default_rng(3)
    codes = rng.integers(0, 4, size=(40, 32), dtype=np.uint8)
    quals = rng.integers(20, 41, size=(40, 32), dtype=np.uint8)
    counts = np.full(10, 4, dtype=np.int64)
    starts = (np.arange(11) * 4).astype(np.int64)
    boom = jax.errors.JaxRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: unsupported op")

    def _raise(*_a, **_k):
        raise boom

    monkeypatch.setattr(K, "_consensus_segments_wire_full_jit", _raise)
    monkeypatch.setenv("FGUMI_TPU_KERNEL", "xla")
    before = K.DEVICE_STATS.snapshot()
    cd, qd, seg, _st, f_pad = K.pad_segments(codes, quals, counts)
    ticket = kern.device_call_segments_wire(cd, qd, seg, f_pad, 10,
                                            full=True)
    with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
        kern.resolve_segments_wire(ticket, codes, quals, starts)
    after = K.DEVICE_STATS.snapshot()
    for key in ("dispatch_retries", "host_fallbacks"):
        assert after.get(key, 0) == before.get(key, 0)


# ---------------------------------------------- (c) compile cache placement

_CACHE_CHILD = r"""
import json, os, sys
sys.path.insert(0, sys.argv[1])
from fgumi_tpu.ops.kernel import _ensure_jax
from fgumi_tpu.utils import compile_cache
from fgumi_tpu.observe.metrics import METRICS

jax = _ensure_jax()
import jax.numpy as jnp

jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)))
snap = METRICS.snapshot()
print(json.dumps({
    "returned": compile_cache.cache_dir(),
    "default": compile_cache.DEFAULT_CACHE_DIR,
    "configured": jax.config.jax_compilation_cache_dir,
    "compiles": snap.get("device.backend_compiles", 0),
    "hits": snap.get("device.compile_cache_hits", 0)}))
"""


def _cache_child(env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "FGUMI_TPU_NO_XLA_CACHE")}
    proc = subprocess.run([sys.executable, "-c", _CACHE_CHILD, REPO],
                          env={**base, **env}, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cache_dir_from_the_environment_is_used_and_filled(tmp_path):
    named = str(tmp_path / "named_cache")
    first = _cache_child({"JAX_COMPILATION_CACHE_DIR": named})
    # the code set no directory of its own, and the sub-second compile of
    # a tiny function still landed where the variable says
    assert first["configured"] == named and first["returned"] == named
    assert first["compiles"] >= 1 and first["hits"] == 0
    assert os.listdir(named)
    # a second process finds it: loads are not counted as compiles
    second = _cache_child({"JAX_COMPILATION_CACHE_DIR": named})
    assert second["compiles"] == 0 and second["hits"] >= 1


def test_cache_dir_default_is_the_checkout():
    got = _cache_child({})
    assert got["default"] == os.path.join(REPO, ".jax_cache")
    assert got["configured"] == got["returned"] == got["default"]


# --------------------------------------- (d) cold router tries the device


def test_cold_router_sends_first_batch_to_the_device():
    from fgumi_tpu.native import batch as nb
    from fgumi_tpu.ops.router import OffloadRouter

    if not nb.available():
        pytest.skip("native engine unavailable")

    class Hybrid:
        @staticmethod
        def hybrid_mode():
            return True

    router = OffloadRouter()
    # leg A's batch shape: ~73k rows of 100 columns, ~15k families
    for _ in range(3):  # until something has been measured
        assert router.decide_batch(Hybrid(), 73000, 15000, 100) == "device"
    assert router.snapshot()["last_decision"]["why"] == "probe-unmeasured"
    # once both sides are measured the cost model decides
    router.observe_device(7_300_000, 8_000_000, 0.004, 0.02, 0.024)
    router.observe_device(7_300_000, 8_000_000, 0.004, 0.02, 0.024)
    router.observe_host(7_300_000, 1.0)
    assert router.decide_batch(Hybrid(), 73000, 15000, 100) == "device"
    assert router.snapshot()["last_decision"]["why"] == "cost"


def test_router_feed_excludes_feeder_queue():
    """A dispatch's service time is its own run on the feeder plus the wait
    after its enqueue stamp — not the time its resolver spent waiting for
    the feeder to get to it (first-sight shapes, whose run is a compile,
    are not fed at all)."""
    from fgumi_tpu.ops.kernel import _device_service_s

    # resolver waited 2.0 s from t=1.0; the feeder (busy compiling an
    # earlier shape) ran this dispatch for 10 ms and enqueued it at
    # t=2.98; fetched at 3.0: 10 ms + 20 ms of service
    entry = {"fetch_wait_s": 2.0, "upload_s": 0.004, "run_s": 0.01,
             "t_exec": 2.98, "t_fetched": 3.0}
    assert _device_service_s(entry) == pytest.approx(0.03)
    # resolver arrived after the enqueue: all of its wait is service
    entry = {"fetch_wait_s": 0.004, "upload_s": 0.004, "run_s": 0.01,
             "t_exec": 2.0, "t_fetched": 3.0}
    assert _device_service_s(entry) == pytest.approx(0.014)
    # no feeder stamps (sync paths): upload + wait, as before
    assert _device_service_s({"fetch_wait_s": 0.5, "upload_s": 0.1}) == 0.6


# ------------------------------------- a compile is not read as a wedge


def test_first_dispatch_of_a_shape_waits_to_the_ceiling(monkeypatch):
    from fgumi_tpu.ops import kernel as K

    monkeypatch.setenv("FGUMI_TPU_DISPATCH_DEADLINE_S", "30:300")
    slot = K.DEVICE_STATS.begin_in_flight(0)
    K.DEVICE_STATS.note_pred(slot, 0.05)
    ticket = K.DispatchTicket()
    ticket.slot = slot
    ticket.new_shape = True
    assert K.ticket_deadline_s(ticket) == 300.0
    ticket.new_shape = False
    assert K.ticket_deadline_s(ticket) == 30.0  # 0.05 s x 20, floored
    K.DEVICE_STATS.end_in_flight(slot, 0, 0.0)


def test_feeder_marks_first_sight_shapes():
    from fgumi_tpu.ops.datapath import SHAPE_REGISTRY
    from fgumi_tpu.ops.kernel import DEVICE_FEEDER

    tickets = []
    for new in (True, False):
        with SHAPE_REGISTRY.attribute_compiles(new):
            tickets.append(DEVICE_FEEDER.submit(lambda: 1))
    for t in tickets:
        assert t.wait(30) == 1
        DEVICE_FEEDER.mark_resolved(t)
    assert [t.new_shape for t in tickets] == [True, False]


# ------------------------------------------- the report says what ran where


def test_report_validation_requires_device_identity():
    from fgumi_tpu.observe.report import SCHEMA_VERSION, validate_report

    report = {"schema_version": SCHEMA_VERSION, "tool": "fgumi-tpu",
              "command": "simplex", "argv": ["simplex"], "started_unix": 1.0,
              "wall_s": 0.5, "exit_status": 0, "pid": 1, "metrics": {},
              "device": {"dispatches": 3}}
    assert any("device.platform" in e for e in validate_report(report))
    report["device"].update(platform="tpu", device_kind="TPU v5 lite",
                            device_count=1)
    assert validate_report(report) == []


def test_unknown_device_has_no_peak(monkeypatch):
    from fgumi_tpu.ops import kernel as K

    stats = K.DeviceStats()
    stats.add_dispatch(10 ** 9)
    stats.add_fetch(1024, 0.5)
    monkeypatch.setattr(K, "_jax_ready", True)
    monkeypatch.setattr(K, "device_identity", lambda: {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1})
    assert "MFU ~" in stats.format_summary()
    monkeypatch.setattr(K, "device_identity", lambda: {
        "platform": "tpu", "device_kind": "TPU v9 mega", "device_count": 1})
    line = stats.format_summary()
    assert "MFU: unknown device" in line and "TPU v9 mega x1" in line


# ------------------------------------------------------------ native build


def test_native_build_failure_warns_with_compiler_stderr(tmp_path,
                                                         monkeypatch, caplog):
    from fgumi_tpu import native

    bad = tmp_path / "bad.cc"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_SRC_PATH", str(bad))
    monkeypatch.setattr(native, "_SO_PATH", str(tmp_path / "lib.so"))
    with caplog.at_level(logging.WARNING, logger="fgumi_tpu"):
        assert native.build() is False
    assert any("native build failed" in r.message and "error" in r.message
               for r in caplog.records)
    # nothing half-written is left where a concurrent process would load it
    assert sorted(os.listdir(tmp_path)) == ["bad.cc"]


# ------------------------------------------------------- serve start-up


def test_serve_warm_up_failure_is_a_failed_start(tmp_path, monkeypatch):
    from fgumi_tpu.ops import kernel as K
    from fgumi_tpu.serve.daemon import JobService

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(K, "_ensure_jax", no_backend)
    svc = JobService(str(tmp_path / "s.sock"), workers=1)
    with pytest.raises(RuntimeError, match="backend"):
        svc.warm_up()


# --------------------------------------------- fused chain vs jax imports


def test_fused_pipeline_survives_concurrent_first_jax_import(tmp_path):
    """2,000 unmapped families put >1,024 UMIs in one position group, so
    the group stage reaches the device Hamming kernel while the simplex
    stage is still importing jax: two first imports on two threads."""
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": ""}

    def run(*args):
        return subprocess.run([sys.executable, "-m", "fgumi_tpu", *args],
                              cwd=tmp_path, env=env, capture_output=True,
                              text=True, timeout=300)

    p = run("simulate", "fastq-reads", "-1", "r1.fq.gz", "-2", "r2.fq.gz",
            "--num-families", "2000", "--family-size", "5",
            "--read-length", "100", "--seed", "7")
    assert p.returncode == 0, p.stderr[-2000:]
    p = run("pipeline", "-i", "r1.fq.gz", "r2.fq.gz", "-r", "8M+T", "+T",
            "-o", "out.bam", "--sample", "s", "--library", "l",
            "--threads", "4", "--filter-min-reads", "3")
    assert p.returncode == 0, p.stderr[-3000:]
    assert "ImportError" not in p.stderr
