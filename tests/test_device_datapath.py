"""Host<->device data-path tests: bucketed shape registry, device-resident
constant cache, and the depth-N feeder pipeline (ops/datapath.py + the
DeviceFeeder rework in ops/kernel.py).

The invariants under test are the ones the perf story leans on: ladder
buckets are monotone with bounded waste, parsing errors are loud, constant
tables upload once per (device, content), padding never changes output
bytes (bucket-boundary e2e), and the feeder honors its depth gate, drains,
and restarts.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from fgumi_tpu.ops import datapath
from fgumi_tpu.ops.datapath import (DeviceConstantCache, ShapeBucketRegistry,
                                    as_device_operand, parse_shape_buckets)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- spec parsing

@pytest.mark.parametrize("spec", ["abc", "0.9", "1.0", "1.001", "2.5",
                                  "-1.5", "1.25:xyz", "1.25:10", "1.25:2:3",
                                  "nan"])
def test_parse_rejects_bad_specs(spec):
    with pytest.raises(ValueError, match="FGUMI_TPU_SHAPE_BUCKETS"):
        parse_shape_buckets(spec)


def test_parse_defaults_and_valid():
    assert parse_shape_buckets(None) == (datapath.DEFAULT_GROWTH,
                                         datapath.DEFAULT_CAP)
    assert parse_shape_buckets("") == (datapath.DEFAULT_GROWTH,
                                       datapath.DEFAULT_CAP)
    assert parse_shape_buckets("1.25") == (1.25, datapath.DEFAULT_CAP)
    assert parse_shape_buckets("1.5:4096") == (1.5, 4096)
    assert parse_shape_buckets("2.0") == (2.0, datapath.DEFAULT_CAP)


def test_env_parse_error_raises_at_first_bucket(monkeypatch):
    monkeypatch.setenv("FGUMI_TPU_SHAPE_BUCKETS", "banana")
    reg = ShapeBucketRegistry()
    with pytest.raises(ValueError, match="banana"):
        reg.bucket_rows(100)


# ------------------------------------------------------------------ ladder

@pytest.mark.parametrize("growth", [1.0625, 1.25, 1.5, 2.0])
def test_ladder_monotone_bounded_waste(growth):
    reg = ShapeBucketRegistry(growth=growth, cap=1 << 20)
    prev = 0
    for n in list(range(1, 400)) + [1000, 4096, 8193, 65537, 300000,
                                    441242, (1 << 20) - 1]:
        p = reg.bucket_rows(n)
        assert p >= n
        assert p % 16 == 0
        assert p >= prev  # monotone in n
        prev_n, prev = n, p
        # waste bounded by one geometric step (+ alignment)
        assert p - n <= (growth - 1.0) * n + 16, (n, p)


def test_ladder_segments_alignment():
    reg = ShapeBucketRegistry(growth=1.0625, cap=1 << 20)
    for j in [1, 2, 7, 8, 9, 100, 1000, 65536]:
        f = reg.bucket_segments(j)
        assert f >= max(j, 8)
        assert f % 8 == 0


def test_cap_behavior():
    reg = ShapeBucketRegistry(growth=1.25, cap=4096)
    lad = reg._ladder(16)
    assert lad[-1] <= -(-4096 // 16) * 16
    # above the cap: multiples of the ladder top, still >= n
    top = lad[-1]
    for n in [top + 1, 3 * top - 5, 10 * top]:
        p = reg.bucket(n, 16)
        assert p >= n and p % top == 0


def test_default_ladder_waste_under_five_percent_large():
    """The acceptance bar: default ladder keeps padding waste <= ~5% for
    the dispatch sizes that dominate transfer time (>= 4k rows)."""
    reg = ShapeBucketRegistry()
    rng = np.random.default_rng(0)
    for n in rng.integers(4096, 2_000_000, size=500):
        p = reg.bucket_rows(int(n))
        assert (p - n) / n <= 0.0665, (n, p)  # 1.0625 step + alignment


def test_observe_hit_miss_counters():
    reg = ShapeBucketRegistry(growth=1.25, cap=1 << 16)
    assert reg.observe("segw", 128, 64, 16, 16) is True
    assert reg.observe("segw", 128, 64, 16, 16) is False
    assert reg.observe("segw", 256, 64, 16, 16) is True
    assert (reg.hits, reg.misses) == (1, 2)


def test_reconfigure_reads_spec_and_env(monkeypatch):
    reg = ShapeBucketRegistry()
    reg.reconfigure("2.0:4096")
    assert reg._config() == (2.0, 4096)
    # pow2 ladder under growth 2.0
    lad = reg._ladder(16)
    assert all(b % a == 0 for a, b in zip(lad, lad[1:]))


# ------------------------------------------------------- operand contiguity

def test_as_device_operand_no_copy_when_dense():
    a = np.zeros((64, 64), dtype=np.uint8)
    assert as_device_operand(a) is a
    strided = a[:, ::2]
    b = as_device_operand(strided)
    assert b is not strided and b.flags.c_contiguous
    np.testing.assert_array_equal(b, strided)


# ------------------------------------------------------------ constant cache

def test_const_cache_uploads_once_per_content():
    cache = DeviceConstantCache()
    arr = np.arange(94, dtype=np.float32)
    h1 = cache.put("tab", arr)
    h2 = cache.put("tab", arr.copy())  # same content, different object
    assert h1 is h2
    assert cache.uploads == 1 and cache.hits == 1
    assert cache.upload_bytes == arr.nbytes
    # different content under the same name is a distinct entry
    h3 = cache.put("tab", arr + 1)
    assert h3 is not h1
    assert cache.uploads == 2
    np.testing.assert_array_equal(np.asarray(h1), arr)
    np.testing.assert_array_equal(np.asarray(h3), arr + 1)


def test_const_cache_invalidate_reuploads():
    cache = DeviceConstantCache()
    arr = np.full(64, 3.5, dtype=np.float32)
    cache.put("t", arr)
    cache.invalidate()
    cache.put("t", arr)
    assert cache.uploads == 2 and cache.hits == 0


def test_const_cache_lru_bound():
    cache = DeviceConstantCache()
    for i in range(cache.MAX_ENTRIES + 10):
        cache.put("dict", np.full(4, i, dtype=np.float32))
    assert len(cache) == cache.MAX_ENTRIES


# ------------------------------------------------------------------- feeder

_test_feeders = []


@pytest.fixture(autouse=True)
def _ungovern_test_feeders():
    """Throwaway feeders register a budget with the process-wide resource
    governor on first _config; leaked entries would count against the
    governor's global cap in every later test."""
    yield
    while _test_feeders:
        _test_feeders.pop().ungovern()


def _fresh_feeder(monkeypatch, depth=None, budget=None):
    from fgumi_tpu.ops.kernel import DeviceFeeder

    if depth is not None:
        monkeypatch.setenv("FGUMI_TPU_FEEDER_DEPTH", str(depth))
    if budget is not None:
        monkeypatch.setenv("FGUMI_TPU_FEEDER_BYTES", str(budget))
    feeder = DeviceFeeder()
    _test_feeders.append(feeder)
    return feeder


def test_feeder_depth_gates_dispatches(monkeypatch):
    feeder = _fresh_feeder(monkeypatch, depth=2)
    ran = []
    tickets = [feeder.submit(lambda i=i: ran.append(i) or i,
                             upload_bytes=10) for i in range(4)]
    tickets[1].wait()
    time.sleep(0.2)  # give the feeder a chance to (wrongly) run item 2
    assert ran == [0, 1], "depth=2 must hold item 2 until item 0 resolves"
    feeder.mark_resolved(tickets[0])
    assert tickets[2].wait() == 2
    feeder.mark_resolved(tickets[1])
    feeder.mark_resolved(tickets[1])  # idempotent
    assert tickets[3].wait() == 3
    for t in tickets[2:]:
        feeder.mark_resolved(t)
    assert feeder.drain(timeout=5)


def test_feeder_depth_env_floor_is_two(monkeypatch):
    """Depth 1 would deadlock the OOM split-halving path behind a
    deferred-resolve caller; the env floor enforces the documented
    depth >= 2 invariant."""
    feeder = _fresh_feeder(monkeypatch, depth=1)
    assert feeder.depth == 2


def test_feeder_byte_budget_gates_dispatches(monkeypatch):
    feeder = _fresh_feeder(monkeypatch, depth=8, budget=1 << 20)
    ran = []
    t0 = feeder.submit(lambda: ran.append(0), upload_bytes=(1 << 20) - 1)
    t1 = feeder.submit(lambda: ran.append(1), upload_bytes=(1 << 20) - 1)
    t0.wait()
    time.sleep(0.2)
    assert ran == [0], "byte budget must hold item 1"
    feeder.mark_resolved(t0)
    t1.wait()
    feeder.mark_resolved(t1)
    assert feeder.drain(timeout=5)


def test_feeder_drain_idle_exit_and_restart(monkeypatch):
    feeder = _fresh_feeder(monkeypatch, depth=2)
    t = feeder.submit(lambda: 41)
    assert t.wait() == 41
    feeder.mark_resolved(t)
    assert feeder.drain(timeout=5)
    thread = feeder._thread
    assert thread is None or not thread.is_alive()
    # a post-drain submit transparently restarts the worker
    t2 = feeder.submit(lambda: 42)
    assert t2.wait() == 42
    feeder.mark_resolved(t2)
    assert feeder.drain(timeout=5)


def test_feeder_exception_releases_waiter(monkeypatch):
    feeder = _fresh_feeder(monkeypatch, depth=2)

    def boom():
        raise RuntimeError("injected")

    t = feeder.submit(boom)
    with pytest.raises(RuntimeError, match="injected"):
        t.wait()
    feeder.mark_resolved(t)
    assert feeder.drain(timeout=5)


def test_feeder_queue_is_deque():
    from fgumi_tpu.ops.kernel import DEVICE_FEEDER
    import collections

    assert isinstance(DEVICE_FEEDER._q, collections.deque)


def test_feeder_overlap_accounting(monkeypatch):
    """With depth 2 and an unresolved first dispatch, the second item's
    execution is counted as pipeline overlap."""
    from fgumi_tpu.ops.kernel import DeviceStats

    feeder = _fresh_feeder(monkeypatch, depth=2)
    stats = DeviceStats()
    monkeypatch.setattr("fgumi_tpu.ops.kernel._GLOBAL_DEVICE_STATS", stats)
    gate = threading.Event()
    t0 = feeder.submit(lambda: 0)
    t1 = feeder.submit(lambda: gate.wait(2) or time.sleep(0.01) or 1)
    t0.wait()
    gate.set()
    t1.wait()
    feeder.mark_resolved(t0)
    feeder.mark_resolved(t1)
    assert stats.upload_overlap_s > 0
    assert stats.feeder_queue_peak >= 1
    assert feeder.drain(timeout=5)


# ------------------------------------------------- bucket-boundary e2e (CPU)

def _run_simplex(workdir, sim, env):
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", "simplex", "-i", str(sim),
         "-o", "cons.bam", "--min-reads", "1", "--allow-unmapped"],
        check=True, cwd=workdir,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "", **env})
    return (workdir / "cons.bam").read_bytes()


@pytest.mark.slow
def test_bucket_ladders_byte_identical_cli(tmp_path):
    """End-to-end: the same input produces byte-identical consensus BAMs
    under different bucket ladders (padding is masked out by construction)
    and on the host engine (no padding at all)."""
    sim = tmp_path / "g.bam"
    subprocess.run(
        [sys.executable, "-m", "fgumi_tpu", "simulate", "grouped-reads",
         "-o", str(sim), "--num-families", "300",
         "--family-size-distribution", "longtail", "--read-length", "60",
         "--seed", "29"],
        check=True, cwd=REPO, env={**os.environ, "PYTHONPATH": REPO})
    outs = {}
    for label, env in (
            ("default", {"FGUMI_TPU_HOST_ENGINE": "0",
                         "FGUMI_TPU_HYBRID": "0"}),
            ("coarse", {"FGUMI_TPU_HOST_ENGINE": "0",
                        "FGUMI_TPU_HYBRID": "0",
                        "FGUMI_TPU_SHAPE_BUCKETS": "1.5"}),
            ("pow2_capped", {"FGUMI_TPU_HOST_ENGINE": "0",
                             "FGUMI_TPU_HYBRID": "0",
                             "FGUMI_TPU_SHAPE_BUCKETS": "2.0:4096"}),
            ("host", {"FGUMI_TPU_HOST_ENGINE": "1"})):
        d = tmp_path / label
        d.mkdir()
        outs[label] = _run_simplex(d, sim, env)
    assert outs["default"] == outs["coarse"]
    assert outs["default"] == outs["pow2_capped"]
    assert outs["default"] == outs["host"]


def test_bucket_boundary_rows_oracle_parity():
    """Rows just below / at / above a ladder edge all produce results that
    match the f64 oracle exactly — the padding rows can never leak into a
    consensus call."""
    from fgumi_tpu.ops import oracle
    from fgumi_tpu.ops.kernel import ConsensusKernel, pad_segments_gather
    from fgumi_tpu.ops.tables import quality_tables

    kernel = ConsensusKernel(quality_tables(45, 40))
    kernel.set_force_device()
    reg = datapath.SHAPE_REGISTRY
    R, L = 4, 16
    # pick a real ladder edge in the few-hundred-rows regime
    edge = reg.bucket_rows(300)
    rng = np.random.default_rng(1)
    for n_rows in (edge - R, edge, edge + R):
        J = n_rows // R
        codes = rng.integers(0, 4, size=(J * R, L), dtype=np.uint8)
        quals = rng.integers(20, 41, size=(J * R, L), dtype=np.uint8)
        counts = np.full(J, R, dtype=np.int64)
        cd, qd, seg, starts, F_pad, N = pad_segments_gather(
            codes, quals, np.arange(J * R), L, counts)
        assert cd.shape[0] == reg.bucket_rows(J * R)
        ticket = kernel.device_call_segments_wire(cd, qd, seg, F_pad, J)
        w, q, d, e = kernel.resolve_segments_wire(ticket, cd[:N], qd[:N],
                                                  starts)
        for j in (0, J // 2, J - 1):
            fc = codes[starts[j]:starts[j + 1]]
            fq = quals[starts[j]:starts[j + 1]]
            ow, oq, od, oe = oracle.call_family(fc, fq, kernel.tables)
            np.testing.assert_array_equal(w[j][:L], ow)
            np.testing.assert_array_equal(q[j][:L], oq)
            np.testing.assert_array_equal(d[j][:L], od)
            np.testing.assert_array_equal(e[j][:L], oe)
