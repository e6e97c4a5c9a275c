"""Multi-host mesh construction (parallel/distributed.py).

The placement policy under test: sp groups never cross a host boundary (the
hot-path psum must ride ICI), dp spans hosts (no collectives). device_grid
is pure, so host-boundary invariants are checked directly; the end-to-end
single-process path runs on the virtual 8-device CPU mesh.
"""

import numpy as np
import pytest

from fgumi_tpu.parallel.distributed import device_grid, make_global_mesh


def test_sp_groups_stay_on_host():
    # 4 "hosts" x 4 devices, tagged host-major like jax.devices() ordering
    devs = [f"h{h}d{d}" for h in range(4) for d in range(4)]
    for sp in (1, 2, 4):
        grid = device_grid(devs, local_count=4, sp=sp)
        assert grid.shape == (16 // sp, sp)
        for row in grid:
            hosts = {name[:2] for name in row}
            assert len(hosts) == 1  # one ICI domain per sp group
        # every device appears exactly once
        assert sorted(np.ravel(grid)) == sorted(devs)


def test_sp_must_divide_local_count():
    devs = [f"h{h}d{d}" for h in range(2) for d in range(4)]
    with pytest.raises(ValueError):
        device_grid(devs, local_count=4, sp=3)
    with pytest.raises(ValueError):
        device_grid(devs, local_count=3, sp=1)


def test_make_global_mesh_single_process():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device mesh")
    mesh = make_global_mesh(sp=2)
    assert dict(mesh.shape) == {"dp": 4, "sp": 2}
    # identical device set to a plain local mesh
    assert set(np.ravel(mesh.devices)) == set(jax.devices())


def test_global_mesh_runs_the_kernel():
    """The distributed-constructed mesh drives the production dp x sp
    segment dispatch end to end (same path as __graft_entry__)."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device mesh")
    from fgumi_tpu.ops.kernel import ConsensusKernel
    from fgumi_tpu.ops import oracle
    from fgumi_tpu.ops.tables import quality_tables
    from fgumi_tpu.consensus.fast import pack_shards_sp
    from fgumi_tpu.ops.kernel import split_row_balanced

    mesh = make_global_mesh(sp=2)
    t = quality_tables(45, 40)
    k = ConsensusKernel(t)
    rng = np.random.default_rng(0)
    J, R, L = 12, 6, 32
    codes = rng.integers(0, 5, size=(J * R, L)).astype(np.uint8)
    quals = rng.integers(2, 94, size=codes.shape).astype(np.uint8)
    counts = np.full(J, R)
    starts = np.concatenate(([0], np.cumsum(counts)))
    jb = split_row_balanced(counts, mesh.shape["dp"])
    codes4, quals4, seg3, shard_starts, _, F_loc = pack_shards_sp(
        codes, quals, starts, jb, L, mesh.shape["sp"])
    dev = k.device_call_segments_dp_sp(codes4, quals4, seg3, F_loc, mesh)
    from fgumi_tpu.ops.kernel import DEVICE_STATS

    packed = DEVICE_STATS.fetch(dev)
    # per-shard resolution equals the oracle on every family
    for d in range(mesh.shape["dp"]):
        lo, hi = int(jb[d]), int(jb[d + 1])
        if hi == lo:
            continue
        rows = slice(int(starts[lo]), int(starts[hi]))
        w, q, dep, err = k._finish_segments(
            packed[d], codes[rows], quals[rows], shard_starts[d])
        for j in range(hi - lo):
            fam = slice(int(starts[lo + j]), int(starts[lo + j + 1]))
            ow, oq, od, oe = oracle.call_family(codes[fam], quals[fam], t)
            np.testing.assert_array_equal(w[j], ow)
            np.testing.assert_array_equal(q[j], oq)
