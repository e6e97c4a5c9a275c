"""Multi-device mesh tests on the 8-device virtual CPU mesh (conftest.py).

Covers VERDICT r1 item 3: sharded-vs-oracle parity for dp-only and dp×sp
meshes, uneven-F padding, and the driver's dryrun entry — so the multi-chip
path is exercised by pytest, not only by the out-of-band graft entry.
"""

import jax
import numpy as np
import pytest

from fgumi_tpu.ops import oracle
from fgumi_tpu.ops.kernel import ConsensusKernel
from fgumi_tpu.ops.tables import quality_tables
from fgumi_tpu.parallel.mesh import make_mesh, pad_for_mesh, sharded_consensus_fn

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


@pytest.fixture(scope="module")
def tables():
    return quality_tables(45, 40)


def _batch(F, R, L, seed):
    rng = np.random.default_rng(seed)
    truth = rng.integers(0, 4, size=(F, 1, L))
    codes = np.broadcast_to(truth, (F, R, L)).copy()
    errs = rng.random(codes.shape) < 0.05
    codes[errs] = rng.integers(0, 4, size=int(errs.sum()))
    # occasional N's and a spread of quals including low ones
    codes[rng.random(codes.shape) < 0.01] = 4
    quals = rng.integers(2, 46, size=codes.shape).astype(np.uint8)
    return codes.astype(np.uint8), quals


def _check_parity(mesh, tables, F, R, L, seed):
    """Sharded kernel == f64 oracle on every non-suspect family/position."""
    fn = sharded_consensus_fn(mesh, tables.adjusted_correct,
                              tables.adjusted_error_per_alt,
                              tables.ln_error_pre_umi)
    codes, quals = _batch(F, R, L, seed)
    pcodes, pquals, F0 = pad_for_mesh(codes, quals, mesh)
    winner, qual, depth, errors, suspect = jax.device_get(fn(pcodes, pquals))
    assert winner.shape == (pcodes.shape[0], L)
    n_suspect = 0
    for f in range(F0):
        ow, oq, od, oe = oracle.call_family(codes[f], quals[f], tables)
        ok_pos = ~np.asarray(suspect[f], dtype=bool)
        n_suspect += int((~ok_pos).sum())
        assert np.array_equal(np.asarray(winner[f])[ok_pos], ow[ok_pos])
        assert np.array_equal(np.asarray(qual[f])[ok_pos], oq[ok_pos])
        assert np.array_equal(np.asarray(depth[f]), od)
        assert np.array_equal(np.asarray(errors[f]), oe)
    # suspect-mask positions fall back on host in production; they must be rare
    assert n_suspect <= 0.05 * F0 * L


def test_dp_only_mesh(tables):
    mesh = make_mesh(jax.devices()[:8], sp=1)
    assert dict(mesh.shape) == {"dp": 8, "sp": 1}
    _check_parity(mesh, tables, F=16, R=6, L=48, seed=3)


def test_dp_sp_mesh(tables):
    mesh = make_mesh(jax.devices()[:8], sp=2)
    assert dict(mesh.shape) == {"dp": 4, "sp": 2}
    _check_parity(mesh, tables, F=8, R=10, L=40, seed=4)


def test_sp4_mesh(tables):
    mesh = make_mesh(jax.devices()[:8], sp=4)
    _check_parity(mesh, tables, F=4, R=8, L=32, seed=5)


def test_uneven_padding(tables):
    """F not divisible by dp and R not divisible by sp: padded rows are
    all-N/Q0 sentinels and real families still match the oracle."""
    mesh = make_mesh(jax.devices()[:8], sp=2)
    _check_parity(mesh, tables, F=7, R=5, L=33, seed=6)


def test_padding_identity(tables):
    mesh = make_mesh(jax.devices()[:8], sp=2)
    codes, quals = _batch(5, 3, 20, seed=7)
    pc, pq, F = pad_for_mesh(codes, quals, mesh)
    assert F == 5 and pc.shape[0] % 8 == 0 or pc.shape[0] % 4 == 0
    assert pc.shape[1] % 2 == 0
    assert (pc[5:] == 4).all() and (pq[5:] == 0).all()
    assert np.array_equal(pc[:5, :3], codes)


def test_sharded_matches_single_device_kernel(tables):
    """The mesh path and the single-device ConsensusKernel batch path agree
    everywhere neither marks suspect (same f32 math, different partitioning)."""
    mesh = make_mesh(jax.devices()[:8], sp=2)
    fn = sharded_consensus_fn(mesh, tables.adjusted_correct,
                              tables.adjusted_error_per_alt,
                              tables.ln_error_pre_umi)
    kernel = ConsensusKernel(tables)
    codes, quals = _batch(8, 6, 32, seed=8)
    mw, mq, md, me, ms = jax.device_get(fn(*pad_for_mesh(codes, quals, mesh)[:2]))
    kw, kq, kd, ke, ks = jax.device_get(kernel.device_call(codes, quals))
    ok = ~(np.asarray(ms[:8], bool) | np.asarray(ks, bool))
    assert np.array_equal(np.asarray(mw[:8])[ok], np.asarray(kw)[ok])
    assert np.array_equal(np.asarray(mq[:8])[ok], np.asarray(kq)[ok])
    assert np.array_equal(np.asarray(md[:8]), np.asarray(kd))


def test_dryrun_multichip_entry():
    """The driver's dry run passes in-suite (env already hardened here)."""
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


# ---------------------------------------------------------------------------
# dp x sp sharding of the PRODUCTION segments path (VERDICT r3 item 7): the
# layout the fast engines actually dispatch, read axis split over sp with a
# psum combine


def _ragged(seed, n_fam=37, L=24):
    rng = np.random.default_rng(seed)
    counts = rng.integers(1, 12, size=n_fam).astype(np.int64)
    N = int(counts.sum())
    truth = rng.integers(0, 4, size=(n_fam, L)).astype(np.uint8)
    codes = np.repeat(truth, counts, axis=0)
    err = rng.random(codes.shape) < 0.05
    codes[err] = rng.integers(0, 4, size=int(err.sum()))
    codes[rng.random(codes.shape) < 0.01] = 4
    quals = rng.integers(2, 46, size=codes.shape).astype(np.uint8)
    starts = np.concatenate(([0], np.cumsum(counts)))
    return codes, quals, counts, starts


@pytest.mark.parametrize("dp,sp", [(4, 2), (2, 4), (8, 1)])
def test_segments_dp_sp_matches_single_device(tables, dp, sp):
    from fgumi_tpu.consensus.fast import pack_shards_sp
    from fgumi_tpu.ops.kernel import pad_segments, split_row_balanced

    kernel = ConsensusKernel(tables)
    codes, quals, counts, starts = _ragged(91)
    L = codes.shape[1]

    # single-device reference
    cd, qd, seg, st, F_pad = pad_segments(codes, quals, counts)
    ref = kernel.resolve_segments(
        kernel.device_call_segments(cd, qd, seg, F_pad), codes, quals, starts)

    mesh = make_mesh(jax.devices()[:dp * sp], dp=dp, sp=sp)
    jb = split_row_balanced(counts, dp)
    codes4, quals4, seg3, shard_starts, n_jobs, F_loc = pack_shards_sp(
        codes, quals, starts, jb, L, sp)
    dev = kernel.device_call_segments_dp_sp(codes4, quals4, seg3, F_loc, mesh)
    packed = np.asarray(jax.device_get(dev))
    # reassemble per-shard results and compare with the reference family-wise
    got = [None] * len(counts)
    for d in range(dp):
        st_d = shard_starts[d]
        c2 = codes[starts[jb[d]]:starts[jb[d + 1]]]
        q2 = quals[starts[jb[d]]:starts[jb[d + 1]]]
        w, q, de, er = kernel._finish_segments(packed[d], c2, q2, st_d)
        for k in range(n_jobs[d]):
            got[jb[d] + k] = (w[k], q[k], de[k], er[k])
    for f in range(len(counts)):
        for a, b in zip(got[f], (ref[0][f], ref[1][f], ref[2][f], ref[3][f])):
            assert np.array_equal(a, b), f


# ---------------------------------------------------------------------------
# Production mesh compile path (ISSUE 10): the shard_map-wrapped wire kernels
# + pad_segments_mesh + FGUMI_TPU_MESH surface. Byte-identity vs the
# single-device wire path is the oracle throughout.


def test_parse_mesh_spec():
    from fgumi_tpu.parallel.mesh import MeshConfigError, parse_mesh_spec

    assert parse_mesh_spec(None) is None
    assert parse_mesh_spec("off") is None
    assert parse_mesh_spec("0") is None
    assert parse_mesh_spec("auto") == "auto"
    assert parse_mesh_spec("dp4xsp2") == (4, 2)
    assert parse_mesh_spec("DP8") == (8, 1)
    for bad in ("banana", "dpxsp2", "sp2", "dp-1", "dp2xsp"):
        with pytest.raises(MeshConfigError):
            parse_mesh_spec(bad)


def test_resolve_mesh_validates_device_count():
    from fgumi_tpu.parallel.mesh import MeshConfigError, resolve_mesh

    devs = jax.devices()
    with pytest.raises(MeshConfigError):
        resolve_mesh(devs, (len(devs) + 1, 2))
    assert resolve_mesh(devs, None) is None
    assert resolve_mesh(devs, (1, 1)) is None  # 1-device mesh = legacy path
    m = resolve_mesh(devs, "auto")
    assert m is not None and m.size == len(devs)


def test_bucket_segments_sharded_one_vocabulary():
    from fgumi_tpu.ops.datapath import SHAPE_REGISTRY

    # per-shard counts come from the same 8-aligned ladder as the
    # single-device bucket, so dp*F_loc is a multiple of dp and the static
    # shard shapes are shared across mesh sizes that land on one rung
    for j, dp in ((37, 4), (100, 8), (7, 2), (1, 8)):
        f_loc = SHAPE_REGISTRY.bucket_segments_sharded(j, dp)
        assert f_loc * dp >= j
        assert f_loc == SHAPE_REGISTRY.bucket_segments(-(-j // dp))


def test_pad_segments_mesh_layout(tables):
    from fgumi_tpu.ops.kernel import pad_segments_mesh

    mesh = make_mesh(jax.devices()[:8], dp=4, sp=2)
    codes, quals, counts, starts = _ragged(17, n_fam=23, L=24)
    cg, qg, sg, st, f_loc, gather = pad_segments_mesh(codes, quals,
                                                      counts, mesh)
    assert cg.shape[0] % 8 == 0  # divisible over every mesh axis
    assert np.array_equal(st, starts)
    assert len(gather) == len(counts)
    assert gather.max() < 4 * f_loc
    # every real row landed somewhere with its bytes intact: count real
    # (non-pad) rows by code sentinel
    assert int((cg != 4).any(axis=1).sum()) <= codes.shape[0]


def _wire_ref(kernel, codes, quals, counts, starts):
    from fgumi_tpu.ops.kernel import pad_segments

    cd, qd, seg, _st, F_pad = pad_segments(codes, quals, counts)
    t = kernel.device_call_segments_wire(cd, qd, seg, F_pad, len(counts),
                                         full=True)
    return kernel.resolve_segments_wire(t, codes, quals, starts)


@pytest.mark.parametrize("dp,sp", [(4, 2), (8, 1), (2, 4)])
def test_mesh_wire_byte_identity(tables, dp, sp):
    from fgumi_tpu.ops.kernel import pad_segments_mesh

    kernel = ConsensusKernel(tables)
    kernel.set_force_device()
    codes, quals, counts, starts = _ragged(29, n_fam=53, L=32)
    ref = _wire_ref(kernel, codes, quals, counts, starts)
    mesh = make_mesh(jax.devices()[:dp * sp], dp=dp, sp=sp)
    cg, qg, sg, _st, f_loc, gather = pad_segments_mesh(codes, quals,
                                                       counts, mesh)
    t = kernel.device_call_segments_wire(cg, qg, sg, f_loc, len(counts),
                                         full=True, mesh=mesh,
                                         mesh_gather=gather)
    got = kernel.resolve_segments_wire(t, codes, quals, starts)
    for i in range(4):
        assert np.array_equal(np.asarray(got[i]), np.asarray(ref[i])), i


def test_mesh_wire_packed2_fallback(tables):
    """>63 distinct quals: the packed2 mesh kernel, still byte-identical."""
    from fgumi_tpu.ops.kernel import pad_segments_mesh

    kernel = ConsensusKernel(tables)
    kernel.set_force_device()
    codes, quals, counts, starts = _ragged(31, n_fam=40, L=32)
    quals = (np.arange(quals.size, dtype=np.int64) % 80 + 3).astype(
        np.uint8).reshape(quals.shape)
    ref = _wire_ref(kernel, codes, quals, counts, starts)
    mesh = make_mesh(jax.devices()[:8], dp=4, sp=2)
    cg, qg, sg, _st, f_loc, gather = pad_segments_mesh(codes, quals,
                                                       counts, mesh)
    t = kernel.device_call_segments_wire(cg, qg, sg, f_loc, len(counts),
                                         full=True, mesh=mesh,
                                         mesh_gather=gather)
    got = kernel.resolve_segments_wire(t, codes, quals, starts)
    for i in range(4):
        assert np.array_equal(np.asarray(got[i]), np.asarray(ref[i])), i


def test_router_per_mesh_ewmas():
    from fgumi_tpu.ops.router import OffloadRouter

    r = OffloadRouter()
    r.observe_device(1 << 20, 1 << 10, 0.01, 0.005, 0.015, devices=1)
    r.observe_device(1 << 20, 1 << 10, 0.001, 0.0005, 0.0015, devices=8)
    snap = r.snapshot()
    assert snap["link_samples"] == 1
    assert "8" in snap["mesh"]
    # the 8-device link EWMA is ~10x the 1-device one, learned separately
    assert snap["mesh"]["8"]["link_mbps"] > 5 * snap["link_mbps"]


def test_publish_mesh_gauges():
    from fgumi_tpu.observe.metrics import METRICS
    from fgumi_tpu.parallel import mesh as pm

    # conftest's _reset_mesh_snapshot clears the process-global afterwards
    m = make_mesh(jax.devices()[:8], dp=4, sp=2)
    snap = pm.publish_mesh(m)
    assert snap == {"dp": 4, "sp": 2, "devices": 8, "platform": "cpu"}
    assert pm.LAST_MESH_SNAPSHOT == snap
    got = METRICS.snapshot()
    assert got["device.mesh.dp"] == 4
    assert got["device.mesh.devices"] == 8


def _cli_mesh_parity(tmp_path, cmd, sim_path, extra_env=()):
    """Byte parity of one engine CLI across FGUMI_TPU_MESH settings."""
    import os

    from fgumi_tpu.cli import main
    from fgumi_tpu.io.bam import BamReader

    def run(tag, mesh):
        out = str(tmp_path / f"{cmd}_{tag}.bam")
        saved = {}
        env = dict(extra_env)
        if mesh is not None:
            env["FGUMI_TPU_MESH"] = mesh
        for k, v in env.items():
            saved[k] = os.environ.get(k)
            os.environ[k] = v
        try:
            assert main([cmd, "-i", sim_path, "-o", out,
                         "--min-reads", "1"]) == 0
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        with BamReader(out) as r:
            return [rec.data for rec in r]

    single = run("single", "off")
    for mesh in ("dp4xsp2", "dp8"):
        assert run(mesh, mesh) == single, (cmd, mesh)


def test_fast_duplex_mesh_byte_parity(tmp_path):
    from fgumi_tpu.simulate import simulate_duplex_bam

    sim = str(tmp_path / "dup.bam")
    simulate_duplex_bam(sim, num_molecules=120, reads_per_strand=3, seed=13)
    # force the device strand combine so the sharded resident path (and
    # its gather remap) is exercised, not just priced
    _cli_mesh_parity(tmp_path, "duplex", sim,
                     extra_env={"FGUMI_TPU_DUPLEX_COMBINE": "device"})


def test_fast_codec_mesh_byte_parity(tmp_path):
    from fgumi_tpu.cli import main

    sim = str(tmp_path / "codec.bam")
    assert main(["simulate", "codec-reads", "-o", sim, "--num-molecules",
                 "150", "--pairs-per-molecule", "2", "--read-length", "60",
                 "--seed", "13"]) == 0
    _cli_mesh_parity(tmp_path, "codec", sim,
                     extra_env={"FGUMI_TPU_CODEC_COMBINE": "device"})


def test_fast_simplex_mesh_env_byte_parity(tmp_path):
    from fgumi_tpu.simulate import simulate_grouped_bam

    sim = str(tmp_path / "sim.bam")
    simulate_grouped_bam(sim, num_families=200, family_size=6,
                         read_length=60, error_rate=0.02, seed=13)
    _cli_mesh_parity(tmp_path, "simplex", sim)


def test_fast_simplex_sp_mesh_byte_parity(tmp_path):
    """FastSimplexCaller with a dp x sp mesh must produce byte-identical
    output to the single-device engine (the --devices + FGUMI_TPU_SP path)."""
    import os

    from fgumi_tpu.cli import main
    from fgumi_tpu.io.bam import BamReader
    from fgumi_tpu.simulate import simulate_grouped_bam

    sim = str(tmp_path / "sim.bam")
    simulate_grouped_bam(sim, num_families=300, family_size=7,
                         read_length=60, error_rate=0.02, seed=9)

    def run(tag, env_sp=None, devices="1"):
        out = str(tmp_path / f"o{tag}.bam")
        old = os.environ.get("FGUMI_TPU_SP")
        if env_sp is not None:
            os.environ["FGUMI_TPU_SP"] = env_sp
        try:
            assert main(["simplex", "-i", sim, "-o", out, "--min-reads", "1",
                         "--devices", devices]) == 0
        finally:
            if env_sp is not None:
                if old is None:
                    os.environ.pop("FGUMI_TPU_SP", None)
                else:
                    os.environ["FGUMI_TPU_SP"] = old
        with BamReader(out) as r:
            return [rec.data for rec in r]

    single = run("single")
    dp_sp = run("dpsp", env_sp="2", devices="8")
    assert dp_sp == single
    sp_only = run("sponly", env_sp="8", devices="8")
    assert sp_only == single
