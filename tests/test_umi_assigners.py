"""UMI assigner unit tests — semantics pinned against the reference
(/root/reference/crates/fgumi-umi/src/assigner.rs test expectations)."""

import numpy as np
import pytest

from fgumi_tpu.umi.assigners import (AdjacencyUmiAssigner, IdentityUmiAssigner,
                                     MoleculeId, PairedUmiAssigner,
                                     SimpleErrorUmiAssigner, make_assigner,
                                     pairwise_distances, _umi_matrix)


def render(ids):
    return [m.render() for m in ids]


def test_molecule_id_render():
    assert MoleculeId("S", 42).render() == "42"
    assert MoleculeId("A", 42).render() == "42/A"
    assert MoleculeId("B", 42).render() == "42/B"


def test_identity():
    a = IdentityUmiAssigner()
    ids = a.assign(["ACGT", "acgt", "TTTT", "ACGT"])
    assert ids[0] == ids[1] == ids[3]  # case-insensitive
    assert ids[2] != ids[0]
    # deterministic: IDs by sorted order -> ACGT gets 0, TTTT gets 1
    assert ids[0].id == 0 and ids[2].id == 1


def test_identity_keeps_n_umis_distinct():
    a = IdentityUmiAssigner()
    ids = a.assign(["ACGN", "ACGN", "ACGT"])
    assert ids[0] == ids[1]
    assert ids[0] != ids[2]


def test_edit_transitive_clustering():
    a = SimpleErrorUmiAssigner(1)
    # AAAA ~ AAAT ~ AATT: chain within distance 1 merges transitively
    ids = a.assign(["AAAA", "AAAT", "AATT", "GGGG"])
    assert ids[0] == ids[1] == ids[2]
    assert ids[3] != ids[0]


def test_edit_invalid_umis_isolated():
    a = SimpleErrorUmiAssigner(1)
    ids = a.assign(["AAAA", "AAAN", "AAAN"])
    # invalid UMI never joins a valid molecule, identical invalids share
    assert ids[1] == ids[2]
    assert ids[0] != ids[1]


def test_adjacency_count_rule():
    a = AdjacencyUmiAssigner(1)
    # UMI-tools rule: child captured iff count <= parent/2 + 1
    # AAAA x10; AAAT x5 (5 <= 6 -> child); GGGG x10, GGGT x7 (7 > 6 -> own root)
    umis = ["AAAA"] * 10 + ["AAAT"] * 5 + ["GGGG"] * 10 + ["GGGT"] * 7
    ids = a.assign(umis)
    assert ids[0] == ids[10]  # AAAT joins AAAA
    assert ids[15] != ids[25]  # GGGT does NOT join GGGG
    assert len({m.id for m in ids}) == 3


def test_adjacency_deterministic_ordering():
    a1 = AdjacencyUmiAssigner(1)
    a2 = AdjacencyUmiAssigner(1)
    umis = ["CCCC", "AAAA", "CCCC", "AAAA", "AAAT"]
    assert render(a1.assign(umis)) == render(a2.assign(list(umis)))
    # equal counts tie-break by string: AAAA root before CCCC
    ids = a1.assign(["CCCC", "CCCC", "AAAA", "AAAA"])
    assert ids[2].id < ids[0].id


def test_paired_strands():
    a = PairedUmiAssigner(1)
    ids = a.assign(["AAAA-CCCC", "CCCC-AAAA", "AAAA-CCCC"])
    # A-B and B-A group into one molecule with opposite strands
    assert ids[0].id == ids[1].id == ids[2].id
    assert ids[0].kind != ids[1].kind
    assert ids[0] == ids[2]
    assert {ids[0].kind, ids[1].kind} == {"A", "B"}


def test_paired_canonical_orientation():
    a = PairedUmiAssigner(1)
    # AAAA-CCCC: first < second so it IS canonical -> /A
    ids = a.assign(["AAAA-CCCC", "CCCC-AAAA"])
    assert ids[0].kind == "A" and ids[1].kind == "B"


def test_paired_error_correction():
    a = PairedUmiAssigner(1)
    # one mismatch in first segment still groups, same strand as the root
    ids = a.assign(["AAAA-CCCC"] * 5 + ["AATA-CCCC"] + ["CCCC-AAAA"] * 3)
    assert ids[0].id == ids[5].id == ids[6].id
    assert ids[5].kind == ids[0].kind
    assert ids[6].kind != ids[0].kind


def test_paired_rejects_malformed():
    a = PairedUmiAssigner(1)
    with pytest.raises(ValueError):
        a.assign(["AAAACCCC"])
    with pytest.raises(ValueError):
        a.assign(["AA-AA-AA"])


def test_uniform_length_guard():
    with pytest.raises(ValueError):
        SimpleErrorUmiAssigner(1).assign(["AAAA", "CCC"])


def test_pairwise_distances_matches_bruteforce():
    rng = np.random.default_rng(0)
    umis = ["".join("ACGT"[c] for c in rng.integers(0, 4, size=10)) for _ in range(50)]
    mat = _umi_matrix(umis)
    d = pairwise_distances(mat)
    for i in range(0, 50, 7):
        for j in range(0, 50, 11):
            expected = sum(x != y for x, y in zip(umis[i], umis[j]))
            assert d[i, j] == expected


def _near_copies(rng, alphabet, n, m, length):
    """(n, length) and (m, length) bytes over ``alphabet``, random but for
    ``b[j]``, which is ``a[j]`` with ``j % 5`` bases rewritten: every
    distance from 0 to 4 occurs, at known places."""
    letters = np.frombuffer(alphabet, np.uint8)
    a = rng.choice(letters, size=(n, length))
    b = rng.choice(letters, size=(m, length))
    k = min(n, m)
    b[:k] = a[:k]
    for j in range(k):
        for pos in rng.choice(length, size=j % 5, replace=False):
            b[j, pos] = letters[(alphabet.index(b[j, pos]) + 1)
                                % len(letters)]
    return (np.ascontiguousarray(a, dtype=np.uint8),
            np.ascontiguousarray(b, dtype=np.uint8))


def _host_within(a, b, edits):
    return (a[:, None, :] != b[None, :, :]).sum(axis=2) <= edits


@pytest.mark.parametrize("edits", [0, 1, 2, 3])
def test_device_pairwise_path(edits, monkeypatch):
    """The device route, forced through the module threshold, builds the
    graph the host route builds, for the single and the dual UMI's bytes."""
    import fgumi_tpu.umi.assigners as A
    from fgumi_tpu.observe.metrics import METRICS

    rng = np.random.default_rng(1)
    for alphabet in (b"ACGT", b"ACGT-:B"):
        mat = np.concatenate(_near_copies(rng, alphabet, 32, 32, 8))
        host = A.build_neighbor_graph(mat, edits)
        before = METRICS.get("group.hamming.dispatches", 0)
        with monkeypatch.context() as mp:
            mp.setattr(A, "DEVICE_THRESHOLD", 1)
            dev = A.build_neighbor_graph(mat, edits)
        assert METRICS.get("group.hamming.dispatches", 0) == before + 1
        np.testing.assert_array_equal(dev._within, host._within)
        np.testing.assert_array_equal(host._within,
                                      _host_within(mat, mat, edits))


def test_make_assigner():
    for s in ("identity", "edit", "adjacency", "paired"):
        assert make_assigner(s) is not None
    with pytest.raises(ValueError):
        make_assigner("bogus")


def test_sparse_graph_matches_dense(monkeypatch):
    """Pigeonhole candidate generation == dense all-pairs, for all users."""
    import numpy as np

    from fgumi_tpu.umi import assigners as ua

    rng = np.random.default_rng(3)
    umis = ["".join(rng.choice(list("ACGT"), size=8)) for _ in range(600)]
    unique = sorted(set(umis))
    mat = ua._umi_matrix(unique)
    dense = ua.build_neighbor_graph(mat, 1)
    monkeypatch.setattr(ua, "SPARSE_THRESHOLD", 10)
    sparse = ua.build_neighbor_graph(mat, 1)
    for i in range(len(unique)):
        assert np.array_equal(dense.neighbors(i), sparse.neighbors(i)), i


def test_sparse_graph_matches_dense_paired(monkeypatch):
    import numpy as np

    from fgumi_tpu.umi import assigners as ua

    rng = np.random.default_rng(5)
    halves = ["".join(rng.choice(list("ACGT"), size=4)) for _ in range(400)]
    unique = sorted({f"{a}-{b}" for a, b in zip(halves[::2], halves[1::2])})
    mat = ua._umi_matrix(unique)
    rev = ua._umi_matrix(["-".join(reversed(u.split("-"))) for u in unique])
    dense = ua.build_neighbor_graph(mat, 1, rev_mat=rev)
    monkeypatch.setattr(ua, "SPARSE_THRESHOLD", 10)
    sparse = ua.build_neighbor_graph(mat, 1, rev_mat=rev)
    for i in range(len(unique)):
        assert np.array_equal(dense.neighbors(i), sparse.neighbors(i)), i


def test_assigners_identical_across_threshold(monkeypatch):
    """Full assign() output must not depend on the dense/sparse crossover."""
    import numpy as np

    from fgumi_tpu.umi import assigners as ua

    rng = np.random.default_rng(7)
    base = ["".join(rng.choice(list("ACGT"), size=8)) for _ in range(120)]
    raw = []
    for u in base:
        raw.extend([u] * int(rng.integers(1, 5)))
        if rng.random() < 0.5:  # 1-mismatch child
            pos = int(rng.integers(8))
            child = u[:pos] + "ACGT"[(("ACGT".index(u[pos])) + 1) % 4] + u[pos + 1:]
            raw.append(child)
    rng.shuffle(raw)
    for cls in (ua.AdjacencyUmiAssigner, ua.SimpleErrorUmiAssigner):
        dense_ids = [str(m) for m in cls(1).assign(list(raw))]
        monkeypatch.setattr(ua, "SPARSE_THRESHOLD", 4)
        sparse_ids = [str(m) for m in cls(1).assign(list(raw))]
        monkeypatch.undo()
        assert dense_ids == sparse_ids


@pytest.mark.parametrize("edits", [0, 1, 2, 3])
@pytest.mark.parametrize("n,m", [(1500, 1500), (2049, 130), (1023, 4097),
                                 (5, 3)])
def test_device_pairwise_parity_at_scale(n, m, edits):
    """The padded device path's bits are the numpy host path's
    ``!= ... sum <= edits`` exactly (VERDICT r3 item 6: huge-position-group
    parity), at non-pow2 sizes, asymmetric (a, b) shapes and a padded side
    under one packed byte, over every byte a strategy puts into a UMI
    matrix: upper-case ACGT, and the ``paired`` strategy's ``-`` between
    the halves and ``AA:`` / ``BB:`` orientation prefixes."""
    from fgumi_tpu.umi import assigners as A

    rng = np.random.default_rng(3)
    a, b = _near_copies(rng, A._ALPHABET, n, m, 9)
    bits = A._device_within_bits(a, b, edits)
    n_pad, m_pad = A._pow2_pad_rows(a).shape[0], A._pow2_pad_rows(b).shape[0]
    assert bits.dtype == np.uint8 and bits.shape == (n_pad, -(-m_pad // 8))
    dev = A._unpack_within(bits, n, m)
    host = _host_within(a, b, edits)
    assert dev.dtype == np.bool_ and np.array_equal(host, dev)
    assert host.any() and not host.all()


def test_the_alphabet_is_what_the_strategies_write():
    """``_ALPHABET`` against the strings themselves: what ``_is_encodable``
    admits once upper-cased, and what ``paired`` hands its matrix."""
    from fgumi_tpu.umi import assigners as A

    assert A._is_encodable("ACGT-TGCA") and not A._is_encodable("ACGN")
    paired = PairedUmiAssigner(3)
    lo, hi = paired.lower_prefix, paired.higher_prefix
    rows = ["ACGTACGT", f"{lo}:ACGT-{hi}:TTGA".upper(),
            paired._reverse(f"{hi}:CCGT-{lo}:TTGA".upper())]
    for umi in rows:
        assert A._is_encodable(umi)
        assert A._device_encodable(_umi_matrix([umi])), umi
    assert set("".join(rows).encode()) == set(A._ALPHABET)
    assert 0 not in A._ALPHABET  # the pad byte matches nothing


def test_edits_is_an_argument_not_an_executable():
    """One executable a padded shape, whatever ``edits``."""
    from fgumi_tpu.umi import assigners as A

    mat = np.concatenate(
        _near_copies(np.random.default_rng(9), b"ACGT", 350, 350, 11))
    A._device_within_bits(mat, mat, 1)
    dist = A._get_dist_jit()
    assert dist.__name__ == "dist"
    compiled = dist._cache_size()
    for edits in (2, 0, 3, 1):
        bits = A._device_within_bits(mat, mat, edits)
        assert np.array_equal(A._unpack_within(bits, 700, 700),
                              _host_within(mat, mat, edits))
    assert dist._cache_size() == compiled


def test_a_byte_outside_the_alphabet_keeps_the_host_route(monkeypatch):
    """``_is_encodable`` lets any text before a ``:`` through; the device's
    one-hot would match such a byte with nothing, so the group stays on the
    host and every route builds one graph."""
    from fgumi_tpu.observe.metrics import METRICS
    from fgumi_tpu.umi import assigners as A

    umis = sorted({f"{p}:{u}" for p in ("XY", "XZ")
                   for u in ("ACGT", "ACGA", "TTTT", "TTTA", "GGGG")})
    assert all(A._is_encodable(u) for u in umis)
    mat = _umi_matrix(umis)
    assert not A._device_encodable(mat)
    host = A.build_neighbor_graph(mat, 1)
    monkeypatch.setattr(A, "DEVICE_THRESHOLD", 1)
    before = METRICS.get("group.hamming.dispatches", 0)
    forced = A.build_neighbor_graph(mat, 1)
    assert METRICS.get("group.hamming.dispatches", 0) == before
    np.testing.assert_array_equal(forced._within, host._within)
    assert host._within.sum() > len(umis)


def _dual_umis(rng, molecules, reads):
    """Dual UMIs as ``paired`` takes them, either strand first, with errors."""
    bases = np.frombuffer(b"ACGT", np.uint8)
    true = rng.choice(bases, size=(molecules, 8))
    arr = true[rng.integers(0, molecules, size=reads)]
    err = rng.random(arr.shape) < 0.02
    arr = np.where(err, rng.choice(bases, size=arr.shape), arr)
    out = []
    for row, flip in zip(arr, rng.random(reads) < 0.5):
        left, right = bytes(row[:4]).decode(), bytes(row[4:]).decode()
        out.append(f"{right}-{left}" if flip else f"{left}-{right}")
    return out


@pytest.mark.parametrize("edits", [1, 2])
def test_paired_device_route_gives_the_host_routes_ids(edits, monkeypatch):
    """``paired``'s second pass (the reversed UMIs against the forward ones)
    is a second dispatch OR-ed into the first: ids and strands as on the
    host route."""
    from fgumi_tpu.observe.metrics import METRICS
    from fgumi_tpu.umi import assigners as A

    umis = _dual_umis(np.random.default_rng(21 + edits), 150, 1200)
    host = render(PairedUmiAssigner(edits).assign(umis))
    monkeypatch.setattr(A, "DEVICE_THRESHOLD", 16)
    before = METRICS.get("group.hamming.dispatches", 0)
    dev = render(PairedUmiAssigner(edits).assign(umis))
    assert METRICS.get("group.hamming.dispatches", 0) == before + 2
    assert dev == host
    assert {i.rsplit("/", 1)[1] for i in dev} == {"A", "B"}
    assert len({i.rsplit("/", 1)[0] for i in dev}) < len(set(umis))


def test_adjacency_16k_group_matches_small_path():
    """A 16k-template group (device pairwise path) must produce the same
    clustering as the same UMIs processed with the device threshold raised
    (pure host path)."""
    import numpy as np

    from fgumi_tpu.umi import assigners as A

    rng = np.random.default_rng(4)
    bases = np.frombuffer(b"ACGT", np.uint8)
    true = rng.choice(bases, size=(400, 8))
    arr = true[rng.integers(0, 400, size=3000)]
    err = rng.random(arr.shape) < 0.01
    arr = np.where(err, rng.choice(bases, size=arr.shape), arr)
    umis = ["".join(chr(c) for c in row) for row in arr]

    old = A.DEVICE_THRESHOLD
    try:
        A.DEVICE_THRESHOLD = 16  # force the device pairwise path
        dev = A.AdjacencyUmiAssigner(1).assign(umis)
        A.DEVICE_THRESHOLD = 1 << 30  # force the pure host path
        host = A.AdjacencyUmiAssigner(1).assign(umis)
    finally:
        A.DEVICE_THRESHOLD = old
    assert [m.render() for m in dev] == [m.render() for m in host]


def test_adjacency_vectorized_matches_scalar_path():
    """The >= _VEC_THRESHOLD numpy assign path must reproduce the scalar
    path's MoleculeIds exactly — including the (-count, string) unique
    order, BFS-root id minting, and first-occurrence invalid-UMI ids."""
    import numpy as np

    from fgumi_tpu.umi.assigners import AdjacencyUmiAssigner

    rng = np.random.default_rng(11)
    bases = np.frombuffer(b"ACGT", np.uint8)
    true = rng.choice(bases, size=(300, 8))
    arr = true[rng.integers(0, 300, size=4000)]
    err = rng.random(arr.shape) < 0.02
    arr = np.where(err, rng.choice(bases, size=arr.shape), arr)
    umis = ["".join(chr(c) for c in row) for row in arr]
    # sprinkle invalid + lowercase + tie-prone entries through the stream
    umis[5] = "NNNNNNNN"
    umis[17] = "acgtacgt"
    umis[100] = "NNNNNNNN"
    umis[2500] = "NNNNNNNA"
    a = AdjacencyUmiAssigner(1)
    a._VEC_THRESHOLD = 1  # force vectorized
    vec = a.assign(umis)
    b = AdjacencyUmiAssigner(1)
    b._VEC_THRESHOLD = 1 << 30  # force scalar
    scalar = b.assign(umis)
    assert [m.render() for m in vec] == [m.render() for m in scalar]


def test_adjacency_vectorized_all_invalid():
    from fgumi_tpu.umi.assigners import AdjacencyUmiAssigner

    umis = ["NNNNNNNN", "NNNNNNNA", "NNNNNNNN", "NNNNNNNB"] * 600
    a = AdjacencyUmiAssigner(1)
    a._VEC_THRESHOLD = 1
    vec = a.assign(umis)
    b = AdjacencyUmiAssigner(1)
    b._VEC_THRESHOLD = 1 << 30
    assert [m.render() for m in vec] == [m.render() for m in b.assign(umis)]


def test_native_neighbor_pairs_match_numpy_pigeonhole():
    """fgumi_umi_neighbor_pairs == the numpy pigeonhole candidate set, as
    canonical undirected pair sets, for same-matrix and cross cases."""
    import numpy as np

    from fgumi_tpu.native import batch as nb
    from fgumi_tpu.umi.assigners import _pigeonhole_pairs

    if not nb.available():
        import pytest
        pytest.skip("native unavailable")
    rng = np.random.default_rng(2)
    for L, d in ((8, 1), (8, 2), (12, 1), (5, 3)):
        base = rng.integers(65, 69, size=(300, L)).astype(np.uint8)
        mat = base[rng.integers(0, 300, size=3000)].copy()
        errs = rng.random(mat.shape) < 0.03
        mat[errs] = rng.integers(65, 69, size=int(errs.sum()))
        ni, nj = nb.umi_neighbor_pairs(mat, None, d)
        pi, pj = _pigeonhole_pairs(mat, mat, d)
        native_set = set(zip(ni.tolist(), nj.tolist()))
        ref_set = set(zip(np.minimum(pi, pj).tolist(),
                          np.maximum(pi, pj).tolist()))
        assert native_set == ref_set
        # cross case (paired reversal analog): rev rows vs rows
        rev = mat[:, ::-1].copy()
        ci, cj = nb.umi_neighbor_pairs(rev, mat, d)
        qi, qj = _pigeonhole_pairs(rev, mat, d)
        assert set(zip(ci.tolist(), cj.tolist())) \
            == set(zip(qi.tolist(), qj.tolist()))


def test_native_bfs_matches_python(monkeypatch):
    import numpy as np

    from fgumi_tpu.native import batch as nb
    from fgumi_tpu.umi import assigners as A

    if not nb.available():
        import pytest
        pytest.skip("native unavailable")
    rng = np.random.default_rng(5)
    bases = np.frombuffer(b"ACGT", np.uint8)
    true = rng.choice(bases, size=(200, 8))
    arr = true[rng.integers(0, 200, size=6000)]
    errs = rng.random(arr.shape) < 0.02
    arr = np.where(errs, rng.choice(bases, size=arr.shape), arr)
    umis = ["".join(chr(c) for c in row) for row in arr]
    a = A.AdjacencyUmiAssigner(1)
    native = [m.render() for m in a.assign(umis)]  # native BFS (>= 512)
    # force the PYTHON BFS on identical input: raise the native threshold
    monkeypatch.setattr(A, "_NATIVE_BFS_THRESHOLD", 1 << 30)
    b = A.AdjacencyUmiAssigner(1)
    python = [m.render() for m in b.assign(umis)]
    assert native == python


def test_bktree_matches_pigeonhole_and_bruteforce():
    """The BK-tree index (reference assigner.rs:228,267 second flavor) must
    produce the identical candidate pair set as the pigeonhole partition
    search and the brute-force truth, same-matrix and cross, d=1..4."""
    import numpy as np

    from fgumi_tpu.native import batch as nb
    from fgumi_tpu.native import get_lib

    if get_lib() is None:
        import pytest

        pytest.skip("native library unavailable")
    rng = np.random.default_rng(11)
    for _ in range(4):
        n = int(rng.integers(2, 150))
        L = int(rng.integers(4, 14))
        mat = rng.integers(0, 4, size=(n, L)).astype(np.uint8)
        for d in (1, 2, 3, 4):
            truth = set()
            for i in range(n):
                for j in range(i + 1, n):
                    if int((mat[i] != mat[j]).sum()) <= d:
                        truth.add((i, j))
            for index in ("pigeonhole", "bktree"):
                pi, pj = nb.umi_neighbor_pairs(mat, None, d, index=index)
                assert set(zip(pi.tolist(), pj.tolist())) == truth, (d, index)
            m2 = rng.integers(0, 4, size=(int(rng.integers(1, 80)), L)) \
                .astype(np.uint8)
            a = nb.umi_neighbor_pairs(m2, mat, d, index="pigeonhole")
            b = nb.umi_neighbor_pairs(m2, mat, d, index="bktree")
            assert set(zip(*map(np.ndarray.tolist, a))) \
                == set(zip(*map(np.ndarray.tolist, b))), (d, "cross")


def test_assign_identical_across_umi_index(monkeypatch):
    """End-to-end grouping must be identical whichever index found the
    candidate pairs (edge sets are equal; BFS order is index-independent)."""
    import numpy as np

    from fgumi_tpu.native import get_lib

    if get_lib() is None:
        import pytest

        pytest.skip("native library unavailable")
    rng = np.random.default_rng(5)
    bases = "ACGT"
    umis = ["".join(rng.choice(list(bases), 8)) for _ in range(300)]
    umis = umis + [u[:3] + "T" + u[4:] for u in umis[:50]]  # near-dupes
    results = {}
    for index in ("pigeonhole", "bktree"):
        monkeypatch.setenv("FGUMI_TPU_UMI_INDEX", index)
        a = AdjacencyUmiAssigner(max_mismatches=3)
        results[index] = [m.render() for m in a.assign(list(umis))]
    assert results["pigeonhole"] == results["bktree"]
