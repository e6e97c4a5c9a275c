"""UMI assignment strategies.

Mirrors /root/reference/crates/fgumi-umi/src/assigner.rs:
- MoleculeId {None, Single, PairedA, PairedB} rendered "42" / "42/A" / "42/B"
  (crates/fgumi-umi/src/lib.rs:20-80)
- identity: exact match on uppercased strings, IDs in sorted order (assigner.rs:915-936)
- edit: transitive single-linkage within Hamming distance; components get IDs in
  order of their smallest member (assigner.rs:999-1108)
- adjacency: UMI-tools directed graph — count-desc (tie: string) order, BFS capture
  of unassigned children with child_count <= parent_count/2 + 1 within distance
  (assigner.rs:1552-1640,1174-1420)
- paired: adjacency over canonicalized dual UMIs (A-B vs B-A), /A-/B strand IDs by
  orientation vs the root (assigner.rs:1735-2235)

Invalid UMIs (non-ACGT, >32 bases per segment) never join a valid molecule; each
distinct (uppercased) invalid string gets its own Single id
(assign_with_invalid_fallback, assigner.rs:692-707).

The all-pairs Hamming distance work — the hot part for large position groups — is
vectorized over byte matrices and takes one of three routes by the group's unique
UMIs (``build_neighbor_graph``): under ``DEVICE_THRESHOLD`` (1,024) a dense numpy
compare on the host; from there to ``SPARSE_THRESHOLD`` (8,192, ``--index-threshold``)
one XLA kernel on the accelerator (``dist``: a one-hot bf16 einsum on the MXU, the
compare with ``edits`` and ``packbits``), the "brute-force-on-accelerator" design
SURVEY.md §7 replaces the reference's BK-tree/N-gram indexes with, which answers
what the graph asks, within ``edits`` or not: one bit a padded pair comes back over
the link, not a distance; from ``SPARSE_THRESHOLD`` on the native pigeonhole
candidate pass on the host, where even a bit a pair is O(U^2) over the link.

Spans and counters (live under ``--trace`` / ``--run-report``; children of
``group.assign``): ``group.assign.umis``, ``group.assign.graph`` (attribute
``route``; on the device route ``group.hamming.upload``, ``group.hamming.dispatch``
and ``device.fetch`` below it), ``group.assign.threshold`` (the host's compare with
``edits``; on the device route the one native pass from the packed bits to the
BFS's neighbour lists, ``nb.within_bits_lists``), ``group.assign.bfs``,
``group.assign.ids``; counters ``group.graph.<route>``, ``group.graph.bits_lists``,
``group.hamming.*``, ``group.neighbor_pairs``, ``group.unique_umis``
(docs/observability.md).
"""

from collections import deque
from dataclasses import dataclass

import numpy as np

from ..observe.metrics import METRICS
from ..observe.trace import NULL_SPAN, span, tracing_enabled

# Unique-UMI count from which the all-pairs search moves to the device.
DEVICE_THRESHOLD = 1024


@dataclass(frozen=True)
class MoleculeId:
    """kind: '' (none), 'S' (single), 'A'/'B' (paired strands)."""

    kind: str
    id: int = -1

    def render(self) -> str:
        if self.kind == "S":
            return str(self.id)
        if self.kind in ("A", "B"):
            return f"{self.id}/{self.kind}"
        return ""


NONE_ID = MoleculeId("")


def molecule_ids(mols) -> np.ndarray:
    """The ``id`` of each MoleculeId of a list, as int64."""
    return np.fromiter((mo.id for mo in mols), np.int64, len(mols))


def molecule_kinds(mols) -> np.ndarray:
    """The ``kind`` of each MoleculeId of a list as its byte (0 for none)."""
    return np.fromiter((ord(mo.kind) if mo.kind else 0 for mo in mols),
                       np.uint8, len(mols))


def render_mis(ids: np.ndarray, kinds: np.ndarray = None) -> np.ndarray:
    """``MoleculeId.render`` over arrays: one S-dtype array (its itemsize
    covers the longest value; consumers read true lengths via
    np.char.str_len). ``kinds`` is None where every id is Single.

    The ids come from one run of a running counter, as a flush's do, so
    they span few values: each value of the span is rendered once and the
    full-size result is one gather."""
    if not len(ids):
        return np.empty(0, "S1")
    lo = int(ids.min())
    rendered = np.arange(lo, int(ids.max()) + 1).astype("S20")[ids - lo]
    if kinds is None:
        return rendered
    out = np.where(kinds == 0, np.bytes_(b""), rendered)
    ab = (kinds == ord("A")) | (kinds == ord("B"))
    if ab.any():
        suffix = np.where(kinds == ord("A"), np.bytes_(b"/A"),
                          np.bytes_(b"/B"))
        out = np.where(ab, np.char.add(rendered, suffix), out)
    return out


_VALID_SET = frozenset("ACGTacgt")


def _is_encodable(umi: str) -> bool:
    """BitEnc-encodable: every dash-separated segment is ACGT (case-folded), <=32."""
    for seg in umi.split("-"):
        # strip orientation prefix ("aa:"/"bb:") if present
        seg = seg.rsplit(":", 1)[-1]
        if len(seg) > 32:
            return False
        if not _VALID_SET.issuperset(seg):
            return False
    return True


def _umi_matrix(umis) -> np.ndarray:
    """(N, L) uint8 byte matrix of equal-length strings."""
    return np.frombuffer("".join(umis).encode(), dtype=np.uint8).reshape(len(umis), -1)


# A UMI as a 2-bit integer code: A=0 C=1 G=2 T=3 after case folding, the
# first base most significant; 4 marks a byte no code holds. For UMIs of one
# length over ACGT, code order is string order.
_CODE_OF = np.full(256, 4, dtype=np.uint8)
for _code, _base in enumerate(b"ACGT"):
    _CODE_OF[_base] = _CODE_OF[_base | 0x20] = _code
_BASE_OF = np.frombuffer(b"ACGT", dtype=np.uint8)
# The longest UMI a uint64 code holds.
CODE_MAX_LEN = 32


def umi_codes(mat: np.ndarray):
    """(codes uint64, valid bool) of the UMIs in the rows of an (n, L) byte
    matrix: a row is valid when L <= ``CODE_MAX_LEN`` and every byte is one
    of ACGTacgt, and only a valid row's code means anything."""
    n, length = mat.shape
    codes = np.zeros(n, dtype=np.uint64)
    if length > CODE_MAX_LEN:
        return codes, np.zeros(n, dtype=bool)
    vals = _CODE_OF[mat]
    for j in range(length):
        codes = (codes << np.uint64(2)) | (vals[:, j] & 3)
    return codes, (vals < 4).all(axis=1)


def _code_matrix(codes: np.ndarray, length: int) -> np.ndarray:
    """The (n, length) byte matrix ``_umi_matrix`` makes of the upper-case
    strings of ``length``-base codes."""
    shifts = np.arange(2 * (length - 1), -1, -2, dtype=np.uint64)
    return _BASE_OF[(codes[:, None] >> shifts) & np.uint64(3)]


# Above this many unique UMIs, dense all-pairs matrices become untenable
# (O(U^2) memory and transfer) and candidate pairs come from pigeonhole
# chunk indexing instead — the analog of the reference's NgramIndex
# (crates/fgumi-umi/src/assigner.rs:228,267,394: exact-match on one of
# d+1 chunks is necessary for Hamming distance <= d).
SPARSE_THRESHOLD = 8192


def set_index_threshold(n):
    """--index-threshold mapping (group.rs:860-863): below the threshold the
    neighbor graph is built by the dense pairwise scan, at/above it by the
    indexed candidate search (pigeonhole n-gram / BK-tree). 0 = always
    dense (linear-scan semantics); None restores the measured default.

    The default here (8192) is far above the reference's 100 because the
    dense path is a vectorized array scan, not a per-pair loop — it wins
    until well past the reference's crossover."""
    global SPARSE_THRESHOLD
    SPARSE_THRESHOLD = (8192 if n is None
                        else (1 << 62) if int(n) == 0 else int(n))
# A sub-group's child spans of ``group.assign`` open from this many UMIs on
# (strings where a span times strings, uniques where it times uniques): under
# it an assign is tens of microseconds, which seven spans and their
# ``getrusage`` pairs would more than double on an input of a million small
# position groups; ``group.assign``'s own record has their time.
_SPAN_MIN_UMIS = 64


def _sized_span(n, name, **span_kw):
    return span(name, **span_kw) if n >= _SPAN_MIN_UMIS else NULL_SPAN


# unique-UMI count above which the directed BFS runs natively
# (fgumi_adjacency_bfs); tests force the Python loop by raising this
_NATIVE_BFS_THRESHOLD = 512


class NeighborGraph:
    """Match-graph adjacency: neighbors(i) -> ascending indices j != i.

    Dense mode wraps a boolean within-matrix (small groups); sparse mode
    holds per-node neighbor lists from pigeonhole candidate generation; flat
    mode holds the native BFS's ``(nbr_flat, nbr_start)`` as the device
    route's native pass made them."""

    def __init__(self, n, within=None, lists=None, flat=None):
        self.n = n
        self._within = within
        self._lists = lists
        self._flat = flat

    def neighbors(self, i: int) -> np.ndarray:
        if self._flat is not None:
            nbr_flat, nbr_start = self._flat
            return nbr_flat[nbr_start[i]:nbr_start[i + 1]]
        if self._within is not None:
            row = np.nonzero(self._within[i])[0]
            return row[row != i]
        return self._lists[i]

    def flat(self):
        """(nbr_flat, nbr_start) arrays for the native BFS: neighbors of i
        are nbr_flat[nbr_start[i]:nbr_start[i+1]], ascending."""
        if self._flat is not None:
            return self._flat
        with _sized_span(self.n, "group.assign.threshold", rusage=True):
            lists = (self._lists if self._lists is not None
                     else [self.neighbors(i) for i in range(self.n)])
            starts = np.zeros(self.n + 1, dtype=np.int64)
            np.cumsum([len(x) for x in lists], out=starts[1:])
            flat = (np.concatenate(lists).astype(np.int64)
                    if self.n else np.empty(0, np.int64))
        return flat, starts


def build_neighbor_graph(mat: np.ndarray, max_mismatches: int,
                         rev_mat: np.ndarray = None) -> NeighborGraph:
    """Graph of pairs with hamming(mat[i], mat[j]) <= d (or, with rev_mat,
    additionally hamming(rev_mat[i], mat[j]) <= d — the paired-UMI cross
    condition, symmetric because strand reversal is an involution)."""
    n = mat.shape[0]
    # pigeonhole completeness needs d+1 disjoint chunks: with d+1 > L a pair
    # can differ everywhere yet still be within distance d, so stay dense
    if n < SPARSE_THRESHOLD or max_mismatches + 1 > mat.shape[1]:
        on_device = n >= DEVICE_THRESHOLD and _device_encodable(mat)
        route = "device" if on_device else "dense_host"
        if n > 1:  # the routes sum to the sub-groups of two or more uniques
            METRICS.inc("group.graph." + route)
        passes = [(mat, mat)] + ([(rev_mat, mat)] if rev_mat is not None
                                 else [])
        if on_device:
            return _device_graph(n, passes, max_mismatches)
        within = None
        for a, b in passes:
            with _sized_span(n, "group.assign.graph", rusage=True,
                             route=route, uniques=n):
                found = pairwise_distances(a, b)
            with _sized_span(n, "group.assign.threshold", rusage=True):
                found = found <= max_mismatches
                if within is None:
                    within = found
                else:
                    within |= found
        return _dense_graph(n, within)
    from ..native import batch as nb

    METRICS.inc("group.graph.sparse_native")
    with span("group.assign.graph", rusage=True, route="sparse_native",
              uniques=n):
        pairs = nb.umi_neighbor_pairs if nb.available() else None
        pair_sets = [pairs(mat, None, max_mismatches) if pairs
                     else _pigeonhole_pairs(mat, mat, max_mismatches)]
        if rev_mat is not None:
            pair_sets.append(
                pairs(rev_mat, mat, max_mismatches) if pairs
                else _pigeonhole_pairs(rev_mat, mat, max_mismatches))
    with span("group.assign.threshold", rusage=True):
        graph = _lists_from_pairs(n, pair_sets)
        METRICS.inc("group.neighbor_pairs",
                    sum(map(len, graph._lists)) // 2)
    return graph


def _pigeonhole_pairs(A: np.ndarray, B: np.ndarray, d: int):
    """Candidate (i, j) index arrays with hamming(A[i], B[j]) <= d, i != j.

    Split columns into d+1 chunks; any pair within distance d agrees exactly
    on at least one chunk, so exact-match buckets per chunk generate a
    complete candidate set which is then distance-verified in bulk."""
    n, L = A.shape
    out_i = []
    out_j = []
    chunks = np.array_split(np.arange(L), min(d + 1, L))
    same = A is B
    for cols in chunks:
        if len(cols) == 0:
            continue
        kb = np.ascontiguousarray(B[:, cols])
        key_b = kb.view([("", np.uint8, kb.shape[1])]).ravel()
        order_b = np.argsort(key_b, kind="stable")
        sb = key_b[order_b]
        bounds = np.flatnonzero(np.concatenate(
            ([True], sb[1:] != sb[:-1], [True])))
        if same:
            for s, e in zip(bounds[:-1], bounds[1:]):
                if e - s < 2:
                    continue
                idxs = np.sort(order_b[s:e])
                dm = pairwise_distances(np.ascontiguousarray(B[idxs]))
                ii, jj = np.nonzero(dm <= d)
                keep = ii < jj
                out_i.append(idxs[ii[keep]])
                out_j.append(idxs[jj[keep]])
        else:
            ka = np.ascontiguousarray(A[:, cols])
            key_a = ka.view([("", np.uint8, ka.shape[1])]).ravel()
            order_a = np.argsort(key_a, kind="stable")
            sa = key_a[order_a]
            a_bounds = np.flatnonzero(np.concatenate(
                ([True], sa[1:] != sa[:-1], [True])))
            # probe B buckets by key bytes (void-dtype ordering comparisons
            # are unreliable; equality via bytes is exact)
            b_index = {sb[bounds[k]].tobytes(): (bounds[k], bounds[k + 1])
                       for k in range(len(bounds) - 1)}
            for s, e in zip(a_bounds[:-1], a_bounds[1:]):
                got = b_index.get(sa[s].tobytes())
                if got is None:
                    continue
                ai = order_a[s:e]
                bj = order_b[got[0]:got[1]]
                dm = pairwise_distances(np.ascontiguousarray(A[ai]),
                                        np.ascontiguousarray(B[bj]))
                ii, jj = np.nonzero(dm <= d)
                gi, gj = ai[ii], bj[jj]
                keep = gi != gj
                out_i.append(gi[keep])
                out_j.append(gj[keep])
    if not out_i:
        return (np.empty(0, np.int64), np.empty(0, np.int64))
    return (np.concatenate(out_i).astype(np.int64),
            np.concatenate(out_j).astype(np.int64))


def _lists_from_pairs(n: int, pair_sets) -> NeighborGraph:
    """Symmetrize + dedupe pair arrays into sorted per-node neighbor lists."""
    all_i = []
    all_j = []
    for pi, pj in pair_sets:
        all_i.append(pi)
        all_j.append(pj)
    i = np.concatenate(all_i) if all_i else np.empty(0, np.int64)
    j = np.concatenate(all_j) if all_j else np.empty(0, np.int64)
    # undirected: add both directions, dedupe on i*n+j
    src = np.concatenate([i, j])
    dst = np.concatenate([j, i])
    enc = np.unique(src * n + dst)
    src = enc // n
    dst = enc % n
    splits = np.searchsorted(src, np.arange(1, n))
    lists = np.split(dst, splits)
    return NeighborGraph(n, lists=lists)


def pairwise_distances(mat_a: np.ndarray, mat_b: np.ndarray = None) -> np.ndarray:
    """All-pairs Hamming distances between byte matrices (int16), on the host:
    the dense route of small groups, the pigeonhole buckets, and the reference
    the tests hold the device's bits to."""
    if mat_b is None:
        mat_b = mat_a
    return (mat_a[:, None, :] != mat_b[None, :, :]).sum(axis=2, dtype=np.int16)


def _pow2_pad_rows(mat: np.ndarray) -> np.ndarray:
    """Pad rows up to the next power of two with an unused byte value.

    Real position groups arrive in every size; without padding each distinct
    (n, m) pair would trigger a fresh XLA compile (~2s — measured as the
    entire 16k-group 'cliff'). Pow2 bucketing keeps the compiled-shape
    vocabulary logarithmic, exactly as the consensus kernel pads its
    segment batches (ops/kernel.py pad_segments)."""
    n = mat.shape[0]
    n_pad = 1 << (n - 1).bit_length() if n > 1 else 1
    if n_pad == n:
        return mat
    pad = np.zeros((n_pad - n, mat.shape[1]), dtype=mat.dtype)
    return np.concatenate([mat, pad])


# Every byte a valid UMI puts into ``_umi_matrix``: the bases ``_is_encodable``
# admits (strings are upper-cased first), the ``-`` between a dual UMI's halves,
# and the ``:`` and ``B`` of the ``paired`` strategy's orientation prefixes
# ("aa:ACGT-bb:TTTT", upper-cased). ``_pow2_pad_rows``' byte 0 is not among
# them, so a pad row's one-hot is all zeros and matches nothing, itself included.
_ALPHABET = b"ACGT-:B"
_IN_ALPHABET = np.zeros(256, dtype=bool)
_IN_ALPHABET[list(_ALPHABET)] = True


def _device_encodable(mat: np.ndarray) -> bool:
    """Whether the device's one-hot sees every byte of ``mat``.
    ``_is_encodable`` lets any text before a ``:`` through ("XY:ACGT"), and a
    byte outside ``_ALPHABET`` would equal nothing on the device, so such a
    group keeps the host's compare: a pass over n x L bytes buys the same
    graph on every route."""
    return bool(_IN_ALPHABET[mat].all())


_dist_jit = None


def _get_dist_jit():
    """Module-level jitted within-``edits`` kernel: one compile per padded
    shape for the process lifetime, whatever ``edits`` (a traced scalar; a
    per-call jax.jit closure would recompile every call — measured at ~0.5s
    per group)."""
    global _dist_jit
    if _dist_jit is None:
        # the one guarded first import of jax (a fused chain's simplex
        # stage may be importing it on another thread right now); it also
        # enables the persistent compile cache, which group/dedup runs
        # reach only through this kernel
        from ..ops.kernel import _ensure_jax

        jax = _ensure_jax()
        import jax.numpy as jnp

        alphabet = np.frombuffer(_ALPHABET, dtype=np.uint8)

        # the device plane names the executable after this function,
        # ``jit_dist(...)``: the benchmark's Hamming readers find it by that
        @jax.jit
        def dist(a, b, edits):
            # one-hot over the alphabet -> matmul on the MXU; the count of
            # matching bases is exact in the f32 accumulator at any length
            oh_a = (a[..., None] == alphabet).astype(jnp.bfloat16)  # (N, L, K)
            oh_b = (b[..., None] == alphabet).astype(jnp.bfloat16)
            matches = jnp.einsum("nlk,mlk->nm", oh_a, oh_b,
                                 preferred_element_type=jnp.float32)
            # the answer the graph asks for, a bit a pair: (N, ceil(M / 8))
            return jnp.packbits(a.shape[1] - matches <= edits, axis=1)

        _dist_jit = dist
    return _dist_jit


def _device_within_bits(mat_a: np.ndarray, mat_b: np.ndarray,
                        edits: int) -> np.ndarray:
    """hamming(mat_a[i], mat_b[j]) <= edits for every pair of the padded
    matrices, as the device packs it: ``(n_pad, ceil(m_pad / 8))`` uint8, the
    high bit first (``nb.within_bits_lists`` reads it, ``_unpack_within``
    undoes it). The real ``n x m`` corner is
    not cut out on the device: every (n, m) would be a shape and a compile,
    and at a bit a pair the padding is a few MB."""
    dist = _get_dist_jit()
    import jax.numpy as jnp

    from ..ops.kernel import DEVICE_STATS

    n, m = mat_a.shape[0], mat_b.shape[0]
    pad_a = _pow2_pad_rows(mat_a)
    pad_b = _pow2_pad_rows(mat_b)
    cells_padded = pad_a.shape[0] * pad_b.shape[0]
    DEVICE_STATS.add_dispatch(2 * cells_padded * pad_a.shape[1]
                              * len(_ALPHABET))  # the one-hot matmul
    with span("group.hamming.upload", rows=pad_a.shape[0] + pad_b.shape[0]):
        dev_a, dev_b = jnp.asarray(pad_a), jnp.asarray(pad_b)
    with span("group.hamming.dispatch"):
        dev_bits = dist(dev_a, dev_b, np.int32(edits))
    bits = DEVICE_STATS.fetch(dev_bits)
    METRICS.inc("group.hamming.dispatches")
    METRICS.inc("group.hamming.rows", n + m)
    METRICS.inc("group.hamming.cells", n * m)
    METRICS.inc("group.hamming.cells_padded", cells_padded)
    METRICS.inc("group.hamming.bytes_fetched", bits.nbytes)
    return bits


def _unpack_within(bits: np.ndarray, n: int, m: int) -> np.ndarray:
    """The real ``(n, m)`` corner of ``_device_within_bits``' answer as the
    boolean matrix ``NeighborGraph`` takes (the device route without the
    native library)."""
    return np.unpackbits(bits[:n], axis=1, count=m).view(np.bool_)


def _device_graph(n: int, passes, edits: int) -> NeighborGraph:
    """The device route's graph: one dispatch a pass, the passes' packed
    answers OR-ed (``paired``'s reversed UMIs against the forward ones), then
    one native pass from the bits to the native BFS's neighbour lists, with
    no ``(n, n)`` matrix between. Without the native library the bits are
    unpacked into the dense matrix instead."""
    from ..native import batch as nb

    found = []
    for a, b in passes:
        with _sized_span(n, "group.assign.graph", route="device", uniques=n):
            found.append(_device_within_bits(a, b, edits))
    with _sized_span(n, "group.assign.threshold", rusage=True):
        bits = found[0] if len(found) == 1 else np.bitwise_or(*found)
        if nb.available():
            nbr_flat, nbr_start = nb.within_bits_lists(bits, n, n)
            if n > 1:  # as ``group.graph.device`` counts
                METRICS.inc("group.graph.bits_lists")
            METRICS.inc("group.neighbor_pairs", len(nbr_flat) // 2)
            return NeighborGraph(n, flat=(nbr_flat, nbr_start))
        within = _unpack_within(bits, n, n)
    return _dense_graph(n, within)


def _dense_graph(n: int, within: np.ndarray) -> NeighborGraph:
    """A graph over the boolean within-matrix, its diagonal set."""
    if tracing_enabled():  # a pass over n x n: only when it is read
        METRICS.inc("group.neighbor_pairs",
                    (int(np.count_nonzero(within)) - n) // 2)
    return NeighborGraph(n, within=within)


def _assert_uniform_length(lengths) -> None:
    it = iter(lengths)
    first = next(it, None)
    if first is None:
        return
    for ln in it:
        if ln != first:
            raise ValueError(f"Multiple UMI lengths: {ln} vs {first}")


class _Counter:
    """An assigner's running tallies: ``value`` the next molecule id (so the
    ids minted so far), ``uniques`` the valid distinct UMIs it has seen,
    summed over sub-groups (run-report counter ``group.unique_umis``)."""

    __slots__ = ("value", "uniques")

    def __init__(self):
        self.value = 0
        self.uniques = 0

    def next_id(self) -> int:
        v = self.value
        self.value += 1
        return v


def _with_invalid_fallback(umis, resolve, counter):
    """Per-input ids with a per-distinct-invalid-string fallback (assigner.rs:692-707)."""
    invalid_to_id = {}
    out = []
    for i, umi in enumerate(umis):
        mid = resolve(i, umi)
        if mid is None:
            key = umi.upper()
            if key not in invalid_to_id:
                invalid_to_id[key] = MoleculeId("S", counter.next_id())
            mid = invalid_to_id[key]
        out.append(mid)
    return out


class _StringsOnly:
    """An assigner that reads UMIs as strings alone."""

    def assign_codes(self, n, coded):
        """None: ``assign`` takes the strings (see
        ``AdjacencyUmiAssigner.assign_codes``)."""
        return None


class IdentityUmiAssigner(_StringsOnly):
    """Exact-match grouping; IDs assigned over sorted unique uppercased UMIs."""

    def __init__(self):
        self.counter = _Counter()

    def split_by_orientation(self) -> bool:
        return True

    def assign(self, raw_umis):
        if not raw_umis:
            return []
        canon = [u.upper() for u in raw_umis]
        mapping = {c: MoleculeId("S", self.counter.next_id()) for c in sorted(set(canon))}
        return [mapping[c] for c in canon]


class SimpleErrorUmiAssigner(_StringsOnly):
    """Transitive single-linkage clustering within ``max_mismatches`` (edit strategy)."""

    def __init__(self, max_mismatches: int = 1):
        self.max_mismatches = max_mismatches
        self.counter = _Counter()

    def split_by_orientation(self) -> bool:
        return True

    def assign(self, raw_umis):
        if not raw_umis:
            return []
        upper = [u.upper() for u in raw_umis]
        valid = sorted({u for u in set(upper) if _is_encodable(u)})
        _assert_uniform_length(len(u) for u in valid)
        umi_to_id = {}
        self.counter.uniques += len(valid)
        if valid:
            mat = _umi_matrix(valid)
            graph = build_neighbor_graph(mat, self.max_mismatches)
            # connected components = transitive closure of the match graph
            n = len(valid)
            comp = np.full(n, -1, dtype=np.int64)
            n_comp = 0
            for i in range(n):
                if comp[i] >= 0:
                    continue
                stack = [i]
                comp[i] = n_comp
                while stack:
                    j = stack.pop()
                    nbrs = graph.neighbors(j)
                    for k in nbrs[comp[nbrs] < 0]:
                        comp[k] = n_comp
                        stack.append(int(k))
                n_comp += 1
            # components ordered by smallest member (valid is sorted, so the
            # first occurrence order IS smallest-member order)
            comp_ids = {}
            for i, u in enumerate(valid):
                c = comp[i]
                if c not in comp_ids:
                    comp_ids[c] = MoleculeId("S", self.counter.next_id())
                umi_to_id[u] = comp_ids[c]
        return _with_invalid_fallback(upper, lambda _i, u: umi_to_id.get(u), self.counter)


def _count_sorted_unique(upper, keys=None):
    """(unique_key, count) sorted by (-count, key). keys default to the UMIs."""
    from collections import Counter

    counts = Counter(keys if keys is not None else upper)
    return sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))


def _adjacency_bfs(unique, counts, graph: NeighborGraph):
    """UMI-tools directed BFS (assigner.rs:1480-1548).

    unique/counts sorted by (-count, string); graph.neighbors(i) = ascending
    candidate matches. Returns (roots, parent_of) where parent_of[i] is the
    component root index.
    """
    n = len(unique)
    counts_arr = np.asarray(counts)
    from ..native import batch as nb

    if n >= _NATIVE_BFS_THRESHOLD and nb.available():
        flat, starts = graph.flat()
        root_of = nb.adjacency_bfs(flat, starts,
                                   counts_arr.astype(np.int64))
        # roots in discovery order == ascending root index (each root is
        # its own first-assigned node), exactly the scalar loop's order
        return np.unique(root_of).tolist(), root_of
    assigned = np.zeros(n, dtype=bool)
    root_of = np.full(n, -1, dtype=np.int64)
    roots = []
    for root in range(n):
        if assigned[root]:
            continue
        roots.append(root)
        assigned[root] = True
        root_of[root] = root
        queue = deque([root])
        while queue:
            idx = queue.popleft()
            max_child = counts[idx] // 2 + 1
            nbrs = graph.neighbors(idx)
            cand = nbrs[~assigned[nbrs] & (counts_arr[nbrs] <= max_child)]
            for child in cand:
                child = int(child)
                assigned[child] = True
                root_of[child] = root_of[idx]
                queue.append(child)
    return roots, root_of


class AdjacencyUmiAssigner:
    """UMI-tools directed adjacency strategy."""

    # above this many input strings, per-read Python loops (upper, Counter,
    # fallback dict walk) dominate the whole group command; the vectorized
    # path does uppercase/unique/count/map-back as numpy C passes over the
    # full input and runs Python only per DISTINCT UMI. Byte-parity with the
    # scalar path is pinned by tests/test_umi_assigners.py.
    _VEC_THRESHOLD = 2048

    def __init__(self, max_mismatches: int = 1):
        self.max_mismatches = max_mismatches
        self.counter = _Counter()

    def split_by_orientation(self) -> bool:
        return True

    def _mint_ids(self, mat, counts):
        """Molecule ids, int64, of (-count, string)-sorted valid unique UMIs
        given as their (n, L) byte matrix: each root of the directed BFS
        takes the counter's next id, in root order, and every unique its
        root's. The scalar, vectorised and coded paths all mint here."""
        n = len(mat)
        self.counter.uniques += n
        if n == 1:
            return np.array([self.counter.next_id()], dtype=np.int64)
        graph = build_neighbor_graph(mat, self.max_mismatches)
        with _sized_span(n, "group.assign.bfs", rusage=True):
            roots, root_of = _adjacency_bfs(mat, counts, graph)
        with _sized_span(n, "group.assign.ids", rusage=True):
            # roots ascend (each is the first index of its component)
            ids = self.counter.value + np.searchsorted(roots, root_of)
            self.counter.value += len(roots)
            return ids

    def _assign_uniques(self, unique, counts):
        """MoleculeIds aligned with (-count, string)-sorted valid unique
        UMIs (see ``_mint_ids``)."""
        with _sized_span(len(unique), "group.assign.umis", rusage=True):
            mat = _umi_matrix(unique)
        return [MoleculeId("S", i)
                for i in self._mint_ids(mat, counts).tolist()]

    def assign(self, raw_umis):
        if not raw_umis:
            return []
        if len(raw_umis) >= self._VEC_THRESHOLD:
            return self._assign_vectorized(raw_umis)
        with _sized_span(len(raw_umis), "group.assign.umis", rusage=True):
            upper = [u.upper() for u in raw_umis]
            # count first, validate per DISTINCT string: distinct UMIs are a
            # small fraction of reads in large position groups, and the
            # filtered list keeps the (-count, umi) order
            # _count_sorted_unique establishes
            counted = [(u, c) for u, c in _count_sorted_unique(upper)
                       if _is_encodable(u)]
        if not counted:
            return _with_invalid_fallback(upper, lambda *_: None, self.counter)
        _assert_uniform_length(len(u) for u, _ in counted)
        unique = [u for u, _ in counted]
        counts = [c for _, c in counted]
        umi_to_id = dict(zip(unique, self._assign_uniques(unique, counts)))
        with _sized_span(len(raw_umis), "group.assign.ids", rusage=True):
            return _with_invalid_fallback(
                upper, lambda _i, u: umi_to_id.get(u), self.counter)

    def assign_codes(self, n, coded):
        """``assign`` for a sub-group of ``n`` UMIs given as 2-bit codes:
        ``coded()`` returns (codes uint64, length int64) a UMI as
        ``umi_codes`` makes them, with length -1 where a UMI has no code.
        Returns molecule ids, int64 a UMI, with no string; or None where
        the codes may not stand in for the strings, which ``assign`` then
        takes: under ``_VEC_THRESHOLD`` UMIs, or UMIs not all coded at one
        length. For UMIs of one length over ACGT code order is string
        order, so the uniques sort by (-count, string) as ``assign``'s do,
        the graph gets the same byte matrix and the roots mint the same
        ids in the same order."""
        if n < self._VEC_THRESHOLD:
            return None
        with span("group.assign.umis", rusage=True):
            codes, length = coded()
            if length[0] < 1 or (length != length[0]).any():
                return None
            uniq, inverse, counts = np.unique(codes, return_inverse=True,
                                              return_counts=True)
            order = np.argsort(-counts, kind="stable")
            mat = _code_matrix(uniq[order], int(length[0]))
        ids = np.empty(len(uniq), dtype=np.int64)
        ids[order] = self._mint_ids(mat, counts[order])
        return ids[inverse]

    def _assign_vectorized(self, raw_umis):
        """Large-group assign: numpy passes over the input, Python per
        distinct UMI only. Semantics identical to the scalar path:

        - valid uniques sorted by (-count, string) — np.unique returns
          string-ascending uniques, so a stable sort by -count reproduces
          _count_sorted_unique's order (filter-then-sort == sort-then-filter);
        - valid molecule ids minted first (BFS-root order), then one id per
          distinct invalid string in first-occurrence input order, exactly
          as _with_invalid_fallback's forward walk mints them."""
        with span("group.assign.umis", rusage=True):
            arr = np.char.upper(np.asarray(raw_umis, dtype=np.str_))
            uniq, first_idx, inverse, ucounts = np.unique(
                arr, return_index=True, return_inverse=True,
                return_counts=True)
            valid_mask = np.fromiter((_is_encodable(u) for u in uniq),
                                     bool, len(uniq))
            mids_u = np.empty(len(uniq), dtype=object)
            valid_idx = np.nonzero(valid_mask)[0]
        if len(valid_idx):
            with span("group.assign.umis", rusage=True):
                order = np.argsort(-ucounts[valid_idx], kind="stable")
                sorted_idx = valid_idx[order]
                unique = [str(uniq[i]) for i in sorted_idx]
                _assert_uniform_length(len(u) for u in unique)
                counts = ucounts[sorted_idx].tolist()
            for i, mid in zip(sorted_idx,
                              self._assign_uniques(unique, counts)):
                mids_u[i] = mid
        with span("group.assign.ids", rusage=True):
            invalid_idx = np.nonzero(~valid_mask)[0]
            if len(invalid_idx):
                for i in invalid_idx[np.argsort(first_idx[invalid_idx],
                                                kind="stable")]:
                    mids_u[i] = MoleculeId("S", self.counter.next_id())
            return list(mids_u[inverse])


class PairedUmiAssigner(_StringsOnly):
    """Dual-UMI (duplex) strategy: A-B and B-A group together with /A-/B strand ids."""

    def __init__(self, max_mismatches: int = 1):
        self.max_mismatches = max_mismatches
        self.counter = _Counter()
        prefix_len = max_mismatches + 1
        self.lower_prefix = "a" * prefix_len
        self.higher_prefix = "b" * prefix_len

    def split_by_orientation(self) -> bool:
        return False

    @staticmethod
    def _split(umi: str):
        parts = umi.split("-")
        if len(parts) != 2:
            raise ValueError(f"UMI {umi!r} is not a valid paired UMI (expected 'A-B')")
        return parts[0], parts[1]

    @classmethod
    def _reverse(cls, umi: str) -> str:
        a, b = cls._split(umi)
        return f"{b}-{a}"

    @classmethod
    def _canonical(cls, umi: str) -> str:
        a, b = cls._split(umi)
        return umi if a <= b else f"{b}-{a}"

    def assign(self, raw_umis):
        if not raw_umis:
            return []
        upper = [u.upper() for u in raw_umis]
        # structure-validate, BitEnc-validate, and canonicalize per DISTINCT
        # string (the '-' split is case-invariant, so distinct uppers cover
        # every raw input); counts aggregate per canonical form exactly as
        # the per-read pass did
        counted_all = _count_sorted_unique(upper)
        for u, _ in counted_all:
            self._split(u)  # validates exactly one '-'
        dvalid = {u for u, _ in counted_all if _is_encodable(u)}
        canon_counts = {}
        for u, c in counted_all:
            if u in dvalid:
                k = self._canonical(u)
                canon_counts[k] = canon_counts.get(k, 0) + c
        counted = sorted(canon_counts.items(), key=lambda kv: (-kv[1], kv[0]))
        if not counted:
            return _with_invalid_fallback(upper, lambda *_: None, self.counter)

        def underlying_len(u):
            a, b = self._split(u)
            return len(a.rsplit(":", 1)[-1]) + len(b.rsplit(":", 1)[-1])

        _assert_uniform_length(underlying_len(u) for u, _ in counted)
        unique = [u for u, _ in counted]
        counts = [c for _, c in counted]

        self.counter.uniques += len(unique)
        umi_to_id = {}
        if len(unique) == 1:
            mid = self.counter.next_id()
            ab, ba = MoleculeId("A", mid), MoleculeId("B", mid)
            u = unique[0]
            umi_to_id[u] = ab
            umi_to_id[self._reverse(u)] = ba
        else:
            mat = _umi_matrix(unique)
            rev_mat = _umi_matrix([self._reverse(u) for u in unique])
            graph = build_neighbor_graph(mat, self.max_mismatches,
                                         rev_mat=rev_mat)
            roots, root_of = _adjacency_bfs(unique, counts, graph)
            root_mid = {r: self.counter.next_id() for r in roots}
            for i, u in enumerate(unique):
                root = int(root_of[i])
                mid = root_mid[root]
                ab, ba = MoleculeId("A", mid), MoleculeId("B", mid)
                if i == root:
                    umi_to_id[u] = ab
                    umi_to_id[self._reverse(u)] = ba
                else:
                    root_umi = unique[root]
                    d_fwd = sum(x != y for x, y in zip(root_umi, u))
                    d_rev = sum(x != y for x, y in zip(root_umi, self._reverse(u)))
                    if d_fwd < d_rev:
                        umi_to_id[u] = ab
                        umi_to_id[self._reverse(u)] = ba
                    else:
                        umi_to_id[u] = ba
                        umi_to_id[self._reverse(u)] = ab
        return _with_invalid_fallback(
            upper, lambda i, u: umi_to_id.get(u) if u in dvalid else None,
            self.counter)


def make_assigner(strategy: str, edits: int = 1):
    """Strategy factory (group.rs Strategy enum)."""
    if strategy == "identity":
        return IdentityUmiAssigner()
    if strategy == "edit":
        return SimpleErrorUmiAssigner(edits)
    if strategy == "adjacency":
        return AdjacencyUmiAssigner(edits)
    if strategy == "paired":
        return PairedUmiAssigner(edits)
    raise ValueError(f"unknown UMI strategy: {strategy}")
