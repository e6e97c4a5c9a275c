"""Numpy-facing wrappers for the native batch record layer.

Each call hands whole numpy arrays to C++ (fgumi_native.cc batch section), so
Python cost is per-batch, not per-record — the discipline the reference keeps
with its raw-record design (crates/fgumi-raw-bam/src/raw_bam_record.rs:6-13).

All wrappers require the native library; callers check `available()` once and
fall back to the pure-Python record path when it is False.
"""

import numpy as np

from . import get_lib
# operand-for-a-C++-entry-point: same object when already a C-contiguous
# ndarray of the requested dtype (the common case on the dispatch hot
# path), one conversion copy otherwise — the shared no-copy rule lives in
# ops/datapath.as_device_operand
from ..ops.datapath import as_device_operand as _as_c
from ..utils import faults


def available() -> bool:
    return get_lib() is not None


def _addr(arr: np.ndarray) -> int:
    assert arr.flags["C_CONTIGUOUS"]
    return arr.ctypes.data


def find_boundaries(buf: np.ndarray, max_records: int):
    """(offsets int64[n], scanned) — record starts in decompressed BAM bytes."""
    import ctypes

    faults.fire("native.batch")
    lib = get_lib()
    offsets = np.empty(max_records, dtype=np.int64)
    scanned = ctypes.c_int64(0)
    n = lib.fgumi_find_record_boundaries(
        buf.ctypes.data_as(ctypes.c_char_p), len(buf),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), max_records,
        ctypes.byref(scanned))
    return offsets[:n], scanned.value


def decode_fields(buf: np.ndarray, rec_off: np.ndarray) -> dict:
    """Struct-of-arrays fixed-field decode (fields.rs:7-24 layout)."""
    lib = get_lib()
    n = len(rec_off)
    i32 = {k: np.empty(n, dtype=np.int32)
           for k in ("ref_id", "pos", "mapq", "flag", "l_seq", "n_cigar",
                     "l_read_name", "next_ref_id", "next_pos", "tlen")}
    data_off = np.empty(n, dtype=np.int64)
    data_end = np.empty(n, dtype=np.int64)
    lib.fgumi_decode_fields(
        _addr(buf), _addr(rec_off), n,
        _addr(i32["ref_id"]), _addr(i32["pos"]), _addr(i32["mapq"]),
        _addr(i32["flag"]), _addr(i32["l_seq"]), _addr(i32["n_cigar"]),
        _addr(i32["l_read_name"]), _addr(i32["next_ref_id"]),
        _addr(i32["next_pos"]), _addr(i32["tlen"]), _addr(data_off),
        _addr(data_end))
    i32["data_off"] = data_off
    i32["data_end"] = data_end
    return i32


def scan_tags(buf: np.ndarray, aux_off: np.ndarray, aux_end: np.ndarray,
              tags: list):
    """Per-record aux-tag locations for k tags.

    Returns (val_off int64[n,k], val_len int32[n,k], val_type uint8[n,k]);
    val_off -1 where the tag is absent.
    """
    lib = get_lib()
    n = len(aux_off)
    k = len(tags)
    tag_bytes = np.frombuffer(b"".join(tags), dtype=np.uint8)
    val_off = np.empty((n, k), dtype=np.int64)
    val_len = np.empty((n, k), dtype=np.int32)
    val_type = np.empty((n, k), dtype=np.uint8)
    lib.fgumi_scan_tags(_addr(buf), _addr(aux_off), _addr(aux_end), n,
                        _addr(tag_bytes), k, _addr(val_off), _addr(val_len),
                        _addr(val_type))
    return val_off, val_len, val_type


def group_starts(buf: np.ndarray, off: np.ndarray, length: np.ndarray):
    """Group indices by byte-range equality; raises if any off < 0 (missing)."""
    lib = get_lib()
    n = len(off)
    starts = np.empty(n, dtype=np.int64)
    # converted arrays must stay referenced until the foreign call returns
    length = np.ascontiguousarray(length, np.int32)
    g = lib.fgumi_group_starts(_addr(buf), _addr(off), _addr(length),
                               n, _addr(starts))
    if g < 0:
        raise ValueError(f"record {-g - 1} missing grouping tag; run `group` first")
    return starts[:g]


def pack_reads(buf: np.ndarray, seq_off: np.ndarray, qual_off: np.ndarray,
               l_seq: np.ndarray, reverse: np.ndarray, clip: np.ndarray,
               min_q: int, stride: int, mode: int = 0):
    """Batch SourceRead conversion into (n, stride) code/qual rows.

    Returns (codes uint8[n,stride], quals uint8[n,stride], final_len int32[n]);
    final_len -1 marks rejected reads (empty / all-0xFF quals). mode bit0
    keeps all-0xFF reads, bit1 keeps trailing Ns (the CODEC conversion).
    """
    lib = get_lib()
    n = len(seq_off)
    codes = np.empty((n, stride), dtype=np.uint8)
    quals = np.empty((n, stride), dtype=np.uint8)
    final_len = np.empty(n, dtype=np.int32)
    # converted arrays must stay referenced until the foreign call returns
    l_seq = np.ascontiguousarray(l_seq, np.int32)
    reverse = np.ascontiguousarray(reverse, np.uint8)
    clip = np.ascontiguousarray(clip, np.int32)
    lib.fgumi_pack_reads(
        _addr(buf), _addr(seq_off), _addr(qual_off), _addr(l_seq),
        _addr(reverse), _addr(clip),
        n, min_q, stride, mode, _addr(codes), _addr(quals),
        _addr(final_len))
    return codes, quals, final_len


def mate_clips(buf: np.ndarray, cigar_off: np.ndarray, n_cigar: np.ndarray,
               flag: np.ndarray, ref_id: np.ndarray, pos: np.ndarray,
               next_ref_id: np.ndarray, next_pos: np.ndarray,
               tlen: np.ndarray, mc_off: np.ndarray, mc_len: np.ndarray):
    """Batch num_bases_extending_past_mate (overlap.rs:117-140) -> int32[n]."""
    lib = get_lib()
    n = len(cigar_off)
    clip = np.empty(n, dtype=np.int32)
    # converted arrays must stay referenced until the foreign call returns
    keep = [np.ascontiguousarray(a, np.int32)
            for a in (n_cigar, flag, ref_id, pos, next_ref_id, next_pos, tlen,
                      mc_len)]
    n_cigar, flag, ref_id, pos, next_ref_id, next_pos, tlen, mc_len = keep
    lib.fgumi_mate_clips(
        _addr(buf), _addr(cigar_off), _addr(n_cigar), _addr(flag),
        _addr(ref_id), _addr(pos), _addr(next_ref_id), _addr(next_pos),
        _addr(tlen), _addr(mc_off), _addr(mc_len), n, _addr(clip))
    return clip


def alignment_filter(buf: np.ndarray, cigar_off: np.ndarray,
                     n_cigar: np.ndarray, reverse: np.ndarray,
                     final_len: np.ndarray, seg_starts: np.ndarray):
    """The most-common-alignment filter over whole segments -> uint8[n] keep.

    Rows are the reads of the segments that need the filter, concatenated
    segment by segment in their original order; seg_starts (int64[n_seg + 1])
    bounds them. A row keeps 1 when its read is in its segment's winning
    compatibility group (core/cigar.py select_most_common_alignment_group on
    the simplified, strand-oriented, final_len-truncated CIGARs).
    """
    lib = get_lib()
    n = len(cigar_off)
    keep = np.empty(n, dtype=np.uint8)
    # converted arrays must stay referenced until the foreign call returns
    cigar_off = np.ascontiguousarray(cigar_off, np.int64)
    n_cigar = np.ascontiguousarray(n_cigar, np.int32)
    reverse = np.ascontiguousarray(reverse, np.uint8)
    final_len = np.ascontiguousarray(final_len, np.int32)
    seg_starts = np.ascontiguousarray(seg_starts, np.int64)
    if not (len(n_cigar) == len(reverse) == len(final_len) == n
            and len(seg_starts) >= 1 and seg_starts[0] == 0
            and seg_starts[-1] == n and (np.diff(seg_starts) >= 0).all()):
        raise ValueError("alignment_filter: seg_starts must run from 0 to "
                         "the row count without a step back, over arrays "
                         "of one length")
    lib.fgumi_alignment_filter(
        _addr(buf), _addr(cigar_off), _addr(n_cigar), _addr(reverse),
        _addr(final_len), _addr(seg_starts), len(seg_starts) - 1,
        _addr(keep))
    return keep


def codec_cigar_prepare(buf: np.ndarray, cigar_off, n_cigar, flag, pos,
                        l_seq, seg_starts, min_reads: int,
                        min_duplex_length: int) -> dict:
    """Codec's prepare (consensus/codec.py prepare(), phases 2-4) for the FR
    pairs of whole molecules: the mate clip, the clipped CIGAR and position,
    the per-strand most-common-alignment filter and the overlap geometry
    with its phase check.

    The read arrays hold 2P entries, the P pairs' R1s and then their R2s in
    the same order; seg_starts (int64[n_seg + 1]) bounds each molecule's
    pairs. Per read: ``clip``, ``clipped_len``, ``adj_pos``, ``ref_len``,
    ``keep`` and the clipped CIGAR, ``words[word_lo:word_lo + word_n]`` (BAM
    words). Per molecule: ``verdict`` (0 kept, 1 InsufficientReads, 2
    InsufficientOverlap, 3 IndelErrorBetweenStrands), ``n_kept1`` /
    ``n_kept2``, ``len1`` / ``len2``, ``longest1`` / ``longest2`` (the read
    index of each strand's longest kept read by clipped reference length, -1
    where the verdict came before phase 4), ``length``; and
    ``tally``, the filter's sets, sets kept whole, reads in and reads
    rejected (`consensus/codec.py FILTER_TALLY`).
    """
    lib = get_lib()
    n = len(cigar_off)
    seg_starts = np.ascontiguousarray(seg_starts, np.int64)
    if not (n % 2 == 0 and len(n_cigar) == len(flag) == len(pos)
            == len(l_seq) == n and len(seg_starts) >= 1
            and seg_starts[0] == 0 and seg_starts[-1] == n // 2
            and (np.diff(seg_starts) >= 0).all()):
        raise ValueError("codec_cigar_prepare: seg_starts must run from 0 to "
                         "the pair count without a step back, over read "
                         "arrays of one even length")
    # converted arrays must stay referenced until the foreign call returns
    cigar_off = np.ascontiguousarray(cigar_off, np.int64)
    n_cigar, flag, pos, l_seq = (np.ascontiguousarray(a, np.int32)
                                 for a in (n_cigar, flag, pos, l_seq))
    n_seg = len(seg_starts) - 1
    out = {"words": np.empty(int(n_cigar.sum()) + n, dtype=np.uint32),
           "word_lo": np.empty(n, dtype=np.int64),
           "word_n": np.empty(n, dtype=np.int32),
           "clip": np.empty(n, dtype=np.int32),
           "clipped_len": np.empty(n, dtype=np.int32),
           "adj_pos": np.empty(n, dtype=np.int64),
           "ref_len": np.empty(n, dtype=np.int64),
           "keep": np.empty(n, dtype=np.uint8),
           "verdict": np.empty(n_seg, dtype=np.int8),
           "n_kept1": np.empty(n_seg, dtype=np.int32),
           "n_kept2": np.empty(n_seg, dtype=np.int32),
           "len1": np.empty(n_seg, dtype=np.int32),
           "len2": np.empty(n_seg, dtype=np.int32),
           "longest1": np.empty(n_seg, dtype=np.int64),
           "longest2": np.empty(n_seg, dtype=np.int64),
           "length": np.empty(n_seg, dtype=np.int64),
           "tally": np.zeros(4, dtype=np.int64)}
    lib.fgumi_codec_cigar_prepare(
        _addr(buf), _addr(cigar_off), _addr(n_cigar), _addr(flag),
        _addr(pos), _addr(l_seq), n // 2, _addr(seg_starts), n_seg,
        min_reads, min_duplex_length,
        *(_addr(out[k]) for k in (
            "words", "word_lo", "word_n", "clip", "clipped_len", "adj_pos",
            "ref_len", "keep", "verdict", "n_kept1", "n_kept2", "len1",
            "len2", "longest1", "longest2", "length", "tally")))
    return out


def build_consensus_records(code_addr, qual_addr, depth_addr, err_addr, lens,
                            flags, prefix: bytes, mi_addr, mi_len,
                            rx_addr, rx_len, rg: bytes,
                            per_base_tags: bool):
    """Serialize J consensus records into one block_size-prefixed wire blob.

    The *_addr arrays are raw element addresses (int64) into caller-owned
    arrays, which MUST stay referenced for the duration of the call; MI/RX
    values are addresses too (rx_addr 0 = absent tag).
    Returns bytes (the concatenated records, ready for BamWriter raw append).
    """
    lib = get_lib()
    J = len(lens)
    lens = np.ascontiguousarray(lens, np.int32)
    flags = np.ascontiguousarray(flags, np.int32)
    mi_len = np.ascontiguousarray(mi_len, np.int32)
    rx_len = np.ascontiguousarray(rx_len, np.int32)
    mi_addr = np.ascontiguousarray(mi_addr, np.int64)
    rx_addr = np.ascontiguousarray(rx_addr, np.int64)
    # exact per-record size bound (mirrors the C size computation)
    per_rec = (4 + 32 + len(prefix) + 1 + mi_len.astype(np.int64) + 1
               + (lens + 1) // 2 + lens + (3 + len(rg) + 1) + 21
               + (3 + mi_len.astype(np.int64) + 1)
               + np.where(rx_addr != 0, 3 + rx_len.astype(np.int64) + 1, 0))
    if per_base_tags:
        per_rec = per_rec + 2 * (8 + 2 * lens.astype(np.int64))
    out_cap = int(per_rec.sum())
    out = np.empty(out_cap, dtype=np.uint8)
    rec_end = np.empty(J, dtype=np.int64)
    prefix_arr = np.frombuffer(prefix, dtype=np.uint8)
    rg_arr = np.frombuffer(rg, dtype=np.uint8)
    total = lib.fgumi_build_consensus_records(
        _addr(code_addr), _addr(qual_addr), _addr(depth_addr),
        _addr(err_addr), _addr(lens), _addr(flags), J,
        _addr(prefix_arr), len(prefix), _addr(mi_addr), _addr(mi_len),
        _addr(rx_addr), _addr(rx_len),
        _addr(rg_arr), len(rg), int(per_base_tags), _addr(out), out_cap,
        _addr(rec_end))
    if total == -2:
        raise ValueError("read name too long (prefix + MI exceeds 254 bytes)")
    if total < 0:
        raise RuntimeError("consensus record serialization overflow")
    return out[:total].tobytes(), rec_end


def build_duplex_records(code_addr, qual_addr, err_addr, lens, flags,
                         prefix: bytes, mi_addr, mi_len,
                         a_code, a_qual, a_depth, a_err, a_len,
                         b_code, b_qual, b_depth, b_err, b_len, b_present,
                         rx_addr, rx_len, rg: bytes, per_base_tags: bool):
    """Serialize J duplex consensus records into one wire blob.

    All *_addr / strand arrays are raw element addresses (int64) into
    caller-owned arrays that MUST stay referenced for the call duration;
    b_present 0 = BA strand absent, rx_addr 0 = no RX tag.
    """
    lib = get_lib()
    J = len(lens)
    lens = np.ascontiguousarray(lens, np.int32)
    flags = np.ascontiguousarray(flags, np.int32)
    mi_len = np.ascontiguousarray(mi_len, np.int32)
    a_len = np.ascontiguousarray(a_len, np.int32)
    b_len = np.ascontiguousarray(b_len, np.int32)
    b_present = np.ascontiguousarray(b_present, np.uint8)
    rx_len = np.ascontiguousarray(rx_len, np.int32)
    addrs = [np.ascontiguousarray(a, np.int64)
             for a in (code_addr, qual_addr, err_addr, mi_addr, a_code, a_qual,
                       a_depth, a_err, b_code, b_qual, b_depth, b_err,
                       rx_addr)]
    (code_addr, qual_addr, err_addr, mi_addr, a_code, a_qual, a_depth, a_err,
     b_code, b_qual, b_depth, b_err, rx_addr) = addrs
    L64 = lens.astype(np.int64)
    aL64 = a_len.astype(np.int64)
    bL64 = np.where(b_present != 0, b_len, 0).astype(np.int64)
    per_rec = (4 + 32 + len(prefix) + 1 + mi_len.astype(np.int64) + 1
               + (L64 + 1) // 2 + L64
               + (3 + mi_len.astype(np.int64) + 1) + (3 + len(rg) + 1)
               + 9 * 7
               + np.where(rx_addr != 0, 3 + rx_len.astype(np.int64) + 1, 0))
    if per_base_tags:
        per_rec = per_rec + 2 * (4 + aL64) + 16 + 4 * aL64 \
            + np.where(b_present != 0, 2 * (4 + bL64) + 16 + 4 * bL64, 0)
    out_cap = int(per_rec.sum())
    out = np.empty(out_cap, dtype=np.uint8)
    rec_end = np.empty(J, dtype=np.int64)
    prefix_arr = np.frombuffer(prefix, dtype=np.uint8)
    rg_arr = np.frombuffer(rg, dtype=np.uint8)
    total = lib.fgumi_build_duplex_records(
        _addr(code_addr), _addr(qual_addr), _addr(err_addr), _addr(lens),
        _addr(flags), J, _addr(prefix_arr), len(prefix), _addr(mi_addr),
        _addr(mi_len), _addr(a_code), _addr(a_qual), _addr(a_depth),
        _addr(a_err), _addr(a_len), _addr(b_code), _addr(b_qual),
        _addr(b_depth), _addr(b_err), _addr(b_len), _addr(b_present),
        _addr(rx_addr), _addr(rx_len), _addr(rg_arr), len(rg),
        int(per_base_tags), _addr(out), out_cap, _addr(rec_end))
    if total == -2:
        raise ValueError("read name too long (prefix + MI exceeds 254 bytes)")
    if total < 0:
        raise RuntimeError("duplex record serialization overflow")
    return out[:total].tobytes(), rec_end


def consensus_segments(codes2d: np.ndarray, quals2d: np.ndarray,
                       starts: np.ndarray, correct_tab: np.ndarray,
                       err_alt_tab: np.ndarray, g_sat: float, qual_const: int,
                       min_phred: int, tab1_winner: np.ndarray,
                       tab1_qual: np.ndarray, tab2_winner: np.ndarray,
                       tab2_qual: np.ndarray):
    """One f64 consensus pass over ragged segments (fgumi_consensus_segments).

    Returns (winner (J,L) u8, qual (J,L) u8, depth (J,L) i32,
    errors (J,L) i32, slow_idx int64[K], slow_ll (K,4) f64,
    slow_obs (K,4) i32): fast/tabled positions are fully resolved; the K slow
    positions carry their bit-exact lane sums and observation counts for the
    caller's oracle epilogue.
    """
    faults.fire("native.batch")
    lib = get_lib()
    J = len(starts) - 1
    L = codes2d.shape[1] if codes2d.ndim == 2 else 0
    codes2d = _as_c(codes2d, np.uint8)
    quals2d = _as_c(quals2d, np.uint8)
    starts = _as_c(starts, np.int64)
    winner = np.empty((J, L), dtype=np.uint8)
    qual = np.empty((J, L), dtype=np.uint8)
    depth = np.empty((J, L), dtype=np.int32)
    errors = np.empty((J, L), dtype=np.int32)
    cap = max(4096, (J * L) // 8)
    while True:
        slow_idx = np.empty(cap, dtype=np.int64)
        slow_ll = np.empty((cap, 4), dtype=np.float64)
        slow_obs = np.empty((cap, 4), dtype=np.int32)
        n_slow = lib.fgumi_consensus_segments(
            _addr(codes2d), _addr(quals2d), _addr(starts), J, L,
            _addr(correct_tab), _addr(err_alt_tab),
            float(g_sat), int(qual_const), int(min_phred),
            _addr(tab1_winner), _addr(tab1_qual), _addr(tab2_winner),
            _addr(tab2_qual), _addr(winner), _addr(qual), _addr(depth),
            _addr(errors), _addr(slow_idx), _addr(slow_ll), _addr(slow_obs),
            cap)
        if n_slow <= cap:
            return (winner, qual, depth, errors, slow_idx[:n_slow],
                    slow_ll[:n_slow], slow_obs[:n_slow])
        cap = n_slow  # adversarial input: every position borderline


def umi_neighbor_pairs(mat_a: np.ndarray, mat_b, d: int, index: str = "auto"):
    """Candidate (i, j) pairs with hamming <= d.

    mat_b None means the symmetric same-matrix case (pairs emitted once,
    i < j); otherwise all cross pairs with i != j. Returns (i, j) int64
    arrays, duplicate-free. `index` selects the search structure
    (reference assigner.rs:228,267 keeps both flavors): "pigeonhole"
    (fgumi_umi_neighbor_pairs sorted partition buckets) or "bktree"
    (fgumi_umi_bktree_pairs triangle-inequality pruning). "auto" picks
    pigeonhole: measured on 4-16k random UMIs of length 8-12 at d=1..4
    the bucketed memcmp scan beats the pointer-chasing tree 3-6x at every
    d — short UMIs distance-discriminate too weakly for BK pruning to pay
    (mean pairwise distance ~0.75*L, so |d(child)-d(query)| <= d prunes
    little). FGUMI_TPU_UMI_INDEX=bktree overrides for verification.
    """
    import os

    lib = get_lib()
    mat_a = np.ascontiguousarray(mat_a, np.uint8)
    n, L = mat_a.shape
    if mat_b is None:
        b_ptr, m = _addr(mat_a), n
    else:
        mat_b = np.ascontiguousarray(mat_b, np.uint8)
        b_ptr, m = _addr(mat_b), mat_b.shape[0]
    if index == "auto":
        index = os.environ.get("FGUMI_TPU_UMI_INDEX", "pigeonhole")
    if index not in ("pigeonhole", "bktree"):
        # a silently-ignored typo would "verify" pigeonhole against itself
        raise ValueError(f"unknown UMI index {index!r} "
                         "(expected pigeonhole or bktree)")
    fn = lib.fgumi_umi_bktree_pairs if index == "bktree" \
        else lib.fgumi_umi_neighbor_pairs
    cap = max(4 * max(n, m), 4096)
    while True:
        out_i = np.empty(cap, dtype=np.int64)
        out_j = np.empty(cap, dtype=np.int64)
        count = fn(_addr(mat_a), n, b_ptr, m, L, int(d), _addr(out_i),
                   _addr(out_j), cap)
        if count <= cap:
            return out_i[:count], out_j[:count]
        cap = count


def adjacency_bfs(nbr_flat: np.ndarray, nbr_start: np.ndarray,
                  counts: np.ndarray):
    """Directed adjacency BFS roots (fgumi_adjacency_bfs): root_of int64[n]."""
    lib = get_lib()
    n = len(nbr_start) - 1
    nbr_flat = np.ascontiguousarray(nbr_flat, np.int64)
    nbr_start = np.ascontiguousarray(nbr_start, np.int64)
    counts = np.ascontiguousarray(counts, np.int64)
    root_of = np.empty(n, dtype=np.int64)
    lib.fgumi_adjacency_bfs(_addr(nbr_flat), _addr(nbr_start), _addr(counts),
                            n, _addr(root_of))
    return root_of


def within_bits_lists(bits: np.ndarray, n: int, m: int):
    """Neighbour lists from the device Hamming kernel's packed answer
    (fgumi_within_bits_lists): ``bits`` is ``(rows, row_bytes)`` uint8, high
    bit first, and only rows < n and columns < m are read. Returns
    ``(nbr_flat int64, nbr_start int64[n + 1])``, ``adjacency_bfs``'s input:
    row i's ascending j != i whose bit is set."""
    lib = get_lib()
    bits = np.ascontiguousarray(bits, np.uint8)
    if bits.ndim != 2 or bits.shape[0] < n or 8 * bits.shape[1] < m:
        raise ValueError(f"within_bits_lists: {bits.shape} bits cannot hold "
                         f"{n} rows of {m} columns")
    nbr_start = np.empty(n + 1, dtype=np.int64)
    total = lib.fgumi_within_bits_lists(_addr(bits), bits.shape[1], n, m,
                                        _addr(nbr_start), None)
    nbr_flat = np.empty(total, dtype=np.int64)
    if total:
        lib.fgumi_within_bits_lists(_addr(bits), bits.shape[1], n, m,
                                    _addr(nbr_start), _addr(nbr_flat))
    return nbr_flat, nbr_start


def segment_depth_errors(codes2d: np.ndarray, winner: np.ndarray,
                         starts: np.ndarray):
    """Per-segment depth/error counts: (J, L) int32 pair.

    codes2d: dense (N, L) uint8 read rows; winner: (J, L) uint8 called bases;
    starts: (J+1,) row boundaries.
    """
    lib = get_lib()
    J, L = winner.shape
    depth = np.empty((J, L), dtype=np.int32)
    errors = np.empty((J, L), dtype=np.int32)
    codes2d = _as_c(codes2d, np.uint8)
    winner = _as_c(winner, np.uint8)
    starts = _as_c(starts, np.int64)
    lib.fgumi_segment_depth_errors(_addr(codes2d), _addr(winner),
                                   _addr(starts), J, L, _addr(depth),
                                   _addr(errors))
    return depth, errors


def wire_inputs_ok(codes: np.ndarray, quals: np.ndarray, rows=None,
                   L: int = None) -> bool:
    """Whether :func:`build_wire` can take these inputs: the library is
    loaded, codes/quals are uint8 matrices of one shape whose rows are
    contiguous at one stride and at least ``L`` wide, and ``rows`` (when
    given) is an int64 vector of in-range row numbers. Anything else is
    the numpy path's to handle (or to refuse, as it always did)."""
    if get_lib() is None:
        return False
    for a in (codes, quals):
        if not (isinstance(a, np.ndarray) and a.dtype == np.uint8
                and a.ndim == 2):
            return False
    if codes.shape != quals.shape or codes.strides != quals.strides:
        return False
    R, width = codes.shape
    if (R > 1 and codes.strides[0] < width) or \
            (width > 1 and codes.strides[1] != 1):
        return False
    if L is not None and not 0 <= L <= width:
        return False
    if rows is None:
        return True
    if not (isinstance(rows, np.ndarray) and rows.dtype == np.int64
            and rows.ndim == 1 and rows.flags.c_contiguous):
        return False
    return not len(rows) or (int(rows.min()) >= 0 and int(rows.max()) < R)


def _row_stride(mat: np.ndarray) -> int:
    """Bytes from one row of a vetted uint8 matrix to the next (numpy
    reports any stride for a matrix of one row)."""
    return mat.strides[0] if len(mat) > 1 else mat.shape[1]


def build_wire(codes: np.ndarray, quals: np.ndarray, rows, n_pad: int,
               L: int, wire: np.ndarray, codes_dev: np.ndarray = None,
               quals_dev: np.ndarray = None):
    """The device layout of one wire dispatch in one native pass
    (fgumi_build_wire; inputs vetted by :func:`wire_inputs_ok`).

    Row i of the layout is ``codes[rows[i], :L]`` (``rows=None``: row i of
    the already dense input); rows up to ``n_pad`` are pad. Fills ``wire``
    and, when given, the dense ``codes_dev`` / ``quals_dev`` (all
    C-contiguous (n_pad, L) uint8). Returns the batch's distinct quals in
    ascending order (uint8[n], n <= 63: the wire's dictionary order), or
    None when there are more, with nothing written."""
    n = len(codes) if rows is None else len(rows)
    outs = [a for a in (wire, codes_dev, quals_dev) if a is not None]
    if not all(a.dtype == np.uint8 and a.shape == (n_pad, L)
               and a.flags.c_contiguous and a.flags.writeable for a in outs) \
            or n > n_pad:
        raise ValueError("build_wire: outputs must be writable C-contiguous "
                         f"({n_pad}, {L}) uint8 arrays holding {n} rows")
    vals = np.empty(64, dtype=np.uint8)
    k = get_lib().fgumi_build_wire(
        codes.ctypes.data, quals.ctypes.data, _row_stride(codes),
        None if rows is None else rows.ctypes.data, n, n_pad, L,
        wire.ctypes.data,
        None if codes_dev is None else codes_dev.ctypes.data,
        None if quals_dev is None else quals_dev.ctypes.data,
        vals.ctypes.data)
    return None if k < 0 else vals[:k]


def segment_depth_errors_ranges(codes: np.ndarray, rows: np.ndarray,
                                winner: np.ndarray, lo, hi):
    """segment_depth_errors over explicit [lo[j], hi[j]) ranges of a row
    list: entry r of a range is ``codes[rows[r], :L]``, read where it lies
    in the batch's packed (R, stride) uint8 ``codes`` (rows contiguous, at
    least ``L`` = ``winner.shape[1]`` wide), so the rows are never copied
    out. ``rows`` is an int64 vector of in-range row numbers and every
    range lies within it."""
    lib = get_lib()
    J, L = winner.shape
    lo = _as_c(lo, np.int64)
    hi = _as_c(hi, np.int64)
    ranges_ok = len(lo) == len(hi) == J and (
        not J or (int(lo.min()) >= 0 and int(hi.max()) <= len(rows)))
    # the wire pass reads the same packed arrays through the same kind of
    # row list: one vetting for both
    if not (ranges_ok and wire_inputs_ok(codes, codes, rows, L)):
        raise ValueError(
            "segment_depth_errors_ranges: codes must be a uint8 matrix of "
            f"contiguous rows at least {L} wide, rows in-range int64, and "
            f"the {J} ranges within the {len(rows)} rows")
    depth = np.empty((J, L), dtype=np.int32)
    errors = np.empty((J, L), dtype=np.int32)
    winner = _as_c(winner, np.uint8)
    lib.fgumi_segment_depth_errors_ranges(
        codes.ctypes.data, _row_stride(codes), rows.ctypes.data,
        _addr(winner), _addr(lo), _addr(hi), J, L,
        _addr(depth), _addr(errors))
    return depth, errors


def ranges_equal(buf: np.ndarray, off_a, len_a, off_b, len_b):
    """uint8[n] mask: byte ranges (off_a, len_a) == (off_b, len_b) in buf."""
    lib = get_lib()
    n = len(off_a)
    out = np.empty(n, dtype=np.uint8)
    off_a = np.ascontiguousarray(off_a, np.int64)
    off_b = np.ascontiguousarray(off_b, np.int64)
    len_a = np.ascontiguousarray(len_a, np.int32)
    len_b = np.ascontiguousarray(len_b, np.int32)
    lib.fgumi_ranges_equal(_addr(buf), _addr(off_a), _addr(len_a),
                           _addr(off_b), _addr(len_b), n, _addr(out))
    return out


def template_coord_keys(batch, lib_ord: np.ndarray):
    """Packed template-coordinate sort keys for a whole RecordBatch.

    Returns (out uint8 blob, out_off int64[n+1]) — record i's key is
    out[out_off[i]:out_off[i+1]].
    """
    lib = get_lib()
    n = batch.n
    # only Z/H-typed tags count as present (RawRecord.get_str semantics);
    # e.g. an MI:i: tag must fall back to (0, 0) like the per-record path
    batch.prefetch_tags([b"MC", b"MI", b"RG"])  # one fused aux scan
    mc_off, mc_len, _ = batch.tag_locs_str(b"MC")
    mi_off, mi_len, _ = batch.tag_locs_str(b"MI")
    key_len = (30 + batch.l_read_name).astype(np.int64)  # 29 + name + NUL + up
    out_off = np.concatenate(([0], np.cumsum(key_len)))
    out = np.empty(int(out_off[-1]), dtype=np.uint8)
    args = [np.ascontiguousarray(a) for a in (
        batch.data_off, batch.l_read_name, batch.cigar_off, batch.n_cigar,
        batch.flag, batch.ref_id, batch.pos, batch.next_ref_id,
        batch.next_pos, mc_off, mc_len, mi_off, mi_len)]
    lib_ord = np.ascontiguousarray(lib_ord, np.int32)
    lib.fgumi_template_coord_keys(
        _addr(batch.buf), *(map(_addr, args)), _addr(lib_ord), n, _addr(out),
        _addr(out_off))
    return out, out_off


def natural_name_keys(batch):
    """Packed natural-queryname sort keys for a whole RecordBatch.

    Returns (out uint8 blob, out_off int64[n], out_len int32[n]).
    """
    lib = get_lib()
    n = batch.n
    # worst case 3 bytes per name char (alternating single-char digit/text
    # runs) + NUL + 4-byte rank
    cap = (3 * batch.l_read_name + 2).astype(np.int64)
    out_off = np.concatenate(([0], np.cumsum(cap)))[:-1]
    out = np.empty(int(cap.sum()), dtype=np.uint8)
    out_len = np.empty(n, dtype=np.int32)
    args = [np.ascontiguousarray(a) for a in (
        batch.data_off, batch.l_read_name, batch.flag)]
    lib.fgumi_natural_name_keys(_addr(batch.buf), *(map(_addr, args)), n,
                                _addr(out), _addr(out_off), _addr(out_len))
    return out, out_off, out_len


def unclipped_5prime(batch):
    """Per-record unclipped 5' positions (int64[n]; meaningful for mapped)."""
    lib = get_lib()
    out = np.empty(batch.n, dtype=np.int64)
    args = [np.ascontiguousarray(a) for a in (
        batch.cigar_off, batch.n_cigar, batch.flag, batch.pos)]
    lib.fgumi_unclipped_5prime(_addr(batch.buf), *(map(_addr, args)), batch.n,
                               _addr(out))
    return out


def umi_scan(buf: np.ndarray, off, length):
    """(has_n uint8[n], bases int32[n], ascii uint8[n]) per byte range;
    off < 0 -> (0, -1, 1)."""
    lib = get_lib()
    n = len(off)
    has_n = np.empty(n, dtype=np.uint8)
    bases = np.empty(n, dtype=np.int32)
    ascii_ = np.empty(n, dtype=np.uint8)
    off = np.ascontiguousarray(off, np.int64)
    length = np.ascontiguousarray(length, np.int32)
    lib.fgumi_umi_scan(_addr(buf), _addr(off), _addr(length), n,
                       _addr(has_n), _addr(bases), _addr(ascii_))
    return has_n, bases, ascii_


def rewrite_tag_records(batch, rows, tag: bytes, values, new_flags=None):
    """Wire blob for `rows` with `tag` replaced by per-row Z values.

    values: list of bytes, parallel to rows. new_flags: optional int32 array
    (per row; -1 = keep the record's flag). Returns the contiguous
    block_size-prefixed wire blob with every prior occurrence of the tag
    removed and the new value appended per record. Raises ValueError on a
    malformed aux region (callers fall back to the Python record editor).
    """
    lib = get_lib()
    rows = np.ascontiguousarray(rows, np.int64)
    k = len(rows)
    if isinstance(values, np.ndarray) and values.dtype.kind == "S":
        # fixed-stride S-array fast path: true lengths + stride offsets
        # into the array's own buffer (NUL padding is simply never read)
        val_len = np.char.str_len(values).astype(np.int32)
        stride = values.dtype.itemsize
        val_off = np.arange(k, dtype=np.int64) * stride
        v = np.ascontiguousarray(values)
        val_blob = v.view(np.uint8) if k else np.zeros(1, np.uint8)
    else:
        val_blob = np.frombuffer(b"".join(values) or b"\x00", dtype=np.uint8)
        val_len = np.array([len(v) for v in values], dtype=np.int32)
        val_off = np.concatenate(
            ([0], np.cumsum(val_len, dtype=np.int64)))[:-1] \
            if k else np.empty(0, dtype=np.int64)
    data_off = np.ascontiguousarray(batch.data_off[rows])
    data_end = np.ascontiguousarray(batch.data_end[rows])
    aux_off = np.ascontiguousarray(batch.aux_off[rows])
    cap = int(((data_end - data_off) + 8 + val_len).sum())
    out = np.empty(cap, dtype=np.uint8)
    flags_arg = 0
    if new_flags is not None:
        new_flags = np.ascontiguousarray(new_flags, np.int32)
        flags_arg = _addr(new_flags)
    total = lib.fgumi_rewrite_tag_records(
        _addr(batch.buf), _addr(data_off), _addr(data_end), _addr(aux_off),
        k, tag[0], tag[1], _addr(val_blob), _addr(val_off), _addr(val_len),
        flags_arg, _addr(out))
    if total < 0:
        raise ValueError(f"malformed aux region in record {-(total + 1)}")
    return out[:total].tobytes()


def qual_scores(batch, min_q: int, cap: int):
    """Per-record Picard base-quality score (sum of quals >= min_q, capped)."""
    lib = get_lib()
    out = np.empty(batch.n, dtype=np.int32)
    qual_off = np.ascontiguousarray(batch.qual_off)
    l_seq = np.ascontiguousarray(batch.l_seq)
    lib.fgumi_qual_scores(_addr(batch.buf), _addr(qual_off), _addr(l_seq),
                          batch.n, min_q, cap, _addr(out))
    return out


def gather_u16_arrays(buf: np.ndarray, val_off, L: int):
    """Dense (n, L) uint16 matrix from B:s/B:S tag values (zero-padded).

    Returns (values, counts): counts -1 = tag absent, -2 = non-16-bit
    subtype (caller reroutes that record).
    """
    lib = get_lib()
    n = len(val_off)
    out = np.empty((n, L), dtype=np.uint16)
    counts = np.empty(n, dtype=np.int32)
    val_off = np.ascontiguousarray(val_off, np.int64)
    lib.fgumi_gather_u16_arrays(_addr(buf), _addr(val_off), n, L, _addr(out),
                                _addr(counts))
    return out, counts


def apply_masks(batch, rows, mask: np.ndarray, skip_existing_n: bool):
    """In-place N/Q2 masking of `rows`' seq/qual regions.

    mask: (len(rows), L) uint8 over each record's first l_seq positions.
    Returns (newly_masked int32[k], n_after int32[k]).
    """
    lib = get_lib()
    rows = np.ascontiguousarray(rows, np.int64)
    k = len(rows)
    mask = np.ascontiguousarray(mask, np.uint8)
    seq_off = np.ascontiguousarray(batch.seq_off[rows])
    qual_off = np.ascontiguousarray(batch.qual_off[rows])
    l_seq = np.ascontiguousarray(batch.l_seq[rows])
    newly = np.empty(k, dtype=np.int32)
    n_after = np.empty(k, dtype=np.int32)
    lib.fgumi_apply_masks(_addr(batch.buf), _addr(seq_off), _addr(qual_off),
                          _addr(l_seq), k, _addr(mask), mask.shape[1],
                          int(skip_existing_n), _addr(newly), _addr(n_after))
    return newly, n_after


def hash_ranges(buf: np.ndarray, off, length):
    """FNV-1a 64-bit hash per byte range (off < 0 -> 0)."""
    lib = get_lib()
    n = len(off)
    out = np.empty(n, dtype=np.uint64)
    off = np.ascontiguousarray(off, np.int64)
    length = np.ascontiguousarray(length, np.int32)
    lib.fgumi_hash_ranges(_addr(buf), _addr(off), _addr(length), n, _addr(out))
    return out


def rx_unanimous(buf: np.ndarray, off, length, starts):
    """Per-segment RX unanimity: (out_off int64[J], out_len int32[J]).

    out_off -1 = no tag anywhere in the segment; -2 = caller must run the
    Python consensus; >= 0 = verbatim unanimous value at that buffer range.
    """
    lib = get_lib()
    J = len(starts) - 1
    out_off = np.empty(J, dtype=np.int64)
    out_len = np.empty(J, dtype=np.int32)
    off = np.ascontiguousarray(off, np.int64)
    length = np.ascontiguousarray(length, np.int32)
    starts = np.ascontiguousarray(starts, np.int64)
    lib.fgumi_rx_unanimous(_addr(buf), _addr(off), _addr(length),
                           _addr(starts), J, _addr(out_off), _addr(out_len))
    return out_off, out_len


def overlap_correct_pairs(buf: np.ndarray, r1_off: np.ndarray,
                          r2_off: np.ndarray, agreement: int,
                          disagreement: int) -> np.ndarray:
    """In-place R1/R2 overlap correction on a WRITABLE buffer.

    agreement: 0=consensus 1=max-qual 2=pass-through; disagreement:
    0=consensus 1=mask-both 2=mask-lower-qual. Returns int64[4] stats
    (overlapping, agreeing, disagreeing, corrected).
    """
    lib = get_lib()
    assert buf.flags["WRITEABLE"]
    stats = np.zeros(4, dtype=np.int64)
    lib.fgumi_overlap_correct_pairs(_addr(buf), _addr(r1_off), _addr(r2_off),
                                    len(r1_off), agreement, disagreement,
                                    _addr(stats))
    return stats


def extract_records(bufs, name_off, name_len, seq_off, seq_len, qual_off,
                    segments, qual_offset: int, rg: bytes,
                    store_umi_quals: bool):
    """Batched FASTQ -> unmapped-BAM record assembly (fgumi_extract_records).

    bufs: list of per-input uint8 chunk buffers; the offset/len arrays are
    (n_inputs, n) int64/int32; segments: flattened [(input, kind, len)] with
    kind 0=template 1=UMI 2=skip and len -1 = rest-of-read.
    Returns the block_size-prefixed wire blob (bytes).
    """
    lib = get_lib()
    n_inputs = len(bufs)
    n = name_off.shape[1]
    buf_addr = np.array([b.ctypes.data for b in bufs], dtype=np.int64)
    name_off = np.ascontiguousarray(name_off, np.int64)
    name_len = np.ascontiguousarray(name_len, np.int32)
    seq_off = np.ascontiguousarray(seq_off, np.int64)
    seq_len = np.ascontiguousarray(seq_len, np.int32)
    qual_off = np.ascontiguousarray(qual_off, np.int64)
    seg_input = np.array([s[0] for s in segments], dtype=np.int32)
    seg_kind = np.array([s[1] for s in segments], dtype=np.int32)
    seg_len = np.array([s[2] for s in segments], dtype=np.int32)
    # capacity: every read byte appears at most twice (packed seq + quals,
    # UMI segments again in RX+QX), plus per emitted record header+name+tags
    n_templates = max(1, int((seg_kind == 0).sum()))
    max_name = int(name_len.max()) if n else 0
    # packed seq + quals appear once per read byte; the joined UMI (fixed M
    # segments only on this path, _fast_extract_ok) repeats in every emitted
    # record's RX and QX
    umi_total = int(seg_len[seg_kind == 1].sum()) + int((seg_kind == 1).sum())
    out_cap = (int(2 * seq_len.astype(np.int64).sum())
               + n * n_templates * (104 + max_name + len(rg) + 2 * umi_total)
               + 4096)
    out = np.empty(out_cap, dtype=np.uint8)
    state = np.zeros(2, dtype=np.int64)
    rg_arr = np.frombuffer(rg, dtype=np.uint8)
    rc = lib.fgumi_extract_records(
        n_inputs, n, _addr(buf_addr), _addr(name_off), _addr(name_len),
        _addr(seq_off), _addr(seq_len), _addr(qual_off), len(segments),
        _addr(seg_input), _addr(seg_kind), _addr(seg_len), qual_offset,
        _addr(rg_arr), len(rg), int(store_umi_quals), _addr(out), out_cap,
        _addr(state))
    if rc == -1:
        raise RuntimeError("extract output capacity overflow")
    if rc in (-2, -3, -4):
        raise NativeExtractError(int(rc), int(state[1]))
    return out[:int(state[0])].tobytes()


class NativeExtractError(ValueError):
    """Record-level extract failure; the caller re-runs the offending record
    through the Python path to produce the canonical error message."""

    def __init__(self, code: int, record_index: int):
        super().__init__(f"extract error {code} at batch record {record_index}")
        self.code = code
        self.record_index = record_index


def build_codec_records(seq_addr, qual_addr, cons_err_addr,
                        a_base, a_qual, a_depth, a_err,
                        b_base, b_qual, b_depth, b_err,
                        lens, name_addr, name_len, mi_addr, mi_len,
                        rx_addr, rx_len, rg: bytes, flags: int,
                        per_base_tags: bool):
    """Serialize J CODEC consensus records into one wire blob.

    Byte-exact analog of CodecConsensusCaller._build_record (codec.py; ref
    codec_caller.rs:1374-1539). All *_addr arrays are raw element addresses
    (int64) into caller-owned arrays that MUST stay referenced for the call;
    seq/qual/strand base+qual rows are uint8, cons_err/depth/error rows are
    int32 (as `codec_combine` and `codec_place` return them), all of length
    lens[j]. mi_len[j] < 0 skips MI; rx_addr[j] == 0
    skips RX.
    """
    lib = get_lib()
    J = len(lens)
    lens = np.ascontiguousarray(lens, np.int32)
    name_len = np.ascontiguousarray(name_len, np.int32)
    mi_len = np.ascontiguousarray(mi_len, np.int32)
    rx_len = np.ascontiguousarray(rx_len, np.int32)
    addrs = [np.ascontiguousarray(a, np.int64)
             for a in (seq_addr, qual_addr, cons_err_addr, a_base, a_qual,
                       a_depth, a_err, b_base, b_qual, b_depth, b_err,
                       name_addr, mi_addr, rx_addr)]
    (seq_addr, qual_addr, cons_err_addr, a_base, a_qual, a_depth, a_err,
     b_base, b_qual, b_depth, b_err, name_addr, mi_addr, rx_addr) = addrs
    L64 = lens.astype(np.int64)
    per_rec = (4 + 32 + name_len.astype(np.int64) + 1 + (L64 + 1) // 2 + L64
               + (3 + len(rg) + 1) + 9 * 7
               + np.where(mi_len >= 0, 3 + mi_len.astype(np.int64) + 1, 0)
               + np.where(rx_addr != 0, 3 + rx_len.astype(np.int64) + 1, 0))
    if per_base_tags:
        per_rec = per_rec + 4 * (8 + 2 * L64) + 4 * (3 + L64 + 1)
    out_cap = int(per_rec.sum())
    out = np.empty(out_cap, dtype=np.uint8)
    rec_end = np.empty(J, dtype=np.int64)
    rg_arr = np.frombuffer(rg, dtype=np.uint8)
    total = lib.fgumi_build_codec_records(
        _addr(seq_addr), _addr(qual_addr), _addr(cons_err_addr),
        _addr(a_base), _addr(a_qual), _addr(a_depth), _addr(a_err),
        _addr(b_base), _addr(b_qual), _addr(b_depth), _addr(b_err),
        _addr(lens), J, _addr(name_addr), _addr(name_len), _addr(mi_addr),
        _addr(mi_len), _addr(rx_addr), _addr(rx_len), _addr(rg_arr), len(rg),
        int(flags), int(per_base_tags), _addr(out), out_cap, _addr(rec_end))
    if total == -2:
        raise ValueError("read name too long (exceeds 254 bytes)")
    if total < 0:
        raise RuntimeError("codec record serialization overflow")
    return out[:total].tobytes(), rec_end


def ref_spans(buf: np.ndarray, cigar_off, n_cigar, pos):
    """Per-record reference-span end (pos + ref-consumed CIGAR length, min 1)."""
    lib = get_lib()
    n = len(pos)
    out = np.empty(n, dtype=np.int32)
    co = np.ascontiguousarray(cigar_off, np.int64)
    nc = np.ascontiguousarray(n_cigar, np.int32)
    ps = np.ascontiguousarray(pos, np.int32)
    lib.fgumi_ref_spans(_addr(buf), _addr(co), _addr(nc), _addr(ps), n,
                        _addr(out))
    return out


def tag_name_list(buf: np.ndarray, aux_off, aux_end, max_per: int = 24):
    """Per-record aux tag names: (names uint16 (n, max_per), counts int32);
    counts[i] == -1 means too many/malformed (caller falls back)."""
    lib = get_lib()
    n = len(aux_off)
    names = np.empty((n, max_per), dtype=np.uint16)
    counts = np.empty(n, dtype=np.int32)
    ao = np.ascontiguousarray(aux_off, np.int64)
    ae = np.ascontiguousarray(aux_end, np.int64)
    lib.fgumi_tag_name_list(_addr(buf), _addr(ao), _addr(ae), n, max_per,
                            _addr(names), _addr(counts))
    return names, counts


def cigar_strings(buf: np.ndarray, cigar_off, n_cigar):
    """Batched CIGAR rendering: (blob bytes, (n+1,) int64 offsets)."""
    lib = get_lib()
    n = len(n_cigar)
    nc = np.ascontiguousarray(n_cigar, np.int32)
    co = np.ascontiguousarray(cigar_off, np.int64)
    cap = int(np.maximum(11 * nc.astype(np.int64), 1).sum())
    out = np.empty(cap, dtype=np.uint8)
    out_off = np.empty(n + 1, dtype=np.int64)
    rc = lib.fgumi_cigar_strings(_addr(buf), _addr(co), _addr(nc), n,
                                 _addr(out), _addr(out_off))
    if rc < 0:
        raise ValueError("invalid CIGAR op code")
    return out, out_off


def rebuild_aux_records(buf: np.ndarray, data_off, aux_off, data_end,
                        drop: np.ndarray, drop_off, appends: np.ndarray,
                        app_off):
    """Rebuild records with filtered aux + appended TLV bytes; returns
    (wire blob bytes incl. block_size prefixes, (n+1,) int64 offsets) or
    None when a record is malformed (caller falls back per record)."""
    lib = get_lib()
    n = len(data_off)
    do = np.ascontiguousarray(data_off, np.int64)
    ao = np.ascontiguousarray(aux_off, np.int64)
    de = np.ascontiguousarray(data_end, np.int64)
    dro = np.ascontiguousarray(drop_off, np.int64)
    apo = np.ascontiguousarray(app_off, np.int64)
    drop = np.ascontiguousarray(drop, np.uint16)
    appends = np.ascontiguousarray(appends, np.uint8)
    cap = int((de - do).sum() + (apo[-1] - apo[0]) + 4 * n)
    out = np.empty(max(cap, 1), dtype=np.uint8)
    out_pos = np.empty(n + 1, dtype=np.int64)
    total = lib.fgumi_rebuild_aux_records(
        _addr(buf), _addr(do), _addr(ao), _addr(de), n, _addr(drop),
        _addr(dro), _addr(appends), _addr(apo), _addr(out), _addr(out_pos))
    if total < 0:
        return None
    return out[:total], out_pos


def concat_spans(srcs, src_id, off, length):
    """Concatenate spans from up to 8 source uint8 arrays: returns
    (blob uint8, (n+1,) int64 offsets). Zero-length spans are legal."""
    lib = get_lib()
    n = len(src_id)
    addrs = np.zeros(8, dtype=np.int64)
    keep = []
    for i, s in enumerate(srcs):
        s = np.ascontiguousarray(s, np.uint8)
        keep.append(s)
        addrs[i] = s.ctypes.data
    sid = np.ascontiguousarray(src_id, np.int32)
    so = np.ascontiguousarray(off, np.int64)
    sl = np.ascontiguousarray(length, np.int32)
    out = np.empty(max(int(sl[sl > 0].sum()), 1), dtype=np.uint8)
    out_off = np.empty(n + 1, dtype=np.int64)
    lib.fgumi_concat_spans(_addr(addrs), _addr(sid), _addr(so), _addr(sl), n,
                           _addr(out), _addr(out_off))
    del keep
    return out, out_off


def codec_combine(b1, b2, q1, q2, d1, d2, e1, e2, min_phred: int,
                  no_call: int, no_call_lower: int, i16_max: int):
    """Single-pass CODEC duplex combine (fgumi_codec_combine).

    The native form of consensus/codec.py combine_arrays plus the
    both/disagree flag derivation — one C pass instead of ~25 whole-array
    numpy passes. Inputs: uint8 base/qual arrays and int32 depth/error
    arrays of equal length. Returns (base u8, qual u8, depth i32,
    errors i32, both bool, disag bool).
    """
    lib = get_lib()
    n = len(b1)
    b1 = np.ascontiguousarray(b1, np.uint8)
    b2 = np.ascontiguousarray(b2, np.uint8)
    q1 = np.ascontiguousarray(q1, np.uint8)
    q2 = np.ascontiguousarray(q2, np.uint8)
    d1 = np.ascontiguousarray(d1, np.int32)
    d2 = np.ascontiguousarray(d2, np.int32)
    e1 = np.ascontiguousarray(e1, np.int32)
    e2 = np.ascontiguousarray(e2, np.int32)
    cb = np.empty(n, dtype=np.uint8)
    cq = np.empty(n, dtype=np.uint8)
    cd = np.empty(n, dtype=np.int32)
    ce = np.empty(n, dtype=np.int32)
    both = np.empty(n, dtype=np.uint8)
    disag = np.empty(n, dtype=np.uint8)
    lib.fgumi_codec_combine(
        _addr(b1), _addr(b2), _addr(q1), _addr(q2), _addr(d1), _addr(d2),
        _addr(e1), _addr(e2), n, int(min_phred), int(no_call),
        int(no_call_lower), int(i16_max), _addr(cb), _addr(cq), _addr(cd),
        _addr(ce), _addr(both), _addr(disag))
    return cb, cq, cd, ce, both.view(np.bool_), disag.view(np.bool_)


def codec_place(sources, sid, rows, ks, base, offs, table, reverse: bool,
                cap: int, pad_base: int):
    """One side's CODEC strands from the rows of the result matrices that
    hold them to the oriented, padded per-molecule arrays, in one ragged
    copy (fgumi_codec_place): no index array on either side of it.

    ``sources``: a sequence of ``None`` (no strand may name it) or ``(bases
    u8, quals u8, depths, errors)``, four matrices of one shape (a 1-D
    array is one row), depths and errors int32 or int64, read as they are.
    Strand ``j`` is the first ``ks[j]`` elements of row ``rows[j]`` of
    source ``sid[j]``; it lands at ``base[j]`` inside its molecule's
    ``offs[j]:offs[j + 1]``, reversed where ``reverse``, its bases through
    the 256-byte ``table``, its depths and errors capped at ``cap``; the
    rest of the molecule is the pad (``pad_base``, Q0, depth 0, errors 0).
    Returns ``(bases u8, quals u8, depths i32, errors i32)`` of
    ``offs[-1]`` elements, each written once.
    """
    lib = get_lib()
    J = len(sid)
    sid = np.ascontiguousarray(sid, np.int32)
    rows, ks, base, offs = (np.ascontiguousarray(a, np.int64)
                            for a in (rows, ks, base, offs))
    table = np.ascontiguousarray(table, np.uint8)
    S = len(sources)
    addr = np.zeros((4, S), dtype=np.int64)
    shape = np.zeros((2, S), dtype=np.int64)  # rows, row stride
    width = np.full(S, 4, dtype=np.int32)
    keep = []
    for s, mats in enumerate(sources):
        if mats is None:
            continue
        b, q = (np.atleast_2d(_as_c(m, np.uint8)) for m in mats[:2])
        wide = np.result_type(mats[2], mats[3]).itemsize > 4
        d, e = (np.atleast_2d(_as_c(m, np.int64 if wide else np.int32))
                for m in mats[2:])
        if not b.shape == q.shape == d.shape == e.shape:
            raise ValueError(f"codec_place: source {s}'s matrices differ "
                             "in shape")
        keep.append((b, q, d, e))
        addr[:, s] = [m.ctypes.data for m in keep[-1]]
        shape[:, s] = b.shape
        width[s] = d.itemsize
    if len(table) != 256 or len(offs) != J + 1 or not (
            len(rows) == len(ks) == len(base) == J):
        raise ValueError("codec_place: column lengths differ")
    if J and not ((sid >= 0).all() and (sid < S).all()
                  and (rows >= 0).all() and (rows < shape[0][sid]).all()
                  and (ks >= 0).all() and (ks <= shape[1][sid]).all()
                  and (base >= offs[:-1]).all()
                  and (base + ks <= offs[1:]).all()):
        raise ValueError("codec_place: a strand lies outside its source "
                         "or its molecule")
    T = int(offs[-1])
    bt = np.empty(T, dtype=np.uint8)
    qt = np.empty(T, dtype=np.uint8)
    dt = np.empty(T, dtype=np.int32)
    et = np.empty(T, dtype=np.int32)
    lib.fgumi_codec_place(
        _addr(addr[0]), _addr(addr[1]), _addr(addr[2]), _addr(addr[3]),
        _addr(shape[1]), _addr(width), _addr(sid), _addr(rows), _addr(ks),
        _addr(base), _addr(offs), J, _addr(table), int(bool(reverse)),
        int(cap), int(pad_base), _addr(bt), _addr(qt), _addr(dt), _addr(et))
    del keep
    return bt, qt, dt, et


def duplex_rx_fast(buf, una_off, una_len, cnt, a_seg, b_seg):
    """Duplex consensus-RX fast path (fgumi_duplex_rx_fast).

    Resolves every output whose contributing segs are unanimous (or
    absent) entirely in C — single-read verbatim / all-equal uppercased,
    with the b-side strand flip done on bytes. Returns (rx_off i64,
    rx_len i32, blob u8, fb_idx i64): outputs listed in fb_idx (divergent
    segs or disagreeing values) are untouched and need the Python
    likelihood path.
    """
    lib = get_lib()
    K = len(a_seg)
    una_off = np.ascontiguousarray(una_off, np.int64)
    una_len = np.ascontiguousarray(una_len, np.int32)
    cnt = np.ascontiguousarray(cnt, np.int64)
    a_seg = np.ascontiguousarray(a_seg, np.int64)
    b_seg = np.ascontiguousarray(b_seg, np.int64)
    # exact bound: each output emits at most one contributing value
    pos_len = np.where(una_off >= 0, una_len.astype(np.int64), 0)
    cap = int(pos_len[a_seg[a_seg >= 0]].sum()
              + pos_len[b_seg[b_seg >= 0]].sum()) + 1
    blob = np.empty(cap, dtype=np.uint8)
    rx_off = np.empty(K, dtype=np.int64)
    rx_len = np.empty(K, dtype=np.int32)
    fb_idx = np.empty(max(K, 1), dtype=np.int64)
    used = np.zeros(1, dtype=np.int64)
    n_fb = lib.fgumi_duplex_rx_fast(
        _addr(buf), _addr(una_off), _addr(una_len), _addr(cnt),
        _addr(a_seg), _addr(b_seg), K, _addr(blob), cap, _addr(rx_off),
        _addr(rx_len), _addr(fb_idx), _addr(used))
    assert n_fb >= 0, "duplex_rx_fast blob overflow (sizing bug)"
    return rx_off, rx_len, blob[:int(used[0])], fb_idx[:n_fb]
