"""Per-kernel micro-benchmarks with per-round JSON (VERDICT r4 item 8).

The reference gates perf-sensitive choices with criterion benches
(/root/reference/benches/core_functions.rs:36-1426); this is the analog for
the hot host/device primitives, emitted as one JSON dict so the driver's
BENCH_r{N}.json files are comparable across rounds (an engine win that
regresses a primitive shows up here even when the macro number moves the
other way — exactly what round 3 lacked).

Covers: consensus kernel (two shapes), dispatch-prep/shape-bucket data-path
primitives, native record decode/tag-scan/pack, sort key extraction, BGZF
codec, and the UMI assigners at 4k/16k.

Run directly (`python microbench.py`). Timings are those of whatever
backend jax starts on — a CPU run measures XLA-CPU and says so in every
tune cell's ``backend``; only a run on the chip is a device number.
"""

import json
import os
import sys
import time

# tools/tune_smoke.py passes the repo root as argv[1]
if len(sys.argv) > 1 and os.path.isdir(sys.argv[1]):
    REPO = sys.argv[1]
else:
    REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def _timeit(fn, *, repeat=3, warmup=1):
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(repeat):
        t0 = time.monotonic()
        fn()
        best = min(best, time.monotonic() - t0)
    return best


def bench_kernel(out):
    import jax
    import numpy as np

    from fgumi_tpu.ops.kernel import ConsensusKernel, pad_segments
    from fgumi_tpu.ops.tables import quality_tables

    kernel = ConsensusKernel(quality_tables(45, 40))
    # this section measures the XLA device kernel; on a CPU-pinned run the
    # production path is the native f64 host engine (measured separately
    # below), so force the device engine or the timed dispatch is a no-op
    # HOST_DISPATCH sentinel
    kernel.set_force_device()
    rng = np.random.default_rng(7)
    for tag, (n_fam, fam, L) in (("kernel_small_8k_rows", (1638, 5, 64)),
                                 ("kernel_64k_rows", (13107, 5, 128))):
        codes, quals = _family_pileup(rng, n_fam, fam, L)
        counts = np.full(n_fam, fam, dtype=np.int64)
        cd, qd, seg, starts, F = pad_segments(codes, quals, counts)

        def run():
            jax.block_until_ready(
                kernel.device_call_segments(cd, qd, seg, F))

        dt = _timeit(run)
        out[f"{tag}_s"] = round(dt, 4)
        out[f"{tag}_reads_per_sec"] = round(n_fam * fam / dt, 1)


def _family_pileup(rng, n_fam, fam, L):
    """Family-consistent reads (shared template + 0.5% errors): consensus
    inputs are never independent random bases, and the host engine's
    saturation economics depend on that — random rows would push every
    position onto the oracle slow path and benchmark the wrong regime."""
    import numpy as np

    template = rng.integers(0, 4, size=(n_fam, 1, L), dtype=np.uint8)
    codes = np.repeat(template, fam, axis=1)
    err = rng.random(codes.shape) < 0.005
    codes[err] = (codes[err] + rng.integers(1, 4, size=int(err.sum()))) % 4
    codes = codes.reshape(n_fam * fam, L)
    quals = rng.integers(25, 41, size=codes.shape, dtype=np.uint8)
    return codes, quals


def bench_full_column(out):
    """Full-column wire kernel vs native host engine at 3 family-size
    profiles (ISSUE 6 satellite): the measured rows/s on each side are the
    crossover constants the offload cost model's EWMAs converge to, made
    reproducible from one command. wire = pad + 1 B/position dispatch +
    full resolve (device depth/errors, no host re-walk); host = the native
    f64 engine on the same pileups."""
    import numpy as np

    from fgumi_tpu.native import batch as nb
    from fgumi_tpu.ops.host_kernel import HostConsensusEngine
    from fgumi_tpu.ops.kernel import ConsensusKernel, pad_segments
    from fgumi_tpu.ops.tables import quality_tables

    tabs = quality_tables(45, 40)
    kernel = ConsensusKernel(tabs)
    kernel.set_force_device()
    host = HostConsensusEngine(tabs) if nb.available() else None
    rng = np.random.default_rng(11)
    L = 100
    for fam, n_fam in ((3, 4000), (10, 1600), (30, 600)):
        codes, quals = _family_pileup(rng, n_fam, fam, L)
        counts = np.full(n_fam, fam, dtype=np.int64)
        starts = (np.arange(n_fam + 1) * fam).astype(np.int64)

        def wire():
            cd, qd, seg, _st, F = pad_segments(codes, quals, counts)
            t = kernel.device_call_segments_wire(cd, qd, seg, F, n_fam,
                                                 full=True)
            kernel.resolve_segments_wire(t, codes, quals, starts)

        dt = _timeit(wire)
        rows = n_fam * fam
        out[f"full_column_fam{fam}_wire_s"] = round(dt, 4)
        out[f"full_column_fam{fam}_wire_rows_per_sec"] = round(rows / dt, 1)
        # machine-readable per-cell record: the `fgumi-tpu tune --replay`
        # input format (ISSUE 20) — same cells, structured instead of
        # flat-keyed, stamped with the backend they ran on
        import jax

        cell = {
            "name": f"fixed{fam}_L{L}", "distribution": "fixed",
            "mean_depth": fam, "read_length": L, "rows": rows,
            "backend": jax.default_backend(),
            "device_rows_per_sec": round(rows / dt, 1),
        }
        if host is not None:
            dth = _timeit(lambda: host.call_segments(codes, quals, starts))
            out[f"full_column_fam{fam}_host_rows_per_sec"] = round(
                rows / dth, 1)
            out[f"full_column_fam{fam}_device_vs_host"] = round(dth / dt, 3)
            cell["host_rows_per_sec"] = round(rows / dth, 1)
            cell["winner"] = "device" if dt <= dth else "host"
        # a --backend child (run first, main()) may already hold this cell
        cells = out.setdefault("tune_cells", [])
        if (cell["name"], cell["backend"]) not in {
                (c["name"], c.get("backend")) for c in cells}:
            cells.append(cell)


def bench_pallas(out):
    """Hand-tiled Pallas wire kernel vs the XLA lowering (ISSUE 19) at
    the same 3 family-size profiles as bench_full_column: full dispatch +
    resolve s and rows/s per backend, plus the ratio ROADMAP item 1's
    hardware round gates on (bar >= 2x kernel compute throughput). On a
    CPU host Pallas runs in Mosaic interpret mode — the recorded numbers
    carry a loud ``pallas_interpreted: true`` flag and must NEVER be read
    as silicon evidence (interpret mode is orders of magnitude slower;
    only the parity matters there)."""
    import numpy as np

    from fgumi_tpu.ops import pallas_kernel
    from fgumi_tpu.ops.kernel import ConsensusKernel, pad_segments
    from fgumi_tpu.ops.tables import quality_tables

    if not pallas_kernel.available():
        out["pallas_available"] = False
        return
    interp = pallas_kernel.interpreted()
    out["pallas_available"] = True
    out["pallas_interpreted"] = interp
    kernel = ConsensusKernel(quality_tables(45, 40))
    kernel.set_force_device()
    rng = np.random.default_rng(31)
    L = 100
    # interpret mode is ~1000x silicon: shrink the batch so CI stays fast
    # while real hardware measures the bench_full_column-scale batches
    scale = 20 if interp else 1
    prev = os.environ.get("FGUMI_TPU_KERNEL")
    try:
        for fam, n_fam in ((3, 4000 // scale), (10, 1600 // scale),
                           (30, 600 // scale)):
            codes, quals = _family_pileup(rng, n_fam, fam, L)
            counts = np.full(n_fam, fam, dtype=np.int64)
            starts = (np.arange(n_fam + 1) * fam).astype(np.int64)
            rows = n_fam * fam

            def wire():
                cd, qd, seg, _st, F = pad_segments(codes, quals, counts)
                t = kernel.device_call_segments_wire(cd, qd, seg, F,
                                                     n_fam, full=True)
                kernel.resolve_segments_wire(t, codes, quals, starts)

            for backend in ("pallas", "xla"):
                os.environ["FGUMI_TPU_KERNEL"] = backend
                dt = _timeit(wire)
                out[f"pallas_fam{fam}_{backend}_s"] = round(dt, 4)
                out[f"pallas_fam{fam}_{backend}_rows_per_sec"] = round(
                    rows / dt, 1)
            out[f"pallas_fam{fam}_speedup_vs_xla"] = round(
                out[f"pallas_fam{fam}_xla_s"]
                / out[f"pallas_fam{fam}_pallas_s"], 3)
    finally:
        if prev is None:
            os.environ.pop("FGUMI_TPU_KERNEL", None)
        else:
            os.environ["FGUMI_TPU_KERNEL"] = prev


def bench_device_filter(out):
    """Fused consensus→filter route vs full-fetch + host filter at 3
    family-size profiles (ISSUE 11): same consensus work on both sides;
    the fused side fetches a 28 B/read stats row + survivors-only masked
    columns, the host side fetches full columns and filters on host. Also
    records the measured fetched-bytes ratio per profile — the structural
    claim behind the route."""
    import numpy as np

    from fgumi_tpu.consensus.device_filter import SimplexFilterStage
    from fgumi_tpu.consensus.filter import FilterConfig
    from fgumi_tpu.ops.kernel import (DEVICE_STATS, ConsensusKernel,
                                      pad_segments)
    from fgumi_tpu.ops.tables import quality_tables

    tabs = quality_tables(45, 40)
    kernel = ConsensusKernel(tabs)
    kernel.set_force_device()
    cfg = FilterConfig.new([5], [0.025], [0.1], min_base_quality=20,
                           min_mean_base_quality=30.0)

    class _Opts:
        min_reads = 1
        min_consensus_base_quality = 40
        produce_per_base_tags = True

    stage = SimplexFilterStage(cfg, _Opts())
    rng = np.random.default_rng(23)
    L = 100
    for fam, n_fam in ((3, 4000), (10, 1600), (30, 600)):
        codes, quals = _family_pileup(rng, n_fam, fam, L)
        counts = np.full(n_fam, fam, dtype=np.int64)
        starts = (np.arange(n_fam + 1) * fam).astype(np.int64)
        lens = np.full(n_fam, L, dtype=np.int32)
        fp = (np.int32(1), np.int32(40), lens, stage.dev_params)

        def fused():
            cd, qd, seg, _st, F = pad_segments(codes, quals, counts)
            t = kernel.device_call_segments_wire(cd, qd, seg, F, n_fam,
                                                 full=True, filter_params=fp)
            got = kernel.resolve_segments_wire_filtered(t, codes, quals,
                                                        starts)
            if got[0] != "stats":
                return
            _, st, resident = got
            verd = stage.read_verdicts(st.astype(np.int64), lens)
            rows = np.nonzero((verd == 0) & (st[:, 6] == 0))[0]
            if len(rows):
                kernel.filter_gather_filtered(resident, rows)
            resident.release()

        def full_then_host():
            cd, qd, seg, _st, F = pad_segments(codes, quals, counts)
            t = kernel.device_call_segments_wire(cd, qd, seg, F, n_fam,
                                                 full=True)
            w, q, d, e = kernel.resolve_segments_wire(t, codes, quals,
                                                      starts)
            from fgumi_tpu.ops import oracle

            b, qq = oracle.apply_consensus_thresholds(w, q, d, 1, 40)
            stage.host_filter_columns(b, qq, d, e, lens)

        b0 = DEVICE_STATS.bytes_fetched
        dt_f = _timeit(fused)
        fused_bytes = DEVICE_STATS.bytes_fetched - b0
        b0 = DEVICE_STATS.bytes_fetched
        dt_h = _timeit(full_then_host)
        full_bytes = DEVICE_STATS.bytes_fetched - b0
        rows = n_fam * fam
        out[f"device_filter_fam{fam}_fused_rows_per_sec"] = round(
            rows / dt_f, 1)
        out[f"device_filter_fam{fam}_hostfilter_rows_per_sec"] = round(
            rows / dt_h, 1)
        out[f"device_filter_fam{fam}_fetch_reduction"] = round(
            full_bytes / max(fused_bytes, 1), 2)


def bench_datapath(out):
    """Dispatch-prep regression bench: operand preparation must be a no-op
    for the common already-contiguous case (the old unconditional
    np.asarray/np.ascontiguousarray habit was free only by accident), and
    the shape-bucket lookup must stay in the nanoseconds.

    dispatch_prep_contig_s: 1000 preps of an already-dense 32 MB operand —
    regression-fails visibly (1000x jump) if someone reintroduces a copy.
    dispatch_prep_copy_s: one genuinely strided operand, the legitimate
    copy cost for scale. shape_bucket_lookup_s: 100k ladder lookups."""
    import numpy as np

    from fgumi_tpu.ops.datapath import SHAPE_REGISTRY, as_device_operand

    big = np.zeros((262144, 128), dtype=np.uint8)  # 32 MB, C-contiguous

    def prep_contig():
        for _ in range(1000):
            a = as_device_operand(big)
            assert a is big  # the no-copy contract this bench guards

    out["dispatch_prep_contig_s"] = round(_timeit(prep_contig), 5)

    strided = big[:, ::2]  # forces one real copy

    def prep_copy():
        assert as_device_operand(strided) is not strided

    out["dispatch_prep_copy_s"] = round(_timeit(prep_copy), 5)

    def lookups():
        for n in range(1, 100001):
            SHAPE_REGISTRY.bucket_rows(n)

    out["shape_bucket_lookup_s"] = round(_timeit(lookups), 4)


def bench_chain(out):
    """Fused-chain handoff primitives (docs/component-map.md chain section).

    chain_handoff_*: producer/consumer threads pumping 4 MiB wire-sized
    blobs through a ChainChannel — the per-batch cost of the in-memory
    stage handoff that replaced intermediate-file encode/decode.
    chain_rechunk_nocopy: the re-chunk path's no-extra-copy contract — a
    writable single-blob batch must WRAP the producer's buffer (asserted
    via shares_memory; regression-fails loudly if a copy sneaks in), and
    the timing covers boundary scan + decode only."""
    import struct

    import numpy as np

    from fgumi_tpu.io.bam import BamHeader, RecordBuilder
    from fgumi_tpu.native import batch as nb
    from fgumi_tpu.pipeline_chain import ChainChannel, ChannelBatchReader

    if not nb.available():
        return
    header = BamHeader(text="@HD\tVN:1.6\tSO:unsorted\tGO:query\n",
                       ref_names=[], ref_lengths=[])
    # a realistic wire blob: ~4 MiB of small unmapped records
    rec = RecordBuilder().start_unmapped(
        b"q" * 30, 4, b"ACGT" * 25, np.full(100, 30, dtype=np.uint8)
    ).tag_str(b"RX", b"ACGTACGT").finish()
    one = struct.pack("<I", len(rec)) + rec
    per_blob = max((4 << 20) // len(one), 1)
    blob_template = np.frombuffer(bytearray(one * per_blob), dtype=np.uint8)
    n_blobs = 64

    def pump():
        import threading

        chan = ChainChannel("bench", max_bytes=32 << 20)
        chan.put_header(header)

        def producer():
            for _ in range(n_blobs):
                chan.put(blob_template.copy())
            chan.close()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        while chan.get() is not None:
            pass
        t.join()

    dt = _timeit(pump)
    total = n_blobs * len(blob_template)
    out["chain_handoff_s"] = round(dt, 4)
    out["chain_handoff_batches_per_sec"] = round(n_blobs / dt, 1)
    out["chain_handoff_mb_per_sec"] = round(total / dt / 1e6, 1)

    def rechunk():
        chan = ChainChannel("bench.rechunk", max_bytes=256 << 20)
        chan.put_header(header)
        blobs = [blob_template.copy() for _ in range(8)]
        for b in blobs:
            chan.put(b)
        chan.close()
        reader = ChannelBatchReader(chan, target_bytes=len(one))
        for blob, batch in zip(blobs, reader):
            # the no-extra-copy contract: a writable whole-blob batch wraps
            # the producer's buffer instead of copying it
            assert np.shares_memory(batch.buf, blob)

    out["chain_rechunk_nocopy_s"] = round(_timeit(rechunk), 4)


def bench_sort_merge(out):
    """Spill-worker overlap (ISSUE 8 satellite): full sort wall clock —
    ingest+spill+k-way merge, with the worker pool compressing spills
    behind ingest and prefetching+decompressing each run's next frame
    behind the merge heap, vs the fully synchronous path. The window is
    the whole run because the pool moves work between phases (with
    workers, spill compression that the sync path pays during ingest
    drains during the merge), so either phase alone mismeasures.
    spill_workers=3 is what the fused chain's sort stage gets at
    --threads 4 (cli: threads - 1), so sort_merge_prefetch_speedup is
    the --threads 4 fused-chain delta for the stage the chain serializes
    on. Byte-identity of the two paths is pinned by
    tests/test_governor.py; this entry records the wall win."""
    import random

    from fgumi_tpu.sort.external import create_sorter

    random.seed(11)
    entries = [(random.randbytes(16), random.randbytes(
        random.randrange(60, 400))) for _ in range(60000)]

    def run(workers):
        t0 = time.perf_counter()
        sorter = create_sorter(lambda r: b"", max_bytes=2 << 20,
                               spill_workers=workers)
        try:
            for k, d in entries:
                sorter.add_entry(k, d)
            n = sum(1 for _ in sorter.sorted_records())
            dt = time.perf_counter() - t0
        finally:
            sorter.close()
        assert n == len(entries)
        return dt

    run(0)  # warm page cache so sync vs prefetch see the same I/O
    sync_s = min(run(0) for _ in range(3))
    pf_s = min(run(3) for _ in range(3))
    out["sort_merge_sync_s"] = round(sync_s, 4)
    out["sort_merge_prefetch_s"] = round(pf_s, 4)
    out["sort_merge_prefetch_speedup"] = round(sync_s / pf_s, 3) if pf_s else 0


def bench_host_engine(out):
    import numpy as np

    from fgumi_tpu.native import batch as nb
    from fgumi_tpu.ops.host_kernel import HostConsensusEngine
    from fgumi_tpu.ops.tables import quality_tables

    if not nb.available():
        return
    eng = HostConsensusEngine(quality_tables(45, 40))
    rng = np.random.default_rng(7)
    for tag, (n_fam, fam, L) in (("host_engine_8k_rows", (1638, 5, 64)),
                                 ("host_engine_64k_rows", (13107, 5, 128))):
        codes, quals = _family_pileup(rng, n_fam, fam, L)
        starts = (np.arange(n_fam + 1) * fam).astype(np.int64)
        dt = _timeit(lambda: eng.call_segments(codes, quals, starts))
        out[f"{tag}_s"] = round(dt, 4)
        out[f"{tag}_reads_per_sec"] = round(n_fam * fam / dt, 1)


def bench_native_batch(out, bam_path):
    import numpy as np

    from fgumi_tpu.io.batch_reader import BamBatchReader
    from fgumi_tpu.native import batch as nb

    with BamBatchReader(bam_path, target_bytes=64 << 20) as r:
        batch = next(iter(r))
    out["batch_records"] = int(batch.n)

    out["scan_tags_s"] = round(_timeit(
        lambda: nb.scan_tags(batch.buf, batch.aux_off, batch.data_end,
                             [b"MI", b"MC", b"RX"])), 4)

    span = np.arange(batch.n, dtype=np.int64)
    reverse = np.zeros(batch.n, dtype=np.uint8)
    clips = np.zeros((batch.n, 2), dtype=np.int32)
    stride = max(-(-int(batch.l_seq.max()) // 32) * 32, 32)

    def pack():
        nb.pack_reads(batch.buf, np.ascontiguousarray(batch.seq_off),
                      np.ascontiguousarray(batch.qual_off), batch.l_seq,
                      reverse, clips, 10, stride)

    out["pack_reads_s"] = round(_timeit(pack), 4)
    out["pack_reads_mrec_per_sec"] = round(
        batch.n / out["pack_reads_s"] / 1e6, 3)


def bench_sort_keys(out, bam_path):
    from fgumi_tpu.io.batch_reader import BamBatchReader
    from fgumi_tpu.sort.keys import make_batch_keys_fn

    with BamBatchReader(bam_path, target_bytes=64 << 20) as r:
        keys_fn = make_batch_keys_fn("template-coordinate", r.header)
        batch = next(iter(r))
        dt = _timeit(lambda: keys_fn(batch))
    out["sort_keys_s"] = round(dt, 4)
    out["sort_keys_mrec_per_sec"] = round(batch.n / dt / 1e6, 3)


def bench_bgzf(out):
    import numpy as np

    from fgumi_tpu import native

    if native.get_lib() is None:
        out["bgzf"] = "native unavailable"
        return
    rng = np.random.default_rng(3)
    # compressible-ish payload (4-letter alphabet like SEQ bytes)
    data = rng.choice(np.frombuffer(b"ACGT", np.uint8),
                      size=16 << 20).tobytes()
    blob = None

    def compress():
        nonlocal blob
        blob, _ = native.bgzf_compress_many(data, level=1)

    dt_c = _timeit(compress)
    out["bgzf_compress_mb_per_sec"] = round(len(data) / dt_c / 1e6, 1)

    import io as _io

    from fgumi_tpu.io.bgzf import BgzfReader

    def decompress():
        r = BgzfReader(_io.BytesIO(blob))
        while r.read(4 << 20):
            pass

    dt_d = _timeit(decompress)
    out["bgzf_decompress_mb_per_sec"] = round(len(data) / dt_d / 1e6, 1)


def bench_assigners(out):
    import numpy as np

    from fgumi_tpu.umi.assigners import (AdjacencyUmiAssigner,
                                         PairedUmiAssigner)

    rng = np.random.default_rng(0)

    def gen(n, paired=False):
        bases = np.frombuffer(b"ACGT", np.uint8)
        true = rng.choice(bases, size=(max(n // 10, 1), 8))
        arr = true[rng.integers(0, len(true), size=n)]
        err = rng.random(arr.shape) < 0.01
        arr = np.where(err, rng.choice(bases, size=arr.shape), arr)
        umis = ["".join(chr(c) for c in row) for row in arr]
        if paired:
            arr2 = rng.choice(bases, size=arr.shape)
            umis = [f"{u}-{''.join(chr(c) for c in r)}"
                    for u, r in zip(umis, arr2)]
        return umis

    for tag, cls, paired in (("adjacency", AdjacencyUmiAssigner, False),
                             ("paired", PairedUmiAssigner, True)):
        for n in (4000, 16000):
            umis = gen(n, paired)
            cls(1).assign(umis)  # warm (jit compile)
            out[f"{tag}_{n}_s"] = round(_timeit(
                lambda: cls(1).assign(umis), repeat=2, warmup=0), 4)


_SHARDED_SCRIPT = r"""
import json, sys, time
import numpy as np
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
from fgumi_tpu.ops.tables import quality_tables
from fgumi_tpu.ops.kernel import (ConsensusKernel, pad_segments,
                                  pad_segments_mesh)
from fgumi_tpu.parallel.mesh import resolve_mesh

kernel = ConsensusKernel(quality_tables(45, 40))
kernel.set_force_device()
rng = np.random.default_rng(23)
n_fam, L = 4096, 96
counts = rng.integers(2, 10, size=n_fam).astype(np.int64)
truth = rng.integers(0, 4, size=(n_fam, L)).astype(np.uint8)
codes = np.repeat(truth, counts, axis=0)
err = rng.random(codes.shape) < 0.03
codes[err] = rng.integers(0, 4, size=int(err.sum()))
quals = rng.integers(10, 42, size=codes.shape).astype(np.uint8)
starts = np.concatenate(([0], np.cumsum(counts)))
rows = int(starts[-1])

def once(mesh):
    t0 = time.monotonic()
    if mesh is None:
        cd, qd, seg, _st, F_pad = pad_segments(codes, quals, counts)
        t = kernel.device_call_segments_wire(cd, qd, seg, F_pad, n_fam,
                                             full=True)
    else:
        cg, qg, sg, _st, F_loc, gather = pad_segments_mesh(
            codes, quals, counts, mesh)
        t = kernel.device_call_segments_wire(
            cg, qg, sg, F_loc, n_fam, full=True, mesh=mesh,
            mesh_gather=gather)
    kernel.resolve_segments_wire(t, codes, quals, starts)
    return time.monotonic() - t0

out = {"rows": rows, "families": n_fam, "read_len": L,
       "devices_visible": len(jax.devices()), "curve": {}}
for dp in (1, 2, 4, 8):
    if dp > len(jax.devices()):
        continue
    mesh = resolve_mesh(jax.devices(), (dp, 1)) if dp > 1 else None
    once(mesh)  # warm: compile
    best = min(once(mesh) for _ in range(3))
    out["curve"][str(dp)] = {"dispatch_s": round(best, 4),
                             "rows_per_sec": round(rows / best, 1)}
base = out["curve"].get("1", {}).get("rows_per_sec")
if base:
    for dp, rec in out["curve"].items():
        rec["speedup_vs_dp1"] = round(rec["rows_per_sec"] / base, 3)
print(json.dumps(out))
"""


def bench_sharded(out):
    """Mesh scaling curve: wire dispatch+resolve rows/s at dp=1/2/4/8 on 8
    virtual CPU devices (subprocess: the forced device count must be set
    before jax initializes). One physical core hosts all virtual devices
    here, so the curve demonstrates functional sharding + dispatch-overhead
    behavior; wall-clock speedup needs real chips (MULTICHIP artifacts
    carry the honest context either way)."""
    import json as _json
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["FGUMI_TPU_HOST_ENGINE"] = "0"
    env["FGUMI_TPU_HYBRID"] = "0"
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT % {"repo": REPO}],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    if proc.returncode != 0:
        raise RuntimeError("sharded bench rc=%d: %s"
                           % (proc.returncode, proc.stderr.strip()[-200:]))
    out["sharded_scaling"] = _json.loads(proc.stdout.strip().splitlines()[-1])


def bench_coalesce(out):
    """Cross-job dispatch coalescing (ISSUE 15): merged vs serial
    aggregate throughput at 1/2/4/8 concurrent same-shape streams, plus a
    window-wait-vs-fill tradeoff row at 4 streams. Small per-stream
    batches on purpose — the dispatch-overhead-dominated regime where the
    serve fleet's concurrent small jobs live. Emulates the daemon's
    arming (serving + live active-job count) rather than force mode, so
    the 1-stream row demonstrates the auto-off no-regression contract."""
    import threading

    import numpy as np

    from fgumi_tpu.observe.metrics import METRICS
    from fgumi_tpu.ops.coalesce import COALESCER
    from fgumi_tpu.ops.kernel import ConsensusKernel, pad_segments
    from fgumi_tpu.ops.tables import quality_tables

    kernel = ConsensusKernel(quality_tables(45, 40))
    kernel.set_force_device()
    rng = np.random.default_rng(23)
    n_fam, fam, L = 32, 4, 64
    codes, quals = _family_pileup(rng, n_fam, fam, L)
    counts = np.full(n_fam, fam, dtype=np.int64)
    batches_per_stream = 12
    reads_per_stream = batches_per_stream * n_fam * fam

    def stream():
        for _ in range(batches_per_stream):
            cd, qd, seg, starts, f_pad = pad_segments(codes, quals, counts)
            t = kernel.device_call_segments_wire(cd, qd, seg, f_pad,
                                                 n_fam, full=True)
            kernel.resolve_segments_wire(t, codes, quals, starts)

    def run_streams(k):
        threads = [threading.Thread(target=stream) for _ in range(k)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.monotonic() - t0

    saved = {k: os.environ.get(k) for k in
             ("FGUMI_TPU_COALESCE", "FGUMI_TPU_COALESCE_WINDOW_MS",
              "FGUMI_TPU_AUDIT")}
    os.environ["FGUMI_TPU_COALESCE"] = ""        # daemon-like auto mode
    os.environ["FGUMI_TPU_COALESCE_WINDOW_MS"] = "4"
    # the shadow audit's background oracle replays steal exactly the CPU
    # this section measures; benchmark the data path, not the audit
    os.environ["FGUMI_TPU_AUDIT"] = "off"
    try:
        stream()  # warm: solo-shape compiles
        section = {}
        for s in (1, 2, 4, 8):
            COALESCER.set_serving(False)
            COALESCER.set_active_jobs(0)
            run_streams(s)
            dt_off = min(run_streams(s) for _ in range(3))
            COALESCER.set_serving(True)
            COALESCER.set_active_jobs(s)
            run_streams(s)  # warm: merged-shape compiles
            dt_on = min(run_streams(s) for _ in range(3))
            reads = s * reads_per_stream
            section[f"streams{s}"] = {
                "serial_reads_per_sec": round(reads / dt_off, 1),
                "merged_reads_per_sec": round(reads / dt_on, 1),
                "speedup": round(dt_off / dt_on, 3),
            }
        # window-wait vs fill tradeoff at 4 streams: a longer window packs
        # fuller merges but each partner waits longer for stragglers.
        # The live job count stays 4 so the early-flush path is the one
        # measured (the serve-realistic configuration).
        COALESCER.set_active_jobs(4)
        tradeoff = []
        for window_ms in (1, 4, 10):
            os.environ["FGUMI_TPU_COALESCE_WINDOW_MS"] = str(window_ms)
            COALESCER.reset()
            h0 = METRICS.histogram("device.coalesce.window_wait_s")
            c0 = h0.count if h0 else 0
            s0 = h0.total if h0 else 0.0
            dt = run_streams(4)
            snap = COALESCER.snapshot()
            h1 = METRICS.histogram("device.coalesce.window_wait_s")
            waits = max((h1.count if h1 else 0) - c0, 1)
            tradeoff.append({
                "window_ms": window_ms,
                "reads_per_sec": round(4 * reads_per_stream / dt, 1),
                "fill_ratio": round(snap["rows_in"]
                                    / max(snap["rows_dispatched"], 1), 4),
                "partners_per_merge": round(
                    snap["partners"] / max(snap["merged_batches"], 1), 2),
                "mean_window_wait_ms": round(
                    ((h1.total if h1 else 0.0) - s0) / waits * 1e3, 3),
            })
        section["window_tradeoff"] = tradeoff
        out["coalesce"] = section
    finally:
        COALESCER.set_serving(False)
        COALESCER.set_active_jobs(0)
        COALESCER.reset()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _parse_args(argv):
    """Repo root as an optional bare positional, plus the ISSUE 20 matrix
    surface."""
    import argparse

    p = argparse.ArgumentParser(
        prog="microbench.py",
        description="per-kernel micro-benchmarks, one JSON dict on stdout")
    p.add_argument("repo", nargs="?", default=None,
                   help="repo root (default: this file's directory)")
    p.add_argument("--backend", action="append", default=None,
                   metavar="NAME", dest="backends",
                   help="also run the tune-cell section under this JAX "
                        "platform (cpu, cuda, tpu, ...) in a subprocess; "
                        "repeat per backend. Cells land in tune_cells "
                        "stamped with their backend; an unavailable "
                        "backend records an error instead of failing the "
                        "run (ROADMAP item 4's CI-runnable matrix)")
    p.add_argument("--tune-cells-only", action="store_true",
                   help="run only the full-column tune-cell section "
                        "(the per-backend subprocess mode)")
    return p.parse_args(argv)


def _bench_backend_matrix(out, backends):
    """Per-backend tune cells via the bench_sharded subprocess recipe
    (the platform pin must be set before jax initializes). Runs BEFORE
    any in-process section: a chip belongs to one process at a time, so
    a child that needs it must finish while this process is still off
    jax."""
    import subprocess

    script = os.path.join(REPO, "microbench.py")
    for backend in backends:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = backend
        try:
            proc = subprocess.run(
                [sys.executable, script, REPO, "--tune-cells-only"],
                capture_output=True, text=True, timeout=600, env=env,
                cwd=REPO)
            if proc.returncode != 0:
                raise RuntimeError("rc=%d: %s" % (
                    proc.returncode, proc.stderr.strip()[-200:]))
            sub = json.loads(proc.stdout.strip().splitlines()[-1])
        except Exception as e:  # an absent backend must not fail the run
            out[f"error_backend_{backend}"] = repr(e)[:200]
            continue
        have = {(c["name"], c.get("backend"))
                for c in out.get("tune_cells", [])}
        for cell in sub.get("tune_cells", []):
            if (cell["name"], cell.get("backend")) not in have:
                out.setdefault("tune_cells", []).append(cell)
        out.setdefault("backends", []).append(backend)


def main():
    import tempfile

    args = _parse_args(sys.argv[1:])
    if args.tune_cells_only:
        out = {}
        try:
            bench_full_column(out)
        except Exception as e:
            out["error_bench_full_column"] = repr(e)[:200]
        print(json.dumps(out))
        return 0

    from fgumi_tpu.simulate import simulate_grouped_bam

    out = {}
    if args.backends:
        _bench_backend_matrix(out, args.backends)
    with tempfile.TemporaryDirectory(prefix="fgumi_micro_") as tmp:
        bam = os.path.join(tmp, "micro.bam")
        simulate_grouped_bam(bam, num_families=20000, family_size=5,
                             read_length=100, seed=17)
        for section in (bench_kernel,
                        bench_full_column,
                        bench_pallas,
                        bench_device_filter,
                        bench_coalesce,
                        bench_sharded,
                        bench_datapath,
                        bench_chain,
                        bench_sort_merge,
                        bench_host_engine,
                        lambda o: bench_native_batch(o, bam),
                        lambda o: bench_sort_keys(o, bam),
                        bench_bgzf,
                        bench_assigners):
            try:
                section(out)
            except Exception as e:  # a broken section must not hide others
                out[f"error_{getattr(section, '__name__', 'section')}"] = \
                    repr(e)[:200]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
