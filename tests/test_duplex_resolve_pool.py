"""Duplex stage 2 on the resolve workers (ISSUE 28 / ROADMAP S11).

Every span of a duplex batch is a pending chunk at every thread count: the
processing thread packs, dispatches and calls the span's fallback molecules,
and whichever thread run_stages resolves on completes stage 1 and runs stage
2. These tests hold that to one record stream and one set of statistics
whatever the thread count, the route, and the order in which chunks finish.
"""

import functools
import gzip
import itertools
import json
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from fgumi_tpu.cli import main as cli_main
from fgumi_tpu.consensus import duplex as duplex_mod
from fgumi_tpu.consensus import fast as fast_mod
from fgumi_tpu.consensus.fast_duplex import FastDuplexCaller
from fgumi_tpu.consensus.overlapping import OverlappingBasesConsensusCaller
from fgumi_tpu.io import batch_reader as batch_reader_mod
from fgumi_tpu.io.bam import BamHeader, RecordBuilder
from fgumi_tpu.io.batch_reader import BamBatchReader
from fgumi_tpu.io.bgzf import BGZF_EOF, BgzfReader, compress_block
from fgumi_tpu.native import batch as nb
from fgumi_tpu.observe.metrics import METRICS
from fgumi_tpu.utils import faults

pytestmark = pytest.mark.skipif(not nb.available(),
                                reason="native library unavailable")

#: the gates and the downsampling every run of this file uses
CALLER_ARGS = ["--min-reads", "2", "1", "0", "--max-reads-per-strand", "3",
               "--seed", "7"]
#: with the input's 6 KB BGZF blocks and `small_reads`, some twenty batches
#: of 60-90 records: every boundary cuts a molecule
BATCH_BYTES = "8192"
ROUTES = {
    "host": {"FGUMI_TPU_HOST_ENGINE": "1"},
    "device": {"FGUMI_TPU_HOST_ENGINE": "0", "FGUMI_TPU_ROUTE": "device"},
}
L = 60


def write_input(path, cycles=12, seed=5):
    """A paired-strategy grouped BAM whose molecules cycle through what the
    engine treats differently: both strands; /A only; /B only; a FIRST|LAST
    read (per-molecule caller); a strand of five pairs (downsampled with
    the seed, per-molecule caller); a mixed-CIGAR set (alignment filter,
    per-molecule caller); one pair (the min-reads gate rejects it); reads
    below the input quality floor on both strands (live, then dropped by
    stage 2); a strand collision; fragments only."""
    rng = np.random.default_rng(seed)
    header = BamHeader(
        text="@HD\tVN:1.6\tSO:unsorted\tGO:query\n@SQ\tSN:chr1\tLN:1000000\n",
        ref_names=["chr1"], ref_lengths=[1000000])
    records = []
    bases = np.frombuffer(b"ACGT", np.uint8)

    def read(name, mi, pos, flags, template, cigar=(("M", L),), qlo=25,
             qhi=41, rx=b"AAT-CCG"):
        seq = template.copy()
        flip = rng.random(L) < 0.03
        seq[flip] = rng.choice(bases, size=int(flip.sum()))
        b = RecordBuilder().start_mapped(
            name, flags, 0, pos, 60, list(cigar), seq.tobytes(),
            rng.integers(qlo, qhi, size=L).astype(np.uint8))
        b.tag_str(b"MI", mi)
        b.tag_str(b"RX", rx)
        records.append(b.finish())

    def pairs(m, strand, n, pos, templates, **kw):
        rev_r1 = strand == "B"
        f1 = 0x1 | 0x40 | (0x10 if rev_r1 else 0x20)
        f2 = 0x1 | 0x80 | (0x20 if rev_r1 else 0x10)
        mi = b"%d/%s" % (m, strand.encode())
        rx = b"CCG-AAT" if rev_r1 else b"AAT-CCG"
        for t in range(n):
            name = b"m%d%s%d" % (m, strand.lower().encode(), t)
            read(name, mi, pos, f1, templates[0], rx=rx, **kw)
            read(name, mi, pos, f2, templates[1], rx=rx, **kw)

    for m in range(cycles * 10):
        kind = m % 10
        pos = 1000 + 200 * m
        # mates cover the same 60 bases, so they hold the same sequence
        tpl = (rng.choice(bases, size=L),) * 2
        if kind == 0:
            pairs(m, "A", 3, pos, tpl)
            pairs(m, "B", 2, pos, tpl)
        elif kind == 1:
            pairs(m, "A", 2, pos, tpl)
        elif kind == 2:
            pairs(m, "B", 3, pos, tpl)
        elif kind == 3:
            read(b"m%dx" % m, b"%d/A" % m, pos, 0x1 | 0x40 | 0x80, tpl[0])
            pairs(m, "A", 2, pos, tpl)
            pairs(m, "B", 2, pos, tpl)
        elif kind == 4:
            pairs(m, "A", 5, pos, tpl)
            pairs(m, "B", 2, pos, tpl)
        elif kind == 5:
            pairs(m, "A", 2, pos, tpl)
            pairs(m, "A", 1, pos, tpl,
                  cigar=(("M", 30), ("I", 2), ("M", L - 32)))
            pairs(m, "B", 2, pos, tpl)
        elif kind == 6:
            pairs(m, "A", 1, pos, tpl)
        elif kind == 7:
            pairs(m, "A", 2, pos, tpl, qlo=2, qhi=9)
            pairs(m, "B", 2, pos, tpl, qlo=2, qhi=9)
        elif kind == 8:
            pairs(m, "A", 1, pos, tpl)
            pairs(m, "B", 1, pos, tpl)
            pairs(m, "B", 1, pos, tpl)
            # an /A pair in /B's orientation: X and Y are strand-mixed
            read(b"m%dz" % m, b"%d/A" % m, pos, 0x1 | 0x40 | 0x10, tpl[0])
            read(b"m%dz" % m, b"%d/A" % m, pos, 0x1 | 0x80 | 0x20, tpl[1])
        else:
            read(b"m%df" % m, b"%d/A" % m, pos, 0, tpl[0])
            read(b"m%dg" % m, b"%d/B" % m, pos, 0x10, tpl[0])
    stream = header.encode() + b"".join(
        len(r).to_bytes(4, "little") + r for r in records)
    with open(path, "wb") as f:
        for o in range(0, len(stream), 6000):
            f.write(compress_block(stream[o:o + 6000]))
        f.write(BGZF_EOF)


@pytest.fixture(scope="module")
def mixed_bam(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("drp") / "mixed.bam")
    write_input(path)
    return path


@pytest.fixture(autouse=True)
def small_reads(monkeypatch):
    """The batch reader decodes whatever one raw read of 1 MiB holds, so an
    input this small would be one batch whatever --batch-bytes says: read
    4 KiB at a time, one or two of the input's blocks."""
    monkeypatch.setattr(batch_reader_mod, "BgzfReader",
                        functools.partial(BgzfReader, chunk_size=4096))


def records_of(path):
    """The record stream, header stripped (@PG CL holds --threads)."""
    with gzip.open(path, "rb") as f:
        data = f.read()
    assert data[:4] == b"BAM\x01"
    o = 8 + int.from_bytes(data[4:8], "little")
    n_ref = int.from_bytes(data[o:o + 4], "little")
    o += 4
    for _ in range(n_ref):
        o += 4 + int.from_bytes(data[o:o + 4], "little") + 4
    return data[o:]


def run_duplex(monkeypatch, tmp_path, bam, name, route, threads, extra=(),
               expect_rc=0):
    """One in-process `duplex` run -> (record stream, the caller's merged
    statistics as a tuple, the run report)."""
    for key in ("FGUMI_TPU_HOST_ENGINE", "FGUMI_TPU_ROUTE",
                "FGUMI_TPU_INLINE_FLIGHT", "FGUMI_TPU_HYBRID",
                "FGUMI_TPU_MAX_INFLIGHT"):
        monkeypatch.delenv(key, raising=False)
    for key, value in ROUTES[route].items():
        monkeypatch.setenv(key, value)
    made = []

    class Spy(duplex_mod.DuplexConsensusCaller):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            made.append(self)

    monkeypatch.setattr(duplex_mod, "DuplexConsensusCaller", Spy)
    out = str(tmp_path / (name + ".bam"))
    report = out + ".json"
    rc = cli_main(["--run-report", report, "duplex", "-i", bam, "-o", out,
                   "--threads", str(threads), "--batch-bytes", BATCH_BYTES,
                   "--devices", "1", *CALLER_ARGS, *extra])
    assert rc == expect_rc
    with open(report) as f:
        rep = json.load(f)
    if rc:
        return None, None, rep
    s = made[-1].merged_stats()
    return records_of(out), (s.input_reads, s.consensus_reads,
                             dict(sorted(s.rejected.items()))), rep


@pytest.fixture(scope="module")
def classic(mixed_bam, tmp_path_factory):
    """The per-molecule caller on the same input: what every run below has
    to write and to count."""
    mp = pytest.MonkeyPatch()
    try:
        recs, stats, _rep = run_duplex(
            mp, tmp_path_factory.mktemp("drp_ref"), mixed_bam, "classic",
            "host", 0, extra=("--classic",))
    finally:
        mp.undo()
    return recs, stats


def test_the_input_has_every_kind(classic):
    _recs, (n_in, n_out, rejected) = classic
    assert n_in > 800 and n_out > 100
    # the gate, stage 2's fallthrough, the collision, the fragments and the
    # alignment filter all reject something
    assert set(rejected) >= {"InsufficientReads", "PotentialCollision",
                             "FragmentRead", "MinorityAlignment"}


@pytest.mark.parametrize("threads", [0, 1, 2, 4, 8])
@pytest.mark.parametrize("route", list(ROUTES))
def test_bytes_and_stats_at_every_thread_count(monkeypatch, tmp_path,
                                               mixed_bam, classic, route,
                                               threads):
    recs, stats, rep = run_duplex(monkeypatch, tmp_path, mixed_bam, "fast",
                                  route, threads)
    assert stats == classic[1]
    assert recs == classic[0]
    m = rep["metrics"]
    # small batches: every boundary cuts a molecule, every span is a chunk
    assert m["duplex.stage2_batches"] >= 8
    assert m["duplex.slow_molecules"] > m["duplex.stage2_batches"]
    if route == "device":
        assert m["device.kernel_xla"] > 0
        assert m["device.resident_bytes"] == 0


@pytest.mark.parametrize("threads,share", [(1, 0.0), (4, 1.0)])
def test_stage2_runs_off_the_processing_thread(monkeypatch, tmp_path,
                                               mixed_bam, threads, share):
    _recs, _stats, rep = run_duplex(monkeypatch, tmp_path, mixed_bam, "where",
                                    "device", threads)
    m = rep["metrics"]
    assert m.get("duplex.stage2_off_thread", 0) / m["duplex.stage2_batches"] \
        == share
    if threads == 4:
        busy = [rep["stages"][f"resolve[{i}]"]["busy_s"] for i in (0, 1)]
        assert min(busy) > 0


def hold_chunks(monkeypatch, resolve):
    """Hand run_stages' resolve_fn, in each chunk's place, an object whose
    ``resolve()`` is ``resolve(i, chunk)``, ``i`` the chunk's place in the
    run (made on the processing thread, in order)."""
    places = itertools.count()
    real_process = FastDuplexCaller.process_batch

    class Held:
        def __init__(self, chunk):
            self.chunk = chunk
            self.i = next(places)

        def resolve(self):
            return resolve(self.i, self.chunk)

    def process_batch(self, batch, *a, **kw):
        return [item if isinstance(item, bytes) else Held(item)
                for item in real_process(self, batch, *a, **kw)]

    monkeypatch.setattr(FastDuplexCaller, "process_batch", process_batch)


class Events:
    """One threading.Event a chunk, made on first use by either side."""

    def __init__(self):
        self._lock = threading.Lock()
        self._events = {}

    def __getitem__(self, i):
        with self._lock:
            return self._events.setdefault(i, threading.Event())


@pytest.mark.parametrize("route,threads", [("device", 4), ("host", 8)])
def test_even_chunks_finish_after_odd_ones(monkeypatch, tmp_path, mixed_bam,
                                           classic, route, threads):
    """Chunk 2k does not resolve until chunk 2k+1 has: the workers hand
    their bytes over out of order, and the writer's serial-number reorder
    is what keeps the record stream in molecule order."""
    done = Events()  # a chunk's place in the run -> set once its bytes exist
    finished = []
    lock = threading.Lock()

    def resolve(i, chunk):
        if i % 2 == 0:
            done[i + 1].wait(timeout=2.0)  # the last one times out
        out = chunk.resolve()
        with lock:
            finished.append(i)
        done[i].set()
        return out

    hold_chunks(monkeypatch, resolve)
    recs, stats, _rep = run_duplex(monkeypatch, tmp_path, mixed_bam, "swap",
                                   route, threads)
    n = len(finished)
    assert n >= 8 and sorted(finished) == list(range(n))
    late = sum(1 for i in range(0, n - 1, 2)
               if finished.index(i) > finished.index(i + 1))
    assert late == n // 2
    assert recs == classic[0]
    assert stats == classic[1]


def test_molecule_tallies_add_up_with_two_chunks_at_once(monkeypatch,
                                                        tmp_path, mixed_bam,
                                                        classic):
    """At --threads 4 chunks 2k and 2k+1 start their stage 2 together, one
    on each resolve worker: every molecule is still counted once, as what
    the columns made of it, a reject, or a call of the per-molecule
    caller."""
    started = Events()
    lock = threading.Lock()
    active = [0, 0]  # chunks inside resolve() now, and the most there were

    def resolve(i, chunk):
        started[i].set()
        started[i ^ 1].wait(timeout=2.0)  # the last one times out
        with lock:
            active[0] += 1
            active[1] = max(active)
        try:
            return chunk.resolve()
        finally:
            with lock:
                active[0] -= 1

    hold_chunks(monkeypatch, resolve)
    recs, stats, rep = run_duplex(monkeypatch, tmp_path, mixed_bam, "pairs",
                                  "device", 4)
    assert active[1] == 2
    assert recs == classic[0] and stats == classic[1]
    m = rep["metrics"]
    parts = [m[f"duplex.{name}"] for name in
             ("full", "ab_only", "ba_only", "rejected", "slow_molecules")]
    assert min(parts) > 0
    assert sum(parts) == m["duplex.molecules"] == 120
    assert m["duplex.rejected"] == sum(
        v for k, v in m.items() if k.startswith("duplex.rejected."))
    assert m["duplex.stage2_off_thread"] == m["duplex.stage2_batches"] >= 8


def _caller():
    return duplex_mod.DuplexConsensusCaller(
        "fgumi", "A", min_reads=(2, 1, 0), max_reads_per_strand=3, seed=7)


def _chunks(monkeypatch, bam, route):
    """Every output item of the engine over the input, none resolved."""
    for key, value in ROUTES[route].items():
        monkeypatch.setenv(key, value)
    caller = _caller()
    fast = FastDuplexCaller(
        caller, b"MI", overlap_caller=OverlappingBasesConsensusCaller(
            "consensus", "consensus"))  # the command's default
    items = []
    with BamBatchReader(bam, target_bytes=int(BATCH_BYTES)) as reader:
        for batch in reader:
            items.extend(fast.process_batch(batch))
    items.extend(fast.flush())
    return caller, items


def _duplex_counters():
    """The engine's counters; the strand combine's rows as one sum, since
    the chooser places them by what it measures."""
    c = {k: v for k, v in METRICS.snapshot().items()
         if k.startswith("duplex.")}
    c["duplex.combine_rows"] = c.pop("duplex.combine_rows_host", 0) \
        + c.pop("duplex.combine_rows_device", 0)
    return c


def _delta(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("route", list(ROUTES))
def test_concurrent_stage2_tallies(monkeypatch, mixed_bam, classic, route):
    """Eight and more chunks resolved at once on four threads, under a
    switch interval short enough to interleave them, against the same
    chunks resolved in turn: bytes, statistics and counters."""
    c0 = _duplex_counters()
    caller_a, items_a = _chunks(monkeypatch, mixed_bam, route)
    turn = [fast_mod.resolve_chunk(item) for item in items_a]
    c1 = _duplex_counters()
    caller_b, items_b = _chunks(monkeypatch, mixed_bam, route)
    assert sum(not isinstance(i, bytes) for i in items_b) >= 8
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(max_workers=4) as pool:
            futures = [pool.submit(fast_mod.resolve_chunk, item)
                       for item in items_b]
            pooled = [f.result(timeout=120) for f in futures]
    finally:
        sys.setswitchinterval(old)
    c2 = _duplex_counters()
    assert pooled == turn
    sa, sb = caller_a.merged_stats(), caller_b.merged_stats()
    assert (sb.input_reads, sb.consensus_reads, sb.rejected) \
        == (sa.input_reads, sa.consensus_reads, sa.rejected)
    assert (sb.input_reads, sb.consensus_reads,
            dict(sorted(sb.rejected.items()))) == classic[1]
    in_turn, at_once = _delta(c1, c0), _delta(c2, c1)
    assert at_once.pop("duplex.stage2_off_thread") \
        == at_once["duplex.stage2_batches"]
    assert "duplex.stage2_off_thread" not in in_turn
    assert at_once == in_turn


def test_a_dropped_chunk_gives_back_its_dispatch(monkeypatch, mixed_bam):
    """Chunks nobody resolves (a run that failed with them in flight) hand
    their dispatches back without waiting for them: no resident bytes, no
    dispatch in flight, no feeder slot held for the next run."""
    from fgumi_tpu.ops import kernel as K

    for key, value in ROUTES["device"].items():
        monkeypatch.setenv(key, value)
    fast = FastDuplexCaller(_caller(), b"MI")
    with BamBatchReader(mixed_bam, target_bytes=int(BATCH_BYTES)) as reader:
        batches = iter(reader)
        items = fast.process_batch(next(batches)) \
            + fast.process_batch(next(batches))
    chunks = [i for i in items if not isinstance(i, bytes)]
    assert len(chunks) == 2 and K.DEVICE_STATS.in_flight_count() == 2
    fast_mod.resolve_chunk(chunks.pop(0))  # one ends as it should
    assert K.DEVICE_STATS.in_flight_count() == 1
    del items, chunks
    assert K.DEVICE_STATS.in_flight_count() == 0
    K.DEVICE_FEEDER.drain(timeout=30)
    assert K.DEVICE_STATS.resident_bytes == 0
    assert K.DEVICE_FEEDER._inflight == 0


def _fault_seed(first_fire, prob):
    """A FGUMI_TPU_FAULT_SEED whose pipeline.process coin first lands on the
    item with index `first_fire`."""
    for seed in range(1, 10000):
        rng = faults._Fault("pipeline.process", "raise", prob, -1, seed).rng
        fires = [rng.random() < prob for _ in range(first_fire + 1)]
        if fires == [False] * first_fire + [True]:
            return seed
    raise AssertionError("no seed found")


@pytest.mark.parametrize("threads", [1, 4])
def test_a_process_fault_leaves_nothing_resident(monkeypatch, tmp_path,
                                                 mixed_bam, threads):
    """The process stage fails on its sixth batch with five chunks made:
    the run ends with exit code 3, and every dispatch it had started has
    been completed and its resident arrays released."""
    from fgumi_tpu.ops import kernel as K

    monkeypatch.setenv("FGUMI_TPU_FAULT", "pipeline.process:raise:0.3:1")
    monkeypatch.setenv("FGUMI_TPU_FAULT_SEED", str(_fault_seed(5, 0.3)))
    faults.reset()
    try:
        _recs, _stats, rep = run_duplex(monkeypatch, tmp_path, mixed_bam,
                                        "fault", "device", threads,
                                        expect_rc=3)
    finally:
        monkeypatch.delenv("FGUMI_TPU_FAULT")
        faults.reset()
    m = rep["metrics"]
    assert m["device.kernel_xla"] >= 4
    assert m["device.resident_bytes_peak"] > 0
    assert m["device.resident_bytes"] == 0
    assert K.DEVICE_STATS.in_flight_count() == 0
