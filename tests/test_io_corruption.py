"""Truncated-file and corrupt-block input tests (BGZF/BAM/FASTQ), plain
and prefetch read paths, plus CLI error hygiene: a diagnosed input problem
is a one-line error with path + byte offset and a nonzero exit code."""

import gzip
import logging
import os

import pytest

from fgumi_tpu.cli import main as cli_main
from fgumi_tpu.io.bam import BamHeader, BamReader, BamWriter
from fgumi_tpu.io.bgzf import BgzfReader
from fgumi_tpu.io.errors import InputFormatError
from fgumi_tpu.io.fastq import FastqBatchReader, FastqReader
from fgumi_tpu.io.prefetch import PrefetchFile

HDR = BamHeader(text="@HD\tVN:1.6\n@SQ\tSN:chr1\tLN:1000\n",
                ref_names=["chr1"], ref_lengths=[1000])


@pytest.fixture()
def small_bam(tmp_path):
    path = str(tmp_path / "small.bam")
    rc = cli_main(["simulate", "grouped-reads", "-o", path,
                   "--num-families", "8", "--family-size", "3", "--seed", "3"])
    assert rc == 0
    return path


def _read_all(reader):
    return [r.data for r in reader]


# ------------------------------------------------------------- BGZF / BAM

def test_truncated_bam_plain_reader(small_bam, tmp_path):
    data = open(small_bam, "rb").read()
    trunc = str(tmp_path / "trunc.bam")
    with open(trunc, "wb") as f:
        f.write(data[:len(data) - 37])  # chop through the EOF + last block
    with pytest.raises(ValueError) as ei:
        with BamReader(trunc) as r:
            _read_all(r)
    err = ei.value
    assert isinstance(err, InputFormatError)
    assert "trunc.bam" in str(err)
    assert "byte offset" in str(err)


def test_truncated_bam_prefetch_path(small_bam, tmp_path):
    data = open(small_bam, "rb").read()
    trunc = str(tmp_path / "trunc2.bam")
    with open(trunc, "wb") as f:
        f.write(data[:len(data) - 37])
    fobj = PrefetchFile(open(trunc, "rb"))
    r = BgzfReader(fobj, owns_fileobj=True, name=trunc)
    with pytest.raises(ValueError, match="truncated BGZF"):
        while r.read(1 << 16):
            pass
    r.close()


def test_corrupt_midstream_block(small_bam, tmp_path):
    data = bytearray(open(small_bam, "rb").read())
    assert len(data) > 200
    mid = len(data) // 2
    for i in range(mid, mid + 8):
        data[i] ^= 0xFF
    bad = str(tmp_path / "corrupt.bam")
    with open(bad, "wb") as f:
        f.write(bytes(data))
    with pytest.raises((ValueError, EOFError)):
        with BamReader(bad) as r:
            _read_all(r)


def test_batch_reader_truncated(small_bam, tmp_path):
    from fgumi_tpu.io.batch_reader import BamBatchReader

    data = open(small_bam, "rb").read()
    trunc = str(tmp_path / "trunc3.bam")
    with open(trunc, "wb") as f:
        f.write(data[:len(data) - 37])
    with pytest.raises((ValueError, EOFError)) as ei:
        with BamBatchReader(trunc) as r:
            for _ in r:
                pass
    assert "trunc3.bam" in str(ei.value)


# ------------------------------------------------------------------ FASTQ

def _write_fastq_gz(path, n=50, truncate=0):
    buf = bytearray()
    for i in range(n):
        buf += f"@read{i}\nACGTACGTAC\n+\nIIIIIIIIII\n".encode()
    blob = gzip.compress(bytes(buf), 1)
    if truncate:
        blob = blob[:len(blob) - truncate]
    with open(path, "wb") as f:
        f.write(blob)


def test_truncated_fastq_gz_reader(tmp_path):
    path = str(tmp_path / "r1.fastq.gz")
    _write_fastq_gz(path, truncate=13)
    with pytest.raises(ValueError) as ei:
        with FastqReader(path) as r:
            list(r)
    # the diagnostic names the input file, whichever layer caught it
    assert "r1.fastq.gz" in str(ei.value) or "gzip" in str(ei.value).lower()


def test_truncated_fastq_gz_batch_reader(tmp_path, monkeypatch):
    # force the streaming BGZF/gzip path (the whole-buffer native path
    # reports truncation through the same ValueError contract)
    monkeypatch.setenv("FGUMI_TPU_GZIP_WHOLE_LIMIT", "0")
    path = str(tmp_path / "r2.fastq.gz")
    _write_fastq_gz(path, truncate=13)
    with pytest.raises(ValueError):
        with FastqBatchReader(path) as r:
            for _ in r:
                pass


def test_mid_record_truncated_plain_fastq(tmp_path):
    path = str(tmp_path / "t.fastq")
    with open(path, "w") as f:
        f.write("@r1\nACGT\n+\nIIII\n@r2\nACGT\n")  # record torn after seq
    with pytest.raises(ValueError, match="truncated FASTQ"):
        with FastqReader(path) as r:
            list(r)


# ----------------------------------------------------------- CLI hygiene

def test_cli_truncated_input_one_line_exit_2(small_bam, tmp_path, caplog):
    data = open(small_bam, "rb").read()
    trunc = str(tmp_path / "cli_trunc.bam")
    with open(trunc, "wb") as f:
        f.write(data[:len(data) - 37])
    out = str(tmp_path / "out.bam")
    with caplog.at_level(logging.ERROR, logger="fgumi_tpu"):
        rc = cli_main(["simplex", "-i", trunc, "-o", out, "--min-reads", "1"])
    assert rc == 2
    assert not os.path.exists(out)
    msgs = [r.message for r in caplog.records if r.levelno >= logging.ERROR]
    assert any("cli_trunc.bam" in m for m in msgs), msgs


def test_cli_corrupt_input_exit_2(small_bam, tmp_path):
    data = bytearray(open(small_bam, "rb").read())
    mid = len(data) // 2
    for i in range(mid, mid + 4):
        data[i] ^= 0xFF
    bad = str(tmp_path / "cli_bad.bam")
    with open(bad, "wb") as f:
        f.write(bytes(data))
    rc = cli_main(["group", "-i", bad,
                   "-o", str(tmp_path / "g.bam"), "--allow-unmapped"])
    assert rc != 0


# -------------------------------------------------------------- prefetch

def test_prefetch_close_surfaces_pending_error(tmp_path, caplog):
    """Satellite: PrefetchFile.close() must log (not silently drop) a
    producer exception the consumer never read far enough to hit."""

    class ExplodingFile:
        name = "exploding.bin"
        _n = 0

        def read(self, n):
            self._n += 1
            if self._n > 2:
                raise OSError("disk pulled")
            return b"x" * n

        def fileno(self):
            raise OSError("no fd")

        def close(self):
            pass

    pf = PrefetchFile(ExplodingFile(), chunk=1024, depth=2)
    import time

    deadline = time.monotonic() + 5
    while pf._exc is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert pf._exc is not None
    with caplog.at_level(logging.WARNING, logger="fgumi_tpu"):
        pf.close()
    assert any("pending read error" in r.message for r in caplog.records)


@pytest.mark.parametrize("path_kind", ["native", "zlib"])
def test_member_crc32_checked(small_bam, tmp_path, monkeypatch, path_kind):
    """A member whose payload inflates to ISIZE bytes but whose stored
    CRC32 does not match them is a diagnosed input error on the native
    inflate and on the zlib fallback alike (ROADMAP D1)."""
    from fgumi_tpu import native

    if path_kind == "zlib":
        monkeypatch.setattr(native, "get_lib", lambda: None)
    elif native.get_lib() is None:
        pytest.skip("native library unavailable")
    data = bytearray(open(small_bam, "rb").read())
    bsize = int.from_bytes(data[16:18], "little") + 1  # first member
    data[bsize - 8] ^= 0x01  # its CRC32, not its payload
    bad = str(tmp_path / "crc.bam")
    with open(bad, "wb") as f:
        f.write(bytes(data))
    r = BgzfReader(open(bad, "rb"), owns_fileobj=True, name=bad)
    with pytest.raises(InputFormatError, match="crc.bam"):
        while r.read(1 << 16):
            pass
    r.close()
