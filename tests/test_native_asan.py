"""ASAN/UBSAN lane for the native layer (VERDICT r4 item 4).

The reference runs Miri nightly over its one unsafe crate
(/root/reference/.github/workflows/miri.yml:1-22); the analog here is the
whole C++ runtime (fgumi_native.cc — raw pointers, caller-supplied offsets
and output capacities), which produces every output byte. This lane builds a
separate sanitized .so (-fsanitize=address,undefined, recover disabled so
any finding aborts) and re-runs the native test suites against it in a
subprocess with the ASAN runtime preloaded (CPython itself is unsanitized,
so libasan must be first in the link order at process start).

Auto-skips when the toolchain lacks the sanitizer runtimes. Leak checking is
off: CPython/numpy hold allocations for the process lifetime by design and
the lane targets memory *errors* (OOB, UAF, UB), not leaks.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "fgumi_tpu", "native", "fgumi_native.cc")

# the suites that exercise every native entry point with real data
# (test_host_engine drives fgumi_consensus_segments, the f64 engine, with
# adversarial pileups — Q0 NaN flows, depth tables, saturation boundary)
SANITIZED_SUITES = ["tests/test_native.py", "tests/test_native_batch.py",
                    "tests/test_host_engine.py"]


def _runtime(name):
    try:
        out = subprocess.run(["g++", f"-print-file-name={name}"],
                             capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    path = out.stdout.strip()
    # g++ echoes the bare name back when the runtime is not installed
    return path if os.path.sep in path and os.path.exists(path) else None


libasan = _runtime("libasan.so")
libubsan = _runtime("libubsan.so")


pytestmark = pytest.mark.skipif(libasan is None or libubsan is None,
                                reason="toolchain lacks ASAN/UBSAN runtimes")


@pytest.fixture(scope="module")
def sanitized_env(tmp_path_factory):
    """The environment of a process that loads the sanitized library."""
    so = str(tmp_path_factory.mktemp("asan") / "libfgumi_native_asan.so")
    build = subprocess.run(
        ["g++", "-O1", "-g", "-shared", "-fPIC", "-pthread",
         "-fsanitize=address,undefined", "-fno-sanitize-recover=all",
         "-o", so, SRC, "-ldeflate"],
        capture_output=True, text=True, timeout=240)
    assert build.returncode == 0, f"sanitized build failed:\n{build.stderr}"

    env = dict(os.environ)
    env.update({
        "FGUMI_TPU_NATIVE_SO": so,
        # python is unsanitized: the ASAN runtime must be present at startup
        "LD_PRELOAD": f"{libasan}:{libubsan}",
        "ASAN_OPTIONS": "detect_leaks=0:abort_on_error=1",
        "UBSAN_OPTIONS": "print_stacktrace=1:halt_on_error=1",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
    })
    return env


def test_native_suites_under_asan_ubsan(sanitized_env):
    env = sanitized_env
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q"] + SANITIZED_SUITES,
        cwd=REPO, capture_output=True, text=True, timeout=900, env=env)
    tail = (proc.stdout + "\n" + proc.stderr)[-4000:]
    assert proc.returncode == 0, f"sanitized native suites failed:\n{tail}"
    assert "ERROR: AddressSanitizer" not in tail
    # guard against a vacuous pass: if the sanitized .so failed to load,
    # get_lib() falls back to None and the native suites all SKIP — the
    # inner run must actually have executed tests against the .so
    import re

    m = re.search(r"(\d+) passed", tail)
    assert m and int(m.group(1)) >= 20, \
        f"sanitized run passed too few tests (skip fallback?):\n{tail}"


def _run_tight(env, script, said):
    """``script`` in a process that loads the sanitized library: it must
    exit 0 having printed ``said``, with no sanitizer report."""
    proc = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                          capture_output=True, text=True, timeout=300,
                          env=env)
    tail = (proc.stdout + "\n" + proc.stderr)[-4000:]
    assert proc.returncode == 0 and said in proc.stdout, tail
    assert "ERROR: AddressSanitizer" not in tail


# the duplex error recount reads rows where they lie in the batch's packed
# codes: the last listed row ends on the buffer's last byte, so a read of
# `stride` bytes a row, or of a row past the list, is out of bounds
_RANGES_TIGHT = """
import numpy as np
from fgumi_tpu.native import batch as nb

assert nb.get_lib() is not None
R, stride, L = 9, 40, 24
buf = np.random.default_rng(3).integers(0, 5, (R - 1) * stride + L)
buf = buf.astype(np.uint8)
codes = np.lib.stride_tricks.as_strided(buf, (R, L), (stride, 1))
rows = np.array([8, 0, 8, 3, 8], dtype=np.int64)
lo = np.array([0, 2, 5, 0], dtype=np.int64)
hi = np.array([2, 5, 5, 5], dtype=np.int64)
winner = np.full((4, L), 1, dtype=np.uint8)
depth, errors = nb.segment_depth_errors_ranges(codes, rows, winner, lo, hi)
for j in range(4):
    seg = codes[rows[lo[j]:hi[j]]]
    assert (depth[j] == (seg != 4).sum(axis=0)).all()
    assert (errors[j] == ((seg != 4) & (seg != 1)).sum(axis=0)).all()
print("ranges ok")
"""


def test_depth_errors_ranges_tight_buffer_under_asan(sanitized_env):
    _run_tight(sanitized_env, _RANGES_TIGHT, "ranges ok")


# the alignment filter decodes CIGAR words where they lie, at odd offsets:
# the last CIGAR ends on the buffer's last byte, so a word read past a
# CIGAR's count, or an aligned 4-byte load, is out of bounds or misaligned
_FILTER_TIGHT = """
import sys
import numpy as np
sys.path.insert(0, "tests")
from cigar_segments import OPS, cigar_buffer, filter_oracle
from fgumi_tpu.native import batch as nb

assert nb.get_lib() is not None
rng = np.random.default_rng(9)
cigars = [[(OPS[int(rng.integers(0, 9))], int(rng.integers(1, 30)))
           for _ in range(int(rng.integers(0, 6)))] for _ in range(400)]
buf, cigar_off, n_cigar = cigar_buffer(cigars, rng)
buf = buf.copy()  # an allocation of its own, exactly as long
assert cigar_off[-1] + 4 * n_cigar[-1] == len(buf) and (cigar_off % 2).all()
reverse = rng.integers(0, 2, 400).astype(np.uint8)
lens = rng.integers(1, 60, 400).astype(np.int32)
starts = np.array([0, 1, 1, 40, 41, 43, 200, 400])
keep = nb.alignment_filter(buf, cigar_off, n_cigar, reverse, lens, starts)
for lo, hi in zip(starts[:-1], starts[1:]):
    assert (keep[lo:hi] == filter_oracle(cigars[lo:hi], reverse[lo:hi],
                                         lens[lo:hi])).all()
print("filter ok")
"""


def test_alignment_filter_tight_buffer_under_asan(sanitized_env):
    _run_tight(sanitized_env, _FILTER_TIGHT, "filter ok")


# the CODEC placement reads a strand where it lies in a result matrix and
# writes a molecule where it lies in the outputs: a strand as wide as its
# matrix in the last row, a materialised strand exactly as long as its
# arrays, reversed or not, int32 or int64 counts; a read or a write one
# element past either end is out of bounds
_PLACE_TIGHT = """
import sys
import numpy as np
sys.path.insert(0, "tests")
from codec_placement import I16_MAX, PAD, PLACE_CASES, numpy_place, place_case
from fgumi_tpu.native import batch as nb

assert nb.get_lib() is not None
table = np.arange(256, dtype=np.uint8)[::-1].copy()
for case in PLACE_CASES:
    for reverse in (False, True):
        args = place_case(case) + (table, reverse, I16_MAX, PAD)
        got, want = nb.codec_place(*args), numpy_place(*args)
        assert all(np.array_equal(g, w) for g, w in zip(got, want)), case
print("place ok")
"""


def test_codec_place_tight_buffers_under_asan(sanitized_env):
    _run_tight(sanitized_env, _PLACE_TIGHT, "place ok")
