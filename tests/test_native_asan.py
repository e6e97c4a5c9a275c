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


@pytest.mark.skipif(libasan is None or libubsan is None,
                    reason="toolchain lacks ASAN/UBSAN runtimes")
def test_native_suites_under_asan_ubsan(tmp_path):
    so = str(tmp_path / "libfgumi_native_asan.so")
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
