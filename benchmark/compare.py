"""The comparison that decides ``correct``: every job's committed output BAM
against the plain reference's records, byte for byte, once the window has
closed. Each number compared has the limit 0: the configuration states exact
records, so there is nothing to tune."""

import hashlib
import json
import os
import re
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import bamio

_EXPECTED_FILES = re.compile(r"^job\d+\.(bam|report\.json)$")


def _differing(got, got_start, want):
    """(records that differ, gap in the record count) of two record streams."""
    a = bamio.record_offsets(got, got_start)
    b = bamio.record_offsets(want, 0)
    n = min(len(a), len(b)) - 1
    bad = sum(1 for i in range(n)
              if got[a[i]:a[i + 1]] != want[b[i]:b[i + 1]])
    return bad, abs(len(a) - len(b))


def judge(outputs, rcs, out_dir, cache_path, expected_fn, dtype=np.float64):
    """Compare every output with the reference. ``expected_fn(dtype)`` works
    the reference out (a dict with ``records``, ``n_records``, ``header``);
    its digest is cached beside the input, so a seed pays it once."""
    cached = None
    if os.path.exists(cache_path) and dtype == np.float64:
        with open(cache_path) as f:
            cached = json.load(f)
    want = None
    lock = threading.Lock()

    def reference():
        nonlocal want
        with lock:
            if want is None:
                exp = expected_fn(dtype)
                want = (memoryview(np.ascontiguousarray(exp["records"]))
                        .cast("B"), exp["n_records"], exp["header"])
        return want

    if cached is None:
        body, n_records, header = reference()
        cached = {"sha256": hashlib.sha256(body).hexdigest(),
                  "n_records": n_records, "header": header}
        if dtype == np.float64:
            with open(cache_path, "w") as f:
                json.dump(cached, f)
    numbers = {"jobs_failed": sum(1 for rc in rcs if rc != 0),
               "records_differing": 0, "record_count_gap": 0,
               "header_lines_differing": 0, "temp_files_left": 0}
    def one(path):
        """(header lines that differ, records that differ, count gap)."""
        payload = bamio.read_bgzf(path)
        text, start = bamio.split_bam(payload)
        lines = [ln for ln in text.splitlines() if not ln.startswith("@PG")]
        head = sum(1 for a, b in zip(lines, cached["header"]) if a != b) \
            + abs(len(lines) - len(cached["header"]))
        got = memoryview(payload)
        if hashlib.sha256(got[start:]).hexdigest() == cached["sha256"]:
            return head, 0, 0
        return (head,) + _differing(got, start, reference()[0])

    done = [p for p, rc in zip(outputs, rcs) if rc == 0]
    numbers["jobs_failed"] += sum(1 for p in done if not os.path.exists(p))
    with ThreadPoolExecutor(8) as pool:  # zlib and sha256 release the GIL
        for head, bad, gap in pool.map(
                one, [p for p in done if os.path.exists(p)]):
            numbers["header_lines_differing"] += head
            numbers["records_differing"] += bad
            numbers["record_count_gap"] += gap
    numbers["temp_files_left"] = sum(
        1 for name in os.listdir(out_dir) if not _EXPECTED_FILES.match(name))
    compared = {k: {"value": v, "limit": 0} for k, v in numbers.items()}
    return {"correct": all(v == 0 for v in numbers.values()) and bool(outputs),
            "compared": compared, "cached": want is None}
