#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place, computed one precision below what the configuration states (float32
for float64), and judged by the same comparison. It has to come out as not
correct. Needs no chip and nothing of the program:

    python benchmark/control.py --workload <config>.<traffic> --seed N
"""

import argparse
import json
import os
import shutil
import sys

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import bamio  # noqa: E402
import compare  # noqa: E402
import run as harness  # noqa: E402
import traffic  # noqa: E402


def write_bam(path, header_lines, records):
    text = "".join(line + "\n" for line in header_lines)
    bamio.write_bgzf(path, bamio.bam_header(text)
                     + np.ascontiguousarray(records).tobytes())


def control(workload, seed, families=None, work=None):
    """Judge the float32 reference against the float64 one. Returns the
    comparison's verdict (``correct`` must be False)."""
    _bench, _cell, config, reference, params = harness.load_cell(workload)
    if families:
        params["num_families"] = families
    work = work or os.path.join(harness.WORK, "control", workload, str(seed))
    out_dir = os.path.join(work, "out")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(out_dir)
    low = reference.expected(traffic.generate(params, seed), config,
                             np.float32)
    path = os.path.join(out_dir, "job0.bam")
    write_bam(path, low["header"], low["records"])
    verdict = compare.judge(
        [path], [0], out_dir, os.path.join(work, "expected.json"),
        lambda dtype: reference.expected(traffic.generate(params, seed),
                                         config, dtype))
    verdict["records"] = low["n_records"]
    shutil.rmtree(work, ignore_errors=True)
    return verdict


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--families", type=int, default=None)
    args = ap.parse_args()
    verdict = control(args.workload, args.seed, args.families)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "control_correct": verdict["correct"],
                      "records": verdict["records"],
                      "compared": verdict["compared"]}))
    return 0 if not verdict["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
