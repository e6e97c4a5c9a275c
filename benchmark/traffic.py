"""The one general input generator: a traffic file's parameters + a seed ->
arrays -> the cell's input files.

A traffic mix is ``benchmark/traffic/<name>.json``. Its ``kind`` names the
layout of the input, and the layout's code is found by that name, as metrics
and references are: ``benchmark/traffic/<kind>.py`` with two functions,
``generate(params, rng, common)`` (the arrays of one input; ``common`` holds
the family sizes, and each read pair's family and ordinal) and
``write(data, prefix, level)`` (the input files, their paths in the order the
configuration's argv template names them). A new kind of input is one new
file there. This module holds what the layouts share: the models of
``fgumi_tpu/simulate.py`` (family-size distributions, 3' quality decay,
per-read length jitter, substitution errors), drawn in bulk with numpy so
that a million reads cost a second or two, not a minute, and the bulk writers
of BAM records. The same arrays feed the plain reference, so nothing is
parsed back.

Run as a program it writes one input and prints its paths and read count:
``run.py`` makes its input that way, in a child process, so that the arrays
built here never touch the heap the program's jobs run in.
"""

import importlib.util
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import bamio  # noqa: E402,F401  (the layouts write with it)

N_CODE = 4
CODE_TO_ASCII = np.frombuffer(b"ACGTN", dtype=np.uint8)
_CODE_TO_NIBBLE = np.array([1, 2, 4, 8, 15], dtype=np.uint8)
COMPLEMENT = np.array([3, 2, 1, 0, 4], dtype=np.uint8)

_DEFAULTS = {"family_size_distribution": "fixed", "read_length": 100,
             "read_length_jitter": 0, "qual_slope": 0.0, "error_rate": 0.01,
             "base_quality": 35, "qual_jitter": 5, "umi_length": 8}


def load(name, root):
    with open(os.path.join(root, "traffic", name + ".json")) as f:
        params = json.load(f)
    return {**_DEFAULTS, **params}


def family_sizes(rng, params):
    n, mean = params["num_families"], params["family_size"]
    dist = params["family_size_distribution"]
    if dist == "fixed":
        return np.full(n, mean, dtype=np.int64)
    if dist == "lognormal":
        return np.maximum(1, rng.lognormal(np.log(max(mean, 1)), 0.6, n)
                          .astype(np.int64))
    if dist == "longtail":
        return np.minimum(50, 1 + (rng.pareto(1.3, n) * max(mean, 1) * 0.5)
                          .astype(np.int64))
    raise ValueError(f"unknown family_size_distribution {dist!r}")


def quals(rng, n, width, params):
    """clip(base - slope * position + jitter, 2, 40), truncated; all in int8."""
    row = np.floor(params["base_quality"]
                   - params["qual_slope"] * np.arange(width)).astype(np.int8)
    j = params["qual_jitter"]
    q = rng.integers(-j, j + 1, (n, width), dtype=np.int8)
    q += row
    return np.clip(q, 2, 40, out=q).view(np.uint8)


def mutate(rng, codes, rate):
    """Substitute each base with probability ``rate`` (a Bernoulli process
    drawn as geometric gaps, so the cost follows the errors, not the bases)."""
    out = np.ascontiguousarray(codes)
    if rate <= 0:
        return out
    flat = out.reshape(-1)
    gaps = rng.geometric(rate, int(flat.size * rate * 1.2) + 64)
    pos = np.cumsum(gaps) - 1
    if pos[-1] < flat.size:
        raise ValueError("error positions ran short; raise the margin")
    pos = pos[pos < flat.size]
    flat[pos] = (flat[pos] + rng.integers(1, 4, len(pos), dtype=np.uint8)) % 4
    return out


def lengths(rng, n, params):
    length = params["read_length"]
    jit = max(min(params["read_length_jitter"], length - 20), 0)
    if not jit:
        return np.full(n, length, dtype=np.int64)
    return length - rng.integers(0, jit + 1, n)


def kind_module(kind):
    """The layout's code, found by its name: ``traffic/<kind>.py``."""
    path = os.path.join(ROOT, "traffic", kind + ".py")
    if not os.path.exists(path):
        raise ValueError(f"unknown traffic kind {kind!r}: no {path}")
    spec = importlib.util.spec_from_file_location("traffic_kind_" + kind, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generate(params, seed):
    """Arrays of one input, from the seed alone."""
    rng = np.random.default_rng(int(seed))
    # every seed gets the same multiset of family sizes, in another order
    sizes = rng.permutation(family_sizes(
        np.random.default_rng(params.get("sizes_seed", 1)), params))
    fam = np.repeat(np.arange(len(sizes)), sizes)
    ordinal = np.arange(len(fam)) - np.repeat(np.cumsum(sizes) - sizes, sizes)
    data = {"kind": params["kind"], "sizes": sizes, "fam": fam,
            "ordinal": ordinal}
    data.update(kind_module(params["kind"]).generate(params, rng, data))
    return data


def write_inputs(data, prefix, level=1):
    """Write the input files of ``data`` beside ``prefix``; returns their
    paths in the order the configuration's argv template names them."""
    return kind_module(data["kind"]).write(data, prefix, level)


# ---------------------------------------------------------------- writing

def digits(values, width):
    """(n, width) ASCII digits of ``values``, left-aligned, and each length."""
    values = np.asarray(values, dtype=np.int64)
    pows = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    dig = (values[:, None] // pows) % 10 + ord("0")
    ndig = np.maximum(1, np.floor(np.log10(np.maximum(values, 1))).astype(
        np.int64) + 1)
    idx = np.minimum((width - ndig)[:, None] + np.arange(width), width - 1)
    return np.take_along_axis(dig, idx, axis=1).astype(np.uint8), ndig


def const(n, data):
    return np.broadcast_to(np.frombuffer(data, dtype=np.uint8), (n, len(data)))


def ints(fmt, *cols):
    """Little-endian struct columns as (n, bytes) uint8."""
    rec = np.empty(len(cols[0]), dtype=np.dtype(
        [(f"f{i}", t) for i, t in enumerate(fmt)]))
    for i, c in enumerate(cols):
        rec[f"f{i}"] = c
    return rec.view(np.uint8).reshape(len(rec), -1)


def pack_rows(segments):
    """Concatenate per-row variable-length segments into one flat byte array.
    Each segment is ``(array2d, lengths or None)``; a row's bytes are its
    segments' first ``lengths`` columns, in order. Returns (flat, row sizes)."""
    n = len(segments[0][0])
    masks, sizes = [], np.zeros(n, dtype=np.int64)
    for arr, lens in segments:
        w = arr.shape[1]
        if lens is None:
            masks.append(np.ones((n, w), dtype=bool))
            sizes += w
        else:
            masks.append(np.arange(w)[None, :] < lens[:, None])
            sizes += lens
    big = np.concatenate([a for a, _ in segments], axis=1)
    return big[np.concatenate(masks, axis=1)], sizes


def bam_record(body, ref_id, pos, l_name, mapq, bin_, n_cigar, flag, l_seq,
               next_ref, next_pos, tlen):
    """``body`` (the variable part of a BAM record as ``pack_rows``
    segments: name, CIGAR, sequence, qualities, tags) behind its 36 fixed
    bytes, ``block_size`` worked out from the segments."""
    sizes = sum((seg.shape[1] if lens is None else lens)
                for seg, lens in body) + 32
    fixed = ints(("<i4", "<i4", "<i4", "u1", "u1", "<u2", "<u2", "<u2",
                  "<i4", "<i4", "<i4", "<i4"),
                 sizes, ref_id, pos, l_name, mapq, bin_, n_cigar, flag, l_seq,
                 next_ref, next_pos, tlen)
    return [(fixed, None)] + body


def pack_seq(codes, lens):
    nib = _CODE_TO_NIBBLE[codes]
    nib = np.where(np.arange(codes.shape[1])[None, :] < lens[:, None], nib, 0)
    if nib.shape[1] % 2:
        nib = np.pad(nib, ((0, 0), (0, 1)))
    return ((nib[:, 0::2] << 4) | nib[:, 1::2]).astype(np.uint8)


def main(argv=None):
    """One input, written by a process of its own (see the docstring)."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prefix", required=True)
    ap.add_argument("--families", type=int, default=None)
    args = ap.parse_args(argv)
    started = time.monotonic()
    params = load(args.traffic, ROOT)
    if args.families:
        params["num_families"] = args.families
    data = generate(params, args.seed)
    os.makedirs(os.path.dirname(args.prefix), exist_ok=True)
    print(json.dumps({"inputs": write_inputs(data, args.prefix),
                      "reads": int(data["n_reads"]),
                      "seconds": time.monotonic() - started}))
    return 0


if __name__ == "__main__":
    sys.modules.setdefault("traffic", sys.modules["__main__"])
    sys.exit(main())
