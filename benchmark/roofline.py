"""Peaks of the chips the benchmark knows, and the work one consensus
dispatch needs, whatever implements the kernel.

Peaks: Google Cloud documentation, "TPU v5e" system architecture: 197 TFLOP/s
in bf16 and 819 GB/s of HBM per chip. Keyed by the exact ``device_kind`` jax
reports; a device that is not in the table is an error, never a default.
"""

PEAKS = {"TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9}}

#: bytes fetched per consensus column on the full-column route: winner and
#: quality one byte each, depth and errors two each
COLUMN_BYTES = 6


def peaks(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"add it to benchmark/roofline.py with its source")
    return PEAKS[device_kind]


def consensus_work(rows, length, families):
    """(operations, bytes) one dispatch over ``rows`` unpadded reads of
    ``length`` positions in ``families`` molecules cannot avoid. Operations
    by the arithmetic of the segment-sum formulation (per observation: the
    lane one-hot and mask 4, the likelihood delta 4, two segment sums 8; per
    consensus column about 40 for the call). Bytes: every observation in at
    one byte (base and quality packed), every consensus column out."""
    operations = rows * length * 16 + families * length * 40
    moved = rows * length * 1 + families * length * COLUMN_BYTES
    return operations, moved


def least_seconds(device_kind, rows, length, families):
    """(least time, which bound sets it)."""
    pk = peaks(device_kind)
    operations, moved = consensus_work(rows, length, families)
    compute, memory = operations / pk["flops_per_s"], moved / pk["bytes_per_s"]
    return max(compute, memory), ("memory" if memory >= compute else "compute")
