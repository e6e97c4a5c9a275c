"""Plain reference of configuration ``simplex-c2``: what ``simplex
--min-reads 1`` must write for an ``aligned_bam`` input (a BAM as an aligner
writes it: soft clips and indels inside the families)."""

import reference_aligned

HEADER = ["@HD\tVN:1.6\tSO:unsorted\tGO:query", "@RG\tID:A\tSM:sample"]


def expected(data, config, dtype):
    flat, n_records, _tallies = reference_aligned.simplex(
        data, config["assumed"]["consensus"], dtype)
    return {"records": flat, "n_records": n_records, "header": HEADER}
