"""Plain reference of configuration ``simplex-c1``: what ``simplex
--min-reads 1`` must write for a ``grouped_bam`` input."""

import reference

HEADER = ["@HD\tVN:1.6\tSO:unsorted\tGO:query", "@RG\tID:A\tSM:sample"]


def expected(data, config, dtype):
    flat, n_records, _reads = reference.simplex(
        data, config["assumed"]["consensus"], dtype)
    return {"records": flat, "n_records": n_records, "header": HEADER}
