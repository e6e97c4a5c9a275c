"""Plain reference of configuration ``chain-c5``: what ``pipeline -r 8M+T +T
--filter-min-reads 3`` must write for a ``paired_fastq`` input."""

import reference

HEADER = ["@HD\tVN:1.6\tSO:unsorted\tGO:query", "@RG\tID:A\tSM:sample"]


def expected(data, config, dtype):
    flat, n_records, _reads = reference.chain(
        data, config["assumed"]["consensus"], dtype)
    return {"records": flat, "n_records": n_records, "header": HEADER}
