"""Plain reference of configuration ``duplex-c3``: what ``duplex --min-reads
1 1 0`` must write for a ``duplex_bam`` input."""

import reference_duplex

HEADER = ["@HD\tVN:1.6\tSO:unsorted\tGO:query", "@RG\tID:A\tSM:sample"]


def expected(data, config, dtype):
    assumed = config["assumed"]
    flat, n_records, _reads = reference_duplex.duplex(
        data, {**assumed["consensus"], "min_reads": assumed["min_reads"]},
        dtype)
    return {"records": flat, "n_records": n_records, "header": HEADER}
