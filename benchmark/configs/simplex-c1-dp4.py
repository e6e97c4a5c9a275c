"""Plain reference of configuration ``simplex-c1-dp4``: what ``--mesh dp4xsp1
simplex --min-reads 1`` must write for a ``grouped_bam`` input. The
deployment's guarantee is that the mesh is invisible in the bytes, so this is
the one-chip configuration's reference, called the same way."""

import reference

HEADER = ["@HD\tVN:1.6\tSO:unsorted\tGO:query", "@RG\tID:A\tSM:sample"]


def expected(data, config, dtype):
    flat, n_records, _reads = reference.simplex(
        data, config["assumed"]["consensus"], dtype)
    return {"records": flat, "n_records": n_records, "header": HEADER}
