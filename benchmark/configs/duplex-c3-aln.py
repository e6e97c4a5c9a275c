"""Plain reference of configuration ``duplex-c3-aln``: what ``duplex
--min-reads 1 1 0`` must write for an ``aligned_duplex_bam`` input (a
paired-strategy grouped BAM as an aligner writes it: soft clips and indels
inside AB/BA molecules)."""

import reference_duplex_aligned

HEADER = ["@HD\tVN:1.6\tSO:unsorted\tGO:query", "@RG\tID:A\tSM:sample"]


def expected(data, config, dtype):
    assumed = config["assumed"]
    flat, n_records, _tallies = reference_duplex_aligned.duplex(
        data, {**assumed["consensus"], "min_reads": assumed["min_reads"]},
        dtype)
    return {"records": flat, "n_records": n_records, "header": HEADER}
