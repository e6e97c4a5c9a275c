"""Plain reference of configuration ``codec-c4-aln``: what ``codec
--min-reads 1`` at upstream's defaults must write for an
``aligned_codec_bam`` input (an MI-grouped CODEC BAM as an aligner writes
it: soft clips and indels inside the molecules)."""

import reference_codec_aligned

HEADER = ["@HD\tVN:1.6\tSO:unsorted\tGO:query", "@RG\tID:A\tSM:sample"]


def expected(data, config, dtype):
    flat, n_records, _tallies = reference_codec_aligned.codec(
        data, config["assumed"]["consensus"], dtype)
    return {"records": flat, "n_records": n_records, "header": HEADER}
