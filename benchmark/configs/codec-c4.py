"""Plain reference of configuration ``codec-c4``: what ``codec --min-reads
1`` at upstream's defaults must write for a ``codec_bam`` input."""

import reference_codec

HEADER = ["@HD\tVN:1.6\tSO:unsorted\tGO:query", "@RG\tID:A\tSM:sample"]


def expected(data, config, dtype):
    flat, n_records, _reads, _counted = reference_codec.codec(
        data, config["assumed"]["consensus"], dtype)
    return {"records": flat, "n_records": n_records, "header": HEADER}
