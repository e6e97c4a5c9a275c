"""A BAM's records cut into RecordBatches by record count, for tests that
have to cross batch boundaries: ``BamBatchReader`` itself cuts no finer than
one decoded chunk, which is a whole small file whatever ``target_bytes``."""

import numpy as np

from fgumi_tpu.io.batch_reader import BamBatchReader, RecordBatch


def record_batches(path, n_records):
    """The file's records as RecordBatches of ``n_records``."""
    with BamBatchReader(path) as reader:
        for batch in reader:
            ends = np.append(batch.rec_off, len(batch.buf))
            for i in range(0, batch.n, n_records):
                j = min(i + n_records, batch.n)
                yield RecordBatch(
                    bytearray(batch.buf[ends[i]:ends[j]]),
                    np.ascontiguousarray(batch.rec_off[i:j] - ends[i]))
