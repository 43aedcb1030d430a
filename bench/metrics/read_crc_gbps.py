"""CRC rate of one reader thread in the restore read: bytes read
(counter ``read.bytes``) over the summed seconds of the per-chunk CRC
folds (span ``read.crc``), GB/s."""
from ._program import rate_gbps


def read(ctx):
    return rate_gbps(ctx, "read.bytes", "read.crc")
