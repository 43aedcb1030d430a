"""Device-to-host transfer rate inside the snapshot: bytes that crossed
(counter ``snapshot.bytes``) over the summed seconds of the blocking
transfers (span ``snapshot.xfer``, device slice and D2H of each chunk),
GB/s."""
from ._program import rate_gbps


def read(ctx):
    return rate_gbps(ctx, "snapshot.bytes", "snapshot.xfer")
