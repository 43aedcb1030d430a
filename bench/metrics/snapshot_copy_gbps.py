"""Host copy rate into the staging arena inside the snapshot: bytes that
crossed (counter ``snapshot.bytes``) over the summed seconds of the
copies (span ``snapshot.copy``), GB/s."""
from ._program import rate_gbps


def read(ctx):
    return rate_gbps(ctx, "snapshot.bytes", "snapshot.copy")
