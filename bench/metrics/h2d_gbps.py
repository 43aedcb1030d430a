"""Host-to-device rate of the resume: bytes put on the device (counter
``restore.bytes``) over the summed seconds of the copies, each ending
once the state landed (span ``restore.h2d``), GB/s."""
from ._program import rate_gbps


def read(ctx):
    return rate_gbps(ctx, "restore.bytes", "restore.h2d")
