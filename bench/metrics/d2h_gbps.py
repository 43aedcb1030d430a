"""Snapshot (device to host) rate over the window's saves:
sum of ``SaveStats.d2h_bytes`` over sum of ``snapshot_seconds``, GB/s."""


def read(ctx):
    saves = ctx["records"].get("saves")
    if not saves:
        return None
    secs = sum(s["snapshot_seconds"] for s in saves)
    return sum(s["d2h_bytes"] for s in saves) / secs / 1e9 if secs else None
