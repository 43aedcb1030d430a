"""Restore read rate: bytes of state loaded over the time inside
``engine.load`` (read, CRC check, into host buffers), GB/s."""


def read(ctx):
    r = ctx["records"]
    if not r.get("resumes") or not r.get("load_seconds"):
        return None
    return r["state_bytes"] * r["resumes"] / r["load_seconds"] / 1e9
