"""The program's own span and counter totals of the window, for the
readers of ``repro.obs`` metrics: the run's ``records["spans"]`` when
the job kept them, else the recorder's table, which records while the
profiler traces the window. None where the program has no recorder."""


def totals(ctx):
    kept = ctx["records"].get("spans")
    if kept is not None:
        return kept
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.totals()


def rate_gbps(ctx, counter: str, span: str):
    """Sum of ``counter`` bytes over the summed seconds of ``span``,
    GB/s; None where either is missing."""
    t = totals(ctx)
    if not t:
        return None
    n = t["counters"].get(counter)
    s = t["spans"].get(span, {}).get("s")
    if not n or not s:
        return None
    return n / s / 1e9
