"""idle_share.cluster: the share of the clustering window in which no op
ran on the device, in %, from the profiler trace (trace_reduce.py)."""


def read(ctx):
    tr = ctx.get("trace")
    return None if tr is None else 100.0 * tr["idle_share"]
