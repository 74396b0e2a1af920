"""Share of the profiled window in which no op ran on the device, in
percent: 1 - (union of device-op intervals / window), the mean over the
cell's chips (``trace_reduce.reduce``)."""


def read(ctx):
    tr = ctx.get("trace")
    return None if tr is None else tr["idle_pct"]
