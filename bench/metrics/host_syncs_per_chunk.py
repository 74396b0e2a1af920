"""Blocking device-to-host reads per chunk of the stream: the program's
``RunTrace`` counter ``host_syncs`` over one more stream under an enabled
trace, divided by its chunks."""


def read(ctx):
    tr, units = ctx.get("runtrace"), ctx.get("units")
    if tr is None or not units or "host_syncs" not in tr.counters:
        return None
    return tr.counters["host_syncs"] / units
