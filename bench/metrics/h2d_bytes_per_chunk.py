"""Bytes put from the host on the device per chunk of the stream: the
program's ``RunTrace`` counter ``h2d_bytes`` over one more stream under an
enabled trace, divided by its chunks."""


def read(ctx):
    tr, units = ctx.get("runtrace"), ctx.get("units")
    if tr is None or not units or "h2d_bytes" not in tr.counters:
        return None
    return tr.counters["h2d_bytes"] / units
