"""Rows the SMM insert loop visits per chunk of the stream: the program's
``RunTrace`` counter ``insert_steps`` over one more stream under an
enabled trace, divided by its chunks."""


def read(ctx):
    tr, units = ctx.get("runtrace"), ctx.get("units")
    if tr is None or not units or "insert_steps" not in tr.counters:
        return None
    return tr.counters["insert_steps"] / units
