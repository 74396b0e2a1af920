"""Programs compiled or loaded from the persistent compile cache inside the
profiled window: the harness's own ``jax.monitoring`` listener
(``bench/compiles.py``)."""


def read(ctx):
    return ctx.get("window_compiles")
