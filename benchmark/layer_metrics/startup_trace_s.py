"""Seconds of tracing (functions to jaxprs) from ``train()``'s entry to the
window's first edge: the union, thread by thread, of the ``jax.trace`` spans.
The compile cache saves none of it."""

from reduce import startup


def read(ctx):
    found = startup.to_edge(ctx, ("jax.trace",))
    return None if found is None else startup.union_s(found)
