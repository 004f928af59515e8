"""Seconds of loading executables from the compile cache from ``train()``'s
entry to the window's first edge: the ``xla.compile`` spans whose ``cache``
is ``hit``. 0.0 where nothing was loaded."""

from reduce import startup


def read(ctx):
    found = startup.compiles_to_edge(ctx)
    if found is None:
        return None
    return sum(s["end_ns"] - s["start_ns"] for s in found
               if s["args"].get("cache") == "hit") / 1e9
