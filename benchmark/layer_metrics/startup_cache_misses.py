"""Programs the compile cache was asked for and did not have, from
``train()``'s entry to the window's first edge: ``xla.compile`` spans whose
``cache`` is ``miss``. A warm run should read 0; what it reads instead was
compiled under the cache's minimum compile time and never written, evicted,
or keyed by something that moved."""

from reduce import startup


def read(ctx):
    found = startup.compiles_to_edge(ctx)
    if found is None:
        return None
    return float(sum(1 for s in found if s["args"].get("cache") == "miss"))
