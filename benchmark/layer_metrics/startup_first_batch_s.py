"""Seconds from the start of ``startup.loader`` to the start of the first
``train.step``: exporter, pools, the first loader, the wait for its first
batch on the device, and what the loop does between a batch and its
dispatch."""

from reduce import startup


def read(ctx):
    loader = [s["start_ns"] for s in ctx["spans"]
              if s["name"] == "startup.loader"]
    step = startup.first_step(ctx["spans"])
    if not loader or step is None:
        return None
    return (step["start_ns"] - min(loader)) / 1e9
