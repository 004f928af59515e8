"""Device time of collective operations per train step in the trace, on the
chip that idled most: all-reduce, all-gather, reduce-scatter, permute and
all-to-all, synchronous ones by their own time and asynchronous ones from
start to done (so time hidden behind compute is counted too)."""

from reduce import xplane


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    chip = xplane.worst(trace)
    steps = sum(len(v) for n, v in chip["modules"].items()
                if xplane.module_base(n) == "jit_step")
    if not steps:
        return None
    return chip["collective_ns"] / steps / 1e6
