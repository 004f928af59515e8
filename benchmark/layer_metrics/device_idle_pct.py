"""100 * (1 - union of device-operation intervals / traced window), on the
chip that idled most."""

from reduce import xplane


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"]:
        return None
    return xplane.worst(trace)["idle_pct"]
