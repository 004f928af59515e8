"""How uneven the load of the experts held here is: assignments of the
busiest held expert over the mean held expert's, in the step at each log
point of the window, averaged. From the program's gauges
``moe_local_load_max`` and ``moe_local_load_mean`` (``trainer._StepStats``,
fed at log points from values the step returns beside the loss; over all
expert layers). 1.0 is perfectly even; the grouped products take as long as
their largest tiles. ``moe_load_max_over_mean`` is the same over all 64
outputs of the router, which in a deployment is what the busiest rank waits
for."""

import statistics


def read(ctx):
    lo, hi = ctx["window_ns"]
    ratios = [p["counters"]["moe_local_load_max"]
              / p["counters"]["moe_local_load_mean"]
              for p in ctx["log_points"]
              if lo <= p["t"] <= hi
              and p["counters"].get("moe_local_load_mean")]
    return statistics.fmean(ratios) if ratios else None
