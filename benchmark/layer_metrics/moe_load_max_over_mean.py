"""How uneven the experts' load is: assignments of the busiest expert over
the mean expert's, in the step at each log point of the window, averaged.
From the program's gauges ``moe_expert_load_max`` and
``moe_expert_load_mean`` (``trainer._ExpertLoad``, fed at log points from
values the step returns beside the loss). 1.0 is perfectly even; the grouped
products take as long as their largest tiles, and a sharded layout would wait
for the busiest chip."""

import statistics


def read(ctx):
    lo, hi = ctx["window_ns"]
    ratios = [p["counters"]["moe_expert_load_max"]
              / p["counters"]["moe_expert_load_mean"]
              for p in ctx["log_points"]
              if lo <= p["t"] <= hi
              and p["counters"].get("moe_expert_load_mean")]
    return statistics.fmean(ratios) if ratios else None
