"""How uneven the load of the 8 experts held here is with 10 experts a
token of 256: assignments of the busiest held expert over the mean held
expert's, in the step at each log point of the window, averaged; from the
gauges ``moe_local_load_max`` and ``moe_local_load_mean``, read as
``moe_local_load_max_over_mean`` reads Moonlight's. 1.0 is perfectly even; the
grouped products take as long as their largest tiles. Uniform ids give the
router nothing learned to go by: no gain in the grouped products is to be
claimed on this traffic."""

from layer_metrics.moe_local_load_max_over_mean import read  # noqa: F401
