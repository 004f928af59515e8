"""Share of the chip's roofline that the held experts' grouped products
reach: the least time the chip could take for them (the larger of their
operations over the bf16 peak and their bytes over the memory bandwidth, from
shapes by ``benchmark/flops/<config>.py``: ``expert_flops``,
``expert_bytes``) over the device time of everything between the gather and
the weighted sum, forward and backward: the kernel XLA makes of
``ragged_dot`` (found by its name), the layout copies of the held experts'
matrices and what is under ``moe.experts``. The rows are those the program
counted in groups, the window's ``moe_local_assignments_total`` over its
steps (``trainer._StepStats`` publishes it from what the step returns beside
the loss): 12.5% of all assignments at even routing; the sorted list's other
rows are dead, and the time they cost counts against the share, as does the
backward pass's recomputation of the three forward products."""

from reduce import named_scopes


def read(ctx):
    ms = named_scopes.per_step_ms(ctx, "moe.experts",
                                  also=named_scopes.GROUPED_PRODUCTS)
    flops, peaks = ctx["flops"], ctx["peaks"]
    rows = ctx["counters"].get("moe_local_assignments_total")
    if not ms or not peaks or not rows or not ctx["steps"] or not hasattr(
            flops, "expert_flops"):
        return None
    model = ctx["cell"]["config"]["model"]
    rows = rows / ctx["steps"] / ctx["chips"]
    least_s = max(flops.expert_flops(model, rows) / peaks["bf16_flops_per_s"],
                  flops.expert_bytes(model, rows) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
