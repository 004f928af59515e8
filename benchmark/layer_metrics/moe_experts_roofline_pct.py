"""Share of the chip's roofline that the grouped expert products reach: the
least time the chip could take for them (the larger of their operations over
the bf16 peak and their bytes over the memory bandwidth, both from shapes by
``benchmark/flops/<config>.py``: ``expert_flops``, ``expert_bytes``) over the
device time of everything between the gather and the weighted sum, forward
and backward: the kernel XLA makes of ``ragged_dot`` (found by its name, it
carries no scope), the layout copies of the experts' matrices, and what is
under ``moe.experts`` (their casts to bf16, SiLU and the product with the up
projection). At this cell's shape the operations bound it (12.6 ms against
7.4 ms of bytes), so it is the share of 197 TFLOP/s."""

from reduce import named_scopes


def read(ctx):
    ms = named_scopes.per_step_ms(ctx, "moe.experts",
                                  also=named_scopes.GROUPED_PRODUCTS)
    flops, peaks = ctx["flops"], ctx["peaks"]
    shapes = ctx["step_shapes"] or ctx["all_step_shapes"]
    if not ms or not peaks or not shapes or not hasattr(flops, "expert_flops"):
        return None
    model = ctx["cell"]["config"]["model"]
    rows, seq = shapes[0]["input_ids"][:2]
    tokens = int(rows) * int(seq) // ctx["chips"]
    least_s = max(
        flops.expert_flops(model, tokens) / peaks["bf16_flops_per_s"],
        flops.expert_bytes(model, tokens) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
