"""Share of the chip's roofline that the attention kernel for heads of
unequal width reaches (``ops/flash.py`` ``unequal_attention``: 192-wide
queries and keys, 128-wide values, causal): the least time the chip could
take for its forward and backward passes (the larger of their operations
over the bf16 peak and their bytes over the memory bandwidth, both from
shapes by ``benchmark/flops/<config>.py``: ``attention_flops``,
``attention_bytes``, over the causal half of the pairs, the backward
kernels' recomputation not counted) over the device time of everything under
the model's ``mla.kernel`` scope, which holds the kernels' three custom calls
and the scaling of the queries before them. The operations bound it (31 ms
against 4 at 8,192 tokens), so it is the share of 197 TFLOP/s."""

from reduce import named_scopes


def read(ctx):
    ms = named_scopes.per_step_ms(ctx, "mla.kernel")
    flops, peaks = ctx["flops"], ctx["peaks"]
    shapes = ctx["step_shapes"] or ctx["all_step_shapes"]
    if not ms or not peaks or not shapes or not hasattr(
            flops, "attention_flops"):
        return None
    model = ctx["cell"]["config"]["model"]
    rows, seq = (int(n) for n in shapes[0]["input_ids"][:2])
    rows //= ctx["chips"]
    least_s = max(
        flops.attention_flops(model, rows, seq) / peaks["bf16_flops_per_s"],
        flops.attention_bytes(model, rows, seq) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
