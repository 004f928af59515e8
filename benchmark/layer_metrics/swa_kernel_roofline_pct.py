"""Share of the chip's roofline that window attention's kernel reaches
(``ops/flash.py`` ``unequal_attention`` with a window: 64-wide queries and
keys, 128-wide values, a causal band of 512 whose blocks outside the band are
skipped): the least time the chip could take for its forward and backward
passes (the larger of operations over the bf16 peak and bytes over the memory
bandwidth, from shapes by ``benchmark/flops/<config>.py`` over the pairs the
band lets through) over the device time under the model's ``attn.window``
scope (the kernels' three custom calls, the scaling of the queries and the
repetition of grouped keys and values). Under ``--remat`` the recomputed
forward is in the time and not in the counted work."""

from reduce import kernel_share


def read(ctx):
    if "layer_kinds" not in ctx["cell"]["config"]["model"]:
        return None
    return kernel_share.share(ctx, ("attn.window",), "attention_flops",
                              "attention_bytes", "S")
