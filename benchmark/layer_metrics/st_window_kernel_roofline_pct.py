"""Share of the chip's roofline that window attention's kernel reaches over
grouped heads at a 16,384-token row (``ops/flash.py`` ``unequal_attention``
with a window: 28 query heads over 4 key/value heads of 128, a causal band of
4,096 keys, eight 512-blocks wide, whose blocks outside the band are
skipped): the least time the chip could take for its forward and backward
passes (the larger of operations over the bf16 peak and bytes over the memory
bandwidth, from shapes by ``benchmark/flops/<config>.py``:
``attention_flops``, ``attention_bytes`` over the 58,722,304 pairs a row the
band lets through in each of the three window layers, the backward kernels'
recomputation not counted) over the device time under the model's
``attn.window`` scope (the kernels' three custom calls, the scaling of the
queries and the seven-fold repetition of keys and values for their groups).
The operations bound it."""

from reduce import kernel_share


def read(ctx):
    return kernel_share.share(ctx, ("attn.window",), "attention_flops",
                              "attention_bytes", "W")
