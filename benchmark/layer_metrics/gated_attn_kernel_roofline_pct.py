"""Share of the chip's roofline that the attention kernel over grouped heads
of 256 reaches (``ops/flash.py`` ``unequal_attention`` for 16 query heads
over 2 key/value heads, causal): the least time the chip could take for its
forward and backward passes (the larger of their operations over the bf16
peak and their bytes over the memory bandwidth, both from shapes by
``benchmark/flops/<config>.py``: ``attention_flops``, ``attention_bytes``,
over the causal half of the pairs, the backward kernels' recomputation not
counted) over the device time of everything under the model's ``attn.kernel``
scope, which holds the kernels' three custom calls, the scaling of the
queries and the repetition of keys and values for their groups. The
operations bound it (8.4 ms against 0.6 a row of 8,192 tokens)."""

from reduce import kernel_share


def read(ctx):
    return kernel_share.share(ctx, ("attn.kernel",), "attention_flops",
                              "attention_bytes")
