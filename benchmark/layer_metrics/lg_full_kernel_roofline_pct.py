"""Share of the chip's roofline that the full attention layers' kernel
reaches (``ops/flash.py`` ``unequal_attention``: 48 query heads over 8
key/value heads of 128, the whole causal row of 8,192 tokens, in published
layers 0 and 4): as ``lg_window_kernel_roofline_pct``, over the causal half
of the pairs (33,558,528 a row and head) and the device time under the
model's ``attn.full`` scope. The operations bound it."""

from reduce import kernel_share


def read(ctx):
    return kernel_share.share(ctx, ("attn.full",), "attention_flops",
                              "attention_bytes", "F")
