"""Share of the chip's roofline that the position-free full attention
layer's kernel reaches (``ops/flash.py`` ``unequal_attention``: 28 query
heads over 4 key/value heads of 128, the whole causal row of 16,384 tokens):
as ``st_window_kernel_roofline_pct``, over the causal half of the pairs
(134,225,920 a row) and the device time under the model's ``attn.full``
scope. The operations bound it."""

from reduce import kernel_share


def read(ctx):
    return kernel_share.share(ctx, ("attn.full",), "attention_flops",
                              "attention_bytes", "N")
