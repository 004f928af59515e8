"""Share of the chip's roofline that the position-free attention layer's
kernel reaches (``ops/flash.py`` ``unequal_attention``: 32 query heads over 8
key/value heads of 64, the whole causal row of 8,192 tokens): the least time
the chip could take for its forward and backward passes (the larger of their
operations over the bf16 peak and their bytes over the memory bandwidth, both
from shapes by ``benchmark/flops/<config>.py``: ``attn_flops``,
``attn_bytes``, over the causal half of the pairs, 33,558,528 a row) over the
device time under the model's ``attn.full`` scope. The operations bound it;
heads of 64 fill half of the matrix unit's columns in the scores' product."""

from reduce import kernel_share


def read(ctx):
    return kernel_share.share(ctx, ("attn.full",), "attn_flops", "attn_bytes")
