"""Share of the chip's roofline that window attention's kernel reaches over
72 query heads on 8 key/value heads of 128 at an 8,192-token row
(``ops/flash.py`` ``unequal_attention`` with a window: a causal band of 512
keys, one 512-block wide, whose blocks outside the band are skipped): the
least time the chip could take for its forward and backward passes (the
larger of operations over the bf16 peak and bytes over the memory bandwidth,
from shapes by ``benchmark/flops/<config>.py``: ``attention_flops``,
``attention_bytes`` over the 4,063,488 pairs a row and head the band lets
through in each of the three window layers, the backward kernels'
recomputation and ``--remat``'s second forward not counted) over the device
time under the model's ``attn.window`` scope (the kernels' three custom
calls, the scaling of the queries and the nine-fold repetition of keys and
values for their groups). The operations bound it (6.8 ms a step against
3.7 for the bytes): with a band one block wide, the kernel's fixed costs a
block and the repetition are what the share shows."""

from reduce import kernel_share


def read(ctx):
    return kernel_share.share(ctx, ("attn.window",), "attention_flops",
                              "attention_bytes", "W")
