"""Share of the chip's roofline that full and cross attention's kernel
reaches (``ops/flash.py`` ``unequal_attention``: 64-wide queries and keys,
128-wide values, causal; the cross layers over the full layer's keys and
values): as ``swa_kernel_roofline_pct``, over the causal half of the pairs
and the device time under the model's ``attn.full`` and ``attn.cross``
scopes."""

from reduce import kernel_share


def read(ctx):
    if "layer_kinds" not in ctx["cell"]["config"]["model"]:
        return None
    return kernel_share.share(ctx, ("attn.full", "attn.cross"),
                              "attention_flops", "attention_bytes", "F* X")
