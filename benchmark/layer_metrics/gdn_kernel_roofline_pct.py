"""Share of the chip's roofline that the gated delta rule reaches
(``ops/delta.py``): the least time the chip could take for its forward and
backward passes (the larger of their operations over the bf16 peak and their
bytes over the memory bandwidth, both from shapes by
``benchmark/flops/<config>.py``: ``delta_flops``, what no chunking avoids, a
token and value head reading its state twice and updating it once;
``delta_bytes``, one read of q, k, v, g, beta and one write of o each way with
the gradients) over the device time of everything under the model's
``gdn.kernel`` scope. The bytes bound it (0.66 ms a layer and row of 8,192
tokens against 0.39), and the share is small by construction, as
``ssm_scan_roofline_pct`` is: neither peak measures 128 dependent chunks, the
chunks' own products (the triangular inverse, the scores inside a chunk) are
in the time and not in the counted work, and so is the backward pass's
second forward."""

from reduce import kernel_share


def read(ctx):
    return kernel_share.share(ctx, ("gdn.kernel",), "delta_flops",
                              "delta_bytes")
