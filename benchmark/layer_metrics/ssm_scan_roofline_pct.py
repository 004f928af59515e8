"""Share of the chip's roofline that the selective scan reaches
(``ops/scan.py``): the least time the chip could take for its forward and
backward passes (the larger of their operations over the bf16 peak and their
bytes over the memory bandwidth, both from shapes by
``benchmark/flops/<config>.py``: ``scan_flops``, ``scan_bytes``; one read of
x, dt, B, C and one write of the sums each way) over the device time of
everything under the model's ``ssm.scan`` scope. The bytes bound it (2.3 ms
against 0.1 at 8,192 tokens of 5,120 channels), and the share is small by
construction: the kernel is bound by the vector unit and by the latency of a
recurrence over tokens, which neither peak measures; under ``--remat`` the
recomputed forward is in the time and not in the counted work."""

from reduce import kernel_share


def read(ctx):
    return kernel_share.share(ctx, ("ssm.scan",), "scan_flops", "scan_bytes")
