"""Share of the chip's roofline that the state-space dual reaches
(``ops/ssd.py``): the least time the chip could take for its forward and
backward passes (the larger of their operations over the bf16 peak and their
bytes over the memory bandwidth, both from shapes by
``benchmark/flops/<config>.py``: ``ssd_flops``, ``ssd_bytes``; one read of x,
B, C, dt and one write of y each way) over the device time of everything
under the model's ``ssd.kernel`` scope. Whichever form runs is held to the
same count. The bytes bound it (3.9 ms against 3.6 at 8,192 tokens of 64
heads of 64 over 128 states, nine layers), and the share is small by
construction, as ``ssm_scan_roofline_pct`` and ``gdn_kernel_roofline_pct``
are: the kernels are bound by the vector unit's work on a head's ``[L, L]``
decay mask and by products that fill half of the matrix unit's columns,
which neither peak measures, and the states between chunks go through HBM,
which the count does not have; under ``--remat`` the recomputed forwards are
in the time and not in the counted work."""

from reduce import kernel_share


def read(ctx):
    return kernel_share.share(ctx, ("ssd.kernel",), "ssd_flops", "ssd_bytes")
