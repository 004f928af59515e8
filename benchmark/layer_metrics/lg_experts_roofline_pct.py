"""Share of the chip's roofline that the held experts' grouped products reach
with 10 experts a token and a thirty-second of them held: 8 groups of
1,024-wide SwiGLU experts of about 320 rows a row of 8,192 tokens. Read as
``moe_local_experts_roofline_pct`` reads Moonlight's (that file says how):
the least time the chip could take for them, from ``expert_flops`` and
``expert_bytes`` of ``benchmark/flops/<config>.py`` at the rows the program
counted in groups (the window's ``moe_local_assignments_total`` over its
steps), over the device time of ``moe.experts`` and the kernel XLA makes of
``ragged_dot``. At 320 rows a group the experts' own matrices are most of
the bytes and the bytes bound it; the list's dead rows (half of it at even
routing) and the backward pass's recomputation of the three forward products
count against the share."""

from layer_metrics.moe_local_experts_roofline_pct import read  # noqa: F401
