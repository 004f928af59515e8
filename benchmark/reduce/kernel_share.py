"""A kernel's share of the chip's roofline from the device time under the
scopes the model names around it: the least time the chip could take (the
larger of operations over the bf16 peak and bytes over the memory bandwidth,
both from shapes by the configuration's functions under ``benchmark/flops/``)
over that time. None, never an error, where the program has no such scope or
the configuration no such function."""

from __future__ import annotations

from reduce import named_scopes


def share(ctx: dict, scopes: tuple, flops_name: str, bytes_name: str, *more):
    """``more`` goes to both functions after ``(model, rows, seq)``."""
    ms = named_scopes.per_step_ms(ctx, *scopes)
    flops, peaks = ctx["flops"], ctx["peaks"]
    shapes = ctx["step_shapes"] or ctx["all_step_shapes"]
    if not ms or not peaks or not shapes or not hasattr(flops, flops_name):
        return None
    model = ctx["cell"]["config"]["model"]
    rows, seq = (int(n) for n in shapes[0]["input_ids"][:2])
    rows //= ctx["chips"]
    least_s = max(
        getattr(flops, flops_name)(model, rows, seq, *more)
        / peaks["bf16_flops_per_s"],
        getattr(flops, bytes_name)(model, rows, seq, *more)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)
