"""Model FLOP/s utilisation of the train step while it runs: the operations
forward and backward need for the traced steps (from shapes, by the
configuration's function under ``benchmark/flops/``; per chip) over their
device time, over the chip's bf16 peak. Where the run used several step
shapes, each compiled shape is matched to its operations by rank (a larger
grid takes longer); if the trace does not hold every shape, the window's mean
operations per step stand in."""

import statistics

from reduce import xplane


def read(ctx):
    by_module = xplane.module_durations(ctx["trace"], "jit_step")
    if not by_module or ctx["flops"] is None or not ctx["peaks"]:
        return None
    model = ctx["cell"]["config"]["model"]
    count = ctx["flops"].step_flops

    def distinct(shapes):
        return sorted({tuple(sorted(s.items())) for s in shapes},
                      key=lambda s: count(model, dict(s)))

    used = distinct(ctx["all_step_shapes"])
    if not used:
        return None
    ranked = sorted(by_module, key=lambda n: statistics.median(by_module[n]))
    if len(ranked) == len(used):
        total = sum(count(model, dict(shape)) * len(by_module[name])
                    for name, shape in zip(ranked, used))
    else:
        in_window = ctx["step_shapes"] or ctx["all_step_shapes"]
        mean = statistics.fmean(count(model, s) for s in in_window)
        total = mean * sum(len(v) for v in by_module.values())
    seconds = sum(d for v in by_module.values() for d in v) / 1e9
    return 100.0 * total / ctx["chips"] / seconds / \
        ctx["peaks"]["bf16_flops_per_s"]
