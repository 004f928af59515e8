"""Seconds the process spent tracing, lowering and compiling (or loading from
the compile cache) before it entered ``train()``: the union, thread by thread,
of the ``jax.trace``, ``jax.lower`` and ``xla.compile`` spans that end before
the first ``startup.devices`` begins. In the benchmark these are the model
check's programs. 0.0 where the program records such spans and none lies
there."""

from reduce import startup


def read(ctx):
    spans = ctx["spans"]
    first = startup.entry(spans)
    if first is None or not any(s["name"] == "jax.trace" for s in spans):
        return None  # a program whose listener starts inside train()
    return startup.union_s([s for s in spans if s["name"] in startup.KINDS
                            and s["end_ns"] <= first["start_ns"]])
