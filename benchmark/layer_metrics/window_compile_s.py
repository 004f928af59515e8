"""Seconds of XLA compilation (or loads from the compile cache) inside the
window, as the program itself saw them: ``xla.compile`` spans, recorded by
the ``jax.monitoring`` listener that ``train()`` registers
(``obs/spans.watch_xla_compiles``). The window is meant to hold none;
``window_compiles`` counts the same events from the benchmark's side."""

from reduce import spans


def read(ctx):
    if not any(s["name"] == "xla.compile" for s in ctx["spans"]):
        return None  # a program that records no compiles (start-up has some)
    return sum(spans.inside(ctx["spans"], "xla.compile",
                            ctx["window_ns"])) / 1e9
