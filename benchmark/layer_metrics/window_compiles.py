"""Compilations (and loads from the compile cache) inside the measured
window, counted from the benchmark's side by JAX's own compile events. The
window is meant to hold none."""


def read(ctx):
    return float(ctx["compiles_in_window"])
