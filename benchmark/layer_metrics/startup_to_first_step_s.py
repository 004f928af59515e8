"""Seconds from ``train()``'s entry (the start of its first phase,
``startup.devices``) to the end of the first ``train.step``, which holds the
step's compile or its load from the compile cache: ``train()``'s own part of
``setup_s``, measured from inside."""


def read(ctx):
    entry = [s["start_ns"] for s in ctx["spans"]
             if s["name"] == "startup.devices"]
    steps = [s["end_ns"] for s in ctx["spans"] if s["name"] == "train.step"]
    if not entry or not steps:
        return None
    return (min(steps) - min(entry)) / 1e9
