"""Start-up as the program's own spans tell it (PR 51): ``train()``'s entry
(the start of its first ``startup.devices`` phase), the first ``train.step``,
and what the process traced, lowered and compiled (``jax.trace``,
``jax.lower``, ``xla.compile`` spans, ``obs/spans.watch_xla_compiles``). The
spans are ``reduce/spans.read``'s records. Start-up lies outside the device
trace; everything here is on the spans' clock."""

from __future__ import annotations

from reduce import xplane

KINDS = ("jax.trace", "jax.lower", "xla.compile")


def entry(spans: list):
    """The first ``startup.devices`` phase, or None."""
    found = [s for s in spans if s["name"] == "startup.devices"]
    return min(found, key=lambda s: s["start_ns"]) if found else None


def first_step(spans: list):
    """The first ``train.step`` phase after entry, or None without either (a
    span file cut from a run's middle has steps and no start-up)."""
    first = entry(spans)
    found = [s for s in spans if s["name"] == "train.step"
             and first is not None and s["start_ns"] >= first["start_ns"]]
    return min(found, key=lambda s: s["start_ns"]) if found else None


def phases_s(spans: list, names: tuple):
    """Seconds of the named phases between entry and the first step's start
    (a phase may occur more than once there), or None without those two."""
    first, step = entry(spans), first_step(spans)
    if step is None:
        return None
    return sum(s["end_ns"] - s["start_ns"] for s in spans
               if s["name"] in names and s["start_ns"] >= first["start_ns"]
               and s["start_ns"] < step["start_ns"]) / 1e9


def cut(spans: list, names: tuple, lo: int, hi: int) -> list:
    """The named spans that overlap ``[lo, hi)``, each cut to it."""
    return [dict(s, start_ns=max(s["start_ns"], lo),
                 end_ns=min(s["end_ns"], hi))
            for s in spans
            if s["name"] in names and s["end_ns"] > lo and s["start_ns"] < hi]


def union_s(spans: list) -> float:
    """Seconds the spans cover: the union of their intervals on each thread,
    summed over threads. A ``jit`` met inside another's trace is traced
    inside it, so durations of one kind nest and may not be added."""
    by_thread: dict = {}
    for s in spans:
        by_thread.setdefault(s["tid"], []).append((s["start_ns"], s["end_ns"]))
    return sum(end - start for own in by_thread.values()
               for start, end in xplane.union(own)) / 1e9


def to_edge(ctx: dict, names: tuple):
    """The named spans from entry to the window's first edge, cut to it; None
    where the program records no such span anywhere (one from before PR 51
    has ``xla.compile`` alone, and only from inside ``train()``)."""
    spans = ctx["spans"]
    first = entry(spans)
    if first is None or not any(s["name"] in names for s in spans):
        return None
    return cut(spans, names, first["start_ns"], ctx["window_ns"][0])


def compiles_to_edge(ctx: dict):
    """``xla.compile`` spans from entry to the edge, or None where none of
    the file's says how the cache answered (``cache``: hit, miss, off)."""
    found = to_edge(ctx, ("xla.compile",))
    if found is None or not any(
            "cache" in s["args"] for s in ctx["spans"]
            if s["name"] == "xla.compile"):
        return None
    return found
