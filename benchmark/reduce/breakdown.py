"""The traced run's ``breakdown``: where the device's time went, and what
the loop thread was inside while the device waited.

``device_ops``: the operations that took most device time on the chip that
idled most, under their XLA names without numbers. ``idle_gaps``: every gap
between device operations on that chip, attributed to the program span the
loop thread (the thread of ``train.step``) was inside at the gap's middle;
outside any span, to the epoch turnover or log point that falls into the
same pause of the loop, else to ``loop.other``. Spans are on the monotonic
clock, the trace on ns from ``profile_start_time`` (Unix): the benchmark
noted the difference of the two clocks when it started the trace.
"""

from __future__ import annotations

import bisect


def loop_spans(spans: list) -> list:
    tids = {s["tid"] for s in spans if s["name"] == "train.step"}
    return sorted((s["start_ns"], s["end_ns"], s["name"]) for s in spans
                  if s["tid"] in tids and s["name"].startswith("train."))


def attribute(gaps: list, loop: list, marks: list, to_mono) -> dict:
    """Seconds of idle by cause. ``marks``: ``(t_ns, name)`` of epoch ends
    and log points; ``to_mono``: trace ns -> monotonic ns."""
    starts = [s for s, _, _ in loop]
    totals: dict = {}
    for a, b in gaps:
        mid = to_mono((a + b) // 2)
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and loop[i][1] >= mid:
            name = loop[i][2]
        else:
            pause = (loop[i][1] if i >= 0 else float("-inf"),
                     loop[i + 1][0] if i + 1 < len(loop) else float("inf"))
            name = next((n for t, n in marks if pause[0] <= t <= pause[1]),
                        "loop.other")
        totals[name] = totals.get(name, 0.0) + (b - a) / 1e9
    return totals


def make(ctx: dict) -> dict:
    from reduce import xplane

    trace = ctx["trace"]
    if not trace["devices"]:
        return {"device_ops": [], "idle_gaps": []}
    chip = xplane.worst(trace)
    ops = sorted(chip["op_kinds"].items(), key=lambda kv: -kv[1])[:10]
    marks = sorted([(t, "between_epochs") for t, _ in ctx["epoch_ends"]]
                   + [(p["t"], "log_point") for p in ctx["log_points"]])
    start, span = trace["start_unix_ns"], trace["trace_span"]
    if start is None or span is None:
        return {"device_ops": [[k, v / 1e9] for k, v in ops], "idle_gaps": []}
    totals = attribute(chip["gaps"], loop_spans(ctx["spans"]), marks,
                       lambda t: t + start - span[2])
    gaps = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
