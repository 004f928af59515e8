"""What a drain costs the chips: for each ``train.drain`` (and each sampled
``loop.transform_await``) that ends inside the trace, the time in which the
chip that idled most ran nothing between the end of the ``jit_step`` run the
loop waited for (the one that ends nearest the wait's end) and the start of
the next ``jit_step`` run, while the loop refills an empty queue; median, by
the join of the two clocks that ``reduce/breakdown.py`` uses. None where no
such wait ends inside the 3 s trace (``reduce/loop_calls.py``)."""

import os
import statistics

from reduce import loop_calls, scopes, xplane


def step_runs(ctx, plane):
    """``[(start, end)]`` of the ``jit_step`` runs on ``plane``, trace ns,
    from the run's ``.xplane.pb`` (``run.py`` wipes ``out/runs`` when it
    starts, so what lies under the cell's name is this run's)."""
    from jax.profiler import ProfileData

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = scopes.newest_xplane(here, "out", "*", ctx["cell"]["name"], "*")
    if not path:
        return []
    data = ProfileData.from_file(path)
    for found in data.planes:
        if found.name != plane:
            continue
        for line in found.lines:
            if line.name == "XLA Modules":
                return sorted(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events
                    if xplane.module_base(e.name) == "jit_step")
    return []


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace["devices"] or not trace.get("trace_span") \
            or trace.get("start_unix_ns") is None:
        return None
    if loop_calls.in_flight_by_step(ctx["spans"]) is None:
        return None  # a program before PR 35: no loop.transform_await
    chip = xplane.worst(trace)
    shift = trace["start_unix_ns"] - trace["trace_span"][2]
    try:
        runs = step_runs(ctx, chip["plane"])
    except (OSError, ValueError, RuntimeError):
        return None  # a profile that cannot be read again: nothing to say
    idle = loop_calls.restart_idle_ns(ctx["spans"], runs, chip["gaps"],
                                      lambda t: t - shift)
    return statistics.median(idle) / 1e6 if idle else None
