"""The loop thread's calls inside its phases, and its steps in flight.

Since PR 35 the program opens an ordinary span around each call inside the
three catch-all phases of ``trainer._train_loop`` (``loop.rng_split``,
``loop.loss_sum``, ``loop.stats_add``, ``loop.cursor`` in
``train.bookkeep``; ``loop.transform_dispatch`` and
``loop.transform_await`` in ``train.transform``; ``loop.log_entry``,
``loop.log_lr``, ``loop.stats_fetch``, ``loop.log_write`` in ``train.log``),
each with the ``step`` it belongs to, and the ``train.step`` phase carries
``in_flight``: the steps still running or queued when its dispatch began,
counted by ``is_ready()`` without a wait. A span file without ``in_flight``
(a program before PR 35) reads as None everywhere here.

A *dispatch* is a call that hands the device a program and returns: the
``train.step`` phase, ``train.transform`` less the sampled await inside it,
and the three small programs of ``train.bookkeep``. The runtime holds only
so many programs in flight, and a dispatch into a full queue returns when a
slot is free: the loop waiting for the device inside a dispatch. A name's
*unblocked cost* is the median of its dispatches in steps that began with at
most one step in flight (the first two after the loop emptied the queue
itself); what a dispatch of a step that began with two or more takes beyond
that is wait.
"""

from __future__ import annotations

import bisect
import statistics

AUX = ("loop.rng_split", "loop.loss_sum", "loop.stats_add")
DISPATCHES = ("train.step", "train.transform") + AUX
AWAIT = "loop.transform_await"
BUCKETS = ("train.bookkeep", "train.log", "train.transform")
BUSY = ("train.step",) + BUCKETS  # the phases ``loop_busy_pct`` sums
EMPTYING = ("train.drain", AWAIT)  # the loop waits until the queue is empty


def loop_thread(spans: list):
    tids = {s["tid"] for s in spans if s["name"] == "train.step"}
    return tids.pop() if len(tids) == 1 else None


def in_flight_by_step(spans: list):
    """``{step: in_flight}`` from the ``train.step`` phases; None where none
    carries it."""
    found = {s["args"]["step"]: s["args"]["in_flight"] for s in spans
             if s["name"] == "train.step" and "in_flight" in s["args"]
             and "step" in s["args"]}
    return found or None


def dispatches(spans: list):
    """``[(name, step, start_ns, duration_ns, in_flight)]`` of every
    dispatch of the loop thread whose step is known, by start; None on a
    span file without ``in_flight``."""
    flight = in_flight_by_step(spans)
    if flight is None:
        return None
    awaited: dict = {}
    for s in spans:
        if s["name"] == AWAIT:
            step = s["args"].get("step")
            awaited[step] = awaited.get(step, 0) + s["end_ns"] - s["start_ns"]
    out = []
    for s in spans:
        step = s["args"].get("step")
        if s["name"] not in DISPATCHES or step not in flight:
            continue
        dur = s["end_ns"] - s["start_ns"]
        if s["name"] == "train.transform":
            dur -= awaited.get(step, 0)
        out.append((s["name"], step, s["start_ns"], max(dur, 0),
                    flight[step]))
    return sorted(out, key=lambda d: d[2])


def unblocked_ns(calls: list, window_ns: tuple = None) -> dict:
    """``{name: ns}``: the median dispatch of each name over the steps that
    began with at most one step in flight; those that start in the window
    where it has any, else the run's."""
    by_name: dict = {}
    inside: dict = {}
    for name, _, start, dur, flight in calls:
        if flight <= 1:
            by_name.setdefault(name, []).append(dur)
            if window_ns and window_ns[0] <= start < window_ns[1]:
                inside.setdefault(name, []).append(dur)
    return {name: statistics.median(inside.get(name, v))
            for name, v in by_name.items()}


def blocked_ns(spans: list, window_ns: tuple):
    """ns of the window the loop thread waited for the device inside a
    dispatch: for every dispatch that starts in the window, in a step that
    began with two or more in flight, its time beyond the name's unblocked
    cost; and the whole of every sampled await. None without ``in_flight``
    or where no step began with at most one in flight (no unblocked cost to
    hold the others to)."""
    calls = dispatches(spans)
    if not calls:
        return None
    base = unblocked_ns(calls, window_ns)
    if "train.step" not in base:
        return None
    lo, hi = window_ns
    total = 0
    for name, _, start, dur, flight in calls:
        if lo <= start < hi and flight >= 2 and name in base:
            total += max(dur - base[name], 0)
    for s in spans:
        if s["name"] == AWAIT and s["end_ns"] > lo and s["start_ns"] < hi:
            total += min(s["end_ns"], hi) - max(s["start_ns"], lo)
    return total


def shares_of_run(ctx: dict):
    """``(blocked, busy)`` as % of the run's window, read once and kept in
    ``ctx``: the wait inside dispatches and the four ``BUSY`` phases."""
    if "_loop_shares" not in ctx:
        from reduce import spans as span_reader

        blocked = blocked_ns(ctx["spans"], ctx["window_ns"])
        window = ctx["window_s"] * 1e9
        if blocked is None or not window:
            ctx["_loop_shares"] = None
        else:
            busy = sum(sum(span_reader.inside(ctx["spans"], name,
                                              ctx["window_ns"]))
                       for name in BUSY)
            ctx["_loop_shares"] = (100.0 * blocked / window,
                                   100.0 * busy / window)
    return ctx["_loop_shares"]


def aux_ns_per_step(spans: list, window_ns: tuple):
    """``[ns]``: ``loop.rng_split`` + ``loop.loss_sum`` + ``loop.stats_add``
    of each step in the window that began with at most one in flight."""
    calls = dispatches(spans)
    if not calls:
        return None
    lo, hi = window_ns
    steps: dict = {}
    for name, step, start, dur, flight in calls:
        if name in AUX and flight <= 1 and lo <= start < hi:
            steps.setdefault(step, {})[name] = dur
    return [sum(parts.values()) for parts in steps.values()
            if len(parts) == len(AUX)]


def restart_idle_ns(spans: list, runs: list, gaps: list, to_trace) -> list:
    """For each ``train.drain`` or ``loop.transform_await`` that ends inside
    the trace: ns in which the chip ran nothing between the end of the
    ``jit_step`` run that ends nearest the wait's end (the step it waited
    for) and the start of the next run. ``runs``: the chip's ``jit_step``
    runs ``(start, end)`` sorted, ``gaps`` its idle gaps, both in trace ns;
    ``to_trace``: monotonic ns -> trace ns."""
    if len(runs) < 2:
        return []
    ends = [r[1] for r in runs]
    gap_starts = [g[0] for g in gaps]
    out = []
    for s in spans:
        if s["name"] not in EMPTYING:
            continue
        end = to_trace(s["end_ns"])
        if not runs[0][1] <= end <= runs[-1][0]:
            continue  # outside the trace, or no run after it
        i = bisect.bisect_left(ends, end)
        if i > 0 and (i == len(ends) or end - ends[i - 1] <= ends[i] - end):
            i -= 1
        if i + 1 >= len(runs):
            continue
        a, b = runs[i][1], runs[i + 1][0]
        idle = 0
        k = max(bisect.bisect_right(gap_starts, a) - 1, 0)
        while k < len(gaps) and gaps[k][0] < b:
            idle += max(min(gaps[k][1], b) - max(gaps[k][0], a), 0)
            k += 1
        out.append(idle)
    return out


def overlap_by_call(gaps: list, spans: list, to_mono) -> tuple:
    """Seconds of a chip's idle by what the loop thread was inside, by
    overlap and not by a gap's middle: ``({phase: s}, {phase: {call: s}})``
    where a call is a ``loop.*`` span under the phase and ``(rest)`` is the
    phase's idle under no such span."""
    tid = loop_thread(spans)
    phases = sorted((s["start_ns"], s["end_ns"], s["name"]) for s in spans
                    if s["tid"] == tid and s["name"].startswith("train."))
    calls = sorted((s["start_ns"], s["end_ns"], s["name"]) for s in spans
                   if s["tid"] == tid and s["name"].startswith("loop."))
    by_phase: dict = {}
    by_call: dict = {}

    def spread(into, items, starts, a, b, key):
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(items) and items[i][0] < b:
            cut = min(items[i][1], b) - max(items[i][0], a)
            if cut > 0:
                k = key(items[i])
                into[k] = into.get(k, 0.0) + cut / 1e9
            i += 1

    phase_starts = [p[0] for p in phases]
    call_starts = [c[0] for c in calls]
    for a, b in gaps:
        a, b = to_mono(a), to_mono(b)
        spread(by_phase, phases, phase_starts, a, b, lambda p: p[2])
        i = max(bisect.bisect_right(phase_starts, a) - 1, 0)
        while i < len(phases) and phases[i][0] < b:
            lo, hi = max(phases[i][0], a), min(phases[i][1], b)
            if hi > lo:
                inner = by_call.setdefault(phases[i][2], {})
                before = sum(inner.values())
                spread(inner, calls, call_starts, lo, hi, lambda c: c[2])
                rest = (hi - lo) / 1e9 - (sum(inner.values()) - before)
                inner["(rest)"] = inner.get("(rest)", 0.0) + rest
            i += 1
    return by_phase, by_call
