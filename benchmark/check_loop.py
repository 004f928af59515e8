#!/usr/bin/env python3
"""Checks the five readers of the loop thread's calls (PR 35) on hand-made
spans small enough to work out by eye, and on a span file of a program from
before the calls had spans (the recorded four-chip fixture), where each has
to read None and not raise.

    python3 benchmark/check_loop.py        # exit 0 and "loop ok", or the faults

The hand-made run: one loop thread, two log intervals of five steps, a drain
after each. A step is 1 ms of ``train.loader``, a ``train.bookkeep`` with
``loop.rng_split`` (1 ms), the ``train.step`` dispatch (2 ms; 12 ms where the
step began with two or more in flight: 10 ms of wait), ``loop.loss_sum`` (0.2
ms) and ``loop.stats_add`` (0.3 ms); the interval's third step has a
``train.transform`` of 7 ms before it, 4 ms of it the sampled await. So in a
window of two intervals the loop waits 2 x (3 x 10 + 4) = 68 ms inside
dispatches, and ``loop.rng_split`` + ``loop.loss_sum`` + ``loop.stats_add``
is 1.5 ms a step.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
MS = 1_000_000
NAMES = ("dispatch_blocked_pct", "loop_own_work_pct", "aux_dispatch_ms",
         "host_starved_steps_pct", "drain_restart_idle_ms")


def hand_made(in_flight: bool = True) -> tuple:
    """``(spans, window_ns, {phase: ns})`` of the run in the docstring."""
    spans, t, step = [], 0, 0
    totals: dict = {}

    def add(name, dur, **args):
        nonlocal t
        spans.append({"name": name, "start_ns": t, "end_ns": t + dur,
                      "tid": 7, "args": args})
        if name.startswith("train."):
            totals[name] = totals.get(name, 0) + dur
            if name in ("train.bookkeep", "train.transform"):
                return t  # its calls lie inside it
        t += dur
        return t

    def call(name, at, dur, **args):
        spans.append({"name": name, "start_ns": at, "end_ns": at + dur,
                      "tid": 7, "args": args})

    lo = t
    for _ in range(2):
        for flight in range(5):
            add("train.loader", 1 * MS, step=step)
            if flight == 2:
                at = add("train.transform", 7 * MS, step=step)
                call("loop.transform_await", at + 3 * MS, 4 * MS, step=step)
                t += 7 * MS
            at = add("train.bookkeep", 1 * MS)
            call("loop.rng_split", at, 1 * MS, step=step)
            t += 1 * MS
            args = {"in_flight": flight, "in_flight_after": flight + 1} \
                if in_flight else {}
            add("train.step", (12 if flight >= 2 else 2) * MS, step=step,
                **args)
            at = add("train.bookkeep", 1 * MS)
            call("loop.loss_sum", at, MS // 5, step=step)
            call("loop.stats_add", at + MS // 5, 3 * MS // 10, step=step)
            t += 1 * MS
            step += 1
        add("train.drain", 20 * MS, step=step - 1)
        add("train.log", 1 * MS, step=step)
    return spans, (lo, t), totals


def context(spans, window, counters=None) -> dict:
    return {"spans": spans, "window_ns": window,
            "window_s": (window[1] - window[0]) / 1e9,
            "counters": counters or {}, "trace": None,
            "cell": {"name": "no-such-cell"}}


def near(got, want) -> bool:
    return got is not None and abs(got - want) <= 1e-9 * max(abs(want), 1)


def main() -> int:
    import run
    from reduce import loop_calls, xplane

    readers = {n: run.load_module("layer_metrics", n) for n in NAMES}
    faults = []
    spans, window, totals = hand_made()
    span_ns = window[1] - window[0]
    ctx = context(spans, window, {"train_steps_dispatched_total": 10.0,
                                  "train_dispatch_starved_total": 1.0})
    blocked = 2 * (3 * 10 + 4) * MS
    busy = sum(totals[n] for n in ("train.step", "train.transform",
                                   "train.bookkeep", "train.log"))
    want = {"dispatch_blocked_pct": 100.0 * blocked / span_ns,
            "loop_own_work_pct": 100.0 * (busy - blocked) / span_ns,
            "aux_dispatch_ms": 1.5, "host_starved_steps_pct": 10.0}
    got = {n: readers[n].read(ctx) for n in NAMES}
    for name, value in want.items():
        if not near(got[name], value):
            faults.append(f"hand-made run: {name} reads {got[name]}, want "
                          f"{value}")
    if got["drain_restart_idle_ms"] is not None:
        faults.append("hand-made run without a trace: drain_restart_idle_ms "
                      f"reads {got['drain_restart_idle_ms']}")
    whole = 100.0 * (totals["train.loader"] + totals["train.drain"]) \
        / span_ns + got["dispatch_blocked_pct"] + got["loop_own_work_pct"]
    if not near(whole, 100.0):
        faults.append(f"input wait + device wait + blocked + own work = "
                      f"{whole}, want 100")
    # the unblocked cost is the median over steps that began with at most
    # one in flight: the dispatches that took 12 ms are not in it
    base = loop_calls.unblocked_ns(loop_calls.dispatches(spans), window)
    if base.get("train.step") != 2 * MS or base.get("train.transform"):
        faults.append(f"unblocked cost of the hand-made run: {base}")
    # a window of one interval: half of it
    half = (window[0], spans[[s["name"] for s in spans].index("train.log")]
            ["end_ns"])
    got_half = readers["dispatch_blocked_pct"].read(context(spans, half))
    if not near(got_half, 100.0 * (blocked // 2) / (half[1] - half[0])):
        faults.append(f"one interval: dispatch_blocked_pct reads {got_half}")
    # no step dispatched in the window, counters at 0: nothing to read
    if readers["host_starved_steps_pct"].read(context(spans, window, {
            "train_steps_dispatched_total": 0.0})) is not None:
        faults.append("host_starved_steps_pct of no steps is not None")

    # a drain that ends at 215 in a trace whose step runs are [0, 100),
    # [110, 210), [260, 360): the chip ran nothing for 20 + 25 of the 50 ns
    # between the second run's end and the third's start (two tiny programs
    # ran in it); a wait that ends before the first run's end or after the
    # last one's start has no restart inside the trace
    runs = [(0, 100), (110, 210), (260, 360)]
    gaps = [(100, 110), (210, 230), (235, 260)]
    waits = [{"name": "train.drain", "start_ns": 1150, "end_ns": 1215,
              "tid": 7, "args": {}},
             {"name": "loop.transform_await", "start_ns": 1090,
              "end_ns": 1104, "tid": 7, "args": {}},
             {"name": "train.drain", "start_ns": 1300, "end_ns": 1340,
              "tid": 7, "args": {}},
             {"name": "train.drain", "start_ns": 1010, "end_ns": 1050,
              "tid": 7, "args": {}},
             {"name": "train.log", "start_ns": 1215, "end_ns": 1220,
              "tid": 7, "args": {}}]
    found = loop_calls.restart_idle_ns(waits, runs, gaps,
                                       lambda t: t - 1000)
    if found != [45, 10]:
        faults.append(f"restart idle of the hand-made trace: {found}, want "
                      "[45, 10]")
    if loop_calls.restart_idle_ns(waits, runs[:1], gaps, lambda t: t):
        faults.append("restart idle with one run in the trace is not empty")
    # idle by overlap: a gap over the end of a bookkeep's call and the start
    # of the next phase is split between them
    by_phase, by_call = loop_calls.overlap_by_call(
        [(spans[2]["start_ns"] + MS // 2, spans[3]["start_ns"] + MS)],
        spans, lambda t: t)
    if by_phase != {"train.bookkeep": 0.0005, "train.step": 0.001} \
            or by_call.get("train.bookkeep") != {"loop.rng_split": 0.0005,
                                                "(rest)": 0.0}:
        faults.append(f"idle by overlap: {by_phase} {by_call}")

    # a program from before PR 35: the recorded fixture's span file, and the
    # hand-made run without in_flight
    fx = xplane.load_fixture(os.path.join(HERE, "fixtures",
                                          "resnet50_4chip_v5e.json.gz"))
    old = fx["spans"]
    edges = (min(s["start_ns"] for s in old), max(s["end_ns"] for s in old))
    bare = hand_made(in_flight=False)
    for tag, ctx in (("fixture", context(old, edges)),
                     ("no in_flight", context(bare[0], bare[1]))):
        for name in NAMES:
            try:
                value = readers[name].read(ctx)
            except Exception as e:  # a reader may never raise
                value = f"raised {e!r}"
            if value is not None:
                faults.append(f"{tag}: {name} reads {value}, want None")
    for fault in faults:
        print("FAULT:", fault)
    print("loop ok" if not faults else f"{len(faults)} fault(s)")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
