#!/usr/bin/env python3
"""How well the two clocks of a traced run are joined, measured.

    python3 benchmark/run.py --workload <cell> ... --trace 1   # its files stay until the next run
    python3 benchmark/check_join.py benchmark/out/runs/<cell>/seed<n>-trace1

The program's spans are on the monotonic clock, the device trace on ns from
``profile_start_time`` (Unix); ``run.py`` joins them by the wall-minus-
monotonic offset it notes when it starts the trace (``trace_span.json``), and
``reduce/breakdown.py`` attributes idle gaps of tens of microseconds by that
join. This takes every idle gap over 1 ms, on the chip that idled most, that
ends where a ``jit_step`` run begins (or the rng split's two tiny programs
just before it). The chip had nothing queued, so that run began as soon as
its dispatch reached it: after the start of the
``train.step`` phase that dispatched it (the last one that begins before the
gap's end) and, the chip waiting, before or just after that phase's end. So
the lag from the phase's start to the gap's end is how far the join could
put the device too early before a run seemed to begin ahead of its own
dispatch and paired with the phase before (a lag of a whole loop period and
a gap that ends outside any ``train.step``), and the time left to the
phase's end is how far it could put it too late. Prints minimum, median,
maximum and count of both, and how much of the traced span the phases of the
loop thread and of the placement thread cover (they tile, so all of it).

On four chips that pairing says little: every step there ends with 1.3 to
1.8 ms in which no chip runs anything, whatever the host does (seen on the
v5e, PR 24), so most such runs were dispatched eight steps earlier. The
join's error is bounded from both sides by each ``train.drain`` inside the
trace instead: the ``float(loss)`` returns when the device has finished the
step's run, so the drain cannot end before the ``jit_step`` run that ends
nearest to it, and with the queue then empty the next run cannot start before
the next ``train.step`` phase does. Both lags are signed; a negative one is a
wrong join. Exit 1 on a negative lag or a hole in the tiling.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
GAP_NS = 1_000_000
# how near the gap's end a jit_step run has to begin: a program's first
# operation starts 7 us after the program (seen on the v5e), and the rng
# split's two tiny programs may run between the gap and the step
NEAR_NS = 200_000


def lags(events: dict, spans: list, anchor_ns: int) -> list:
    """``(ns from the dispatching train.step phase's start to the run's
    start, ns from the run's start to that phase's end)`` for each
    ``jit_step`` run that ends an idle gap over 1 ms."""
    from reduce import xplane

    trace = xplane.reduce_events(events)
    phases = sorted((s["start_ns"], s["end_ns"]) for s in spans
                    if s["name"] == "train.step")
    if not trace["devices"] or not phases:
        return []
    chip = xplane.worst(trace)
    runs = sorted(s for n, s, _ in events["devices"][chip["plane"]]
                  ["XLA Modules"] if xplane.module_base(n) == "jit_step")
    to_mono = events["start_unix_ns"] - anchor_ns
    starts = [p[0] for p in phases]
    out = []
    for start, end in chip["gaps"]:
        i = bisect.bisect_left(runs, end - NEAR_NS)
        # the trace's first gap is the chip's late start, not a wait
        if end - start < GAP_NS or start == trace["window"][0] \
                or i == len(runs) or runs[i] > end + NEAR_NS:
            continue
        at = runs[i] + to_mono
        j = bisect.bisect_right(starts, at) - 1
        if j >= 0:
            out.append((at - phases[j][0], phases[j][1] - at))
    return out


def drain_anchors(events: dict, spans: list, anchor_ns: int) -> tuple:
    """Per ``train.drain`` phase inside the trace and per chip: ns from the
    end of the ``jit_step`` run that ends nearest the drain's end to that
    end, and ns from the start of the next ``train.step`` phase to the start
    of the next run."""
    from reduce import xplane

    to_mono = events["start_unix_ns"] - anchor_ns
    steps = sorted(s["start_ns"] for s in spans if s["name"] == "train.step")
    released, started = [], []
    for lines in events["devices"].values():
        runs = sorted((s + to_mono, s + d + to_mono)
                      for n, s, d in lines.get("XLA Modules", [])
                      if xplane.module_base(n) == "jit_step")
        if len(runs) < 3:
            continue
        for drain in spans:
            end = drain["end_ns"]
            if drain["name"] != "train.drain" \
                    or not runs[1][1] <= end <= runs[-2][1]:
                continue  # the trace's first and last run are cut
            i = min(range(len(runs)), key=lambda k: abs(runs[k][1] - end))
            j = bisect.bisect_left(steps, end)
            released.append(end - runs[i][1])
            if i + 1 < len(runs) and j < len(steps):
                started.append(runs[i + 1][0] - steps[j])
    return released, started


def coverage(spans: list, prefixes: tuple, lo: int, hi: int) -> tuple:
    """Share of its own life inside ``[lo, hi]`` that a thread's spans named
    ``prefixes...`` cover, and the largest hole or overlap in ns between
    neighbours within one life. A placement thread lives for one epoch and
    the next may get its thread id: a new life begins at the phase before
    ``placement.h2d`` of batch 0."""
    threads: dict = {}
    for s in sorted(spans, key=lambda s: s["start_ns"]):
        if s["name"].startswith(prefixes) and s["end_ns"] > lo \
                and s["start_ns"] < hi:
            threads.setdefault(s["tid"], []).append(s)
    covered = life = seam = 0
    for own in threads.values():
        for a, b, after in zip(own, own[1:], own[2:] + [None]):
            reborn = after is not None and after["name"] == "placement.h2d" \
                and after["args"].get("batch_seq") == 0
            if not reborn:
                seam = max(seam, abs(b["start_ns"] - a["end_ns"]))
                life += max(min(b["start_ns"], hi) - max(a["end_ns"], lo), 0)
        covered += sum(min(s["end_ns"], hi) - max(s["start_ns"], lo)
                       for s in own)
    life += covered
    return (100.0 * covered / life if life else 0.0), seam


def main(argv) -> int:
    from reduce import spans as span_reader
    from reduce import xplane

    if not argv:
        print(__doc__)
        return 2
    run_dir = argv[0]
    with open(os.path.join(run_dir, "trace_span.json")) as f:
        t_start, t_stop, anchor = json.load(f)
    spans = span_reader.read(os.path.join(run_dir, "spans.jsonl"))
    events = xplane.load_events(os.path.join(run_dir, "profile"))
    found = lags(events, spans, int(anchor))
    faults = 0
    if found:
        for what, values in (
                ("from the dispatching train.step phase's start to the "
                 "run's start", [a for a, _ in found]),
                ("from the run's start to that phase's end (negative: it "
                 "began after the call returned)", [b for _, b in found])):
            print(f"join: {len(values)} jit_step runs end an idle gap over 1 "
                  f"ms; {what}: min {min(values) / 1e3:.1f} us, "
                  f"median {statistics.median(values) / 1e3:.1f} us, max "
                  f"{max(values) / 1e3:.1f} us")
        faults += min(a for a, _ in found) < 0
    else:
        print("join: no jit_step run ends an idle gap over 1 ms (a "
              "device-bound run has none); nothing to measure")
    released, started = drain_anchors(events, spans, int(anchor))
    for what, values in (
            ("from the end of the device's run to the end of the train.drain "
             "that waited for it", released),
            ("from the start of the next train.step phase to the start of "
             "the next run, the queue being empty", started)):
        if values:
            print(f"join: {len(values)} drains x chips; {what}: min "
                  f"{min(values) / 1e3:.1f} us, median "
                  f"{statistics.median(values) / 1e3:.1f} us, max "
                  f"{max(values) / 1e3:.1f} us")
            faults += min(values) < 0
    if not released:
        print("join: no train.drain ends inside the trace (the loop drains "
              "every sync_every steps); its anchors have nothing to measure")
    for thread, prefixes in (("loop", ("train.", "startup.")),
                             ("placement", ("placement.",))):
        share, seam = coverage(spans, prefixes, int(t_start), int(t_stop))
        print(f"tiling: the {thread} thread's phases cover {share:.4f} % of "
              f"its time in the traced span ({(t_stop - t_start) / 1e9:.3f} "
              f"s); largest seam between neighbours {seam} ns")
        # the span file keeps microseconds as floats: a seam of a ns or two
        # is the rounding of the file, not a hole
        faults += abs(share - 100.0) > 0.1 or seam > 1000
    print("join ok" if not faults else f"{faults} fault(s)")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
