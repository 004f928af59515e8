#!/usr/bin/env python3
"""What the loop thread's calls cost, and which of them the chips waited in.

    python3 benchmark/run.py --workload <cell> ... --trace 1   # its files stay until the next run
    python3 benchmark/loop_report.py benchmark/out/runs/<cell>/seed<n>-trace1 [out.json]

Over one traced run's directory (spans, trace, ``trace_span.json``), inside
the traced span:

* the loop thread's phases and the ``loop.*`` calls under them: count, total,
  median and 95th percentile, and per step;
* every dispatch by the steps in flight when its step began (``in_flight`` on
  the ``train.step`` phase): what ``reduce/loop_calls.py`` takes as unblocked
  and what as wait;
* the idle of the chip that idled most, by the phase at each gap's middle (as
  ``breakdown.idle_gaps`` has it) and by overlap with the phases and with the
  ``loop.*`` calls inside ``train.bookkeep``, ``train.log`` and
  ``train.transform`` (``(rest)`` is a phase's idle under none of them);
* the device programs a step by name, from the ``XLA Modules`` line;
* the five metrics of PR 35 over the traced span (the run's result line has
  them over the window), and the loop thread's budget, which makes 100.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def quantile(values: list, q: float) -> float:
    values = sorted(values)
    return values[min(int(q * len(values)), len(values) - 1)]


def calls_table(spans: list, tid, lo: int, hi: int, steps: int) -> dict:
    names: dict = {}
    for s in spans:
        if s["tid"] == tid and s["name"].startswith(("train.", "loop.")) \
                and s["start_ns"] >= lo and s["end_ns"] <= hi:
            names.setdefault(s["name"], []).append(s["end_ns"] - s["start_ns"])
    return {n: {"count": len(v), "total_s": sum(v) / 1e9,
                "median_ms": statistics.median(v) / 1e6,
                "p95_ms": quantile(v, 0.95) / 1e6,
                "ms_per_step": sum(v) / 1e6 / max(steps, 1)}
            for n, v in sorted(names.items())}


def by_in_flight(calls: list, lo: int, hi: int) -> dict:
    out: dict = {}
    for name, _, start, dur, flight in calls:
        if lo <= start < hi:
            out.setdefault(name, {}).setdefault(min(flight, 8), []).append(dur)
    return {name: {str(k): {"count": len(v),
                            "median_ms": statistics.median(v) / 1e6,
                            "max_ms": max(v) / 1e6}
                   for k, v in sorted(groups.items())}
            for name, groups in out.items()}


def programs(events: dict, plane: str) -> dict:
    from reduce import xplane

    modules = events["devices"][plane].get("XLA Modules", [])
    runs = sum(1 for n, _, _ in modules
               if xplane.module_base(n) == "jit_step")
    by_name: dict = {}
    for name, _, dur in modules:
        by_name.setdefault(xplane.module_base(name), []).append(dur)
    return {"jit_step_runs": runs, "programs": {
        n: {"runs": len(v), "per_step": len(v) / max(runs, 1),
            "median_us": statistics.median(v) / 1e3}
        for n, v in sorted(by_name.items(), key=lambda kv: -len(kv[1]))}}


def report(run_dir: str) -> dict:
    from reduce import breakdown, loop_calls, xplane
    from reduce import spans as span_reader

    with open(os.path.join(run_dir, "trace_span.json")) as f:
        t_start, t_stop, anchor = (int(x) for x in json.load(f))
    spans = span_reader.read(os.path.join(run_dir, "spans.jsonl"))
    events = xplane.load_events(os.path.join(run_dir, "profile"))
    trace = xplane.reduce_events(events, (t_start, t_stop, anchor))
    tid = loop_calls.loop_thread(spans)
    window = (t_start, t_stop)
    steps = sum(1 for s in spans if s["name"] == "train.step"
                and t_start <= s["start_ns"] < t_stop)
    out = {"run_dir": run_dir, "traced_span_s": (t_stop - t_start) / 1e9,
           "steps_dispatched_in_span": steps,
           "calls": calls_table(spans, tid, t_start, t_stop, steps)}
    calls = loop_calls.dispatches(spans)
    if calls:
        out["dispatches_by_in_flight"] = by_in_flight(calls, *window)
        out["unblocked_ms"] = {n: v / 1e6 for n, v in
                               loop_calls.unblocked_ns(calls, window).items()}
        after = [s["args"]["in_flight_after"] for s in spans
                 if s["name"] == "train.step"
                 and "in_flight_after" in s["args"]]
        out["in_flight_after_max"] = max(after) if after else None
    if trace["devices"] and events["start_unix_ns"] is not None:
        chip = xplane.worst(trace)
        shift = events["start_unix_ns"] - anchor
        idle = sum(b - a for a, b in chip["gaps"]) / 1e9
        by_middle = breakdown.attribute(
            chip["gaps"], breakdown.loop_spans(spans), [],
            lambda t: t + shift)
        by_phase, by_call = loop_calls.overlap_by_call(
            chip["gaps"], spans, lambda t: t + shift)
        buckets = {p: by_call.get(p, {}) for p in loop_calls.BUCKETS}
        in_buckets = sum(sum(v.values()) for v in buckets.values())
        rest = sum(v.get("(rest)", 0.0) for v in buckets.values())
        out["idle"] = {
            "plane": chip["plane"], "idle_pct": chip["idle_pct"],
            "idle_s": idle, "by_middle_s": by_middle,
            "by_overlap_s": by_phase, "buckets_by_call_s": buckets,
            "buckets_idle_s": in_buckets,
            "buckets_under_a_call_pct":
                100.0 * (1 - rest / in_buckets) if in_buckets else None}
        out["device_programs"] = programs(events, chip["plane"])
        runs = sorted((s, s + d) for n, s, d in
                      events["devices"][chip["plane"]].get("XLA Modules", [])
                      if xplane.module_base(n) == "jit_step")
        restarts = loop_calls.restart_idle_ns(
            spans, runs, chip["gaps"], lambda t: t - shift)
        out["drain_restart_idle_ms"] = [r / 1e6 for r in restarts]
    span_ns = t_stop - t_start
    blocked = loop_calls.blocked_ns(spans, window)
    budget = {name: 100.0 * sum(span_reader.inside(spans, name, window))
              / span_ns for name in (
                  "train.loader", "train.drain", "train.step",
                  "train.transform", "train.bookkeep", "train.log",
                  "train.epoch_end", "train.epoch_start")}
    out["budget_pct_of_span"] = budget
    if blocked is not None:
        busy = sum(budget[n] for n in loop_calls.BUSY)
        aux = loop_calls.aux_ns_per_step(spans, window)
        out["metrics_over_span"] = {
            "dispatch_blocked_pct": 100.0 * blocked / span_ns,
            "loop_own_work_pct": busy - 100.0 * blocked / span_ns,
            "aux_dispatch_ms": statistics.median(aux) / 1e6 if aux else None,
            "sum_pct": sum(budget.values())}
    return out


def main(argv) -> int:
    if not argv:
        print(__doc__)
        return 2
    found = report(argv[0])
    text = json.dumps(found, indent=1, default=float)
    if len(argv) > 1:
        os.makedirs(os.path.dirname(os.path.abspath(argv[1])), exist_ok=True)
        with open(argv[1], "w") as f:
            f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
