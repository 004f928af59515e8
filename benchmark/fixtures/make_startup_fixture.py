#!/usr/bin/env python3
"""Cuts a traced run's span file down to the fixture ``check_startup.py``
reads (``benchmark/fixtures/startup/``).

    python3 benchmark/run.py --workload <cell> ... --trace 1   # its files stay until the next run
    python3 benchmark/fixtures/make_startup_fixture.py benchmark/out/runs/<cell>/seed<n>-trace1/spans.jsonl <out.json.gz> [step]

Keeps the loop thread's phases from ``train()``'s entry to the log point of
``step`` (the first of the run's ``window:`` line; default the second log
point, where a cell with ``warmup_min_log_points`` 2 opens its window at the
earliest) and a little beyond it, and every ``jax.trace``, ``jax.lower`` and
``xla.compile`` span of the process. The edge is the start of that log
point's ``loop.log_write``: the benchmark stamps a log point as the line is
written. What the readers have to find is worked out here a second
way, by a sweep over start and end points, and stored beside the spans.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

KINDS = ("jax.trace", "jax.lower", "xla.compile")


def sweep_s(spans: list, lo: int, hi: int) -> float:
    """Seconds in ``[lo, hi)`` during which some span of a thread is open,
    summed over threads."""
    total = 0
    for tid in {s["tid"] for s in spans}:
        points = []
        for s in spans:
            a, b = max(s["start_ns"], lo), min(s["end_ns"], hi)
            if s["tid"] == tid and b > a:
                points += [(a, 1), (b, -1)]
        points.sort(key=lambda p: (p[0], -p[1]))
        depth, last = 0, None
        for t, step in points:
            if depth > 0:
                total += t - last
            depth += step
            last = t
    return total / 1e9


def main(argv) -> int:
    from reduce import spans as span_file

    source, out = argv[1], argv[2]
    spans = span_file.read(source)
    entry = min(s["start_ns"] for s in spans
                if s["name"] == "startup.devices")
    loop_tid = next(s["tid"] for s in spans if s["name"] == "startup.devices")
    writes = sorted((s for s in spans if s["name"] == "loop.log_write"),
                    key=lambda s: s["start_ns"])
    step = int(argv[3]) if len(argv) > 3 else writes[1]["args"]["step"]
    edge = next(s["start_ns"] for s in writes if s["args"]["step"] == step)
    keep = [s for s in spans if s["name"] in KINDS or (
        s["tid"] == loop_tid and s["start_ns"] <= edge + 10**9
        and s["name"].startswith(("startup.", "train.")))]
    keep.sort(key=lambda s: (s["start_ns"], s["end_ns"]))
    steps = [s for s in keep if s["name"] == "train.step"]
    compiles = [s for s in keep if s["name"] == "xla.compile"
                and s["start_ns"] >= entry and s["end_ns"] <= edge]
    expected = {
        "entry_to_first_step_s": (steps[0]["end_ns"] - entry) / 1e9,
        "entry_to_edge_s": (edge - entry) / 1e9,
        "pre_train_compile_s": sweep_s(
            [s for s in keep if s["name"] in KINDS
             and s["end_ns"] <= entry], 0, entry),
        "startup_trace_s": sweep_s(
            [s for s in keep if s["name"] == "jax.trace"], entry, edge),
        "startup_lower_s": sweep_s(
            [s for s in keep if s["name"] == "jax.lower"], entry, edge),
        "cache": {c: [sum(1 for s in compiles
                          if s["args"].get("cache") == c),
                      sum(s["end_ns"] - s["start_ns"] for s in compiles
                          if s["args"].get("cache") == c) / 1e9]
                  for c in ("hit", "miss", "off")},
    }
    with gzip.open(out, "wt") as f:
        json.dump({"source": os.path.relpath(source), "step": step,
                   "window_ns": [edge, edge + 10**9], "spans": keep,
                   "expected": expected}, f)
    print(f"{out}: {len(keep)} spans, {os.path.getsize(out)} bytes, "
          f"expected {json.dumps(expected)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
