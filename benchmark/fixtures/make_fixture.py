#!/usr/bin/env python3
"""Cuts a recorded run down to the fixture ``check_reduce.py`` reads.

    python3 benchmark/run.py --workload <cell> ... --trace 1   # its files stay until the next run
    python3 benchmark/fixtures/make_fixture.py benchmark/out/runs/<cell>/seed<n>-trace1 <out.json.gz> [seconds]

Keeps the
first ``seconds`` (default 0.25) of the traced window on every chip: the
programs that end in it, the operations and asynchronous operations up to then,
and the loop thread's spans around it. The expected numbers are worked out
here by a sweep over start and end points, a second way than the union of
sorted intervals the reduction uses, and are stored beside the events.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def sweep_busy(events, lo, hi):
    points = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            points += [(a, 1), (b, -1)]
    points.sort(key=lambda p: (p[0], -p[1]))
    depth, busy, last = 0, 0, None
    for t, step in points:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def is_collective(head: str, synchronous: bool) -> bool:
    """By plain substring, not the reduction's regular expression: in the
    operations line the start and done halves of an asynchronous collective
    are instants and are left out; the asynchronous line has it whole."""
    named = any(w in head for w in ("all-reduce", "all-gather",
                                    "reduce-scatter", "collective-permute",
                                    "all-to-all"))
    return named and not (synchronous and ("-start" in head
                                           or "-done" in head))


def expected_of(events: dict, lo: int, hi: int) -> dict:
    from reduce import xplane

    expected = {"window_ns": hi - lo, "devices": {}}
    for plane, lines in events["devices"].items():
        by_module = {}
        for name, _, d in lines["XLA Modules"]:
            by_module.setdefault(xplane.module_base(name), []).append(d)
        expected["devices"][plane] = {
            "busy_ns": sweep_busy(lines["XLA Ops"], lo, hi),
            "module_ns": {k: sum(v) for k, v in by_module.items()},
            "module_runs": {k: len(v) for k, v in by_module.items()},
            "collective_ns": sum(
                d for line in ("XLA Ops", "Async XLA Ops")
                for n, _, d in lines.get(line, [])
                if is_collective(n.split(" = ")[0], line == "XLA Ops"))}
    return expected


def main(argv) -> int:
    from reduce import spans as span_reader
    from reduce import xplane

    run_dir, out = argv[:2]
    seconds = float(argv[2]) if len(argv) > 2 else 0.25
    span_path = os.path.join(run_dir, "spans.jsonl")
    with open(os.path.join(run_dir, "trace_span.json")) as f:
        anchor = json.load(f)[2]  # wall ns minus monotonic ns at the start
    events = xplane.load_events(os.path.join(run_dir, "profile"))
    first = min(s for lines in events["devices"].values()
                for _, s, _ in lines["XLA Modules"])
    cut = first + int(seconds * 1e9)
    for lines in events["devices"].values():
        # whole programs only: those that end inside the cut, and what ran
        # on the chip up to the last one's end
        lines["XLA Modules"] = [e for e in lines["XLA Modules"]
                                if e[1] + e[2] <= cut]
        end = max(s + d for _, s, d in lines["XLA Modules"])
        for name in ("XLA Ops", "Async XLA Ops"):
            # an operation's name is its whole HLO text; its head is enough
            lines[name] = [(n[:n.find(" = ") + 28] if " = " in n else n, s, d)
                           for n, s, d in lines.get(name, []) if s + d <= end]
    events.pop("bytes", None)
    ends = [s + d for lines in events["devices"].values()
            for _, s, d in lines["XLA Modules"]]
    lo, hi = first, max(ends)
    to_mono = events["start_unix_ns"] - int(anchor)
    spans = [s for s in span_reader.read(span_path)
             if s["name"].startswith("train.")
             and s["end_ns"] >= lo + to_mono - 5e7
             and s["start_ns"] <= hi + to_mono + 5e7]
    expected = expected_of(events, lo, hi)
    with gzip.open(out, "wt") as f:
        json.dump({"events": events, "spans": spans, "anchor": int(anchor),
                   "expected": expected}, f)
    print(out, os.path.getsize(out), "bytes", json.dumps(expected)[:600])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
