"""The program's span file (``LDT_TRACE_PATH``, one Chrome trace event a line,
times in microseconds on the monotonic clock) as a list of plain records."""

from __future__ import annotations

import json
import os


def read(path: str) -> list:
    spans = []
    if not os.path.exists(path):
        return spans
    with open(path, encoding="utf-8") as f:
        for line in f:
            try:
                event = json.loads(line)
            except ValueError:
                continue  # a torn last line
            if event.get("ph") != "X":
                continue
            start = int(event["ts"] * 1e3)
            spans.append({"name": event["name"], "start_ns": start,
                          "end_ns": start + int(event["dur"] * 1e3),
                          "tid": event.get("tid"),
                          "args": event.get("args", {})})
    return spans


def inside(spans: list, name: str, window_ns: tuple) -> list:
    """Durations in ns of the named spans, each cut to the window."""
    lo, hi = window_ns
    out = []
    for s in spans:
        if s["name"] == name and s["end_ns"] > lo and s["start_ns"] < hi:
            out.append(min(s["end_ns"], hi) - max(s["start_ns"], lo))
    return out


def whole_inside(spans: list, name: str, window_ns: tuple) -> list:
    """Durations in ns of the named spans that lie wholly in the window."""
    lo, hi = window_ns
    return [s["end_ns"] - s["start_ns"] for s in spans
            if s["name"] == name and s["start_ns"] >= lo and s["end_ns"] <= hi]
