"""The profiler's ``.xplane.pb`` reduced to what the metrics read.

What a trace of this program on a TPU v5e holds (looked at by hand, PR 23, in
PERF.md): one plane ``/device:TPU:<n>`` per chip with the lines ``XLA
Modules`` (one event per executed program, named ``jit_<function>(<id>)``),
``XLA Ops`` (every operation, named by its HLO text ``%name = ...``), ``Async
XLA Ops`` (copies and collectives in flight, start to done) and ``Steps``; a
plane ``/host:CPU`` with one line per thread; and ``Task Environment`` with
``profile_start_time`` in Unix ns. Event times are ns from that start.

Busy is the union of the intervals of ``XLA Ops`` (of ``XLA Modules`` where a
trace has no ops line). The traced window runs from the first module's start
to the last module's end over all chips. Reads ``.xplane.pb`` with
``jax.profiler.ProfileData`` or, for the fixture, the same events from JSON.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import statistics

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:[.\-_]\d+)*(?:\s|=|$)")


def load_events(profile_dir: str) -> dict:
    """``{"start_unix_ns", "devices": {plane: {line: [(name, start, dur)]}}}``
    from the newest ``.xplane.pb`` under ``profile_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return {"start_unix_ns": None, "devices": {}}
    data = ProfileData.from_file(paths[-1])
    out = {"start_unix_ns": None, "devices": {}, "bytes":
           os.path.getsize(paths[-1])}
    for plane in data.planes:
        if plane.name == "Task Environment":
            for key, value in plane.stats:
                if key == "profile_start_time":
                    out["start_unix_ns"] = int(value)
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in ("XLA Modules", "XLA Ops", "Async XLA Ops"):
                lines[line.name] = [(e.name, int(e.start_ns),
                                     int(e.duration_ns)) for e in line.events]
        out["devices"][plane.name] = lines
    return out


def load_fixture(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals: list) -> list:
    """Sorted, merged ``[start, end)`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def op_kind(name: str) -> str:
    """``%convolution_add_fusion.12 = bf16[...] fusion(...)`` ->
    ``convolution_add_fusion``: the XLA name without its number."""
    match = OP_NAME.match(name)
    return match.group(1) if match else name[:40]


def module_base(name: str) -> str:
    return name.split("(")[0]


def reduce_events(events: dict, trace_span=None) -> dict:
    devices = []
    starts, ends = [], []
    for lines in events["devices"].values():
        for _, start, dur in lines.get("XLA Modules") or lines.get("XLA Ops", []):
            starts.append(start)
            ends.append(start + dur)
    if not starts:
        return {"busy_s": 0.0, "window_s": 0.0, "devices": [],
                "summary": {"devices": 0}, "start_unix_ns": None,
                "trace_span": trace_span}
    lo, hi = min(starts), max(ends)
    for plane, lines in sorted(events["devices"].items()):
        ops = lines.get("XLA Ops") or lines.get("XLA Modules", [])
        busy = union([(max(s, lo), min(s + d, hi)) for _, s, d in ops
                      if s + d > lo and s < hi])
        busy_ns = sum(e - s for s, e in busy)
        gaps, cursor = [], lo
        for s, e in busy:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if hi > cursor:
            gaps.append((cursor, hi))
        modules, kinds = {}, {}
        for name, _, dur in lines.get("XLA Modules", []):
            modules.setdefault(name, []).append(dur)
        for name, _, dur in lines.get("XLA Ops", []):
            kind = op_kind(name)
            kinds[kind] = kinds.get(kind, 0) + dur
        collective = sum(
            d for n, _, d in lines.get("XLA Ops", [])
            if COLLECTIVE.search(op_kind(n))
            and not re.search(r"-(start|done)", op_kind(n)))
        collective += sum(d for n, _, d in lines.get("Async XLA Ops", [])
                          if COLLECTIVE.search(op_kind(n)))
        devices.append({"plane": plane, "busy_ns": busy_ns, "gaps": gaps,
                        "idle_pct": 100.0 * (1 - busy_ns / (hi - lo)),
                        "modules": modules, "op_kinds": kinds,
                        "collective_ns": collective})
    summary = {
        "devices": len(devices), "window_ms": (hi - lo) / 1e6,
        "idle_pct": [round(d["idle_pct"], 3) for d in devices],
        "modules": {n: [len(v), round(statistics.median(v) / 1e6, 4)]
                    for n, v in devices[0]["modules"].items()},
        "xplane_bytes": events.get("bytes")}
    return {"busy_s": sum(d["busy_ns"] for d in devices) / len(devices) / 1e9,
            "window_s": (hi - lo) / 1e9, "window": (lo, hi),
            "devices": devices, "summary": summary,
            "start_unix_ns": events.get("start_unix_ns"),
            "trace_span": trace_span}


def reduce_profile(profile_dir: str, trace_span=None) -> dict:
    return reduce_events(load_events(profile_dir), trace_span)


def module_durations(trace: dict, base: str) -> dict:
    """``{full module name: [ns]}`` on the first chip for programs whose
    name without its id is ``base``."""
    if not trace or not trace["devices"]:
        return {}
    return {n: v for n, v in trace["devices"][0]["modules"].items()
            if module_base(n) == base}


def module_ms(trace: dict, base: str):
    """Device time of one execution of program ``base`` in ms. A program
    compiled for several shapes (a packed grid of 16 or of 24 rows) is
    several modules: each one's median, weighted by how often it ran in the
    trace, so that batch over this time is the rate. None if it never ran."""
    runs = module_durations(trace, base)
    count = sum(len(v) for v in runs.values())
    if not count:
        return None
    return sum(statistics.median(v) * len(v)
               for v in runs.values()) / count / 1e6


def worst(trace: dict) -> dict:
    """The chip that idled most."""
    return max(trace["devices"], key=lambda d: d["idle_pct"])
