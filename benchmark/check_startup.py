#!/usr/bin/env python3
"""Checks the twelve readers of start-up (PR 51) on hand-made spans small
enough to work out by eye, on the recorded span files under
``benchmark/fixtures/startup/`` (cut by ``fixtures/make_startup_fixture.py``,
which works the expected numbers out a second way), and on span files of a
program from before the spans they read, where a reader has to give None and
not raise.

    python3 benchmark/check_startup.py        # exit 0 and "startup ok", or the faults
    python3 benchmark/check_startup.py <spans.jsonl> [step]   # one run's start-up, read by hand

The hand-made run, in seconds on the loop thread (7): the process is 40 s old
at entry (t = 100); before entry it traced 1 s, lowered 1 s and compiled 5 s
(a miss) one after the other, while thread 9 loaded 2 s from the cache: 9 s
before ``train()``. ``startup.devices`` 1, ``startup.dataset`` 2,
``startup.state`` 6 (a 1 s trace, a 1 s lower, a 3 s load from the cache),
``startup.loader`` 3, ``train.loader`` 1, ``train.bookkeep`` 1, the first
``train.step`` 20 (a 10 s trace with one of 3 s nested in it and a 0.5 s
compile of an eager constant, ``cache`` ``off``, too; a 2 s lower; a 6 s
compile that missed), then 16 s to the edge, in which thread 9 compiled 4 s
(a miss), 1 s of it beyond the edge.
"""

from __future__ import annotations

import glob
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
S = 1_000_000_000
TILE = ("startup_open_s", "startup_state_s", "startup_first_batch_s",
        "first_step_dispatch_s")
NAMES = ("train_entry_process_age_s", "pre_train_compile_s", *TILE,
         "warmup_after_first_step_s", "startup_trace_s", "startup_lower_s",
         "startup_compile_s", "startup_cache_load_s", "startup_cache_misses")
# what a span file from before PR 51 still lets a reader find: the phases
OLD = (*TILE, "warmup_after_first_step_s")


def hand_made(new: bool = True) -> tuple:
    """``(spans, window_ns)`` of the run in the docstring; without ``new``,
    as a program from before PR 51 records it."""
    spans = []

    def add(name, start, dur, tid=7, **args):
        if not new:
            if name in ("jax.trace", "jax.lower") or start < 100:
                return
            args = {k: v for k, v in args.items()
                    if k not in ("cache", "retrieval_s", "process_age_s")}
        spans.append({"name": name, "start_ns": int(start * S),
                      "end_ns": int((start + dur) * S), "tid": tid,
                      "args": args})

    add("jax.trace", 80, 1, fun_name="compare")
    add("jax.lower", 81, 1, fun_name="jit(compare)")
    add("xla.compile", 82, 5, fun_name="jit(compare)", cache="miss")
    add("xla.compile", 83, 2, tid=9, fun_name="jit(make)", cache="hit",
        retrieval_s=1.9)
    add("startup.devices", 100, 1, process_age_s=40.0, cache_entries=3)
    add("startup.dataset", 101, 2)
    add("startup.state", 103, 6)
    add("jax.trace", 103, 1, fun_name="_create")
    add("jax.lower", 104, 1, fun_name="jit(_create)")
    add("xla.compile", 105, 3, fun_name="jit(_create)", cache="hit",
        retrieval_s=2.5)
    add("startup.loader", 109, 3)
    add("train.loader", 112, 1, step=0)
    add("train.bookkeep", 113, 1)
    add("train.step", 114, 20, step=0)
    add("jax.trace", 116, 3, fun_name="kernel")
    add("xla.compile", 120, 0.5, fun_name="jit(iota)", cache="off")
    add("jax.trace", 114, 10, fun_name="step")
    add("jax.lower", 124, 2, fun_name="jit(step)")
    add("xla.compile", 126, 6, fun_name="jit(step)", cache="miss")
    add("train.bookkeep", 134, 1)
    add("train.loader", 135, 1, step=1)
    add("train.step", 136, 1, step=1)
    add("xla.compile", 147, 4, tid=9, fun_name="jit(pack)", cache="miss")
    return spans, (150 * S, 180 * S)


def context(spans, window) -> dict:
    return {"spans": spans, "window_ns": tuple(window),
            "window_s": (window[1] - window[0]) / 1e9, "counters": {},
            "trace": None, "cell": {"name": "no-such-cell"}}


def near(got, want, tol=1e-9) -> bool:
    return got is not None and abs(got - want) <= tol * max(abs(want), 1)


def read_all(readers, ctx) -> dict:
    got = {}
    for name, reader in readers.items():
        try:
            got[name] = reader.read(ctx)
        except Exception as e:  # a reader may never raise
            got[name] = f"raised {e!r}"
    return got


def tiling_faults(tag, got, to_first_step, entry_to_edge) -> list:
    """The four that tile ``startup_to_first_step_s``, and with the seventh
    the time from entry to the edge."""
    faults = []
    if any(not isinstance(got[n], float) for n in (*TILE,
                                                   "warmup_after_first_step_s")):
        return [f"{tag}: a tiling reader gave no number: "
                f"{ {n: got[n] for n in TILE} }"]
    four = sum(got[n] for n in TILE)
    if not near(four, to_first_step):
        faults.append(f"{tag}: open + state + first batch + first step = "
                      f"{four}, startup_to_first_step_s reads {to_first_step}")
    if not near(four + got["warmup_after_first_step_s"], entry_to_edge):
        faults.append(f"{tag}: the five from entry to the edge make "
                      f"{four + got['warmup_after_first_step_s']}, want "
                      f"{entry_to_edge}")
    return faults


def report(path: str, step) -> int:
    """One run's start-up from its span file: the twelve readers, how much of
    the first ``train.step`` the loop thread's trace, lower and compile spans
    cover, and every program of half a second or more."""
    import run
    from reduce import spans as span_file
    from reduce import startup

    spans = span_file.read(path)
    logs = sorted((s for s in spans if s["name"] == "loop.log_write"),
                  key=lambda s: s["start_ns"])
    if step is None:
        step = logs[1]["args"]["step"]
    edge = next(s["start_ns"] for s in logs if s["args"]["step"] == step)
    ctx = context(spans, (edge, edge + S))
    readers = {n: run.load_module("layer_metrics", n)
               for n in (*NAMES, "startup_to_first_step_s")}
    for name, value in read_all(readers, ctx).items():
        print(f"{name} = {value}")
    first, step0 = startup.entry(spans), startup.first_step(spans)
    inside = startup.cut([s for s in spans if s["tid"] == step0["tid"]],
                         startup.KINDS, step0["start_ns"], step0["end_ns"])
    whole = (step0["end_ns"] - step0["start_ns"]) / 1e9
    covered = startup.union_s(inside)
    by_kind = ", ".join(
        f"{kind} {startup.union_s([s for s in inside if s['name'] == kind]):.3f}"
        for kind in startup.KINDS)
    print(f"first train.step {whole:.3f} s; its thread's trace, lower and "
          f"compile spans cover {covered:.3f} s ({100 * covered / whole:.1f}%)"
          f": {by_kind}")
    for s in sorted(spans, key=lambda s: s["start_ns"]):
        if s["name"] in startup.KINDS and s["end_ns"] - s["start_ns"] >= S // 2:
            print(f"  {(s['start_ns'] - first['start_ns']) / 1e9:9.3f} "
                  f"{(s['end_ns'] - s['start_ns']) / 1e9:8.3f} s "
                  f"{s['name']:<11} {s['args'].get('cache', ''):<5}"
                  f"{s['args'].get('fun_name')}")
    return 0


def main(argv) -> int:
    import run
    from reduce import xplane

    if len(argv) > 1:
        return report(argv[1], int(argv[2]) if len(argv) > 2 else None)
    readers = {n: run.load_module("layer_metrics", n) for n in NAMES}
    whole = run.load_module("layer_metrics", "startup_to_first_step_s")
    faults = []

    spans, window = hand_made()
    ctx = context(spans, window)
    got = read_all(readers, ctx)
    want = {"train_entry_process_age_s": 40.0, "pre_train_compile_s": 9.0,
            "startup_open_s": 3.0, "startup_state_s": 6.0,
            "startup_first_batch_s": 5.0, "first_step_dispatch_s": 20.0,
            "warmup_after_first_step_s": 16.0, "startup_trace_s": 11.0,
            "startup_lower_s": 3.0, "startup_compile_s": 9.5,
            "startup_cache_load_s": 3.0, "startup_cache_misses": 2.0}
    for name, value in want.items():
        if not (isinstance(got[name], float) and near(got[name], value)):
            faults.append(f"hand-made run: {name} reads {got[name]}, want "
                          f"{value}")
    faults += tiling_faults("hand-made run", got, whole.read(ctx), 50.0)
    # a second train() in the process: entry and first step stay the first
    again = spans + [dict(s, start_ns=s["start_ns"] + 200 * S,
                          end_ns=s["end_ns"] + 200 * S) for s in spans
                     if s["name"].startswith(("startup.", "train."))]
    if read_all(readers, context(again, window)) != got:
        faults.append("hand-made run: a second train() after the window "
                      "moves a reader")
    # nothing loaded, nothing before train(): 0.0, not None
    bare = [s for s in spans if s["start_ns"] >= 100 * S
            and s["args"].get("cache") != "hit"]
    for name in ("pre_train_compile_s", "startup_cache_load_s"):
        value = readers[name].read(context(bare, window))
        if value != 0.0:
            faults.append(f"hand-made run with no such span: {name} reads "
                          f"{value}, want 0.0")

    paths = sorted(glob.glob(os.path.join(HERE, "fixtures", "startup",
                                          "*.json.gz")))
    if not paths:
        faults.append("no fixture under benchmark/fixtures/startup/")
    for path in paths:
        tag = os.path.basename(path)
        fx = xplane.load_fixture(path)
        ctx = context(fx["spans"], fx["window_ns"])
        got = read_all(readers, ctx)
        expected = fx["expected"]
        for name in NAMES:
            if not (isinstance(got[name], float)
                    and math.isfinite(got[name])):
                faults.append(f"{tag}: {name} reads {got[name]}")
        if any(t.startswith(tag) for t in faults):
            continue
        faults += tiling_faults(tag, got, whole.read(ctx),
                                expected["entry_to_edge_s"])
        if not near(whole.read(ctx), expected["entry_to_first_step_s"]):
            faults.append(f"{tag}: startup_to_first_step_s reads "
                          f"{whole.read(ctx)}")
        cache = expected["cache"]
        for name, value in (
                ("pre_train_compile_s", expected["pre_train_compile_s"]),
                ("startup_trace_s", expected["startup_trace_s"]),
                ("startup_lower_s", expected["startup_lower_s"]),
                ("startup_compile_s", cache["miss"][1] + cache["off"][1]),
                ("startup_cache_load_s", cache["hit"][1]),
                ("startup_cache_misses", float(cache["miss"][0]))):
            if not near(got[name], value):
                faults.append(f"{tag}: {name} reads {got[name]}, the sweep "
                              f"gives {value}")

    # programs from before PR 51: the phases are there, the rest is not
    old = xplane.load_fixture(os.path.join(
        HERE, "fixtures", "resnet50_4chip_v5e.json.gz"))["spans"]
    edges = (min(s["start_ns"] for s in old), max(s["end_ns"] for s in old))
    before = hand_made(new=False)
    for tag, ctx, phases in (("fixture of PR 24", context(old, edges), False),
                             ("no new spans", context(*before), True)):
        for name, value in read_all(readers, ctx).items():
            if phases and name in OLD:
                if not near(value, want[name]):
                    faults.append(f"{tag}: {name} reads {value}, want "
                                  f"{want[name]}")
            elif value is not None:
                faults.append(f"{tag}: {name} reads {value}, want None")
    for fault in faults:
        print("FAULT:", fault)
    print("startup ok" if not faults else f"{len(faults)} fault(s)")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
