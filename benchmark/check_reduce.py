#!/usr/bin/env python3
"""Checks the trace reduction against the small recorded traces under
``benchmark/fixtures/`` (cut by ``fixtures/make_fixture.py`` from runs on the
v5e): the busy union, the idle gaps and what they are attributed to, device
time per module, and collective time.

    python3 benchmark/check_reduce.py        # exit 0 and "reduction ok", or the faults
"""

from __future__ import annotations

import glob
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def check_fixture(path: str) -> list:
    from reduce import breakdown, xplane

    fx = xplane.load_fixture(path)
    events = fx["events"]
    events["devices"] = {p: {n: [tuple(e) for e in evs]
                             for n, evs in lines.items()}
                         for p, lines in events["devices"].items()}
    trace = xplane.reduce_events(events, (0, 0, fx["anchor"]))
    want = fx["expected"]
    faults = []
    tag = os.path.basename(path)
    if round(trace["window_s"] * 1e9) != want["window_ns"]:
        faults.append(f"{tag}: window {trace['window_s'] * 1e9} ns, want "
                      f"{want['window_ns']}")
    for chip in trace["devices"]:
        exp = want["devices"][chip["plane"]]
        if chip["busy_ns"] != exp["busy_ns"]:
            faults.append(f"{tag} {chip['plane']}: busy {chip['busy_ns']} ns,"
                          f" want {exp['busy_ns']} (sweep)")
        gap_ns = sum(b - a for a, b in chip["gaps"])
        if gap_ns + chip["busy_ns"] != want["window_ns"]:
            faults.append(f"{tag} {chip['plane']}: gaps {gap_ns} + busy "
                          f"{chip['busy_ns']} != window {want['window_ns']}")
        if any(b <= a for a, b in chip["gaps"]) or any(
                g1[1] > g2[0] for g1, g2 in zip(chip["gaps"],
                                                chip["gaps"][1:])):
            faults.append(f"{tag} {chip['plane']}: gaps overlap or are empty")
        for base, total in exp["module_ns"].items():
            got = sum(sum(v) for n, v in chip["modules"].items()
                      if xplane.module_base(n) == base)
            runs = sum(len(v) for n, v in chip["modules"].items()
                       if xplane.module_base(n) == base)
            if (got, runs) != (total, exp["module_runs"][base]):
                faults.append(f"{tag} {chip['plane']}: module {base} "
                              f"{got} ns in {runs} runs, want {total} in "
                              f"{exp['module_runs'][base]}")
        if chip["collective_ns"] != exp["collective_ns"]:
            faults.append(f"{tag} {chip['plane']}: collective "
                          f"{chip['collective_ns']} ns, want "
                          f"{exp['collective_ns']}")
    chip = xplane.worst(trace)
    start = events["start_unix_ns"]
    totals = breakdown.attribute(chip["gaps"], breakdown.loop_spans(fx["spans"]),
                                 [], lambda t: t + start - fx["anchor"])
    idle = sum(b - a for a, b in chip["gaps"]) / 1e9
    if abs(sum(totals.values()) - idle) > 1e-9:
        faults.append(f"{tag}: attributed {sum(totals.values())} s of "
                      f"{idle} s idle")
    known = {"train.loader", "train.step", "train.transform", "loop.other"}
    if set(totals) - known:
        faults.append(f"{tag}: causes {sorted(set(totals) - known)}")
    for cause, share in want.get("idle_share_at_least", {}).items():
        if totals.get(cause, 0.0) < share * idle:
            faults.append(f"{tag}: {cause} holds {totals.get(cause, 0.0)} s "
                          f"of {idle} s idle, under {share:.0%}")
    print(f"  {tag}: window {trace['window_s'] * 1e3:.3f} ms, idle "
          f"{[round(d['idle_pct'], 2) for d in trace['devices']]} %, causes "
          f"{ {k: round(v * 1e3, 3) for k, v in totals.items()} } ms")
    return faults


def main() -> int:
    faults = []
    paths = sorted(glob.glob(os.path.join(HERE, "fixtures", "*.json.gz")))
    if not paths:
        faults.append("no fixture under benchmark/fixtures/")
    for path in paths:
        faults += check_fixture(path)
    # the union itself, on intervals small enough to check by eye
    from reduce import xplane

    if xplane.union([(5, 7), (0, 2), (1, 3), (7, 9), (20, 21)]) != [
            [0, 3], [5, 9], [20, 21]]:
        faults.append("union of hand-made intervals")
    kinds = {"%convolution_add_fusion.12 = bf16[8]{0} fusion(...)":
             "convolution_add_fusion",
             "%all-reduce-start.3 = f32[2] all-reduce-start(...)":
             "all-reduce-start",
             "%fusion = f32[] fusion()": "fusion",
             "%copy.1 = u8[4] copy(%x)": "copy"}
    got = {name: xplane.op_kind(name) for name in kinds}
    if got != kinds:
        faults.append(f"op_kind of hand-made names: {got}")
    # a program in two shapes, as the ragged cell's traced run had it (36
    # steps at 58 ms, 9 at 93 ms): each shape's median, weighted by its runs
    two = {"devices": [{"modules": {"jit_step(1)": [58e6] * 35 + [70e6],
                                    "jit_step(2)": [93e6] * 9,
                                    "jit_add(3)": [1e3] * 45}}]}
    got = xplane.module_ms(two, "jit_step")
    if abs(got - (36 * 58 + 9 * 93) / 45) > 1e-9 \
            or xplane.module_ms(two, "jit_pack_token_batch") is not None:
        faults.append(f"module_ms of a hand-made two-shape trace: {got}")
    for fault in faults:
        print("FAULT:", fault)
    print("reduction ok" if not faults else f"{len(faults)} fault(s)")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
