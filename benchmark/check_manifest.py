#!/usr/bin/env python3
"""Checks ``BENCHMARK.json`` and every file it points to, in the sandbox,
before a second of chip time is spent. The rules are the contract's (the
builder's instructions), written out as code; PR 22 was refused for a
``layer`` that was a phrase, which nothing checked before the driver did.

    python3 benchmark/check_manifest.py        # exit 0 and "manifest ok", or the faults
"""

from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state_size", "proj",
               "head_size", "expansion", "experts_per_tok")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
MAX_CELLS, FULL_CHECK_S, RUN_EXTRA_S, COMPILE_S, SPARE_S = 24, 43200, 60, 180, 1200


def one_line(text, limit=200) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def under_paths(path: str, paths: list) -> bool:
    return any(path == p or path.startswith(p.rstrip("/") + "/") for p in paths)


def resolve_traffic(name: str, seen: tuple = ()):
    """A traffic file's keys over those of the ``base`` mix it may name, as
    ``run.load_traffic`` reads it; None if a file is missing or bases loop."""
    path = os.path.join(HERE, "traffic", f"{name}.json")
    if name in seen or not os.path.isfile(path):
        return None
    body = json.load(open(path))
    if "base" not in body:
        return body
    base = resolve_traffic(str(body["base"]), seen + (name,))
    return None if base is None else {**base, **body}


def check(root: str = ROOT) -> list:
    faults = []
    say = faults.append
    raw = open(os.path.join(root, "BENCHMARK.json"), "rb").read()
    if len(raw) > 64 * 1024:
        say("BENCHMARK.json is over 64 KiB")
    m = json.loads(raw)
    if set(m) != TOP_KEYS:
        say(f"top-level keys {sorted(set(m) ^ TOP_KEYS)} missing or unknown")
        return faults

    paths, command = m["paths"], m["command"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        say("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            say(f"path {p!r}: relative, at most 200 of letters digits _ . - /")
        elif not os.path.isdir(os.path.join(root, p)):
            say(f"path {p!r} is no directory")
    if not (isinstance(command, list) and 1 <= len(command) <= 32
            and all(one_line(w) for w in command)):
        say("command: 1 to 32 words of 1 to 200 characters on one line")
    for word in command:
        if word.startswith("/") or ".." in word.split("/"):
            say(f"command word {word!r} leaves the checkout")
        elif os.path.exists(os.path.join(root, word)) and "/" in word \
                and not under_paths(word, paths):
            say(f"command names {word!r}, a file of the repo outside paths")
    rs = m["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        say("run_seconds: a whole number from 1 to 51")
    else:
        need = (2 + 14 * MAX_CELLS) * (rs + RUN_EXTRA_S) \
            + MAX_CELLS * COMPILE_S + SPARE_S
        if need > FULL_CHECK_S:
            say(f"run_seconds {rs}: a full check of {MAX_CELLS} cells needs "
                f"{need} s, over {FULL_CHECK_S}")

    for kind, allowed in KEYS.items():
        entries = m[kind]
        lo, hi = {"configs": (1, 24), "workloads": (2, 24),
                  "end_to_end": (1, 16), "per_layer": (1, 128)}[kind]
        if not (isinstance(entries, list) and lo <= len(entries) <= hi):
            say(f"{kind}: {lo} to {hi} entries")
        for e in entries:
            extra = set(e) - allowed - ({"workloads"} if kind in (
                "end_to_end", "per_layer") else set())
            missing = allowed - set(e)
            if extra or missing:
                say(f"{kind} {e.get('name')!r}: unknown keys {sorted(extra)}, "
                    f"missing {sorted(missing)}")
            if not NAME.match(str(e.get("name", ""))):
                say(f"{kind} name {e.get('name')!r}: 1 to 64 of letters "
                    "digits _ . -, starting with a letter, digit or _")
        names = [e.get("name") for e in entries]
        if len(set(names)) != len(names):
            say(f"{kind}: two entries share a name")
    metric_names = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    if len(set(metric_names)) != len(metric_names):
        say("two metrics share a name")

    configs = {c["name"]: c for c in m["configs"]}
    files = [c.get("file") for c in m["configs"]]
    if len(set(files)) != len(files):
        say("two configurations share a file")
    for c in m["configs"]:
        if not one_line(c.get("source")) or not one_line(c.get("why")):
            say(f"config {c['name']}: source and why are 1 to 200 characters "
                "on one line")
        f = c.get("file", "")
        if not PATH.match(f) or not under_paths(f, paths):
            say(f"config {c['name']}: file {f!r} is not under paths")
        elif not os.path.isfile(os.path.join(root, f)):
            say(f"config {c['name']}: no file {f}")
        else:
            body = json.load(open(os.path.join(root, f)))
            if not isinstance(body, dict):
                say(f"config {c['name']}: {f} holds no JSON object")
            elif body.get("source") != c["source"]:
                say(f"config {c['name']}: source differs from {f}'s")
        reduced = c.get("reduced", [])
        if not (isinstance(reduced, list) and len(reduced) <= 16):
            say(f"config {c['name']}: reduced has at most 16 keys")
        for key in reduced:
            if not NAME.match(str(key)):
                say(f"config {c['name']}: reduced key {key!r} is no name")
            low = str(key).lower()
            if low.endswith(("_dim", "_rank", "_size")) and "vocab" not in low \
                    or any(w in low for w in WIDTH_WORDS):
                say(f"config {c['name']}: reduced names a width, {key!r}")
        if c["name"] not in {w["config"] for w in m["workloads"]}:
            say(f"config {c['name']} is used by no cell")

    cells = {w["name"]: w for w in m["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    if len(set(pairs)) != len(pairs):
        say("a pair of configuration and traffic appears twice")
    for w in m["workloads"]:
        for key in ("config", "traffic"):
            if not NAME.match(str(w.get(key, ""))):
                say(f"cell {w['name']}: {key} {w.get(key)!r} is no name")
        if w["config"] not in configs:
            say(f"cell {w['name']}: no configuration {w['config']!r}")
        if w.get("chips") not in (1, 4):
            say(f"cell {w['name']}: chips is 1 or 4")
        if not one_line(w.get("why")):
            say(f"cell {w['name']}: why is 1 to 200 characters on one line")
        traffic = resolve_traffic(w["traffic"])
        if traffic is None:
            say(f"cell {w['name']}: no benchmark/traffic/{w['traffic']}.json, "
                "or its chain of base mixes is broken")
        else:
            for key, folder in (("generator", "traffic"),
                                ("batch_check", "batch_checks")):
                if not os.path.isfile(os.path.join(
                        HERE, folder, f"{traffic.get(key)}.py")):
                    say(f"traffic {w['traffic']}: no {key} "
                        f"benchmark/{folder}/{traffic.get(key)}.py")
        for folder in ("reference", "flops"):
            if not os.path.isfile(os.path.join(HERE, folder,
                                               w["config"] + ".py")):
                say(f"cell {w['name']}: no benchmark/{folder}/{w['config']}.py")
    four = sum(1 for w in m["workloads"] if w.get("chips") == 4)
    if four > max(len(m["workloads"]) // 4, 1):
        say(f"{four} of {len(m['workloads'])} cells ask for 4 chips: at most "
            "a quarter, rounded down, and one always may")

    def cells_of(metric):
        listed = metric.get("workloads")
        if listed is None:
            return set(cells)
        if not (isinstance(listed, list) and listed) or set(listed) - set(cells):
            say(f"metric {metric['name']}: workloads names no cell, or an "
                "unknown one")
            return set()
        return set(listed)

    end = {e["name"]: cells_of(e) for e in m["end_to_end"]}
    for e in m["end_to_end"] + m["per_layer"]:
        if not UNIT.match(str(e.get("unit", ""))):
            say(f"metric {e['name']}: unit {e.get('unit')!r} is 1 to 16 of "
                "letters digits _ / % . -")
        if e.get("better") not in ("lower", "higher"):
            say(f"metric {e['name']}: better is lower or higher")
        if e.get("source") not in SOURCES:
            say(f"metric {e['name']}: source is one of {sorted(SOURCES)}")
    for e in m["end_to_end"]:
        if e.get("source") not in ("host_clock", "device_trace"):
            say(f"end-to-end metric {e['name']}: source is host_clock or "
                "device_trace")
        b = e.get("bound")
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.1):
            say(f"end-to-end metric {e['name']}: bound from 0.01 to 0.1")
        if not os.path.isfile(os.path.join(HERE, "end_to_end",
                                           e["name"] + ".py")):
            say(f"end-to-end metric {e['name']}: no reader "
                f"benchmark/end_to_end/{e['name']}.py")
    if "setup_s" not in end:
        say("one end-to-end metric must be setup_s")
    for p in m["per_layer"]:
        if not NAME.match(str(p.get("layer", ""))):
            say(f"per_layer metric {p['name']}: layer {p.get('layer')!r} must "
                "be 1 to 64 of letters digits _ . -, starting with a letter, "
                "digit or _ (a token, not a phrase)")
        if p.get("moves") not in end:
            say(f"per_layer metric {p['name']}: moves {p.get('moves')!r} is no "
                "end-to-end metric")
        elif cells_of(p) - end[p["moves"]]:
            say(f"per_layer metric {p['name']}: moves {p['moves']}, which "
                f"{sorted(cells_of(p) - end[p['moves']])} do not report")
        if not os.path.isfile(os.path.join(HERE, "layer_metrics",
                                           p["name"] + ".py")):
            say(f"per_layer metric {p['name']}: no reader "
                f"benchmark/layer_metrics/{p['name']}.py")
    for name in cells:
        if name not in end.get("setup_s", set()):
            say(f"cell {name} does not report setup_s")
        if not any(name in c for n, c in end.items() if n != "setup_s"):
            say(f"cell {name} reports no end-to-end metric besides setup_s")
        if not any(name in cells_of(p) for p in m["per_layer"]):
            say(f"cell {name} reports no per-layer metric")

    for p in paths:
        for d, _, fs in os.walk(os.path.join(root, p)):
            rel = os.path.relpath(d, root)
            if any(part in ("data", "out", "__pycache__")
                   for part in rel.split(os.sep)):
                continue
            for f in fs:
                if not PATH.match(os.path.join(rel, f)):
                    say(f"file {os.path.join(rel, f)!r}: named from letters "
                        "digits _ . - and /")
    return faults


def main() -> int:
    faults = check()
    for fault in faults:
        print("FAULT:", fault)
    print("manifest ok" if not faults else f"{len(faults)} fault(s)")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
