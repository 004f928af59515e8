#!/usr/bin/env python3
"""Cuts a traced run down to the fixture ``check_scopes.py`` reads.

    python3 benchmark/run.py --workload <cell> ... --trace 1   # its files stay until the next run
    python3 benchmark/fixtures/make_scopes_fixture.py benchmark/out/runs/<cell>/seed<n>-trace1 <out.json.gz> [runs]

Keeps, of the first chip's plane, the first ``runs`` (default 2) whole runs
of ``jit_step`` after the trace's cut first one, the programs between them,
every operation that lies in that stretch, and of each operation's metadata
what ``reduce/scopes.py`` reads (the head of its HLO text, ``tf_op``,
``program_id``, ``hlo_category``). The expected numbers are worked out here a
second way than the reduction's: by regular expressions over the ``tf_op``,
not by its path segments.
"""

from __future__ import annotations

import gzip
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

OPTIMIZER = re.compile(r"(^|/)optimizer(/|$)")
SCOPED = re.compile(r"(^|[/(])(forward|loss)([/)]|$)")


def phase(tf_op: str) -> str:
    if OPTIMIZER.search(tf_op):
        return "optimizer"
    if SCOPED.search(tf_op):
        return "backward" if "transpose(" in tf_op else "forward"
    return "unattributed"


def main(argv) -> int:
    from reduce import scopes

    run_dir, out = argv[:2]
    keep = int(argv[2]) if len(argv) > 2 else 2
    raw = scopes.load_run(run_dir)
    steps = sorted((start, start + dur) for m, start, dur in raw["modules"]
                   if raw["metadata"][m]["name"].startswith("jit_step("))
    lo, hi = steps[1][0], steps[keep][1]  # the trace cuts its first run
    modules = [e for e in raw["modules"] if e[1] >= lo and e[1] + e[2] <= hi]
    ops = [e for e in raw["ops"] if e[1] >= lo and e[1] + e[2] <= hi]
    used = {e[0] for e in modules + ops}
    metadata = {k: dict(v, name=v["name"][:v["name"].find(" = ") + 28]
                        if " = " in v["name"] else v["name"])
                for k, v in raw["metadata"].items() if k in used}
    program = next(v["program_id"] for v in metadata.values()
                   if v["tf_op"].startswith("jit(step)"))
    expected = {"forward": 0, "backward": 0, "optimizer": 0,
                "unattributed": 0, "ops_ps": 0, "runs": keep}
    for m, _, dur in ops:
        if metadata[m]["program_id"] == program:
            expected[phase(metadata[m]["tf_op"])] += dur
            expected["ops_ps"] += dur
    with gzip.open(out, "wt") as f:
        json.dump({"metadata": metadata, "modules": modules, "ops": ops,
                   "expected": expected}, f, separators=(",", ":"))
    print(out, os.path.getsize(out), "bytes", json.dumps(expected))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
