#!/usr/bin/env python3
"""Where a step's device time goes: phase x flax module x XLA operation.

    python3 benchmark/run.py --workload <cell> ... --trace 1   # its files stay until the next run
    python3 benchmark/scopes_report.py benchmark/out/runs/<cell>/seed<n>-trace1 [rows]

Prints, for the step program (``jit_step``) on the first chip of the traced
run, the device time of one step by phase (forward with the loss, backward,
optimizer, unattributed), then the largest rows of phase x module x
operation kind, blocks of one kind counted together (``BottleneckBlock_*``).
The table a ``perf_opt`` issue on the step starts from. Device times come
from a chip's trace; this only reads them.
"""

from __future__ import annotations

import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def table(raw: dict, rows: int = 25) -> list:
    from reduce import scopes, xplane

    found = scopes.split(raw)
    if found is None:
        return ["no jit_step operation carries a forward, loss or optimizer "
                "scope (a trace of a program from before PR 24?)"]
    runs, ops = scopes.step_ops(raw)
    cells: dict = {}
    for meta, _, dur in ops:
        module = re.sub(r"_\d+$", "_*", scopes.module_of(meta["tf_op"]))
        key = (scopes.phase_of(meta["tf_op"]), module,
               xplane.op_kind(meta["name"]))
        cells[key] = cells.get(key, 0) + dur
    step_ms = sum(found["per_step_ms"].values())
    total = found["ops_ps"]  # a row's share of it, times a step's ms
    lines = [f"jit_step: {len(runs)} runs in the trace, {step_ms:.3f} ms of "
             f"operations a step (median run, weighted over shapes)"]
    for phase in scopes.PHASES:
        ms = found["per_step_ms"][phase]
        lines.append(f"  {phase:<13}{ms:>9.3f} ms {100 * ms / step_ms:>6.2f} %")
    lines.append("unattributed, by hlo_category (ms a step): " + ", ".join(
        f"{k} {step_ms * v / total:.3f}" for k, v in sorted(
            found["unattributed_kinds"].items(), key=lambda kv: -kv[1])[:6]))
    lines.append(f"{'phase':<13}{'module':<24}{'operation':<34}"
                 f"{'ms/step':>9}{'%':>7}")
    for (phase, module, kind), ps in sorted(cells.items(),
                                            key=lambda kv: -kv[1])[:rows]:
        lines.append(f"{phase:<13}{module[:23]:<24}{kind[:33]:<34}"
                     f"{step_ms * ps / total:>9.3f}"
                     f"{100 * ps / total:>7.2f}")
    return lines


def main(argv) -> int:
    from reduce import scopes

    if not argv:
        print(__doc__)
        return 2
    raw = scopes.load_run(argv[0])
    if raw is None:
        print(f"no {scopes.PLANE} plane in a trace under {argv[0]}/profile")
        return 1
    print("\n".join(table(raw, int(argv[1]) if len(argv) > 1 else 25)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
