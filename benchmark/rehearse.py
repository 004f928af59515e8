#!/usr/bin/env python3
"""Rehearsal without the chip: every cell's whole code path at a tiny preset.

    python3 benchmark/rehearse.py [--cells a,b] [--seconds 6] [--checks 1]

For each cell of ``BENCHMARK.json`` it starts ``benchmark/run.py``'s own
``run_cell`` in a child process under ``JAX_PLATFORMS=cpu`` (a four-chip cell
under ``--xla_force_host_platform_device_count=4``), at the sizes the
``rehearsal`` block of the cell's configuration and traffic files give, once
untraced and once traced: generator, ``cli.main``, log-point clock, SIGTERM
stop, span parsing, the plain-reader and plain-model comparisons, the trace
reduction and the last line's shape. It prints counts and ``correct`` only,
never a number under a device metric's name: a CPU run gives no time, rate or
share of a device. ``run.py`` itself still refuses anything but a TPU.

With ``--checks 1`` it also runs what needs no cell: the manifest check, the
trace reduction against the recorded fixture, and each configuration's
operation count against XLA's own ``cost_analysis()`` of the forward compiled
here at the real widths and batch 2 (margin 5%: XLA also counts the
normalisations, softmax and activation functions that the functions under
``benchmark/flops/`` leave out, and leaves out a convolution's products with
its zero padding, which is why a 32 px picture would not do).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FLOPS_MARGIN = 0.05
LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def child(workload: str, seed: int, seconds: float, trace: int) -> int:
    """Runs inside the child: one rehearsal run, result as the last line."""
    sys.path[:0] = [ROOT, HERE]
    import run

    code, result = run.run_cell(workload, seed, seconds, bool(trace),
                                platform="cpu", rehearsal=True)
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


def rehearse_cell(cell: dict, seconds: float, seed: int) -> list:
    faults = []
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{cell['chips']}")
    for trace in (0, 1):
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             cell["name"], "--seed", str(seed), "--seconds", str(seconds),
             "--trace", str(trace)],
            env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
        lines = done.stdout.strip().splitlines()
        tag = f"{cell['name']} trace={trace}"
        if done.returncode != 0 or not lines:
            faults.append(f"{tag}: exit {done.returncode}\n"
                          + done.stdout[-1500:] + done.stderr[-3000:])
            continue
        try:
            last = json.loads(lines[-1])
        except ValueError:
            faults.append(f"{tag}: last line is no JSON: {lines[-1][:200]}")
            continue
        window = next((ln for ln in lines if ln.startswith("window:")), "")
        missing = LAST_LINE_KEYS - set(last)
        if missing:
            faults.append(f"{tag}: last line lacks {sorted(missing)}")
        if not last.get("correct"):
            faults.append(f"{tag}: correct is false\n" + "\n".join(
                ln for ln in lines if "against" in ln))
        if last.get("device", {}).get("count") != cell["chips"]:
            faults.append(f"{tag}: device {last.get('device')}")
        if trace and not {"busy_s", "window_s"} <= set(last.get("device", {})):
            faults.append(f"{tag}: traced run without busy_s and window_s")
        print(f"  {tag}: correct={last.get('correct')} attempted="
              f"{last.get('attempted')} failed={last.get('failed')} "
              f"metrics={sorted(last.get('metrics', {}))} "
              f"breakdown={sorted(last.get('breakdown', {}))} | {window}",
              flush=True)
    return faults


def check_flops() -> list:
    """Each configuration's count against XLA's for the forward compiled at
    the real widths, batch 2."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path[:0] = [ROOT, HERE]
    import jax
    import run

    from lance_distributed_training_tpu.models import get_task

    faults = []
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for entry in manifest["configs"]:
        cell = next(w for w in manifest["workloads"]
                    if w["config"] == entry["name"])
        loaded = run.load_cell(cell["name"])
        config = loaded["config"]
        task = get_task(**config["task"])
        shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
        module = run.load_module("flops", entry["name"])
        batch = module.example_batch(config, 2)

        def forward(v, b):
            return task.forward(v, b, False, None)[0]

        cost = jax.jit(forward).lower(shapes, batch).compile().cost_analysis()
        ours = module.step_flops(config["model"], {
            k: v.shape for k, v in batch.items()}) / 3.0
        ratio = ours / cost["flops"]
        print(f"  flops {entry['name']}: ours/XLA forward = {ratio:.4f}",
              flush=True)
        if abs(ratio - 1) > FLOPS_MARGIN:
            faults.append(f"flops {entry['name']}: ours {ours:.4g} against "
                          f"XLA's {cost['flops']:.4g}")
    return faults


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--child")
    ap.add_argument("--cells", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--checks", type=int, default=1)
    args = ap.parse_args(argv)
    if args.child:
        return child(args.child, args.seed, args.seconds, args.trace)

    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    wanted = [c for c in args.cells.split(",") if c]
    faults = []
    if args.checks:
        for script in ("check_manifest.py", "check_reduce.py"):
            done = subprocess.run([sys.executable, os.path.join(HERE, script)],
                                  cwd=ROOT, capture_output=True, text=True)
            print(f"  {script}: {done.stdout.strip().splitlines()[-1:]}",
                  flush=True)
            if done.returncode:
                faults.append(f"{script}:\n{done.stdout}{done.stderr[-2000:]}")
        faults += check_flops()
    for cell in manifest["workloads"]:
        if not wanted or cell["name"] in wanted:
            faults += rehearse_cell(cell, args.seconds, args.seed)
    for fault in faults:
        print("FAULT:", fault)
    print("rehearsal ok" if not faults else f"{len(faults)} fault(s)")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
