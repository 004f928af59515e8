"""Does the benchmark's comparison tell Laguna's layers from their near
misses? The cell's model check (``benchmark/run.py`` ``check_model``) at the
published widths and the timed row, once for the program as it runs and once
for each of five wrong programs, all against the plain float32 reference fed
the same perturbed parameters and read on the same tokens:

* ``no_gate``: no gate a head (the attention output as it is);
* ``plain_rotary_in_full``: the full layers turn half a head at theta's own
  frequencies, with no YaRN table and no factor on cos and sin;
* ``no_window``: the window layers see the whole causal row;
* ``softmax_scores``: the router's scores are a softmax, not sigmoids;
* ``reference_bf16``: the reference itself in the precision below.

Each has to read over the reference's ``TOLERANCE`` where the program reads
under it. Readings are worst logit difference over the logits' spread on the
tokens ``live`` keeps, as ``check_model`` computes them.

    chiprun --chips 1 --timeout 1800 -- python3 scripts/laguna_variants.py
    python3 scripts/laguna_variants.py --tiny 1
        # here, on the CPU: the control flow at the tiny preset, no reading
        # that means anything for the chip (--seq 1024 --only program at the
        # published widths takes some minutes and 10 GB here)

Prints a line a reading and writes ``chiprun_out/laguna_variants.jsonl``.
Not tier-1; ``PERF.md`` section 6 (PR 53) holds what it gave.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
CELL = "c4-laguna-ep32-prepacked-8k"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1746031391,2994967295,3,77")
    ap.add_argument("--seq", type=int, default=0, help="0: the cell's row")
    ap.add_argument("--tiny", type=int, default=0)
    ap.add_argument("--only", default="", help="these variants alone")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import run

    from lance_distributed_training_tpu.models import get_task, transformer

    cell = run.load_cell(CELL, rehearsal=bool(args.tiny))
    config = cell["config"]
    ref = run.load_module("reference", config["name"])
    task_args = dict(config["task"])
    if args.seq:
        task_args["seq_len"] = args.seq
    seq, vocab = task_args["seq_len"], task_args["vocab_size"]
    preset = transformer.CAUSAL_LMS[task_args.pop("model_name")]

    def task(sizes=None, **changes):
        """The cell's task with fields of the preset's constructor changed;
        ``sizes``: a layer kind to the fields of its mixer that change."""
        if sizes:
            changes["parts"] = tuple(
                (kind, functools.partial(part, **sizes.get(kind, {})))
                for kind, part in preset.ctor.keywords["parts"])
        if "moe" in changes:
            changes["moe"] = tuple({**dict(preset.ctor.keywords["moe"]),
                                    **changes["moe"]}.items())
        transformer.CAUSAL_LMS["laguna_variant"] = preset._replace(
            ctor=functools.partial(preset.ctor, **changes))
        try:
            return get_task(model_name="laguna_variant", **task_args)
        finally:
            del transformer.CAUSAL_LMS["laguna_variant"]

    right = task()
    programs = {
        "program": right,
        "no_gate": task({"GW": {"head_gate": False},
                         "GF": {"head_gate": False}}),
        "plain_rotary_in_full": task({"GF": {"yarn": ()}}),
        "no_window": task({"GW": {"window": 0}}),
        "softmax_scores": task(moe={"scoring": "softmax"}),
    }
    if args.only:
        programs = {k: v for k, v in programs.items()
                    if k in args.only.split(",")}
    ref.configure(config)

    @jax.jit
    def make(key):
        key_init, key_perturb = jax.random.split(key)
        return ref.perturb(right.init_variables(key_init), key_perturb)

    def reading(got, want, live):
        live = live[..., None]
        n = live.sum() * want.shape[-1]
        mean = jnp.where(live, want, 0).sum() / n
        spread = jnp.sqrt(jnp.where(live, (want - mean) ** 2, 0).sum() / n)
        worst = jnp.where(live, jnp.abs(got - want), 0).max()
        return worst / spread, spread

    @jax.jit
    def reference(variables, b):
        want = ref.forward(variables, b)
        return want, ref.live(b, want)

    @jax.jit
    def reference_low(variables, b, want, live):
        return reading(ref.forward(variables, b, dtype=jnp.bfloat16), want,
                       live)

    compiled = {}
    for name, variant in programs.items():
        def compare(variables, b, want, live, variant=variant):
            got = variant.forward(variables, b, False, None)[0][0]
            return reading(got.astype(jnp.float32), want, live)
        compiled[name] = jax.jit(compare)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "laguna_variants.jsonl"),
               "a")
    device = jax.devices()[0]
    print(f"device: {device.device_kind} x {jax.device_count()} "
          f"({device.platform}); seq {seq}, vocab {vocab}, TOLERANCE "
          f"{ref.TOLERANCE}, MARGIN {ref.MARGIN}, OFFSET {ref.OFFSET}",
          flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        ids = np.random.default_rng(seed).integers(2, vocab, (1, seq))
        batch = {"input_ids": ids.astype(np.int32),
                 "attention_mask": np.ones((1, seq), np.int8)}
        variables = make(jax.random.key(seed))
        t0 = time.monotonic()
        want, live = reference(variables, batch)
        row = {"seed": seed, "margin": ref.MARGIN, "seq": seq,
               "platform": device.platform,
               "kept_pct": round(100 * float(live.mean()), 2)}
        for name, fn in compiled.items():
            value, spread = fn(variables, batch, want, live)
            row[name] = round(float(value), 4)
        value, spread = reference_low(variables, batch, want, live)
        row["reference_bf16"] = round(float(value), 4)
        row["spread"] = round(float(spread), 4)
        row["seconds"] = round(time.monotonic() - t0, 1)
        print(json.dumps(row), flush=True)
        out.write(json.dumps(row) + "\n")
        out.flush()


if __name__ == "__main__":
    main()
