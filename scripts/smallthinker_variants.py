"""Does the benchmark's comparison tell SmallThinker's layers from their near
misses? The cell's model check (``benchmark/run.py`` ``check_model``) at the
published widths and the timed row, once for the program as it runs and once
for each of five wrong programs, all against the plain float32 reference fed
the same perturbed parameters and read on the same tokens:

* ``no_window``: the W layers see the whole causal row;
* ``rotary_in_full``: the N layer turns its queries and keys too;
* ``router_late``: the router reads ``ln_mlp``'s output, not the layer's input;
* ``silu``: the experts' gate is SiLU, not ReLU;
* ``reference_bf16``: the reference itself in the precision below.

Each has to read over the reference's ``TOLERANCE`` where the program reads
under it. Readings are worst logit difference over the logits' spread on the
tokens ``live`` keeps, as ``check_model`` computes them.

    chiprun --chips 1 --timeout 1800 -- python3 scripts/smallthinker_variants.py
    python3 scripts/smallthinker_variants.py --seq 512 --window 128 --tiny 1
        # here, on the CPU: the control flow at the tiny preset, no reading
        # that means anything for the chip

Prints a line a reading and writes ``chiprun_out/smallthinker_variants.jsonl``.
Not tier-1; ``PERF.md`` section 6 (PR 46) holds what it gave.
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


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1746031391,2994967295,3")
    ap.add_argument("--seq", type=int, default=0, help="0: the cell's row")
    ap.add_argument("--window", type=int, default=0, help="0: the model's")
    ap.add_argument("--tiny", type=int, default=0)
    ap.add_argument("--margins", default="",
                    help="further margins, read on the first seed alone")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import run

    from lance_distributed_training_tpu.models import get_task, transformer

    cell = run.load_cell("c4-smallthinker-ep4-prepacked-16k",
                         rehearsal=bool(args.tiny))
    config = cell["config"]
    ref = run.load_module("reference", config["name"])
    task_args = dict(config["task"])
    if args.seq:
        task_args["seq_len"] = args.seq
    seq, vocab = task_args["seq_len"], task_args["vocab_size"]
    base = task_args.pop("model_name")
    preset = transformer.CAUSAL_LMS[base]
    attention = preset.ctor.keywords["parts"][0]
    window = args.window or attention.keywords["window"]

    def task(**changes):
        """The cell's task with fields of the preset's constructor changed."""
        parts = (functools.partial(attention, window=changes.pop(
            "window", window)),)
        if "moe" in changes:
            changes["moe"] = tuple({**dict(preset.ctor.keywords["moe"]),
                                    **changes["moe"]}.items())
        transformer.CAUSAL_LMS["smallthinker_variant"] = preset._replace(
            ctor=functools.partial(preset.ctor, parts=parts, **changes))
        try:
            return get_task(model_name="smallthinker_variant", **task_args)
        finally:
            del transformer.CAUSAL_LMS["smallthinker_variant"]

    # a full layer that turns its queries and keys: a kind of this script's
    transformer.LAYER_KINDS["N+rotary"] = transformer.LAYER_KINDS[
        "N"]._replace(fixed=(("window", 0),))
    turned = tuple("N+rotary" if kind == "N" else kind
                   for kind in preset.ctor.keywords["layer_kinds"])

    def router_into_the_expert_layer(variables):
        """The same parameters where the late router looks for them."""
        params = {name: ({**{k: v for k, v in layer.items() if k != "router"},
                          "moe": {**layer["moe"], "router": layer["router"]}}
                         if name.startswith("layer_") else layer)
                  for name, layer in variables["params"].items()}
        return {**variables, "params": params}

    right = task()
    programs = {
        "program": (right, lambda v: v),
        "no_window": (task(window=0), lambda v: v),
        "rotary_in_full": (task(layer_kinds=turned), lambda v: v),
        "router_late": (task(router_early=False),
                        router_into_the_expert_layer),
        "silu": (task(moe={"activation": "silu"}), lambda v: v),
    }
    model = config["model"]  # what eval_batch reads of the configuration
    ref.TOP_K = int(model["moe_num_active_primary_experts"])
    ref.THETA = float(model["rope_theta"])
    ref.WINDOW = window
    ref.FIRST = 0

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

    @functools.lru_cache(maxsize=None)
    def reference(margin):
        """The reference's logits and the tokens ``live`` keeps at ``margin``
        (read while the program is traced: a program a margin)."""
        def both(variables, b):
            ref.MARGIN = margin
            want = ref.forward(variables, b)
            return want, ref.live(b, want)
        return jax.jit(both)

    @jax.jit
    def reference_low(variables, b, want, live):
        return reading(ref.forward(variables, b, dtype=jnp.bfloat16), want,
                       live)

    compiled = {}
    for name, (variant, move) in programs.items():
        def compare(variables, b, want, live, variant=variant, move=move):
            got = variant.forward(move(variables), b, False, None)[0][0]
            return reading(got.astype(jnp.float32), want, live)
        compiled[name] = jax.jit(compare)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out",
                            "smallthinker_variants.jsonl"), "a")
    device = jax.devices()[0]
    print(f"device: {device.device_kind} x {jax.device_count()} "
          f"({device.platform}); seq {seq}, window {window}, vocab {vocab}, "
          f"TOLERANCE {ref.TOLERANCE}, MARGIN {ref.MARGIN}", flush=True)
    margins = [ref.MARGIN] + [float(m) for m in args.margins.split(",") if m]
    seeds = [int(s) for s in args.seeds.split(",")]
    first = seeds[0]
    for seed in seeds:
        ids = np.random.default_rng(seed).integers(2, vocab, (1, seq))
        batch = {"input_ids": ids.astype(np.int32),
                 "attention_mask": np.ones((1, seq), np.int8)}
        variables = make(jax.random.key(seed))
        for margin in margins if seed == first else margins[:1]:
            t0 = time.monotonic()
            want, live = reference(margin)(variables, batch)
            row = {"seed": seed, "margin": margin, "seq": seq,
                   "window": window, "platform": device.platform,
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
