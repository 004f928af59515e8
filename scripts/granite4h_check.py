"""The builder's run of the Granite cell on the chip: how close the program
comes to the plain reference at the published widths and the cell's row of
8,192 tokens, in values and in gradients, and whether it trains.

    chiprun --chips 1 --timeout 3000 -- bash -c "
        python3 scripts/granite4h_check.py logits --seeds 4900000101,... &&
        python3 scripts/granite4h_check.py grads --seed 4900000201 &&
        python3 scripts/granite4h_check.py train --steps 200"
    python3 scripts/granite4h_check.py logits --tiny 1   # here: control flow

* ``logits``: the cell's model check (``benchmark/run.py`` ``check_model``'s
  statistic: worst logit difference over the logits' spread, every token
  live) for the program as it runs (the timed path's own attention,
  convolution, dual and norm forms, whichever the rules chose) and for the
  reference computed in bf16 (``forward(..., dtype=jnp.bfloat16)``), both
  against the float32 reference fed the same perturbed parameters, a seed a
  line; the row is the data set's row ``seed % 8``.
* ``grads``: one step's gradients of the training loss, the program's
  (bf16 compute, ``--remat`` as the cell runs) against ``jax.grad`` of the
  reference's loss in float32, on one row: relative error by leaf (norm of
  the difference over norm), worst first. The reference's layers are
  recomputed in its backward pass (``jax.checkpoint`` around ``layer``, here
  and not in the reference's file) so that both fit the chip.
* ``train``: ``--steps`` steps of ``train()`` under the cell's own flags on
  the cell's own data, the loss printed every 20 steps: it starts at
  ln(12,544) = 9.44 and has to fall.

Each part is a process of its own (one holds the chip at a time). Prints a
line a reading and appends to ``chiprun_out/granite4h_check.jsonl``. Not
tier-1; ``PERF.md`` section 6 (PR 49) holds what it gave.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
CELL = "c4-granite4h-vp8-prepacked-8k"


def say(record: dict) -> None:
    print(json.dumps(record), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "granite4h_check.jsonl"), "a") as f:
        f.write(json.dumps(record) + "\n")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("part", choices=("logits", "grads", "train"))
    ap.add_argument("--seeds", default="4900000101,4900000102,4900000103")
    ap.add_argument("--seed", type=int, default=4900000201)
    ap.add_argument("--data_seed", type=int, default=4900000001)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--tiny", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import run
    from reference import reader

    from lance_distributed_training_tpu import trainer
    from lance_distributed_training_tpu.models import get_task

    cell = run.load_cell(CELL, rehearsal=bool(args.tiny))
    config, traffic = cell["config"], cell["traffic"]
    trainer.maybe_enable_compile_cache(jax.devices()[0].platform)
    _, dataset_dir, info = run.author_dataset(
        traffic, args.data_seed, os.path.join(ROOT, "benchmark", "data"))
    print("data set:", dataset_dir, flush=True)

    if args.part == "train":
        from lance_distributed_training_tpu import cli

        flags = [f if f != "5" else "20" for f in traffic["train_flags"]]
        results = cli.main([
            "train", "--dataset_path", dataset_dir, "--batch_size",
            str(traffic["global_batch"]), "--epochs", "1", "--max_steps",
            str(args.steps), "--seed", str(args.seed), "--no_wandb",
            "--no_eval_at_end", *config["train_flags"], *flags])
        say({"part": "train", "steps": args.steps,
             "loss": float(results["loss"]),
             "start": float(np.log(config["task"]["vocab_size"]))})
        return

    ref = run.load_module("reference", config["name"])
    rows = reader.read_rows(dataset_dir, 0, 8)
    ref.EVAL_ROWS = 8
    eight = ref.eval_batch(rows, config)
    task = get_task(**config["task"], remat=True)

    def make(key):
        key_init, key_perturb = jax.random.split(key)
        return ref.perturb(task.init_variables(key_init), key_perturb)

    def one_row(seed):
        at = seed % 8
        return {k: v[at:at + 1] for k, v in eight.items()}

    def reading(got, want, live):
        live = live[..., None]
        n = live.sum() * want.shape[-1]
        mean = jnp.where(live, want, 0).sum() / n
        spread = jnp.sqrt(jnp.where(live, (want - mean) ** 2, 0).sum() / n)
        return jnp.where(live, jnp.abs(got - want), 0).max() / spread, spread

    if args.part == "logits":
        @jax.jit
        def compare(variables, b):
            want = ref.forward(variables, b)
            live = ref.live(b, want)
            got = task.forward(variables, b, False, None)[0][0].astype(
                jnp.float32)
            low = ref.forward(variables, b, dtype=jnp.bfloat16)
            seq = want.shape[1]
            quarters = [reading(got[:, q:q + seq // 4],
                                want[:, q:q + seq // 4],
                                live[:, q:q + seq // 4])[0]
                        for q in range(0, seq, seq // 4)]
            return (*reading(got, want, live), reading(low, want, live)[0],
                    jnp.stack(quarters))

        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.monotonic()
            program, spread, below, quarters = compare(
                jax.jit(make)(jax.random.key(seed)), one_row(seed))
            say({"part": "logits", "seed": seed, "row": seed % 8,
                 "program": float(program), "reference_bf16": float(below),
                 "spread": float(spread), "tolerance": ref.TOLERANCE,
                 "program_by_quarter": [float(q) for q in quarters],
                 "kernels": task.kernels,
                 "seconds": round(time.monotonic() - t, 1)})
        return

    # grads
    batch = one_row(args.seed)
    variables = jax.jit(make)(jax.random.key(args.seed))
    layer = ref.layer
    ref.layer = jax.checkpoint(layer, static_argnums=(3,))
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda v: ref.loss(v, batch)))(variables)
    want = jax.device_get(want["params"])
    ref.layer = layer

    def program_loss(v):
        outputs, _ = task.forward(v, batch, True, None)
        return task.loss(outputs, batch)

    got_loss, got = jax.jit(jax.value_and_grad(program_loss))(variables)
    got = jax.device_get(got["params"])
    leaves = []
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        leaves.append(("/".join(p.key for p in path),
                       float(np.linalg.norm(g - w) / np.linalg.norm(w)),
                       float(np.linalg.norm(w))))
    leaves.sort(key=lambda r: -r[1])
    by_name: dict = {}
    for name, err, _ in leaves:
        last = name.split("/", 1)[1] if name.startswith("layer_") else name
        by_name[last] = max(by_name.get(last, 0.0), err)
    say({"part": "grads", "seed": args.seed, "loss": float(got_loss),
         "reference_loss": float(want_loss),
         "worst": [[n, round(e, 5), w] for n, e, w in leaves[:8]],
         "worst_by_parameter": {k: round(v, 5) for k, v in sorted(
             by_name.items(), key=lambda kv: -kv[1])}})


if __name__ == "__main__":
    main()
