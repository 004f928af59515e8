#!/usr/bin/env python3
"""What one train step of a benchmark configuration takes of a v5e chip's
memory, by the chip's compiler and without the chip: the whole step
(forward, backward, AdamW with clipping, the step's stats) is lowered for a
*described* v5e with the kernel forms the chip would choose, and the
compiler's report is printed. A compile, not a run: the rungs of a
configuration's ``ladder`` are made with this.

    python3 scripts/step_memory.py granite-4.0-h-micro-c4 [--rows 1]
        [--no_remat] [--text out.txt] [--largest 12]

The program's rules ask ``jax.default_backend()`` and ``jax.device_count()``
while a step is traced, and here both would answer for the CPU; this script
answers "tpu" and 1 for them while it traces, in the script and not through
an option of the program (the ``on-chip-measurement`` guide, section 2).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
GIB = 2.0 ** 30


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("config")
    parser.add_argument("--rows", type=int, default=1)
    parser.add_argument("--no_remat", action="store_true")
    parser.add_argument("--text", help="write the compiled HLO text here")
    parser.add_argument("--largest", type=int, default=12,
                        help="print this many of the largest temporaries")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lance_distributed_training_tpu import cli, trainer

    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{args.config}.json")) as f:
        config = json.load(f)
    flags = [f for f in config["train_flags"]
             if not (args.no_remat and f == "--remat")]
    parsed = cli.build_parser().parse_args(
        ["--dataset_path", "-", "--batch_size", str(args.rows),
         "--no_wandb", *flags])
    train_config = trainer.TrainConfig(**{
        field: getattr(parsed, field)
        for field in trainer.TrainConfig.__dataclass_fields__
        if hasattr(parsed, field)})

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    jax.default_backend = lambda: "tpu"  # the rules' two questions, while
    jax.device_count = lambda *a, **k: 1  # this script traces
    task = trainer._task_from_config(train_config, mesh)
    print("kernels:", trainer._kernel_paths(task, train_config), flush=True)
    state = jax.eval_shape(
        lambda key: trainer.create_train_state(key, task, train_config),
        jax.random.key(0))
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(state.params))
    repl = NamedSharding(mesh, P())

    def on_chip(tree, sharding=repl):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    seq = int(config["task"]["seq_len"])
    batch = {"input_ids": jax.ShapeDtypeStruct((args.rows, seq), jnp.int32),
             "attention_mask": jax.ShapeDtypeStruct((args.rows, seq),
                                                    jnp.int8)}
    step = trainer.make_train_step(task, mesh, stats=True)
    compiled = step.lower(
        on_chip(state), on_chip(batch, NamedSharding(mesh, P("data"))),
        on_chip(jax.eval_shape(lambda: jax.random.key(0)))).compile()
    memory = compiled.memory_analysis()
    kept = memory.argument_size_in_bytes + memory.output_size_in_bytes \
        - memory.alias_size_in_bytes
    print(f"{args.config}: {held:,} parameters, {args.rows} row(s) of {seq}, "
          f"remat={'--remat' in flags}")
    print(f"state {kept / GIB:.2f} GiB + scratch "
          f"{memory.temp_size_in_bytes / GIB:.2f} GiB = "
          f"{(kept + memory.temp_size_in_bytes) / GIB:.2f} GiB "
          f"(arguments {memory.argument_size_in_bytes / GIB:.2f}, outputs "
          f"{memory.output_size_in_bytes / GIB:.2f}, aliased "
          f"{memory.alias_size_in_bytes / GIB:.2f}, code "
          f"{memory.generated_code_size_in_bytes / GIB:.3f})")
    text = compiled.as_text()
    print("tpu_custom_call:", text.count("tpu_custom_call"),
          " while(:", text.count(" while("))
    if args.text:
        with open(args.text, "w") as f:
            f.write(text)
    sizes = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "s8": 1, "pred": 1,
             "f16": 2, "u8": 1}
    seen: dict = {}
    for dtype, dims in re.findall(r"= (\w+)\[([0-9,]+)\]", text):
        if dtype in sizes:
            n = sizes[dtype] * int(np.prod([int(d) for d in dims.split(",")]))
            key = f"{dtype}[{dims}]"
            seen[key] = (n, seen.get(key, (n, 0))[1] + 1)
    for key, (n, count) in sorted(seen.items(), key=lambda kv: -kv[1][0])[
            :args.largest]:
        print(f"  {n / GIB:6.3f} GiB  x{count:<4d} {key}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
