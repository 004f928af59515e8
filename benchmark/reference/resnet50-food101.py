"""Plain reference of the ResNet-50 classifier (He et al., arXiv:1512.03385,
the v1.5 layout with the stride on the 3x3 convolution, as torchvision and
the program have it): float32 ``jax.numpy`` and ``lax.conv`` only, eval mode
(batch norm on its running statistics), NHWC, fed the program's own
parameter tree. Departures from the paper, all the program's: none in the
network; the input is uint8 scaled to [0, 1] and normalised with the
ImageNet mean and deviation, and the picture is squashed to 224 x 224
without keeping its aspect (the reference job's ``Resize((224, 224))``).
"""

from __future__ import annotations

import numpy as np

STAGES = (3, 4, 6, 3)
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)

# bf16 activations through 53 convolutions against float32 at highest
# precision: the logits' worst difference stays near 2% of their spread
# (measured on the v5e, PR 23, in PERF.md). Leaving out one block, one
# shortcut or one batch norm moves them by more than half their spread.
TOLERANCE = 0.10


def eval_batch(rows, config: dict) -> dict:
    """The batch the program's task takes, made by the plain reader."""
    from reference import reader

    size = int(config["task"]["image_size"])
    return {"image": reader.decode_images(rows, size),
            "label": np.asarray(rows.column("label").to_numpy(), np.int32)}


def perturb(variables, rng):
    """Seeded values for what initialisation leaves degenerate: the last
    batch norm of every block starts with scale 0, which silences the whole
    residual branch, and the running statistics start at 0 and 1. Give the
    scales 0.5, and the statistics a seeded spread, so that every layer
    takes part in the logits."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(variables)[0]
    keys = jax.random.split(rng, len(flat))
    out = []
    for (path, leaf), key in zip(flat, keys):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if name.endswith("scale"):
            leaf = jnp.where(jnp.any(leaf != 0), leaf, 0.5)
        elif name.endswith("/mean"):
            leaf = 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype)
        elif name.endswith("/var"):
            leaf = jax.random.uniform(key, leaf.shape, leaf.dtype, 0.5, 1.5)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(variables), out)


def live(batch, want):
    """Which logits mean something: every picture's."""
    import jax.numpy as jnp

    return jnp.ones(want.shape[:1], bool)


def forward(variables, batch):
    import jax
    import jax.numpy as jnp
    from jax import lax

    params, stats = variables["params"], variables["batch_stats"]

    def conv(x, p, stride=1, pad=0):
        return lax.conv_general_dilated(
            x, p["kernel"].astype(jnp.float32), (stride, stride),
            [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def bn(x, p, s):
        inv = p["scale"] / jnp.sqrt(s["var"] + 1e-5)
        return (x - s["mean"]) * inv + p["bias"]

    with jax.default_matmul_precision("highest"):
        x = batch["image"].astype(jnp.float32) / 255.0
        x = (x - jnp.asarray(MEAN, jnp.float32)) / jnp.asarray(STD, jnp.float32)
        x = jax.nn.relu(bn(conv(x, params["conv_init"], 2, 3),
                           params["norm_init"], stats["norm_init"]))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                              [(0, 0), (1, 1), (1, 1), (0, 0)])
        n = 0
        for stage, count in enumerate(STAGES):
            for j in range(count):
                stride = 2 if stage > 0 and j == 0 else 1
                p = params[f"BottleneckBlock_{n}"]
                s = stats[f"BottleneckBlock_{n}"]
                y = jax.nn.relu(bn(conv(x, p["Conv_0"]), p["BatchNorm_0"],
                                   s["BatchNorm_0"]))
                y = jax.nn.relu(bn(conv(y, p["Conv_1"], stride, 1),
                                   p["BatchNorm_1"], s["BatchNorm_1"]))
                y = bn(conv(y, p["Conv_2"]), p["BatchNorm_2"], s["BatchNorm_2"])
                if "conv_proj" in p:
                    x = bn(conv(x, p["conv_proj"], stride), p["norm_proj"],
                           s["norm_proj"])
                x = jax.nn.relu(x + y)
                n += 1
        x = x.mean(axis=(1, 2))
        return x @ params["head"]["kernel"] + params["head"]["bias"]
