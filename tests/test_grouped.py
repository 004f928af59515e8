"""The held experts' grouped products (``ops/grouped.py``): each group of
sorted rows times its own expert's matrix.

*Is the kernel form the plain form?* The library's Pallas kernels in
interpret mode on one device, under this module's differentiation rule,
against ``jax.lax.ragged_dot`` and against a loop over the groups in
``jax.numpy``, all f32: the values and the cotangents of the rows and of the
matrices, with a group of no rows, groups that end inside a tile, rows past
the last group (exactly zero, and passing nothing back even where they hold
what is not finite), even and skewed sizes, tiles that divide the shape and a
ragged last tile. *Does the rule choose as it says?* Every entry of the table
fits its shape and names a cell's call; a shape without an entry, a mesh,
several devices and a CPU take the plain form. *Does the layer call it?*
``DroplessMoE`` with the kernels bound against its plain self, whole and
under a share, and the gauge. *Does the table hold to its rule of admission?*
Every entry is the tilings at which a committed sweep file
(``scripts/grouped_sweep/<shape>.jsonl``) shows the whole-layer program
ahead of XLA's on even and on skewed groups; a shape without such a file
has no entry.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lance_distributed_training_tpu.models import moe
from lance_distributed_training_tpu.obs.registry import default_registry
from lance_distributed_training_tpu.ops import grouped as ops

SQUARE = ops.Tiling(*((128, 128, 128),) * 3)


def _loop(xs, w, sizes):
    """Group after group in ``jax.numpy``; the rows past the last are zeros."""
    out, at = jnp.zeros((xs.shape[0], w.shape[2]), jnp.float32), 0
    for g, size in enumerate(np.asarray(sizes)):
        out = out.at[at:at + size].set(jnp.matmul(
            xs[at:at + size], w[g], precision="highest"))
        at += size
    return out


def _both(form, xs, w, ct):
    def loss(xs, w):
        y = form(xs, w)
        return (y * ct).sum(), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(xs, w)
    return jax.block_until_ready((y, *grads))


def _kernel(xs, w, sizes, ct, tiling):
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        return _both(lambda xs, w: ops.kernel_product(
            xs, w, jnp.asarray(sizes, jnp.int32), tiling), xs, w, ct)


CASES = {  # rows built, K, N, the groups' sizes, tilings
    "even": (512, 256, 128, (128, 128, 128, 128), SQUARE),
    "skewed_ends_inside_tiles": (512, 256, 384, (100, 7, 300, 105), SQUARE),
    "a_group_of_no_rows": (512, 128, 256, (200, 0, 57, 0, 130), SQUARE),
    "rows_past_the_last_group": (768, 256, 256, (90, 0, 210, 37), SQUARE),
    "whole_tiles_past_the_last_group": (1024, 128, 128, (60, 70), SQUARE),
    "no_live_row": (256, 128, 128, (0, 0, 0), SQUARE),
    "one_group": (256, 128, 256, (256,), SQUARE),
    "wide_tiles": (1024, 512, 384, (300, 200, 24, 400),
                   ops.Tiling((256, 512, 384), (512, 384, 256),
                              (256, 256, 384))),
    # eleven lane groups as Moonlight's 1,408 is, in tiles of four: ragged
    "a_ragged_last_tile": (512, 256, 1408, (100, 200, 50, 60),
                           ops.Tiling((128, 256, 512), (128, 512, 256),
                                      (128, 256, 512))),
}


def _entry_case(shape):
    """An entry's call cut down for the interpreter: its K, N and tile
    shapes, four of its widest row tiles, three groups that end inside
    tiles and one of no rows; every row live where the cell's list has no
    dead rows (all 64 of OLMoE's experts held), a dead tail elsewhere."""
    _, groups, k, n = shape
    tiling = ops.TILINGS[shape]
    rows = 4 * max(tm for tm, _, _ in tiling)
    live = rows if groups == 64 else rows - rows // 8 - 5
    first = rows // 4 + 37
    return rows, k, n, (first, 0, live - first - 100, 100), tiling


CASES.update({"entry_" + "x".join(map(str, shape)): _entry_case(shape)
              for shape in sorted(ops.TILINGS)})


@pytest.mark.parametrize("case", CASES)
def test_the_kernels_in_interpret_mode_are_the_plain_form(case):
    rows, k, n, sizes, tiling = CASES[case]
    assert ops.fits(rows, k, n, tiling)
    keys = jax.random.split(jax.random.key(len(case)), 3)
    xs = jax.random.normal(keys[0], (rows, k))
    w = jax.random.normal(keys[1], (len(sizes), k, n)) * 0.1
    ct = jax.random.normal(keys[2], (rows, n))
    live = sum(sizes)
    # what the dead rows hold reaches nothing
    xs = xs.at[live:].set(jnp.nan)
    gs = jnp.asarray(sizes, jnp.int32)
    got = _kernel(xs, w, sizes, ct, tiling)
    clean = jnp.nan_to_num(xs)
    by_loop = _both(lambda xs, w: _loop(xs, w, sizes), clean, w, ct)
    by_xla = _both(lambda xs, w: jax.lax.ragged_dot(
        xs, w, gs, precision="highest"), clean, w, ct)
    for name, a, b, c in zip(("y", "d_xs", "d_w"), got, by_loop, by_xla):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-4, err_msg=name)
        np.testing.assert_allclose(a, c, rtol=2e-5, atol=1e-4, err_msg=name)
    assert not np.asarray(got[0][live:]).any()  # exactly zero: y, d_xs
    assert not np.asarray(got[1][live:]).any()


def test_bf16_operands_give_bf16_results_and_cotangents():
    rows, k, n, sizes, tiling = CASES["skewed_ends_inside_tiles"]
    keys = jax.random.split(jax.random.key(0), 3)
    xs = jax.random.normal(keys[0], (rows, k), jnp.bfloat16)
    w = (jax.random.normal(keys[1], (len(sizes), k, n)) * 0.1).astype(
        jnp.bfloat16)
    ct = jax.random.normal(keys[2], (rows, n), jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    got = _kernel(xs, w, sizes, ct, tiling)
    want = _both(lambda xs, w: jax.lax.ragged_dot(xs, w, gs), xs, w, ct)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32), rtol=2e-2, atol=2e-2)


class _Mesh:
    def __init__(self, size):
        self.size = size


CELLS = {  # (rows built, groups held, hidden, expert width) of the usual list
    "moonlight": (12288, 8, 2048, 1408),
    "qwen3_next": (20480, 32, 2048, 512),
    "smallthinker": (49152, 16, 2560, 768),
    "zaya1": (8192, 8, 2048, 2048),
    "olmoe": (65536, 64, 2048, 1024),
}


def test_every_entry_fits_its_shape_and_is_a_cells_call():
    calls = {(r, g, *kn) for r, g, h, d in CELLS.values()
             for kn in ((h, d), (d, h))}
    for shape, tiling in ops.TILINGS.items():
        assert shape in calls, shape
        rows, _, k, n = shape
        assert isinstance(tiling, ops.Tiling)
        assert ops.fits(rows, k, n, tiling), (shape, tiling)
        assert all(tm in (128, 256, 512) for tm, _, _ in tiling)


SWEEPS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts", "grouped_sweep")


@pytest.mark.parametrize("cell", CELLS)
def test_the_table_holds_to_its_rule_of_admission(cell):
    """An entry rests on a committed file of
    ``scripts/grouped_products_sweep.py`` in which the layer's whole program
    (three products, their gate, forward and ``value_and_grad``) under the
    kernels *at the entry's tilings* is ahead of XLA's by ``AHEAD`` on even
    groups and on the cell's skew alike; the one shape with no such file
    has no entry (SmallThinker's: behind on even groups in PR 50's
    sweep)."""
    rows, groups, hidden, width = CELLS[cell]
    calls = {"in": (rows, groups, hidden, width),
             "out": (rows, groups, width, hidden)}
    held = {side: ops.TILINGS.get(call) for side, call in calls.items()}
    path = os.path.join(SWEEPS, f"{cell}.jsonl")
    if not os.path.exists(path):
        assert cell == "smallthinker" and not any(held.values())
        return
    assert all(held.values()), "gate, up and down run one form"
    with open(path) as f:
        whole = [r for r in map(json.loads, f)
                 if r["stage"] == "whole" and r.get("ms")]
    xla = {r["sizes"]: r["ms"] for r in whole if not r["tiling"]}
    ours = {r["sizes"]: r["ms"] for r in whole if r["tiling"] and all(
        ops.Tiling(*map(tuple, r["tiling"][side])) == held[side]
        for side in calls)}
    assert set(xla) == set(ours) == {"even", "skewed"}, (xla, ours)
    for kind in ("even", "skewed"):
        assert ours[kind] <= (1 - ops.AHEAD) * xla[kind], (kind, xla, ours)


@pytest.mark.parametrize("tiling,says", [
    (SQUARE, True),
    (ops.Tiling((512, 2048, 1408), (512, 1408, 2048), (512, 2048, 1408)),
     True),
    (ops.Tiling((512, 1024, 768), (512, 512, 1024), (256, 1024, 512)), True),
    (ops.Tiling((1024, 128, 128), (128, 128, 128), (128, 128, 128)), True),
    (ops.Tiling((5120, 128, 128), (128, 128, 128), (128, 128, 128)), False),
    (ops.Tiling((128, 704, 128), (128, 128, 128), (128, 128, 128)), False),
    (ops.Tiling((128, 128, 128), (128, 128, 704), (128, 128, 128)), False),
    (ops.Tiling((128, 128, 128), (128, 128, 128), (100, 128, 128)), False),
    (ops.Tiling((128, 4096, 128), (128, 128, 128), (128, 128, 128)), False),
], ids=["square", "whole", "ragged", "tm_1024", "tm_over_rows", "half_lanes",
        "half_lanes_dlhs", "tm_100", "wider_than_k"])
def test_which_tilings_fit_moonlights_call(tiling, says):
    assert ops.fits(12288, 2048, 1408, tiling) is says


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("why,kwargs,devices", [
    ("a CPU", {"platform": "cpu"}, 1),
    ("a mesh", {"platform": "tpu", "mesh": _Mesh(4)}, 1),
    ("several devices", {"platform": "tpu"}, 4),
])
def test_off_one_tpu_device_every_shape_takes_the_plain_form(
        cell, why, kwargs, devices, monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda *a: devices)
    rows, groups, hidden, width = CELLS[cell]
    for k, n in ((hidden, width), (width, hidden)):
        assert ops.grouped_tiling(rows, groups, k, n, **kwargs) is None, why


@pytest.mark.parametrize("cell", CELLS)
def test_on_one_tpu_device_the_table_is_the_rule(cell, monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    rows, groups, hidden, width = CELLS[cell]
    for k, n in ((hidden, width), (width, hidden)):
        for kwargs in ({}, {"mesh": _Mesh(1)}):
            assert ops.grouped_tiling(
                rows, groups, k, n, platform="tpu", **kwargs) is \
                ops.TILINGS.get((rows, groups, k, n))
        # the worst-case list, another count of groups, a shape nobody timed
        assert ops.grouped_tiling(rows * 4 + 128, groups, k, n,
                                  platform="tpu") is None
        assert ops.grouped_tiling(rows, groups + 1, k, n,
                                  platform="tpu") is None
        assert ops.grouped_tiling(rows, groups, k + 128, n,
                                  platform="tpu") is None


def test_moonlights_call_has_its_entries_both_ways(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    assert ops.grouped_tiling(12288, 8, 2048, 1408, platform="tpu")
    assert ops.grouped_tiling(12288, 8, 1408, 2048, platform="tpu")
    assert ops.grouped_tiling(49152, 8, 2048, 1408, platform="tpu") is None


def test_off_the_tpu_the_chooser_is_ragged_dot_and_the_gauge_stays():
    default_registry().gauge("grouped_products_fused").set(0.0)
    keys = jax.random.split(jax.random.key(1), 2)
    xs = jax.random.normal(keys[0], (256, 128), jnp.bfloat16)
    w = jax.random.normal(keys[1], (3, 128, 256), jnp.bfloat16)
    gs = jnp.asarray((100, 0, 90), jnp.int32)

    def lowered(form):
        return jax.jit(jax.grad(lambda xs, w: form(xs, w, gs).astype(
            jnp.float32).sum(), argnums=(0, 1))).lower(xs, w).as_text()

    assert lowered(ops.grouped_product) == lowered(jax.lax.ragged_dot)
    np.testing.assert_array_equal(ops.grouped_product(xs, w, gs),
                                  jax.lax.ragged_dot(xs, w, gs))
    assert default_registry().gauge("grouped_products_fused").value == 0.0


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_without_an_entry_lowers_ragged_dot_on_one_tpu_device(
        cell, monkeypatch):
    """On one TPU device (the rule's two questions answered for it here) a
    call at the cell's full shapes lowers, value and both cotangents, to
    ``jax.lax.ragged_dot``'s text where the table has no entry
    (SmallThinker's step is the parent's) and traces the kernels where it has;
    the worst-case list lowers ``ragged_dot``'s in every cell."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    rows, groups, hidden, width = CELLS[cell]

    def call(rows):
        return (jax.ShapeDtypeStruct((rows, hidden), jnp.bfloat16),
                jax.ShapeDtypeStruct((groups, hidden, width), jnp.bfloat16),
                jax.ShapeDtypeStruct((groups,), jnp.int32))

    def lowered(form, rows):
        return jax.jit(jax.grad(lambda xs, w, gs: form(xs, w, gs).astype(
            jnp.float32).sum(), argnums=(0, 1))).lower(*call(rows)).as_text()

    if (rows, groups, hidden, width) in ops.TILINGS:
        # Mosaic lowers for a TPU alone: the traced program says it
        assert "pallas_call" in str(jax.make_jaxpr(ops.grouped_product)(
            *call(rows)))
    else:
        assert lowered(ops.grouped_product, rows) == lowered(
            jax.lax.ragged_dot, rows)
    assert lowered(ops.grouped_product, 4 * rows) == lowered(
        jax.lax.ragged_dot, 4 * rows)


def _bound(monkeypatch):
    """``grouped_product`` choosing the kernels (in interpret mode) at
    square tiles, as on one TPU device at a shape the table has."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(ops, "grouped_tiling", lambda *shape, **_: SQUARE)
    return pltpu.force_tpu_interpret_mode()


@pytest.mark.parametrize("held", [0, 4], ids=["all_held", "a_share"])
def test_the_layer_with_the_kernels_bound_is_its_plain_self(held, monkeypatch):
    """``DroplessMoE``, f32 throughout: the output and the gradients to the
    input, the router and the three expert matrices with the grouped
    products running the library's kernels (forward, the routed rule's
    recomputed forward and both derivatives) against the same layer on
    ``jax.lax.ragged_dot``."""
    layer = moe.DroplessMoE(16, 128, 2, dtype=jnp.float32,
                            first_expert=held, held_experts=held)
    x = jax.random.normal(jax.random.key(0), (2, 128, 128))
    ct = jax.random.normal(jax.random.key(1), x.shape)
    variables = layer.init(jax.random.key(2), x)

    def run(params, x):
        y, _ = layer.apply({"params": params}, x,
                           mutable=["aux_loss", "moe_stats"])
        return (y * ct).sum(), y

    step = jax.jit(jax.value_and_grad(run, argnums=(0, 1), has_aux=True))
    default_registry().gauge("grouped_products_fused").set(0.0)
    (_, want), want_grads = step(variables["params"], x)
    assert default_registry().gauge("grouped_products_fused").value == 0.0
    with _bound(monkeypatch):
        step = jax.jit(jax.value_and_grad(run, argnums=(0, 1), has_aux=True))
        (_, got), got_grads = jax.block_until_ready(
            step(variables["params"], x))
    assert default_registry().gauge("grouped_products_fused").value == 1.0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("flags", [
    ("--model_name", "olmoe_tiny"),
    ("--model_name", "moonlight_tiny", "--expert_share", "0/4"),
], ids=["all_held", "a_share"])
def test_train_puts_the_gauge_on_every_log_line(flags, tmp_path, monkeypatch):
    """Four steps of ``train()`` on the CPU, logged every two: a stack with
    expert layers says 0 here on both lines and in the epoch's line."""
    import json

    from lance_distributed_training_tpu import cli
    from lance_distributed_training_tpu.data import create_text_token_dataset

    rng = np.random.default_rng(0)
    docs = [rng.integers(2, 64, 64).tolist() for _ in range(40)]
    uri = str(tmp_path / "tok")
    create_text_token_dataset(uri, docs, seq_len=64, fragment_size=64)
    metrics_path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("LDT_METRICS_PATH", str(metrics_path))
    default_registry().gauge("grouped_products_fused").set(-1.0)
    cli.main([
        "train", "--dataset_path", uri, "--task_type", "causal_lm", *flags,
        "--seq_len", "64", "--vocab_size", "64", "--batch_size", "8",
        "--epochs", "1", "--max_steps", "4", "--log_every", "2", "--no_ddp",
        "--no_wandb", "--no_eval_at_end", "--no_autotune"])
    assert default_registry().gauge("grouped_products_fused").value == 0.0
    lines = [json.loads(line) for line in open(metrics_path)]
    steps = [ln for ln in lines if "images_per_sec_dispatch" in ln]
    assert len(steps) == 2
    for ln in steps:
        assert ln["grouped_products_fused"] == 0.0
        assert ln["rows_sum_fused"] == 0.0
