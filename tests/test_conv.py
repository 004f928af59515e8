"""The depthwise causal convolution and its SiLU (``ops/conv.py``): the
kernel pair in interpret mode against the plain form in float32 (values and
the gradients for ``x``, ``taps`` and ``bias``), the rows a sequence block
hands to the next one each way, the rule that chooses between the two forms,
and what a run says of the choice (``conv=`` in the first log line, the gauge
``conv_fused``). The two mixers with the kernel bound as the chip binds it
are held to their plain selves here; the v5e compiles at the cells' widths
are in ``tests/test_attention_choice.py``."""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lance_distributed_training_tpu.models.tasks import get_task
from lance_distributed_training_tpu.models.transformer import (
    GatedDeltaNet,
    Mamba2Mixer,
    MambaMixer,
)
from lance_distributed_training_tpu.ops import conv

TAPS = 4


def _plain(width, dtype=None):
    def form(x, taps, bias):
        return jax.nn.silu(conv.causal_depthwise_conv(
            x[..., :width], taps, bias)).astype(dtype or x.dtype)
    return form


def _inputs(rows, seq, wide, width, has_bias, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (rows, seq, wide)).astype(dtype)
    taps = jax.random.normal(ks[1], (TAPS, width)) * 0.5
    bias = jax.random.normal(ks[2], (width,)) if has_bias else None
    return (x, taps, bias), jax.random.normal(ks[3], (rows, seq, width))


def _both(form, args, ct):
    """``(y, gradients)`` of ``sum(form(*args) * ct)`` in one program."""
    def run(*a):
        y = form(*a)
        return (y.astype(jnp.float32) * ct).sum(), y
    n = 2 if args[2] is None else 3
    (_, y), grads = jax.jit(jax.value_and_grad(
        run, argnums=range(n), has_aux=True))(*args)
    return y, grads


def _kernel_run(args, ct, block_s, block_d=128, dtype=None):
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        return jax.block_until_ready(_both(functools.partial(
            conv.conv_kernel, dtype=dtype, block_s=block_s,
            block_d=block_d), args, ct))


# channels (256: two blocks of one lane group; 640: one block of five), a
# bias, rows, sequence blocks (of 128 tokens: two loop steps of 64 each, so
# the rows handed on inside a tile are walked too), x a slice of a wider array
CASES = list(itertools.product((256, 640), (False, True), (1, 2), (2, 3),
                               (False, True)))
# a Mamba-2 layer's convolved columns at the published sizes, 34 lane groups
# with a bias, ahead of further columns of the projection
CASES.append((4352, True, 1, 1, True))


@pytest.mark.parametrize(
    "width,has_bias,rows,blocks,sliced", CASES,
    ids=[f"{w}ch-{'bias' if b else 'nobias'}-{r}row-{n}blocks-"
         f"{'slice' if s else 'whole'}" for w, b, r, n, s in CASES])
def test_the_kernels_in_interpret_mode_are_the_plain_form(
        width, has_bias, rows, blocks, sliced):
    args, ct = _inputs(rows, 128 * blocks, width + 128 * sliced, width,
                       has_bias, seed=width + blocks)
    y_want, g_want = _both(_plain(width), args, ct)
    y_got, g_got = _kernel_run(args, ct, 128, 128 if width == 256 else 640)
    assert y_got.shape == (rows, 128 * blocks, width)
    # the same products and sums in the same order: to an ulp of the SiLU's
    # exponential
    np.testing.assert_allclose(y_got, y_want, rtol=1e-6, atol=1e-6)
    for name, got, want in zip(("x", "taps", "bias"), g_got, g_want):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        np.testing.assert_allclose(
            got, want, rtol=2e-5, atol=2e-5 * float(jnp.abs(want).max()),
            err_msg=name)
    if sliced:  # the columns the convolution never read take no gradient
        assert float(jnp.abs(g_got[0][..., width:]).max()) == 0


def test_a_bf16_row_is_cast_once_each_way():
    """The layer's types: ``x`` and the cotangent bf16, the taps f32. The
    output is the plain form's to the bit; ``dx`` is rounded once where the
    plain form's derivative sums four rounded slices."""
    args, ct = _inputs(1, 256, 384, 256, True, jnp.bfloat16)
    ct = ct.astype(jnp.bfloat16).astype(jnp.float32)
    y_want, g_want = _both(_plain(256), args, ct)
    y_got, g_got = _kernel_run(args, ct, 128, 256)
    assert y_got.dtype == jnp.bfloat16 and g_got[0].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(y_got, np.float32),
                                  np.asarray(y_want, np.float32))
    args32 = (args[0].astype(jnp.float32), *args[1:])
    _, g32 = _both(_plain(256, jnp.bfloat16), args32, ct)

    def far(got):
        return float(jnp.linalg.norm(got.astype(jnp.float32) - g32[0])
                     / jnp.linalg.norm(g32[0]))
    assert far(g_got[0]) < 0.004 and far(g_got[0]) <= far(g_want[0])
    for got, want in zip(g_got[1:], g32[1:]):
        np.testing.assert_allclose(got, want, rtol=1e-4,
                                   atol=1e-4 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("at", [127, 63], ids=["block_end", "step_end"])
def test_a_token_at_a_blocks_last_row_moves_the_next_three_outputs(at):
    """The halo, forward: token ``at`` is the last row of a sequence block
    (127) or of a loop step inside one (63); with it moved, exactly outputs
    ``at`` to ``at + 3`` move, in the next block or step, and nothing before
    or after."""
    (x, taps, bias), ct = _inputs(1, 256, 128, 128, False)
    taps = jnp.abs(taps) + 0.1  # every tap counts
    y, _ = _kernel_run((x, taps, bias), ct, 128)
    y_moved, _ = _kernel_run((x.at[0, at].add(1.0), taps, bias), ct, 128)
    moved = np.flatnonzero(np.asarray(jnp.abs(y_moved - y).max(-1)[0]))
    assert moved.tolist() == list(range(at, at + TAPS))


@pytest.mark.parametrize("at", [128, 64], ids=["block_start", "step_start"])
def test_a_cotangent_at_a_blocks_first_row_moves_the_three_dx_before(at):
    """The mirror, backward: with the cotangent at row ``at`` (the first of
    a sequence block, or of a loop step) moved, exactly ``dx`` at ``at - 3``
    to ``at`` moves: the rows carried from the block after."""
    (x, taps, bias), ct = _inputs(1, 256, 128, 128, False)
    taps = jnp.abs(taps) + 0.1
    _, (dx, _) = _kernel_run((x, taps, bias), ct, 128)
    _, (dx_moved, _) = _kernel_run((x, taps, bias), ct.at[0, at].add(1.0),
                                   128)
    moved = np.flatnonzero(np.asarray(jnp.abs(dx_moved - dx).max(-1)[0]))
    assert moved.tolist() == list(range(at - TAPS + 1, at + 1))


def test_a_row_starts_from_zeros_and_rows_are_apart():
    """Two rows in one call: the second row's first outputs see zeros before
    them, not the first row's last tokens (the carried rows are cleared at a
    row's first block, the sums over rows are the rows' own)."""
    (x, taps, bias), ct = _inputs(2, 128, 128, 128, True)
    y, grads = _kernel_run((x, taps, bias), ct, 128)
    for row in range(2):
        y_one, g_one = _kernel_run((x[row:row + 1], taps, bias),
                                   ct[row:row + 1], 128)
        np.testing.assert_array_equal(y[row:row + 1], y_one)
        np.testing.assert_array_equal(grads[0][row:row + 1], g_one[0])


def test_the_rule(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    applies = conv.conv_fused_applies
    assert applies(8192, 8192, 4, platform="tpu")  # Qwen3-Next's
    assert applies(8192, 5120, 4, platform="tpu")  # Phi-4-mini-flash's
    assert not applies(8192, 8192, 4, platform="cpu")
    assert not applies(8192, 8192, 4)  # here: the CPU
    assert not applies(8192 + 64, 8192, 4, platform="tpu")  # a ragged row
    assert not applies(8192, 100, 4, platform="tpu")
    assert not applies(8192, 8192, 8, platform="tpu")  # no sublane left
    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    assert applies(8192, 8192, 4, mesh=mesh, platform="tpu")

    class MeshOfTwo:
        size = 2
    assert not applies(8192, 8192, 4, mesh=MeshOfTwo(), platform="tpu")
    monkeypatch.setattr(jax, "device_count", lambda *a: 4)
    assert not applies(8192, 8192, 4, platform="tpu")


def test_the_kernel_refuses_what_it_cannot_tile():
    (x, taps, bias), _ = _inputs(1, 24, 128, 128, False)
    with pytest.raises(ValueError, match="whole tiles of 16"):
        conv.conv_kernel(x, taps)
    (x, taps, bias), _ = _inputs(1, 32, 100, 100, False)
    with pytest.raises(ValueError, match="whole groups of 128"):
        conv.conv_kernel(x, taps)


def test_off_the_chip_the_op_is_the_plain_form_to_the_letter():
    """``causal_conv_silu`` here, on the CPU, lowers to what the layers wrote
    before it: the slice, the padded copy, the shifted sums, ``silu``, one
    cast."""
    (x, taps, bias), _ = _inputs(2, 32, 192, 128, True, jnp.bfloat16)

    def before(x, taps, bias):
        return jax.nn.silu(conv.causal_depthwise_conv(
            x[..., :128], taps, bias)).astype(jnp.bfloat16)

    def now(x, taps, bias):
        return conv.causal_conv_silu(x, taps, bias, dtype=jnp.bfloat16)

    def text(fn):
        return jax.jit(fn).lower(x, taps, bias).as_text().replace(
            fn.__name__, "f")
    assert text(now) == text(before)


def test_a_projection_of_ragged_width_is_sliced_in_front_of_the_kernel():
    """192 columns of which 128 are convolved (``qwen3_next_tiny``'s): no
    block of whole lane groups walks them in place, so the op hands the
    kernel the slice."""
    from jax.experimental.pallas import tpu as pltpu

    (x, taps, bias), ct = _inputs(1, 128, 192, 128, False)
    y_want, g_want = _both(_plain(128), (x, taps, bias), ct)
    with pytest.MonkeyPatch.context() as patch, \
            pltpu.force_tpu_interpret_mode():
        patch.setattr(conv, "conv_fused_applies", lambda *a, **k: True)
        y_got, g_got = jax.block_until_ready(_both(
            conv.causal_conv_silu, (x, taps, bias), ct))
    np.testing.assert_allclose(y_got, y_want, rtol=1e-6, atol=1e-6)
    assert g_got[0].shape == x.shape
    for got, want in zip(g_got, g_want):
        np.testing.assert_allclose(
            got, want, rtol=2e-5, atol=2e-5 * float(jnp.abs(want).max()))


# -- what a run says of the choice --------------------------------------------


@pytest.mark.parametrize("model,says", [
    ("qwen3_next_tiny", "plain"), ("phi4_mini_flash_tiny", "plain"),
    ("granite4_h_tiny", "plain"), ("olmoe_tiny", None)])
def test_the_first_log_line_names_the_convolutions_path(model, says):
    from lance_distributed_training_tpu import trainer

    config = trainer.TrainConfig(dataset_path="", task_type="causal_lm",
                                 model_name=model, seq_len=128)
    task = get_task("causal_lm", model_name=model, seq_len=128)
    assert trainer._kernel_paths(task, config).get("conv") == says
    assert ("conv" in task.kernels) is (says is not None)


@pytest.mark.parametrize("model,shape", [
    ("qwen3_next_80b_a3b", (8192, 4)), ("phi4_mini_flash", (5120, 4)),
    ("qwen3_next_tiny", (128, 4)), ("phi4_mini_flash_tiny", (128, 4)),
    ("granite4_h_micro", (4352, 4)), ("granite4_h_tiny", (96, 4))])
def test_a_stack_knows_its_convolutions_shape(model, shape, monkeypatch):
    """The mixer asks the op's own rule with its channels and taps."""
    from lance_distributed_training_tpu.models.transformer import CAUSAL_LMS

    asked = []
    monkeypatch.setattr(conv, "conv_fused_applies",
                        lambda seq, *shape: asked.append(shape) or True)
    assert CAUSAL_LMS[model].ctor(vocab_size=512).kernels(256)["conv"] is True
    assert set(asked) == {shape}


def test_a_span_without_a_mixer_has_no_convolution():
    task = get_task("causal_lm", model_name="qwen3_next_tiny", seq_len=128,
                    layer_span="3:4")  # the gated attention layer alone
    assert set(task.kernels) == {"attention"}


@pytest.mark.parametrize("model", ["qwen3_next_tiny", "phi4_mini_flash_tiny"])
def test_a_training_step_reports_conv_fused_0_on_the_cpu(model):
    task = get_task("causal_lm", model_name=model, seq_len=32)
    variables = jax.jit(task.init_variables)(jax.random.key(0))
    batch = {"input_ids": jnp.zeros((2, 32), jnp.int32),
             "attention_mask": jnp.ones((2, 32), jnp.int8)}
    stats = jax.jit(lambda v: task.stats(
        task.forward(v, batch, True, None)[0]))(variables)
    assert float(stats["conv_fused"]) == 0.0


# -- the two mixers with the kernel bound, as the chip binds it ---------------


MIXERS = {
    # Mamba: 128 channels out of a projection of 256, a bias
    "mamba": (lambda: MambaMixer(128, 8, 4, 4, dtype=jnp.float32), 64),
    # a Gated DeltaNet: 2 x 128 + 256 = 512 channels out of 768, no bias
    "gated_delta_net": (
        lambda: GatedDeltaNet(1, 2, 128, 128, 4, dtype=jnp.float32), 64),
    # Mamba-2: 128 + 2 x 64 = 256 channels out of 384, a bias
    "mamba2": (lambda: Mamba2Mixer(128, 2, 64, 64, 4, dtype=jnp.float32),
               64),
}


@pytest.mark.parametrize("name", MIXERS)
def test_a_mixer_with_the_kernel_bound_is_its_plain_self(name):
    """Forward, the recomputed forward and the backward pass through the
    mixer, the kernels read off the fused projection in place. (The kernels
    by ``interpret=True``: a Gated DeltaNet runs them under
    ``jax.checkpoint``, which cannot take the callbacks of the TPU
    interpreter the other tests use.)"""
    from jax.experimental import pallas as pl

    make, hidden = MIXERS[name]
    mixer = make()
    u = jax.random.normal(jax.random.key(1), (2, 128, hidden))
    variables = mixer.init(jax.random.key(2), u)
    # a convolution that matters: Mamba's bias starts at zero
    variables = jax.tree_util.tree_map_with_path(
        lambda path, p: p + 0.3 if "conv_bias" in str(path) else p, variables)

    def program():  # a function of its own a trace: jit keeps traces by it
        def loss(v, u):
            out = mixer.apply(v, u, mutable=["mixer_stats"])[0]
            out = out[0] if isinstance(out, tuple) else out
            return (out * jnp.cos(jnp.arange(out.shape[-1]))).sum(), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))

    (_, want), g_want = program()(variables, u)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(conv, "conv_fused_applies", lambda *a, **k: True)
        patch.setattr(pl, "pallas_call", functools.partial(
            pl.pallas_call, interpret=True))
        traced = program().trace(variables, u)
        (_, got), g_got = jax.block_until_ready(
            traced.lower().compile()(variables, u))
    text = str(traced.jaxpr)
    assert "_conv_forward" in text and "_conv_backward" in text
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    flat_got, _ = jax.tree_util.tree_flatten_with_path(g_got)
    flat_want = jax.tree_util.tree_leaves(g_want)
    for (path, a), b in zip(flat_got, flat_want):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-4 * float(jnp.abs(b).max()) + 1e-7,
            err_msg=jax.tree_util.keystr(path))
