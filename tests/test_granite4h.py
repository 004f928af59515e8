"""granite-4.0-h-micro's decoder layers (nine Mamba-2 mixers to one grouped
attention layer without a position term, a dense SwiGLU in every layer, the
model's four multipliers; here ``granite4_h_tiny``: 4 layers M2, N, M2, M2, 4
Mamba heads of 16 over 16 states, 4 query heads over 2 key/value heads of
16) against the published class and against the plain float32 reference the
benchmark keeps in ``benchmark/reference/granite-4.0-h-micro-c4.py``, on
seeded weights, on the CPU.

*Is the program the model?* Where ``torch`` and ``transformers`` import, the
mixer equals ``GraniteMoeHybridMambaLayer.torch_forward``, the gated norm
``GraniteMoeHybridRMSNormGated`` and the whole stack
``GraniteMoeHybridForCausalLM``'s logits on copied weights (the projection
permuted, all four multipliers off 1). *Is the program's mathematics the
reference's?* The program computed in float32 against the reference: logits,
loss and every parameter group's gradient to ``F32_TOL``. Then what only
these layers have: the dual's chunked form against its token-by-token
recurrence, values and gradients, at rows that are and are not whole chunks
and at two head groups; the dual's kernel pair in interpret mode against the
chunked form, forward and every gradient, from arrays of their own and from
the columns of one; the mixer with both kernels bound against its plain
self; and the configuration's file against the program.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import register_preset

from lance_distributed_training_tpu.models import get_task
from lance_distributed_training_tpu.models.transformer import (
    GroupedAttention,
    Mamba2Mixer,
    granite4_layers,
)
from lance_distributed_training_tpu.ops import norm, ssd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, ROWS, VOCAB = 96, 2, 512
F32_TOL = 2e-4  # float32 against float32: summation order and chunking only
GROUPS = ("in_proj_xbcz", "in_proj_dt", "conv_kernel", "conv_bias", "A_log",
          "dt_bias", "D", "norm_scale", "out_proj", "query", "key", "value",
          "out", "gate", "up", "down", "scales", "tok_embed")
CELL = "c4-granite4h-vp8-prepacked-8k"


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "reference",
                        "granite-4.0-h-micro-c4.py")
    spec = importlib.util.spec_from_file_location("granite4h_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.STATES, module.HEAD_DIM = 16, 16
    return module


@pytest.fixture(scope="module")
def ref():
    return _load_reference()


def _task(seq=SEQ, **changes):
    if not changes:
        return get_task("causal_lm", model_name="granite4_h_tiny",
                        seq_len=seq)
    presets = register_preset("granite4_h_tiny_changed", "granite4_h_tiny",
                              **changes)
    try:
        return get_task("causal_lm", model_name="granite4_h_tiny_changed",
                        seq_len=seq)
    finally:
        del presets["granite4_h_tiny_changed"]


@pytest.fixture(scope="module")
def f32_task():
    return _task(dtype=jnp.float32)


@pytest.fixture(scope="module")
def bf16_task():
    return _task()


@pytest.fixture(scope="module")
def variables(ref, bf16_task):
    """Seeded, perturbed as the benchmark's check perturbs them, and with
    the attention layer's query and key matrices 16 times as large: at these
    widths the scores are a few hundredths and every softmax is flat
    whatever multiplies them, where at the published ones (32 times as wide,
    the same 0.02) it is not."""
    variables = ref.perturb(
        jax.jit(bf16_task.init_variables)(jax.random.key(3)),
        jax.random.key(4))
    return dict(variables, params=jax.tree_util.tree_map_with_path(
        lambda path, x: 16 * x if path[-2].key in ("query", "key") else x,
        variables["params"]))


@pytest.fixture(scope="module")
def batch():
    ids = np.random.default_rng(5).integers(2, VOCAB, (ROWS, SEQ))
    mask = np.ones((ROWS, SEQ), np.int8)
    mask[-1, SEQ - 5:] = 0  # a padded tail: live tokens only in the losses
    return {"input_ids": ids.astype(np.int32), "attention_mask": mask}


def _groups(tree) -> dict:
    """Parameter groups, layers together, by the leaf's or its module's
    name; every norm's plain scale under ``scales``."""
    out: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        names = [p.key for p in path]
        name = names[-1] if names[-1] not in ("kernel", "embedding") \
            else names[-2]
        name = "scales" if name == "scale" else name
        out.setdefault(name, []).append(leaf.reshape(-1))
    return {name: jnp.concatenate(leaves) for name, leaves in out.items()}


def _relative(got, want) -> float:
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _one_program(fn, *args):
    """One jitted program, waited for (``tests/test_olmoe.py`` tells why)."""
    return jax.block_until_ready(jax.jit(fn)(*args))


def _spread_error(got, want, live) -> float:
    """The benchmark's statistic (``benchmark/run.py`` ``check_model``)."""
    live = live[..., None]
    n = live.sum() * want.shape[-1]
    mean = jnp.where(live, want, 0).sum() / n
    spread = jnp.sqrt(jnp.where(live, (want - mean) ** 2, 0).sum() / n)
    return float(jnp.where(live, jnp.abs(got - want), 0).max() / spread)


def _logits(task, variables, batch):
    return _one_program(
        lambda v: task.forward(v, batch, False, None)[0][0], variables)


def _program_loss(task, batch):
    def loss(v):
        outputs, _ = task.forward(v, batch, True, None)
        return task.loss(outputs, batch)

    return loss


# -- the mathematics, float32 against float32 ---------------------------------


@pytest.fixture(scope="module")
def want(ref, variables, batch):
    return _one_program(lambda v: ref.forward(v, batch), variables)


def test_logits_match_reference_in_float32(ref, f32_task, variables, batch,
                                           want):
    live = ref.live(batch, want)
    assert _spread_error(_logits(f32_task, variables, batch), want,
                         live) < F32_TOL


@pytest.fixture(scope="module")
def reference_loss_and_grads(ref, variables, batch):
    loss, grads = _one_program(
        jax.value_and_grad(lambda v: ref.loss(v, batch)), variables)
    return loss, _groups(grads["params"])


def test_loss_matches_reference(f32_task, variables, batch,
                                reference_loss_and_grads):
    got = _one_program(_program_loss(f32_task, batch), variables)
    want = reference_loss_and_grads[0]
    assert abs(float(got) - float(want)) < F32_TOL * float(want)


@pytest.fixture(scope="module")
def f32_grads(f32_task, variables, batch):
    grads = _one_program(jax.grad(_program_loss(f32_task, batch)), variables)
    return _groups(grads["params"])


@pytest.mark.parametrize("group", GROUPS)
def test_gradient_matches_reference_in_float32(group, f32_grads,
                                               reference_loss_and_grads):
    want = reference_loss_and_grads[1][group]
    assert float(jnp.linalg.norm(want)) > 0
    assert _relative(f32_grads[group], want) < F32_TOL


@pytest.mark.parametrize("field,value", [
    ("embed_scale", 1.0), ("branch_scale", 1.0), ("logit_scale", 1.0),
    ("score_scale", 0.0), ("norm_eps", 1e-6)])
def test_a_multiplier_left_out_fails_the_float32_comparison(
        field, value, ref, variables, batch, want):
    """Each of the four multipliers at its default (absent), and another
    epsilon in both norms, is another model: the comparison shows it."""
    changes = {field: value}
    if field == "score_scale":  # the attention mixer's own field
        changes = {"parts": {GroupedAttention: changes}}
    task = _task(dtype=jnp.float32, **changes)
    assert _spread_error(_logits(task, variables, batch), want,
                         ref.live(batch, want)) > 20 * F32_TOL


# -- the program as it runs, and the precision below --------------------------


@pytest.fixture(scope="module")
def row_of_the_check():
    ids = np.random.default_rng(6).integers(2, VOCAB, (1, 1024))
    return {"input_ids": ids.astype(np.int32),
            "attention_mask": np.ones((1, 1024), np.int8)}


def test_the_program_in_bf16_passes_and_the_reference_in_bf16_reads_further(
        ref, variables, row_of_the_check):
    """One row of 1,024 tokens under ``perturb``'s long memory: the program
    as it runs (bf16 operands, float32 dt, decay, state, norm and logits)
    reads under ``TOLERANCE``, and the reference with the state, dt and the
    decay rounded to bf16 where they stand reads further from itself in
    float32 than the program does (at this width and length by a little; on
    the chip at the published widths and 8,192 tokens by what ``TOLERANCE``'s
    comment and PERF.md section 6 report, where it has to fail)."""
    task = _task(seq=1024)
    want, low = _one_program(
        lambda v: (ref.forward(v, row_of_the_check),
                   ref.forward(v, row_of_the_check, dtype=jnp.bfloat16)),
        variables)
    live = ref.live(row_of_the_check, want)
    program = _spread_error(_logits(task, variables, row_of_the_check), want,
                            live)
    below = _spread_error(low, want, live)
    print(f"program in bf16 reads {program:.3f}, reference in bf16 "
          f"{below:.3f}")
    assert program < ref.TOLERANCE
    assert below > 1.5 * program


def test_perturb_gives_the_state_long_memory(ref, variables):
    ssm = variables["params"]["layer_0"]["ssm"]
    decay = np.exp(-np.exp(np.asarray(ssm["A_log"])) * np.log1p(
        np.exp(np.asarray(ssm["dt_bias"]))))
    assert np.all((1 - decay > 5e-6) & (1 - decay < 2e-3)), decay
    assert not np.allclose(np.asarray(ssm["D"]), 1)
    assert not np.allclose(np.asarray(ssm["conv_bias"]), 0)
    assert not np.allclose(np.asarray(ssm["norm_scale"]), 1)


def test_a_training_step_reports_its_gauges(bf16_task, batch):
    variables = jax.jit(bf16_task.init_variables)(jax.random.key(0))

    def step(v):
        outputs, _ = bf16_task.forward(v, batch, True, None)
        return bf16_task.stats(outputs)

    stats = {k: float(v) for k, v in _one_program(step, variables).items()}
    assert {"ssd_fused", "conv_fused", "ssd_state_abs_max", "ssd_decay_min",
            "ssd_dt_mean"} <= set(stats)
    assert stats["ssd_fused"] == 0 and stats["conv_fused"] == 0
    assert "norm_fused" not in stats  # the first log line's word alone
    # dt = softplus(1 + small), A = -1..-4: the fastest head forgets at once
    assert 1.0 < stats["ssd_dt_mean"] < 1.6
    assert 0 < stats["ssd_decay_min"] < 0.02
    assert stats["ssd_state_abs_max"] > 0


def test_the_first_log_line_names_the_duals_path():
    from lance_distributed_training_tpu import trainer

    config = trainer.TrainConfig(
        dataset_path="", task_type="causal_lm",
        model_name="granite4_h_tiny", seq_len=SEQ)
    assert trainer._kernel_paths(_task(), config) == {
        "attention": "dense", "conv": "plain", "norm": "plain",
        "ssd": "chunked"}


@pytest.mark.parametrize("platform,seq,heads,head_dim,states,devices,runs", [
    ("tpu", 8192, 64, 64, 128, 1, True),  # the cell's
    ("tpu", 256, 2, 64, 128, 1, True),  # two heads of 64: one lane group
    ("tpu", 8192, 64, 64, 128, 4, False),  # no mesh says how to split
    ("cpu", 8192, 64, 64, 128, 1, False),
    ("tpu", 8192 + 128, 64, 64, 128, 1, False),  # not whole chunks
    ("tpu", 8192, 64, 64, 16, 1, False),  # states under a lane group
    ("tpu", 8192, 3, 64, 128, 1, False),  # no block of heads in whole lanes
    ("tpu", 64, 4, 16, 16, 1, False),  # the tiny preset
])
def test_the_duals_rule(monkeypatch, platform, seq, heads, head_dim, states,
                        devices, runs):
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    assert ssd.ssd_fused_applies(seq, heads, head_dim, states,
                                 platform=platform) is runs


# -- the state-space dual: chunked against token by token ---------------------

SSD_INPUTS = ("x", "dt", "a", "b", "c", "d")


def _ssd_inputs(seq, heads=8, width=16, states=16, rows=2, seed=0):
    keys = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(keys[0], (rows, seq, heads, width))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (rows, seq, heads)))
    a = -jnp.exp(jax.random.uniform(keys[2], (heads,), minval=-4, maxval=1))
    b = jax.random.normal(keys[3], (rows, seq, states))
    c = jax.random.normal(keys[4], (rows, seq, states))
    d = jax.random.normal(keys[5], (heads,))
    return (x, dt, a, b, c, d), jax.random.normal(keys[6], x.shape)


def _forms(form, args, ct):
    def loss(*args):
        y, last = form(*args)
        return (y.astype(jnp.float32) * ct).sum(), (y, last)

    return jax.jit(jax.value_and_grad(loss, argnums=range(len(args)),
                                      has_aux=True))(*args)


@pytest.fixture(scope="module", params=[
    (64, 16, 4), (64, 16, 8), (50, 16, 2), (64, 64, 4), (24, 32, 8)],
    ids=["whole_chunks_groups_of_4", "whole_chunks_one_group",
         "a_ragged_row_groups_of_2", "one_chunk", "a_row_under_a_chunk"])
def ssd_case(request):
    seq, chunk, group = request.param
    args, ct = _ssd_inputs(seq)
    with jax.default_matmul_precision("highest"):
        return (_forms(functools.partial(ssd.ssd_chunked, chunk=chunk,
                                         group=group), args, ct),
                _forms(ssd.ssd_recurrence, args, ct))


def test_the_chunked_form_is_the_recurrence(ssd_case):
    ((_, (y, last)), _), ((_, (y_want, last_want)), _) = ssd_case
    assert _relative(y, y_want) < 1e-5
    assert _relative(last, last_want) < 1e-5


@pytest.mark.parametrize("which", range(6), ids=SSD_INPUTS)
def test_the_chunked_forms_gradient_is_the_recurrences(which, ssd_case):
    (_, got), (_, want) = ssd_case
    assert _relative(got[which], want[which]) < 2e-5


def test_the_last_state_takes_no_gradient():
    args, _ = _ssd_inputs(32)
    grads = jax.grad(lambda *a: ssd.ssd_chunked(*a, chunk=16)[1].sum(),
                     argnums=(0, 1))(*args)
    assert all(float(jnp.abs(g).max()) == 0 for g in grads)


def test_bf16_operands_keep_dt_the_sums_and_the_state_in_float32():
    """Operands in bf16, and a decay within 1e-4 of 1 over 2,048 tokens:
    the chunked form stays where bf16 inputs put it (a few 1e-3 of the
    float32 recurrence on the same rounded inputs), which a state or a
    running sum kept in bf16 would not."""
    (x, _, _, b, c, d), _ = _ssd_inputs(2048, heads=4, rows=1, seed=1)
    dt = jnp.full((1, 2048, 4), 0.01)
    a = -jnp.array([1e-3, 3e-3, 1e-2, 1e-1])
    low = [t.astype(jnp.bfloat16) for t in (x, b, c)]
    y, _ = _one_program(lambda: ssd.ssd_chunked(
        low[0], dt, a, low[1], low[2], d, chunk=64, group=2))
    assert y.dtype == jnp.bfloat16
    want, _ = _one_program(lambda: ssd.ssd_recurrence(
        low[0], dt, a, low[1], low[2], d))
    assert _relative(y.astype(jnp.float32), want) < 6e-3


def test_shapes_that_are_not_the_duals_are_refused_by_name():
    (x, dt, a, b, c, d), _ = _ssd_inputs(16)
    with pytest.raises(ValueError, match="the state-space dual takes"):
        ssd.ssd_chunked(x, dt[..., :4], a, b, c, d)
    with pytest.raises(ValueError, match="the state-space dual takes"):
        ssd.ssd(x, dt, a, b[:, :8], c, d)


# -- the kernel pair, in interpret mode, against the chunked form -------------

# rows, tokens, heads, heads a grid step, chunk: two and three chunks (the
# state rides from step to step and its cotangent back), one and two blocks
# of heads, two rows, the chunk the cell runs and a longer one
KERNEL_CASES = [(1, 256, 2, 2, 128), (2, 384, 4, 2, 128), (1, 512, 8, 4, 256)]


def _kernel_inputs(rows, seq, heads, seed=0):
    args, ct = _ssd_inputs(seq, heads=heads, width=64, states=128, rows=rows,
                           seed=seed)
    x, dt, a, b, c, d = args
    return (x, dt - 0.5, a, 0.3 * b, 0.3 * c, d), ct


@pytest.fixture(scope="module", params=KERNEL_CASES,
                ids=[f"{r}row-{s}tok-{h}heads-by{b}-chunk{c}"
                     for r, s, h, b, c in KERNEL_CASES])
def kernel_case(request):
    from jax.experimental.pallas import tpu as pltpu

    rows, seq, heads, block_h, chunk = request.param
    args, ct = _kernel_inputs(rows, seq, heads)
    with jax.default_matmul_precision("highest"):
        want = _forms(functools.partial(ssd.ssd_chunked, chunk=chunk,
                                        group=2), args, ct)
        with pltpu.force_tpu_interpret_mode():
            got = jax.block_until_ready(_forms(functools.partial(
                ssd.ssd_kernel, chunk=chunk, block_h=block_h), args, ct))
    return got, want


def test_the_kernel_in_interpret_mode_is_the_chunked_form(kernel_case):
    ((_, (y, last)), _), ((_, (y_want, last_want)), _) = kernel_case
    assert y.shape == y_want.shape and last.shape == last_want.shape
    assert _relative(y, y_want) < 1e-5
    assert _relative(last, last_want) < 1e-5


@pytest.mark.parametrize("which", range(6), ids=SSD_INPUTS)
def test_the_kernels_gradient_is_the_chunked_forms(which, kernel_case):
    (_, got), (_, want) = kernel_case
    assert got[which].shape == want[which].shape
    assert _relative(got[which], want[which]) < 2e-5


def test_the_kernels_read_x_b_and_c_from_one_arrays_columns():
    """The layer's convolved projection ``[x; b; c]`` handed whole: values
    and the gradient of the whole array are those of the three slices, and
    no slice of it is in the traced program."""
    from jax.experimental.pallas import tpu as pltpu

    (x, dt, a, b, c, d), ct = _kernel_inputs(1, 256, 4, seed=2)
    xbc = jnp.concatenate([x.reshape(1, 256, 256), b, c], axis=-1)

    def sliced(xbc, dt, a, d):
        return ssd.ssd_chunked(
            xbc[..., :256].reshape(1, 256, 4, 64), dt, a,
            xbc[..., 256:384], xbc[..., 384:], d, chunk=128)

    packed = functools.partial(ssd.ssd_kernel_packed, head_dim=64,
                               chunk=128, block_h=2)
    with jax.default_matmul_precision("highest"):
        (_, want), g_want = _forms(sliced, (xbc, dt, a, d), ct)
        with pltpu.force_tpu_interpret_mode():
            (_, got), g_got = jax.block_until_ready(
                _forms(packed, (xbc, dt, a, d), ct))
    assert _relative(got[0], want[0]) < 1e-5
    for g, w in zip(g_got, g_want):
        assert g.shape == w.shape and _relative(g, w) < 2e-5
    text = str(jax.make_jaxpr(lambda *a: packed(*a)[0])(xbc, dt, a, d))
    assert "ssd_fwd" in text and " slice[" not in text


def test_the_kernels_take_bf16_operands_and_keep_the_state_in_float32():
    from jax.experimental.pallas import tpu as pltpu

    (x, dt, a, b, c, d), ct = _kernel_inputs(1, 256, 2, seed=3)
    low = (x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
           c.astype(jnp.bfloat16), d)
    (_, want), g_want = _forms(functools.partial(ssd.ssd_chunked, chunk=128),
                               low, ct)
    with pltpu.force_tpu_interpret_mode():
        (_, got), g_got = jax.block_until_ready(_forms(functools.partial(
            ssd.ssd_kernel, chunk=128, block_h=2), low, ct))
    assert got[0].dtype == g_got[0].dtype == jnp.bfloat16
    assert got[1].dtype == jnp.float32 and g_got[1].dtype == jnp.float32
    assert _relative(got[0].astype(jnp.float32),
                     want[0].astype(jnp.float32)) < 1e-2
    for g, w in zip(g_got, g_want):
        assert _relative(g.astype(jnp.float32), w.astype(jnp.float32)) < 2e-2


def test_rows_the_kernels_cannot_take_are_refused_by_name():
    (x, dt, a, b, c, d), _ = _kernel_inputs(1, 192, 2)
    with pytest.raises(ValueError, match="rows of whole chunks of 256"):
        ssd.ssd_kernel(x, dt, a, b, c, d)
    args, _ = _ssd_inputs(256, heads=2, width=64, states=16, rows=1)
    with pytest.raises(ValueError, match="states in whole groups of 128"):
        ssd.ssd_kernel(*args)
    args, _ = _ssd_inputs(256, heads=3, width=64, states=128, rows=1)
    with pytest.raises(ValueError, match="no block of up to 16 heads of 64"):
        ssd.ssd_kernel(*args)


def test_the_mixer_with_its_kernels_bound_is_its_plain_self():
    """Forward and backward through a Mamba-2 mixer with the convolution's
    and the dual's kernels bound as the chip binds them, both reading the
    fused projection's columns in place (``interpret=True``, as
    ``tests/test_conv.py`` binds the convolution's under a mixer)."""
    from jax.experimental import pallas as pl

    from lance_distributed_training_tpu.ops import conv

    mixer = Mamba2Mixer(inner=128, heads=2, head_dim=64, states=128, conv=4,
                        dtype=jnp.float32)
    u = jax.random.normal(jax.random.key(1), (1, 512, 64))  # two chunks
    variables = mixer.init(jax.random.key(2), u)
    variables = jax.tree_util.tree_map_with_path(
        lambda path, p: p + 0.3 if "conv_bias" in str(path) else
        p - 3.0 if "dt_bias" in str(path) else p, variables)

    def program():  # a function of its own a trace: jit keeps traces by it
        def loss(v, u):
            out = mixer.apply(v, u, mutable=["mixer_stats"])[0]
            return (out * jnp.cos(jnp.arange(out.shape[-1]))).sum(), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, want), g_want = program()(variables, u)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(conv, "conv_fused_applies", lambda *a, **k: True)
            patch.setattr(ssd, "ssd_fused_applies", lambda *a, **k: True)
            patch.setattr(pl, "pallas_call", functools.partial(
                pl.pallas_call, interpret=True))
            traced = program().trace(variables, u)
            (_, got), g_got = jax.block_until_ready(
                traced.lower().compile()(variables, u))
    text = str(traced.jaxpr)
    assert "ssd_fwd" in text and "ssd_bwd" in text
    assert "_conv_forward" in text
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(g_got)[0],
            jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=1e-4 * float(jnp.abs(b).max()) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


# -- parity with the published class ------------------------------------------


@pytest.fixture(scope="module")
def published():
    """The published classes at the tiny preset's sizes, float32."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    try:
        from transformers.models.granitemoehybrid import (
            modeling_granitemoehybrid as hf,
        )
        from transformers.models.granitemoehybrid import (
            configuration_granitemoehybrid as hf_config,
        )
    except ImportError as e:  # an older transformers
        pytest.skip(f"no granitemoehybrid in this transformers: {e}")
    config = hf_config.GraniteMoeHybridConfig(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
        shared_intermediate_size=128, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2,
        layer_types=["mamba", "attention", "mamba", "mamba"],
        mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
        mamba_expand=1, mamba_n_groups=1, mamba_chunk_size=32,
        mamba_conv_bias=True, mamba_proj_bias=False, num_local_experts=0,
        num_experts_per_tok=0, position_embedding_type="nope",
        rms_norm_eps=1e-5, tie_word_embeddings=True, attention_dropout=0.0,
        embedding_multiplier=12.0, attention_multiplier=0.015625,
        residual_multiplier=0.22, logits_scaling=8.0)
    config._attn_implementation = "eager"
    torch.manual_seed(0)
    return torch, hf, config


def _np(t):
    return jnp.asarray(t.detach().numpy())


PARITY = 1e-4  # float32 torch against float32 jax.numpy: summation order


def _mixer_params(module, inner=64):
    """A published Mamba layer's weights as the program lays them out: the
    projection ``[z; xBC; dt]`` permuted to ``[xBC; z]`` and ``dt``."""
    w = _np(module.in_proj.weight).T  # [hidden, inner + conv_dim + heads]
    conv_dim = module.conv_dim
    return {
        "in_proj_xbcz": {"kernel": jnp.concatenate(
            [w[:, inner:inner + conv_dim], w[:, :inner]], axis=1)},
        "in_proj_dt": {"kernel": w[:, inner + conv_dim:]},
        "conv_kernel": _np(module.conv1d.weight)[:, 0, :].T,
        "conv_bias": _np(module.conv1d.bias),
        "A_log": _np(module.A_log), "dt_bias": _np(module.dt_bias),
        "D": _np(module.D), "norm_scale": _np(module.norm.weight),
        "out_proj": {"kernel": _np(module.out_proj.weight).T}}


def _stir(torch, module):
    """A published Mamba layer off its start values, decays slow to fast."""
    with torch.no_grad():
        module.A_log.uniform_(-5.0, 1.0)
        module.dt_bias.uniform_(-3.0, 1.0)
        module.D.uniform_(0.5, 1.5)
        module.norm.weight.uniform_(0.75, 1.25)
        module.conv1d.bias.normal_(0.0, 0.1)


def test_the_mixer_is_the_published_mamba_layer(published):
    torch, hf, config = published
    module = hf.GraniteMoeHybridMambaLayer(config, layer_idx=0).float()
    _stir(torch, module)
    x = torch.randn(2, SEQ, 64)
    with torch.no_grad():
        want = module.torch_forward(x)
    mixer = Mamba2Mixer(inner=64, heads=4, head_dim=16, states=16, conv=4,
                        norm_eps=1e-5, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _one_program(lambda p, x: mixer.apply({"params": p}, x),
                           _mixer_params(module), _np(x))
    np.testing.assert_allclose(got, _np(want), atol=PARITY)
    assert float(jnp.abs(_np(want)).max()) > 0.05


def test_the_gated_norm_gates_first_and_norms_the_whole_row(published):
    torch, hf, _ = published
    module = hf.GraniteMoeHybridRMSNormGated(64, eps=1e-5)
    with torch.no_grad():
        module.weight.uniform_(0.5, 1.5)
    o, z = torch.randn(2, 24, 64), torch.randn(2, 24, 96)
    with torch.no_grad():
        want = module(o, z[..., 32:])
    got = norm.gate_then_rms_norm(
        _np(o).reshape(2, 24, 4, 16), _np(z), _np(module.weight), eps=1e-5)
    np.testing.assert_allclose(got, _np(want), atol=1e-5)
    # the other order, a Gated DeltaNet's, is another function
    other = norm.gated_rms_norm_plain(
        _np(o).reshape(2, 24, 1, 64), _np(z), _np(module.weight), eps=1e-5)
    assert float(jnp.abs(other - _np(want)).max()) > 0.1


def test_the_stack_is_the_published_model(published, f32_task, batch):
    """``GraniteMoeHybridForCausalLM`` on copied weights, every parameter
    off its start value, all four multipliers off 1: logits to ``PARITY`` of
    their spread."""
    torch, hf, config = published
    model = hf.GraniteMoeHybridForCausalLM(config).float().eval()
    with torch.no_grad():
        for module in model.modules():
            if isinstance(module, hf.GraniteMoeHybridMambaLayer):
                _stir(torch, module)
            if isinstance(module, hf.GraniteMoeHybridRMSNorm):
                module.weight.uniform_(0.75, 1.25)
    params = {"tok_embed": {"embedding": _np(model.model.embed_tokens.weight)},
              "ln_final": {"scale": _np(model.model.norm.weight)}}
    for i, layer in enumerate(model.model.layers):
        fused = _np(layer.shared_mlp.input_linear.weight).T  # [gate; up]
        p = {"ln_attn": {"scale": _np(layer.input_layernorm.weight)},
             "ln_mlp": {"scale": _np(layer.post_attention_layernorm.weight)},
             "mlp": {"gate": {"kernel": fused[:, :128]},
                     "up": {"kernel": fused[:, 128:]},
                     "down": {"kernel": _np(
                         layer.shared_mlp.output_linear.weight).T}}}
        if layer.mamba is not None:
            p["ssm"] = _mixer_params(layer.mamba)
        else:
            attn = layer.self_attn
            p["attn"] = {
                "query": {"kernel": _np(attn.q_proj.weight).T.reshape(
                    64, 4, 16)},
                "key": {"kernel": _np(attn.k_proj.weight).T.reshape(
                    64, 2, 16)},
                "value": {"kernel": _np(attn.v_proj.weight).T.reshape(
                    64, 2, 16)},
                "out": {"kernel": _np(attn.o_proj.weight).T.reshape(
                    4, 16, 64)}}
        params[f"layer_{i}"] = p
    ids = torch.tensor(batch["input_ids"][:, :SEQ].astype(np.int64))
    with torch.no_grad():
        want = _np(model(input_ids=ids).logits)
    whole = dict(batch, attention_mask=np.ones_like(batch["attention_mask"]))
    with jax.default_matmul_precision("highest"):
        got = _logits(f32_task, {"params": params}, whole)
    assert float(jnp.abs(got - want).max()) < 10 * PARITY * float(want.std())
    assert float(want.std()) > 0.01


# -- the stack and its spans ---------------------------------------------------


def test_the_published_layout_is_attention_at_5_15_25_35():
    kinds = granite4_layers(40)
    assert [i for i, k in enumerate(kinds) if k == "N"] == [5, 15, 25, 35]
    assert set(kinds) == {"N", "M2"}
    task = get_task("causal_lm", model_name="granite4_h_micro", seq_len=8192,
                    layer_span="10:20", vocab_size=12544)
    assert task.model.held_kinds == ("M2",) * 5 + ("N",) + ("M2",) * 4


@pytest.mark.parametrize("span,message", [
    ("30:41", "not inside the preset's 40 layers"),
    ("40:50", "not inside the preset's 40 layers"),
    ("5", "layer_span is 'first:end'"),
])
def test_a_span_outside_the_model_is_refused_by_name(span, message):
    with pytest.raises(ValueError, match=message):
        get_task("causal_lm", model_name="granite4_h_micro", seq_len=128,
                 layer_span=span)


def test_the_cells_flags_train_two_steps_under_remat():
    """The flags the cell passes, at the tiny preset, through the CLI's own
    parser and the trainer's own builders (``--layer_span``, ``--vocab_size``,
    ``--remat``, AdamW with clipping and a warm-up): two steps of the step
    ``train()`` runs, the loss finite and falling from ln(512), every
    Mamba-2 parameter moved. (The whole loop, from a data set on disk, is
    ``test_the_cell_rehearses_end_to_end_on_the_cpu``.)"""
    from lance_distributed_training_tpu import cli, trainer
    from lance_distributed_training_tpu.parallel import get_mesh

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro-c4.json")) as f:
        flags = json.load(f)["rehearsal"]["train_flags"]
    parsed = cli.build_parser().parse_args(["--dataset_path", "-", *flags])
    config = trainer.TrainConfig(**{
        field: getattr(parsed, field)
        for field in trainer.TrainConfig.__dataclass_fields__
        if hasattr(parsed, field)})
    assert (config.model_name, config.layer_span, config.remat,
            config.vocab_size, config.optimizer) == (
        "granite4_h_tiny", "0:4", True, VOCAB, "adamw")
    task = trainer._task_from_config(config)
    assert task.model.remat and task.model.held_kinds == (
        "M2", "N", "M2", "M2")
    state = trainer.create_train_state(jax.random.key(0), task, config)
    step = trainer.make_train_step(task, get_mesh(jax.devices()[:1]),
                                   donate=False, stats=True)
    ids = np.random.default_rng(0).integers(2, VOCAB, (2, 64))
    batch = {"input_ids": ids, "attention_mask": np.ones((2, 64), np.int8)}
    losses = []
    for i in range(3):
        state, loss, stats = step(state, batch, jax.random.key(i))
        losses.append(float(loss))
    assert abs(losses[0] - np.log(VOCAB)) < 0.2
    assert np.all(np.isfinite(losses)) and losses[2] < losses[0]
    assert "ssd_state_abs_max" in stats
    first = task.init_variables(jax.random.key(0))["params"]["layer_0"]["ssm"]
    moved = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         state.params["layer_0"]["ssm"], first)
    assert all(v > 0 for v in jax.tree.leaves(moved)), moved


# -- the other presets are what they were --------------------------------------

# Every other preset's parameter tree (paths, shapes, types; sha256 of the
# listing) and six more lowered steps than ``tests/test_zaya.py``'s and
# ``tests/test_smallthinker.py``'s tables hold, hashed on the parent of PR 49
# (commit 6332c22) with the functions below: the four multipliers at their
# defaults, ``residual_scales`` in place of ``kind == "C"`` and the tenth
# mixer add no operation and no parameter to any of them.
TREES_ON_THE_PARENT = {
    "gpt_base": "7ec064b038cef787", "gpt_small": "416c5b6fbb0a53e5",
    "moonlight_16b_a3b": "afc703f6ef7494ce",
    "moonlight_tiny": "850d87f042f39572",
    "olmoe_1b_7b": "c33cda9adc19f681", "olmoe_tiny": "e86f39493f6e800e",
    "phi4_mini_flash": "528ddfec463d4ed1",
    "phi4_mini_flash_tiny": "bacdced9e357c2a5",
    "qwen3_next_80b_a3b": "77b27c26a0c932cf",
    "qwen3_next_tiny": "a554394b188a79c2",
    "smallthinker_21b_a3b": "043c74b46a0845c7",
    "smallthinker_tiny": "3a1e005b8b2f40cf",
    "zaya1_8b": "0389cb0806ba9873", "zaya_tiny": "633344e5db1b353a",
    # hashed on the parent of PR 53 (commit 9e1392c): sizes found by a
    # layer's kind before its class, ``GroupedAttention``'s ``head_gate``,
    # ``rotary_dim`` and ``yarn`` at their defaults, and the rotary turn's
    # table and factor leave this model's two, and the fourteen above, as
    # they were
    "granite4_h_micro": "f64c8ad29890f3e9",
    "granite4_h_tiny": "34756dc3ac75d858",
}
LOWERED_ON_THE_PARENT = {
    ("smallthinker_tiny", None, False): "5affa78410ae5c12",
    ("smallthinker_tiny", "0/4", True): "7065778dac1fce49",
    ("zaya_tiny", None, True): "219dd31d84012407",
    ("olmoe_tiny", None, True): "3a30b8b4fd566d4e",
    ("qwen3_next_tiny", None, True): "6c5176c365f91e7b",
    ("moonlight_tiny", None, False): "af9422493615d9e6",
    # hashed on the parent of PR 53 (commit 9e1392c), as the two trees above
    ("granite4_h_tiny", None, False): "6e17611f8711f9a4",
    ("granite4_h_tiny", None, True): "6ac2b8f90d60b00f",
    ("smallthinker_tiny", None, True): "22073aa4eb832d8e",
    ("moonlight_tiny", None, True): "5aed9823be060478",
}


@pytest.mark.parametrize("name", sorted(TREES_ON_THE_PARENT))
def test_another_presets_parameters_are_what_they_were(name):
    import hashlib

    task = get_task("causal_lm", model_name=name, seq_len=32)
    shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
    listing = "\n".join(
        f"{jax.tree_util.keystr(path)} {leaf.shape} {leaf.dtype}"
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes))
    assert hashlib.sha256(listing.encode()).hexdigest()[:16] == \
        TREES_ON_THE_PARENT[name]


@pytest.mark.parametrize("name,share,remat", sorted(LOWERED_ON_THE_PARENT,
                                                    key=str))
def test_another_presets_step_lowers_as_before_this_model(name, share, remat):
    from test_zaya import _lowered_hash

    assert _lowered_hash(name, share, remat) == LOWERED_ON_THE_PARENT[
        name, share, remat]


def test_the_table_of_presets_gained_two():
    """Every preset but the newest model's two is in the table above."""
    from lance_distributed_training_tpu.models.transformer import CAUSAL_LMS

    assert set(CAUSAL_LMS) == set(TREES_ON_THE_PARENT) | {
        "laguna_s_2_1", "laguna_tiny"}


# -- the configuration's file against the program ----------------------------


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro-c4.json")) as f:
        return json.load(f)


def test_the_cut_holds_the_parameters_the_file_states(config):
    task = get_task(**config["task"], remat=True)
    shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
    held = sum(int(np.prod(leaf.shape))
               for leaf in jax.tree.leaves(shapes["params"]))
    assert held == config["held_parameters"] == 772_160_448
    count = {
        (layer, name): sum(int(np.prod(leaf.shape))
                           for leaf in jax.tree.leaves(part))
        for layer in ("layer_0", "layer_5")
        for name, part in shapes["params"][layer].items()}
    assert count == {
        ("layer_0", "ssm"): 25_847_232, ("layer_5", "attn"): 10_485_760,
        **{(layer, name): n for layer in ("layer_0", "layer_5")
           for name, n in (("mlp", 50_331_648), ("ln_attn", 2048),
                           ("ln_mlp", 2048))}}
    assert "batch_stats" not in shapes  # no router: no state
    assert task.model.held_kinds == ("M2",) * 5 + ("N",) + ("M2",) * 4
    assert [k == "N" for k in task.model.held_kinds] == [
        k == "attention" for k in config["model"]["layer_kinds_held"]]


def test_every_width_is_the_published_one(config):
    """The catalog row's ``config`` (copied into the test: the guide is not
    part of the repository), key by key, but for the two keys ``reduced``
    names, which the file gives beside their published values."""
    types = ["attention" if i % 10 == 5 else "mamba" for i in range(40)]
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192, "layer_types": types,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    reduced = {"num_hidden_layers": 10, "vocab_size": 12544}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in published.items():
        assert config[key] == reduced.get(key, value), key
        assert config["model"][key] == reduced.get(key, value), key
        if key in reduced:
            assert config["model"][f"{key}_published"] == value
    model = get_task(**config["task"]).model
    assert (model.hidden_size, model.num_heads, model.dense_dim,
            model.num_experts, model.norm_eps, model.tied_head,
            model.embed_scale, model.branch_scale, model.logit_scale) == (
        2048, 32, 8192, 0, 1e-5, True, 12.0, 0.22, 1 / 8)
    assert {p.func: p.keywords for p in model.parts} == {
        Mamba2Mixer: dict(inner=4096, heads=64, head_dim=64, states=128,
                          conv=4),
        GroupedAttention: dict(kv_heads=8, head_dim=64,
                               score_scale=0.015625)}
    assert tuple("N" if t == "attention" else "M2" for t in types) == \
        granite4_layers(40) == model.layer_kinds
    assert (config["model"]["ssd_chunk"], config["model"]["ssd_head_block"]
            ) == (ssd.CHUNK, ssd.BLOCK_H)
    assert ssd.CHUNK == config["model"]["mamba_chunk_size"]


def _flops():
    spec = importlib.util.spec_from_file_location(
        "granite4h_flops", os.path.join(
            ROOT, "benchmark", "flops", "granite-4.0-h-micro-c4.py"))
    flops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops)
    return flops


def test_the_flops_file_counts_what_the_cells_why_says(config):
    flops, model = _flops(), config["model"]
    per_token = flops.forward_flops(model, 1, 8192) / 2 / 8192
    assert per_token == pytest.approx(803.0e6, rel=1e-4)
    assert flops.step_flops(model, {"input_ids": (1, 8192)}) == \
        pytest.approx(39.47e12, rel=1e-3)
    # the dual at the published chunk: 1.59 M a token and layer, 1.8% in all
    assert flops._ssd_per_token(model) == 128 * 128 + 4096 * 128 \
        + 2 * 4096 * 128
    assert 9 * flops._ssd_per_token(model) / per_token == pytest.approx(
        0.018, abs=0.001)
    assert flops.ssd_flops(model, 1, 8192) == \
        9 * 8192 * flops._ssd_per_token(model) * 6
    # x, B, C in bf16 and dt in f32 read each way and their gradients
    # written, y written and its gradient read
    assert flops.ssd_bytes(model, 1, 8192) == 9 * 8192 * (
        3 * (4096 * 2 + 2 * 128 * 2 + 64 * 4) + 2 * 4096 * 2)
    assert flops._pairs(8192) == 33_558_528
    assert flops.attn_flops(model, 1, 8192) == 32 * 33_558_528 * 6 * 64 * 2
    assert flops.attn_bytes(model, 1, 8192) == 32 * 8192 * 64 * 2 * 12


# -- the cell's readers -------------------------------------------------------

_FWD = "jit(step)/jvp(forward)/TransformerDecoder/"
_BWD = "jit(step)/transpose(jvp(forward))/TransformerDecoder/"
_REMAT = _BWD + "layer_0/checkpoint/"
# op_name -> ps in one run of the step: a hand-made plane with the scopes
# these layers name
_OPS = {
    _FWD + "layer_0/state_space/ssm/ssd.project/in_proj_xbcz/dot_general":
        3_000_000_000,
    _FWD + "layer_0/state_space/ssm/ssd.conv/causal_conv_silu_fwd":
        500_000_000,
    _FWD + "layer_0/state_space/ssm/ssd.kernel/while/body/exp":
        6_000_000_000,
    _REMAT + "state_space/ssm/ssd.kernel/while/body/exp": 6_000_000_000,
    _BWD + "layer_0/state_space/ssm/ssd.kernel/while/body/mul":
        12_000_000_000,
    _BWD + "layer_0/state_space/ssm/ssd.conv/causal_conv_silu_bwd":
        700_000_000,
    _FWD + "layer_0/state_space/ssm/ssd.norm/rsqrt": 800_000_000,
    _BWD + "layer_0/state_space/ssm/ssd.norm/mul": 1_200_000_000,
    _FWD + "layer_0/mlp.dense/mlp/gate/dot_general": 9_000_000_000,
    _BWD + "layer_0/mlp.dense/mlp/down/dot_general": 18_000_000_000,
    _FWD + "layer_5/attention/attn/attn.project/query/dot_general":
        1_000_000_000,
    _FWD + "layer_5/attention/attn/attn.full/splash_mha_fwd": 4_000_000_000,
    _BWD + "layer_5/attention/attn/attn.full/splash_mha_dkv": 8_000_000_000,
    _BWD + "layer_5/attention/attn/attn.out/out/dot_general": 500_000_000,
    _FWD + "lm_head/dot_general": 3_000_000_000,
    "jit(step)/optimizer/add": 1_000_000_000,
}
_READS = {  # ms a step, or the share the reader makes of them
    "ssd_mixer_ms": 30.2, "ssd_kernel_ms": 24.0, "ssd_conv_ms": 1.2,
    "ssd_norm_ms": 2.0, "g4_attention_ms": 13.5, "g4_mlp_ms": 27.0,
    "ssd_kernel_roofline_pct": None, "g4_attn_kernel_roofline_pct": None,
}


def _reader_ctx(ops: dict, config: dict) -> tuple:
    """What ``benchmark/run.py`` hands a reader, around a plane with two
    runs of ``jit_step(7)`` whose operations are ``ops``: the plane
    ``tests/test_bringup.py`` makes for the Moonlight cell's readers, under
    this cell's configuration and shapes."""
    from test_bringup import _moonlight_ctx

    ctx = _moonlight_ctx(ops)
    import run  # benchmark/run.py: on the path since _moonlight_ctx

    ctx.update(
        cell={"name": CELL, "config": config},
        flops=run.load_module("flops", "granite-4.0-h-micro-c4"),
        step_shapes=[{"input_ids": (1, 8192)}])
    return ctx, run


@pytest.mark.parametrize("metric", sorted(_READS))
def test_a_reader_reads_the_scopes_the_layers_name(metric, config):
    ctx, run = _reader_ctx(_OPS, config)
    value = run.load_module("layer_metrics", metric).read(ctx)
    want = _READS[metric]
    model, flops = config["model"], ctx["flops"]
    if metric == "ssd_kernel_roofline_pct":  # the bytes bound it
        want = 100 * flops.ssd_bytes(model, 1, 8192) / 819e9 / 0.024
    if metric == "g4_attn_kernel_roofline_pct":  # the operations do
        want = 100 * flops.attn_flops(model, 1, 8192) / 197e12 / 0.012
    assert value == pytest.approx(want, rel=1e-6)
    # on a program without these scopes (the parent, or another model's
    # step): nothing, and no error
    bare, _ = _reader_ctx({_FWD + "layer_0/attn/dot_general": 1_000_000},
                          config)
    assert run.load_module("layer_metrics", metric).read(bare) is None


def test_the_manifest_lists_the_cell_and_its_eight_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro-c4", "c4-prepacked-8k-vp8-12544", 1)
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [cell["name"]]]
    assert sorted(m["name"] for m in mine) == sorted(_READS)
    first = manifest["per_layer"].index(mine[0])  # appended in a block
    assert manifest["per_layer"][first:first + len(mine)] == mine
    assert {m["moves"] for m in mine} == {"samples_per_s_chip"}
    assert {m["source"] for m in mine} == {"device_trace"}
    config = next(c for c in manifest["configs"]
                  if c["name"] == "granite-4.0-h-micro-c4")
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert len(cell["why"]) <= 200 and "803.0 M" in cell["why"]


def test_the_cell_rehearses_end_to_end_on_the_cpu():
    """``benchmark/run.py``'s whole path for the cell at the tiny preset,
    untraced and traced: the generator, the model check against the
    reference, ``train`` with ``--layer_span`` and ``--remat``, the
    log-point clock, the stop, the readers."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--cells", CELL, "--checks", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "rehearsal ok"
    assert proc.stdout.count(f"{CELL} trace=") == 2
    assert "correct=False" not in proc.stdout
