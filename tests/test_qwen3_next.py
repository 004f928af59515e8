"""Qwen3-Next-80B-A3B's decoder layers (three Gated DeltaNets to one gated
attention layer, every layer top-k experts beside a gated shared one, under
RMSNorm's ``1 + w`` form; here ``qwen3_next_tiny``: 4 layers, 2 key heads
serving 4 value heads of 16, 4 query heads over 2 key/value heads of 32 with
rotary on 8, 64 experts of 32 with 4 a token) against the plain float32
reference the benchmark keeps in ``benchmark/reference/
qwen3-next-80b-a3b-c4.py``, on seeded weights, on the CPU.

*Is the reference the model?* Where ``torch`` and ``transformers`` import,
the reference's three sub-layers equal the published ``Qwen3NextGatedDeltaNet``,
``Qwen3NextAttention`` and ``Qwen3NextSparseMoeBlock`` on copied weights.
*Is the program's mathematics the reference's?* The program computed in
float32 against the reference, whole and under a share of the experts:
logits, loss and every parameter group's gradient to ``F32_TOL``. *Does the
share add up?* The four quarters' routed parts and the shared expert, once,
are the uncut layer. Then what only these layers have: the rule's chunked
form, its recurrence and its kernel (interpret mode) agree in values and
gradients; nothing before token t moves when token t does; the rotary turn
takes a quarter of a head; and the configuration's file holds the published
widths and the parameters the program counts.
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
from conftest import grouped_kernels_are_the_plain_form, register_preset

from lance_distributed_training_tpu.models import get_task
from lance_distributed_training_tpu.models.moe import DroplessMoE
from lance_distributed_training_tpu.models.transformer import (
    GatedAttention,
    GatedDeltaNet,
    RMSNorm,
    causal_depthwise_conv,
    rotary_embedding,
)
from lance_distributed_training_tpu.ops import conv, delta, norm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, ROWS, VOCAB, EXPERTS, TOP_K = 96, 2, 512, 64, 4  # 96: a chunk and a half
F32_TOL = 2e-4  # float32 against float32: summation order and grouping only
GROUPS = ("router", "w_gate", "w_up", "w_down", "shared", "shared_gate",
          "in_proj_qkvz", "in_proj_ba", "conv_kernel", "gates", "out_proj",
          "query", "key", "value", "out", "scales", "tok_embed", "lm_head")
SHARES = (None, "1/4")  # whole; experts 16..31 of 64


def _load_reference(first: int = 0):
    path = os.path.join(ROOT, "benchmark", "reference",
                        "qwen3-next-80b-a3b-c4.py")
    spec = importlib.util.spec_from_file_location(
        f"qwen3_next_reference_{first}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.ROTARY, module.KEY_DIM, module.TOP_K = 8, 16, TOP_K
    module.FIRST = first
    return module


@pytest.fixture(scope="module", params=SHARES, ids=["whole", "share"])
def share(request):
    return request.param


@pytest.fixture(scope="module")
def ref(share):
    return _load_reference(first=16 if share else 0)


def _task(share, **changes):
    if not changes:
        return get_task("causal_lm", model_name="qwen3_next_tiny",
                        seq_len=SEQ, expert_share=share)
    presets = register_preset("qwen3_next_tiny_changed", "qwen3_next_tiny",
                              **changes)
    try:
        return get_task("causal_lm", model_name="qwen3_next_tiny_changed",
                        seq_len=SEQ, expert_share=share)
    finally:
        del presets["qwen3_next_tiny_changed"]


@pytest.fixture(scope="module")
def f32_task(share):
    return _task(share, dtype=jnp.float32)


@pytest.fixture(scope="module")
def bf16_task(share):
    return _task(share)


@pytest.fixture(scope="module")
def variables(ref, bf16_task):
    """Seeded, perturbed as the benchmark's check perturbs them, and with
    every expert's last matrix 32 times as large: at these widths an expert
    adds a few percent of the stream's scale where at the published ones (32
    times as wide, same 0.02) it adds as much as the stream holds, and a
    token that takes another expert has to show."""
    variables = ref.perturb(
        jax.jit(bf16_task.init_variables)(jax.random.key(3)),
        jax.random.key(4))
    return dict(variables, params=jax.tree_util.tree_map_with_path(
        lambda path, x: 32 * x if path[-1].key == "w_down" else x,
        variables["params"]))


@pytest.fixture(scope="module")
def batch():
    ids = np.random.default_rng(5).integers(2, VOCAB, (ROWS, SEQ))
    mask = np.ones((ROWS, SEQ), np.int8)
    mask[-1, SEQ - 5:] = 0  # a padded tail: live tokens only in the losses
    return {"input_ids": ids.astype(np.int32), "attention_mask": mask}


def _groups(tree) -> dict:
    """Parameter groups, layers together: the router, the held experts'
    three, the shared expert and its gate, the linear-attention layers'
    projections, taps and per-head gates (``A_log``, ``dt_bias``), the
    attention layer's projections, every learned scale, the embedding and
    the head."""
    out: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [k.key for k in path if hasattr(k, "key")]
        last = keys[-1]
        if last.endswith("scale"):
            name = "scales"
        elif last in ("A_log", "dt_bias"):
            name = "gates"
        else:
            name = next(k for k in (
                "router", "w_gate", "w_up", "w_down", "shared_gate", "shared",
                "in_proj_qkvz", "in_proj_ba", "conv_kernel", "out_proj",
                "query", "key", "value", "out", "tok_embed", "lm_head")
                if k in keys)
        out.setdefault(name, []).append(jnp.ravel(leaf))
    return {k: jnp.concatenate(v) for k, v in out.items()}


def _relative(got, want) -> float:
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _one_program(fn, *args):
    """One jitted program, waited for (``tests/test_olmoe.py`` tells why)."""
    return jax.block_until_ready(jax.jit(fn)(*args))


def _reference(ref, variables, batch, dtype=None):
    """``(logits, the tokens the comparison keeps)`` in one program, as
    ``benchmark/run.py`` makes them (``live`` reads what ``forward`` noted
    while it was traced)."""
    def both(v):
        want = ref.forward(v, batch, dtype=dtype)
        return want, ref.live(batch, want)

    return _one_program(both, variables)


def _spread_error(got, want_and_live) -> float:
    """The benchmark's statistic (``benchmark/run.py`` ``check_model``)."""
    want, live = want_and_live
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


# -- the mathematics, float32 against float32, whole and under a share -------


@pytest.fixture(scope="module")
def want(ref, variables, batch):
    return _reference(ref, variables, batch)


def test_logits_match_reference_in_float32(f32_task, variables, batch, want):
    assert _spread_error(_logits(f32_task, variables, batch), want) < F32_TOL
    assert 0.1 < float(want[1].mean()) < 1  # tokens stay to be compared


@pytest.fixture(scope="module")
def reference_loss_and_grads(ref, variables, batch):
    loss, grads = _one_program(
        jax.value_and_grad(lambda v: ref.loss(v, batch)), variables)
    return loss, _groups(grads["params"])


@pytest.fixture(scope="module")
def reference_grads(reference_loss_and_grads):
    return reference_loss_and_grads[1]


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
                                               reference_grads):
    assert float(jnp.linalg.norm(reference_grads[group])) > 0
    assert _relative(f32_grads[group], reference_grads[group]) < F32_TOL


# -- the grouped products' kernel form ---------------------------------------


@pytest.mark.slow  # the stack twice in interpret mode: 17-54 s a case
def test_the_grouped_products_kernels_are_the_plain_form_and_the_gauge_says(
        f32_task, variables, batch, monkeypatch):
    """Whole and under the share, as the cell's shape runs on the chip since
    PR 52."""
    grouped_kernels_are_the_plain_form(
        f32_task, variables, batch, lambda v: _groups(v["params"]), F32_TOL,
        monkeypatch)


@pytest.fixture(scope="module")
def rows_of_the_check():
    """Eight rows for the two tests of ``TOLERANCE``: the statistic is a
    maximum over the tokens that stay, a sixth of them where every expert is
    held, and at this width a reading moves with any change in the order of
    the arithmetic; the chip's readings at the published widths are what
    ``TOLERANCE`` lies between (the reference's file has them)."""
    ids = np.random.default_rng(5).integers(2, VOCAB, (8, SEQ))
    return {"input_ids": ids.astype(np.int32),
            "attention_mask": np.ones((8, SEQ), np.int8)}


@pytest.fixture(scope="module")
def want_of_the_check(ref, variables, rows_of_the_check):
    return _reference(ref, variables, rows_of_the_check)


def test_logits_of_the_program_as_it_runs(ref, bf16_task, variables,
                                          rows_of_the_check,
                                          want_of_the_check):
    reading = _spread_error(_logits(bf16_task, variables, rows_of_the_check),
                            want_of_the_check)
    print(f"program in bf16 reads {reading:.3f}")
    assert reading < ref.TOLERANCE


def test_reference_in_the_precision_below_fails_the_benchmark_comparison(
        ref, variables, rows_of_the_check, want_of_the_check):
    """The reference with every tensor in bf16, and ``g``, ``beta``, the
    rule's state, the router's logits and scores and the logits rounded to
    bf16 where they stand, reads over ``TOLERANCE`` against itself in
    float32 on the tokens the comparison keeps."""
    low, _ = _reference(ref, variables, rows_of_the_check,
                        dtype=jnp.bfloat16)
    reading = _spread_error(low, want_of_the_check)
    print(f"reference in bf16 reads {reading:.3f}")
    assert reading > ref.TOLERANCE


def test_a_training_step_reports_its_gauges(bf16_task, variables, batch,
                                            share):
    def step(v):
        outputs, _ = bf16_task.forward(v, batch, True, None)
        return bf16_task.stats(outputs)

    stats = {k: float(v) for k, v in _one_program(step, variables).items()}
    assert {"delta_fused", "conv_fused", "delta_state_abs_max",
            "delta_decay_min", "delta_beta_mean", "attn_gate_mean",
            "shared_gate_mean", "moe_assignments_total"} <= set(stats)
    assert stats["delta_fused"] == 0  # the CPU: the plain chunked form
    assert stats["conv_fused"] == 0  # and the convolution's plain form
    assert "norm_fused" not in stats  # the first log line's word alone
    assert stats["moe_assignments_total"] == 4 * ROWS * SEQ * TOP_K
    assert 0 < stats["delta_decay_min"] < 0.5  # some head forgets fast
    assert stats["delta_state_abs_max"] > 1e-3
    for name in ("delta_beta_mean", "attn_gate_mean", "shared_gate_mean"):
        assert 0.4 < stats[name] < 0.6  # sigmoids of small arguments
    assert ("moe_local_fallback_total" in stats) is bool(share)


def test_the_first_log_line_names_the_delta_path():
    from lance_distributed_training_tpu import trainer

    config = trainer.TrainConfig(
        dataset_path="", task_type="causal_lm",
        model_name="qwen3_next_tiny", seq_len=SEQ)
    paths = trainer._kernel_paths(_task(None), config)
    assert paths["delta"] == "chunked" and paths["norm"] == "plain"
    config = trainer.TrainConfig(
        dataset_path="", task_type="causal_lm", model_name="olmoe_tiny",
        seq_len=SEQ)
    olmoe = get_task("causal_lm", model_name="olmoe_tiny", seq_len=SEQ)
    assert not {"delta", "norm"} & set(trainer._kernel_paths(olmoe, config))


@pytest.mark.parametrize("backend,devices,says", [
    ("tpu", 1, "fused kernel"), ("tpu", 4, "plain"), ("cpu", 1, "plain")])
def test_the_first_log_line_names_the_norms_path(monkeypatch, backend,
                                                 devices, says):
    """The cell's span at the cell's row on a described one-device TPU runs
    the gated norm's kernels beside the rule's (which hold the unit norms
    and have no word of their own) and the convolution's; several devices
    or another platform, the plain lines."""
    from lance_distributed_training_tpu import trainer

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda *a: devices)
    config = trainer.TrainConfig(
        dataset_path="", task_type="causal_lm",
        model_name="qwen3_next_80b_a3b", seq_len=8192)
    task = get_task("causal_lm", model_name="qwen3_next_80b_a3b",
                    seq_len=8192, layer_span="0:4", expert_share="0/16",
                    attention_fn=lambda *a, **k: None)
    paths = trainer._kernel_paths(task, config)
    assert paths["norm"] == paths["conv"] == says
    assert paths["delta"] == ("fused kernel" if says != "plain"
                              else "chunked")


# -- the share ---------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """The four ranks' routed parts, with the shared expert (which every
    rank computes alike) counted once, are the whole expert layer as the
    reference computes it uncut: all 64 experts on every token under the
    top-4 mask, weighted by the scores over their sum."""
    ref = _load_reference(first=0)
    x = jax.random.normal(jax.random.key(0), (ROWS, SEQ, 64))

    def layer(**kw):
        return DroplessMoE(num_experts=EXPERTS, expert_dim=32,
                           experts_per_token=TOP_K, dtype=jnp.float32,
                           norm_topk=True, shared_dim=32, shared_gate=True,
                           **kw)

    whole = layer().init(jax.random.key(3), x)["params"]
    whole = jax.tree.map(lambda w: 8 * w, whole)  # a router that decides
    tokens = x.reshape(-1, 64)
    want = _one_program(lambda p: ref._sparse_block(tokens, p)[0],
                        whole).reshape(x.shape)
    np.testing.assert_allclose(_one_program(
        lambda p: layer().apply({"params": p}, x), whole), want,
                               rtol=2e-5, atol=2e-4)
    none_held = dict(whole, **{name: whole[name][:0]
                               for name in ("w_gate", "w_up", "w_down")})
    shared = _one_program(lambda p: ref._sparse_block(tokens, p)[0],
                          none_held).reshape(x.shape)
    assert float(jnp.abs(shared).max()) > 0.1
    parts = []
    for rank in range(4):
        held = slice(16 * rank, 16 * rank + 16)
        params = dict(whole, **{name: whole[name][held]
                                for name in ("w_gate", "w_up", "w_down")})
        parts.append(_one_program(
            lambda p: layer(first_expert=16 * rank, held_experts=16).apply(
                {"params": p}, x), params) - shared)
        assert float(jnp.abs(parts[-1]).max()) > 0
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5,
                               atol=2e-4)


@pytest.mark.parametrize("crowded", [False, True],
                         ids=["usual_list", "worst_case_in_pieces"])
def test_a_sixteenth_held_builds_the_worst_case_in_pieces(crowded):
    """4 of 64 experts held with 4 a token: twice an even share is an eighth
    of the assignments, so the worst case (a step whose routing sends more
    than that here) is the sorted list in eight pieces, not one array of
    ``T * k`` rows. Both branches are the reference's held part, values and
    gradients; a router that sends nearly every assignment here takes the
    worst case."""
    ref = _load_reference(first=0)
    x = jax.random.normal(jax.random.key(0), (2, 128, 64))
    layer = DroplessMoE(num_experts=EXPERTS, expert_dim=32,
                        experts_per_token=TOP_K, dtype=jnp.float32,
                        norm_topk=True, shared_dim=32, shared_gate=True,
                        first_expert=0, held_experts=4)
    params = layer.init(jax.random.key(3), x)["params"]
    params = jax.tree.map(lambda w: 8 * w, params)
    if crowded:  # every token's four largest logits are the held experts'
        params["router"]["kernel"] = params["router"]["kernel"].at[:, :4].set(
            jnp.abs(x).mean() * jnp.sign(x.reshape(-1, 64).mean(0))[:, None])
        x = x + 2 * jnp.sign(x.reshape(-1, 64).mean(0))
    ct = jax.random.normal(jax.random.key(5), x.shape)

    def program(p, x):
        y, sown = layer.apply({"params": p}, x, mutable=["moe_stats",
                                                         "aux_loss"])
        return (y * ct).sum(), (y, sown["moe_stats"])

    def reference(p, x):
        y = ref._sparse_block(x.reshape(-1, 64), p)[0].reshape(x.shape)
        return (y * ct).sum(), y

    (_, (got, stats)), g_got = _one_program(
        jax.value_and_grad(program, argnums=(0, 1), has_aux=True), params, x)
    (_, want), g_want = _one_program(
        jax.value_and_grad(reference, argnums=(0, 1), has_aux=True), params,
        x)
    assert float(stats["over_usual"][0]) == float(crowded)
    assert (float(stats["held_sizes"][0].sum()) > 128) is crowded
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        assert _relative(a, b) < F32_TOL
    text = str(jax.make_jaxpr(lambda p, x: program(p, x)[0])(params, x))
    assert "1024,64" not in text  # no array of T * k rows


# -- ops/delta.py: recurrence, chunked form, kernel ---------------------------


def _recurrence(q, k, v, g, beta):
    """The rule a token at a time, written out here a second time: not the
    reference's, not the program's."""
    rep = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(t, rep, axis=2) for t in (q, k))

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        delta_t = (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)) * b_t[..., None]
        s = s + k_t[..., :, None] * delta_t[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    rows, _, heads, d_k = q.shape
    last, o = jax.lax.scan(
        token, jnp.zeros((rows, heads, d_k, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


def _rule_inputs(seq, key_heads=2, heads=4, d=16, rows=2, seed=0):
    """Unit keys, queries over sqrt(d), and decays a token from near 0 to
    -20, log-uniformly."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (rows, seq, key_heads, d))
    k = jax.random.normal(ks[1], (rows, seq, key_heads, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (rows, seq, heads, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (rows, seq, heads),
                                    minval=np.log(1e-3), maxval=np.log(20.0)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, seq, heads)))
    return q, k, v, g, beta


RULE_INPUTS = ("q", "k", "v", "g", "beta")


@pytest.fixture(scope="module", params=[128, 100],
                ids=["whole_chunks", "a_ragged_tail"])
def rule_case(request):
    args = _rule_inputs(request.param)
    ct = jax.random.normal(jax.random.key(9), args[2].shape)

    def both(fn):
        def run(*a):
            o, last = fn(*a)
            return (o * ct).sum(), (o, last)
        (_, out), grads = _one_program(jax.value_and_grad(
            run, argnums=range(5), has_aux=True), *args)
        return out, grads

    with jax.default_matmul_precision("highest"):
        want, g_want = both(_recurrence)
        got, g_got = both(delta.delta_chunked)
        return (args, want, got, g_want, g_got,
                _one_program(delta.gated_delta_rule, *args))


def test_the_chunked_form_is_the_recurrence(rule_case):
    args, (o_want, s_want), (o_got, s_got), _, _, (o_rule, _) = rule_case
    assert float(args[3].min()) < -15 and float(args[3].max()) > -0.01
    np.testing.assert_allclose(o_got, o_want, atol=2e-5)
    np.testing.assert_allclose(s_got, s_want, atol=5e-5)
    np.testing.assert_allclose(o_rule, o_got, atol=1e-6)  # off a TPU


@pytest.mark.parametrize("which", range(5), ids=RULE_INPUTS)
def test_the_chunked_forms_gradient_is_the_recurrences(which, rule_case):
    want, got = rule_case[3][which], rule_case[4][which]
    assert _relative(got, want) < F32_TOL


def test_the_head_groups_go_one_after_the_other_and_agree():
    """More value heads than ``GROUP_H``: the rule maps over groups of them,
    and gives what one call over all of them gives."""
    args = _rule_inputs(64, key_heads=8, heads=16, d=8, rows=2, seed=2)
    assert args[2].shape[2] > delta.GROUP_H

    def both(fn):
        def run(*a):
            o, last = fn(*a)
            return (o ** 2).sum(), (o, last)
        return _one_program(jax.value_and_grad(
            run, argnums=range(5), has_aux=True), *args)

    with jax.default_matmul_precision("highest"):
        (_, (o, last)), g_got = both(delta.gated_delta_rule)
        (_, (o_want, last_want)), g_want = both(delta.delta_chunked)
    np.testing.assert_allclose(o, o_want, atol=1e-6)
    np.testing.assert_allclose(last, last_want, atol=1e-6)
    for got, want in zip(g_got, g_want):
        assert _relative(got, want) < 1e-5


def _inverse_in_a_kernel(a):
    """``delta._blocks_inverse`` as the rule's kernels run it: on two ``[C,
    C]`` matrices side by side along the lanes, in VMEM (interpret mode)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(a_ref, inv_ref):
        inv_ref[0] = delta._blocks_inverse(a_ref[0])

    c = a.shape[-1]
    pairs = jnp.concatenate([a, a[::-1]], -1)  # [n, C, 2C]
    spec = pl.BlockSpec((1, c, 2 * c), lambda i: (i, 0, 0))
    with pltpu.force_tpu_interpret_mode():
        inv = jax.block_until_ready(pl.pallas_call(
            kernel, grid=(a.shape[0],), in_specs=[spec], out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(pairs.shape, a.dtype))(pairs))
    np.testing.assert_array_equal(inv[..., :c], inv[::-1, :, c:])
    return inv[..., :c]


@pytest.mark.parametrize("inverse", [
    lambda a: jax.jit(delta._unit_lower_inverse)(a), _inverse_in_a_kernel],
    ids=["in_xla", "in_a_kernel"])
def test_the_unit_lower_inverse_is_the_inverse(inverse):
    """Keys all alike, no decay, full write strength: the powers of the
    strictly lower part grow like binomials (a product over the whole
    chunk's powers cancels in float32 there); by blocks of 16 the inverse
    stays the bidiagonal matrix it is."""
    c = delta.CHUNK
    a = jnp.tril(jnp.ones((c, c), jnp.float32), -1)
    with jax.default_matmul_precision("highest"):
        inv = inverse(a[None])[0]
        want = jnp.eye(c) - jnp.eye(c, k=-1)
        np.testing.assert_allclose(inv, want, atol=1e-4)
        rng = jax.random.normal(jax.random.key(0), (3, c, c)) * 0.3
        a = jnp.tril(rng, -1)
        inv = inverse(a)
        np.testing.assert_allclose(inv @ (jnp.eye(c) + a),
                                   jnp.broadcast_to(jnp.eye(c), a.shape),
                                   atol=1e-4)


def _alike(args):
    """The same inputs with a chunk's keys nearly one direction, written at
    nearly full strength and hardly forgotten: ``I + A`` is then far from
    the identity, the case ``delta._blocks_inverse`` goes by blocks for."""
    q, k, v, g, beta = args
    k = k[:, :1] + 0.05 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return q, k, v, g * 1e-3, 0.9 + 0.1 * beta


def _slow_and_fast(args):
    """The same inputs with ``g`` from -1e-4 to -20 a token: the first chunk
    barely decays (-1e-4 every token), the second underflows (-20 every
    token: ``exp`` of a chunk's sum is 0 in float32), the rest mix both."""
    q, k, v, g, beta = args
    c = delta.CHUNK
    spread = -jnp.exp(jax.random.uniform(
        jax.random.key(11), g.shape, minval=np.log(1e-4), maxval=np.log(20.0)))
    g = spread.at[:, :c].set(-1e-4).at[:, c:2 * c].set(-20.0)
    assert float(jnp.exp(g[:, c:2 * c].sum(1)).max()) == 0
    return q, k, v, g, beta


KERNEL_CASES = {
    # name: (_rule_inputs' arguments, heads a grid step, what is done to them)
    "two_heads_of_one_key_head": (
        dict(seq=256, key_heads=1, heads=2, d=128, rows=1, seed=1), 8, None),
    "two_rows_a_block_of_two_key_heads": (
        dict(seq=128, key_heads=2, heads=4, d=128, rows=2, seed=3), 4, None),
    "keys_nearly_alike": (
        dict(seq=128, key_heads=1, heads=2, d=128, rows=1, seed=4), 8, _alike),
    "decays_from_1e-4_to_20_a_token": (
        dict(seq=256, key_heads=1, heads=2, d=128, rows=1, seed=5), 8,
        _slow_and_fast),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernel_in_interpret_mode_is_the_chunked_form(case):
    """Small shapes at the kernel's widths (heads of 128): values, the
    final state and every gradient, all finite."""
    from jax.experimental.pallas import tpu as pltpu

    shapes, block_h, change = KERNEL_CASES[case]
    args = _rule_inputs(**shapes)
    if change:
        args = change(args)
    ct = jax.random.normal(jax.random.key(9), args[2].shape)

    def both(fn):
        def run(*a):
            o, last = fn(*a)
            return (o * ct).sum(), (o, last)
        return jax.jit(jax.value_and_grad(run, argnums=range(5),
                                          has_aux=True))(*args)

    with jax.default_matmul_precision("highest"):
        (_, (o_want, s_want)), g_want = both(delta.delta_chunked)
        with pltpu.force_tpu_interpret_mode():
            (_, (o_got, s_got)), g_got = jax.block_until_ready(both(
                functools.partial(delta.delta_kernel, block_h=block_h)))
    for t in (o_got, s_got, *g_got):
        assert bool(jnp.isfinite(t).all())
    np.testing.assert_allclose(o_got, o_want, atol=1e-5)
    np.testing.assert_allclose(s_got, s_want, atol=1e-5)
    for name, got, want in zip(RULE_INPUTS, g_got, g_want):
        assert _relative(got, want) < 1e-4, name


def _raw_rule_inputs(seq, key_heads, heads, rows=1, seed=0, d=128):
    """``[q; k; v]`` as a layer's convolved projection holds them (raw: the
    queries some four times the keys' length, nothing a unit vector), and
    ``g`` and ``beta`` as :func:`_rule_inputs` makes them."""
    _, _, v, g, beta = _rule_inputs(seq, key_heads, heads, d, rows, seed)
    q, k = jax.random.normal(jax.random.key(seed + 100),
                             (2, rows, seq, key_heads * d))
    return jnp.concatenate([2.0 * q, 0.5 * k, v.reshape(rows, seq, -1)],
                           -1), g, beta


def _plain_norm_then_chunked(key_heads, d=128):
    """``unit`` ahead of ``delta_chunked``: the layer's lines off the chip,
    on ``[q; k; v]``."""
    def form(qkv, g, beta):
        q, k, v = delta._columns(qkv, key_heads, d, g.shape[2])
        q = delta.unit_heads(q) * d ** -0.5
        return delta.delta_chunked(q, delta.unit_heads(k), v, g, beta)
    return form


NORM_CASES = {
    # name: (_raw_rule_inputs' arguments, heads a grid step, in place?)
    "16_key_heads_serving_32_value_heads": (
        dict(seq=128, key_heads=16, heads=32), 8, True),
    "a_value_head_a_key_head_two_rows": (
        dict(seq=256, key_heads=2, heads=2, rows=2, seed=6), 8, True),
    # a block of four value heads is 512 columns and v starts at 256
    "v_off_a_whole_block_is_sliced_out": (
        dict(seq=128, key_heads=1, heads=4, seed=7), 4, False),
}


@pytest.fixture(scope="module", params=NORM_CASES)
def norm_case(request):
    """The rule's kernels with the norm inside, reading ``[q; k; v]`` where
    they lie, against the plain norm and the chunked form in f32: ``(o,
    S_last)`` and the gradients of ``q``, ``k``, ``v`` (the three column
    ranges of ``d qkv``), ``g`` and ``beta``, each way."""
    from jax.experimental.pallas import tpu as pltpu

    shapes, block_h, in_place = NORM_CASES[request.param]
    key_heads = shapes["key_heads"]
    args = _raw_rule_inputs(**shapes)
    ct = jax.random.normal(jax.random.key(9), (
        *args[1].shape, 128))

    def both(fn):
        def run(*a):
            o, last = fn(*a)
            return (o * ct).sum(), (o, last)
        return jax.jit(jax.value_and_grad(run, argnums=range(3),
                                          has_aux=True))

    kernel = both(functools.partial(
        delta.delta_kernel_packed, key_heads=key_heads, key_dim=128,
        block_h=block_h, qk_norm=True))
    text = str(kernel.trace(*args).jaxpr)
    with jax.default_matmul_precision("highest"):
        (_, want), g_want = both(_plain_norm_then_chunked(key_heads))(*args)
        with pltpu.force_tpu_interpret_mode():
            (_, got), g_got = jax.block_until_ready(kernel(*args))
    keys = key_heads * 128

    def five(grads):  # d qkv by its column ranges, then dg and d beta
        return (grads[0][..., :keys], grads[0][..., keys:2 * keys],
                grads[0][..., 2 * keys:], *grads[1:])
    return got, want, five(g_got), five(g_want), text, in_place


def test_the_kernels_norm_q_and_k_as_they_load_them(norm_case):
    (o_got, s_got), (o_want, s_want), _, _, text, in_place = norm_case
    assert bool(jnp.isfinite(o_got).all())
    np.testing.assert_allclose(o_got, o_want, atol=1e-5)
    np.testing.assert_allclose(s_got, s_want, atol=1e-5)
    # in place: no slice of the projection ahead of the kernels' call
    assert ("slice" not in text.split("pallas_call")[0]) is in_place


@pytest.mark.parametrize("which", range(5), ids=RULE_INPUTS)
def test_the_kernels_turn_the_cotangents_back_through_the_norm(which,
                                                               norm_case):
    got, want = norm_case[2][which], norm_case[3][which]
    assert got.shape == want.shape and bool(jnp.isfinite(got).all())
    assert _relative(got, want) < 1e-4


def test_arrays_of_their_own_are_normed_in_the_kernels_too():
    """``delta_kernel(q, k, v, .., qk_norm=True)`` on three arrays is the
    packed call on their concatenation, and ``gated_delta_rule`` hands the
    word through."""
    from jax.experimental.pallas import tpu as pltpu

    qkv, g, beta = _raw_rule_inputs(seq=128, key_heads=1, heads=2, seed=8)
    q, k, v = delta._columns(qkv, 1, 128, 2)

    def loss(fn, *a):
        return jax.jit(jax.value_and_grad(
            lambda *a: (fn(*a)[0] ** 2).sum(), argnums=range(len(a))))(*a)

    with jax.default_matmul_precision("highest"), \
            pltpu.force_tpu_interpret_mode(), \
            pytest.MonkeyPatch.context() as patch:
        packed, g_packed = loss(functools.partial(
            delta.delta_kernel_packed, key_heads=1, key_dim=128,
            qk_norm=True), qkv, g, beta)
        patch.setattr(delta, "delta_fused_applies", lambda *a, **k: True)
        apart, g_apart = loss(functools.partial(
            delta.gated_delta_rule, qk_norm=True), q, k, v, g, beta)
    assert float(packed) == float(apart)
    np.testing.assert_array_equal(
        jnp.concatenate([t.reshape(1, 128, -1) for t in g_apart[:3]], -1),
        g_packed[0])
    for a, b in zip(g_apart[3:], g_packed[1:]):
        np.testing.assert_array_equal(a, b)


def test_the_kernels_round_the_normed_rows_where_the_plain_norm_does():
    """bf16 operands: the kernels' normed ``q`` and ``k`` are the plain
    norm's bit for bit (f32 statistics, one rounding), so ``o`` and the
    state are those of the kernels fed the plain norm's rows, and so are the
    gradients that do not pass the norm; ``d qkv``'s first two ranges pass
    it in f32 here and in bf16 there, a rounding apart."""
    from jax.experimental.pallas import tpu as pltpu

    qkv, g, beta = _raw_rule_inputs(seq=128, key_heads=1, heads=2, seed=12)
    qkv = qkv.astype(jnp.bfloat16)
    ct = jax.random.normal(jax.random.key(9), (1, 128, 2, 128))

    def both(fn):
        def run(*a):
            o, last = fn(*a)
            return (o.astype(jnp.float32) * ct).sum(), (o, last)
        return jax.jit(jax.value_and_grad(run, argnums=range(3),
                                          has_aux=True))(qkv, g, beta)

    def plain_norm(qkv, g, beta):
        return delta.delta_kernel(
            *delta._columns(qkv, 1, 128, 2, delta._normed), g, beta)

    with pltpu.force_tpu_interpret_mode():
        (_, (o_want, s_want)), g_want = both(plain_norm)
        (_, (o_got, s_got)), g_got = both(functools.partial(
            delta.delta_kernel_packed, key_heads=1, key_dim=128,
            qk_norm=True))
    assert o_got.dtype == g_got[0].dtype == jnp.bfloat16
    np.testing.assert_array_equal(o_got, o_want)
    np.testing.assert_array_equal(s_got, s_want)
    np.testing.assert_array_equal(g_got[0][..., 256:], g_want[0][..., 256:])
    for got, want in zip(g_got[1:], g_want[1:]):
        np.testing.assert_array_equal(got, want)
    assert _relative(g_got[0].astype(jnp.float32),
                     g_want[0].astype(jnp.float32)) < 2 ** -7


def test_off_the_chip_the_packed_rule_is_the_layers_lines_to_the_letter():
    """``gated_delta_rule_packed`` here, on the CPU, lowers to what the
    layer wrote before it: each of q and k sliced, normed and rounded before
    the next, the slice of v, the chunked rule."""
    qkv, g, beta = _raw_rule_inputs(seq=96, key_heads=2, heads=4, rows=2,
                                    d=16)
    qkv = qkv.astype(jnp.bfloat16)

    def before(qkv, g, beta):
        b, s, _ = qkv.shape

        def unit(t):
            t = t.reshape(b, s, 2, 16).astype(jnp.float32)
            return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

        q = (unit(qkv[..., :32]) * 16 ** -0.5).astype(jnp.bfloat16)
        k = unit(qkv[..., 32:64]).astype(jnp.bfloat16)
        v = qkv[..., 64:].reshape(b, s, 4, 16)
        return delta.gated_delta_rule(q, k, v, g, beta)

    def now(qkv, g, beta):
        return delta.gated_delta_rule_packed(qkv, g, beta, key_heads=2,
                                             key_dim=16, qk_norm=True)

    def text(fn):
        return jax.jit(fn).lower(qkv, g, beta).as_text().replace(
            fn.__name__, "f")
    assert text(now) == text(before)


def test_the_kernel_is_for_a_tpu_and_whole_chunks_of_whole_lane_groups(
        monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    applies = delta.delta_fused_applies
    assert applies(8192, 32, 128, 128, platform="tpu")
    assert not applies(8192, 32, 128, 128, platform="cpu")
    assert not applies(8192 + 32, 32, 128, 128, platform="tpu")
    assert not applies(8192 + 64, 32, 128, 128, platform="tpu")  # no pair
    assert not applies(8192, 32, 64, 128, platform="tpu")
    assert not applies(8192, 32, 128, 96, platform="tpu")
    with pytest.raises(ValueError, match="whole chunks"):
        delta.delta_kernel(*_rule_inputs(100, d=128))
    with pytest.raises(ValueError, match="Hk dividing Hv"):
        delta.gated_delta_rule(*_rule_inputs(64, key_heads=3, heads=4))


# -- ops/norm.py: the gated RMSNorm ------------------------------------------


def _norm_inputs(rows=2, seq=256, heads=4, d=128, ahead=256, seed=0,
                 dtype=jnp.float32):
    """The rule's output, a projection whose last ``heads * d`` columns are
    the gate behind ``ahead`` others, a scale near 1 and a cotangent."""
    ks = jax.random.split(jax.random.key(seed), 4)
    o = 3.0 * jax.random.normal(ks[0], (rows, seq, heads, d))
    z = jax.random.normal(ks[1], (rows, seq, ahead + heads * d))
    scale = 1.0 + 0.1 * jax.random.normal(ks[2], (d,))
    ct = jax.random.normal(ks[3], (rows, seq, heads * d))
    return (o.astype(dtype), z.astype(dtype), scale), ct.astype(dtype)


def _the_layers_norm(o, z, scale, eps=1e-6, dtype=jnp.float32):
    """The lines ``GatedDeltaNet`` had under ``gdn.norm``."""
    b, s, hv, dv = o.shape
    o = o.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * scale
    z = z[..., z.shape[2] - hv * dv:].reshape(b, s, hv, dv)
    return (o * jax.nn.silu(z.astype(jnp.float32))).astype(dtype).reshape(
        b, s, hv * dv)


def _norm_both(form, args, ct):
    def loss(*a):
        y = form(*a)
        return (y.astype(jnp.float32) * ct).sum(), y
    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=range(3), has_aux=True))(*args)
    return y, grads


NORM_TILES = {
    # name: (_norm_inputs' arguments, block_s, block_d, step_rows)
    "two_blocks_each_way": (dict(), 128, 256, 64),
    "the_gate_alone": (dict(ahead=0), 256, 512, 32),
    "a_gate_behind_one_head": (dict(ahead=128, heads=3, seq=128), 64, 1024, 8),
    "heads_of_two_lane_groups": (dict(d=256, heads=2, ahead=512), 128, 256,
                                 64),
}


@pytest.mark.parametrize("case", NORM_TILES)
def test_the_gated_norms_kernels_in_interpret_mode_are_the_layers_lines(case):
    """Values and the gradients of ``o``, ``z`` (zero ahead of the gate's
    columns) and ``scale``, the gate read from the last columns of a wider
    array."""
    from jax.experimental.pallas import tpu as pltpu

    shapes, block_s, block_d, step_rows = NORM_TILES[case]
    args, ct = _norm_inputs(**shapes)
    y_want, g_want = _norm_both(_the_layers_norm, args, ct)
    with pltpu.force_tpu_interpret_mode():
        y_got, g_got = jax.block_until_ready(_norm_both(functools.partial(
            norm.norm_kernel, block_s=block_s, block_d=block_d,
            step_rows=step_rows), args, ct))
    np.testing.assert_allclose(y_got, y_want, rtol=1e-5, atol=1e-5)
    ahead = shapes.get("ahead", 256)
    assert float(jnp.abs(g_got[1][..., :ahead]).sum()) == 0
    for name, got, want in zip(("o", "z", "scale"), g_got, g_want):
        assert got.shape == want.shape, name
        assert _relative(got, want) < 1e-5, name


def test_the_gated_norm_casts_a_bf16_row_once_each_way():
    """bf16 operands: f32 inside, one rounding of the output and of each
    cotangent, as the plain lines round."""
    from jax.experimental.pallas import tpu as pltpu

    args, ct = _norm_inputs(rows=1, seq=128, dtype=jnp.bfloat16)
    args = (*args[:2], args[2].astype(jnp.float32))
    form = functools.partial(_the_layers_norm, dtype=jnp.bfloat16)
    y_want, g_want = _norm_both(form, args, ct)
    with pltpu.force_tpu_interpret_mode():
        y_got, g_got = jax.block_until_ready(_norm_both(functools.partial(
            norm.norm_kernel, block_s=128, block_d=256), args, ct))
    assert y_got.dtype == g_got[0].dtype == g_got[1].dtype == jnp.bfloat16
    assert g_got[2].dtype == jnp.float32
    # the same f32 values rounded once: a unit in the last place at most
    np.testing.assert_allclose(y_got.astype(jnp.float32),
                               y_want.astype(jnp.float32), rtol=2 ** -7)
    for got, want in zip(g_got, g_want):
        assert _relative(got, want) < 2 ** -7


def test_off_the_chip_the_gated_norm_is_the_layers_lines_to_the_letter():
    args, _ = _norm_inputs(heads=4, d=16, ahead=96, seq=32,
                           dtype=jnp.bfloat16)

    def before(o, z, scale):
        return _the_layers_norm(o, z, scale, 1e-6, jnp.bfloat16)

    def now(o, z, scale):
        return norm.gated_rms_norm(o, z, scale, eps=1e-6, dtype=jnp.bfloat16)

    def text(fn):
        return jax.jit(fn).lower(*args).as_text().replace(fn.__name__, "f")
    assert text(now) == text(before)


def test_the_gated_norms_rule(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    applies = norm.norm_fused_applies
    assert applies(8192, 32, 128, platform="tpu")  # the cell's
    assert applies(8192, 6, 256, platform="tpu")
    assert not applies(8192, 32, 128, platform="cpu")
    assert not applies(8192, 32, 128)  # here: the CPU
    assert not applies(8192 + 64, 32, 128, platform="tpu")  # a ragged row
    assert not applies(8192, 4, 16, platform="tpu")  # qwen3_next_tiny's
    assert not applies(8192, 32, 192, platform="tpu")
    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    assert applies(8192, 32, 128, mesh=mesh, platform="tpu")

    class MeshOfTwo:
        size = 2
    assert not applies(8192, 32, 128, mesh=MeshOfTwo(), platform="tpu")
    monkeypatch.setattr(jax, "device_count", lambda *a: 4)
    assert not applies(8192, 32, 128, platform="tpu")
    args, _ = _norm_inputs(seq=100)
    with pytest.raises(ValueError, match="whole tiles of 8"):
        norm.norm_kernel(*args)
    args, _ = _norm_inputs(d=96)
    with pytest.raises(ValueError, match="whole groups of 128"):
        norm.norm_kernel(*args)


def test_a_gate_off_a_whole_head_is_sliced_in_front_of_the_kernel():
    """64 columns ahead of the gate (no block of whole heads walks them in
    place): the op hands the kernel the slice, and ``dz`` comes back as
    wide as ``z``."""
    from jax.experimental.pallas import tpu as pltpu

    args, ct = _norm_inputs(rows=1, seq=128, ahead=64)
    y_want, g_want = _norm_both(_the_layers_norm, args, ct)
    with pytest.MonkeyPatch.context() as patch, \
            pltpu.force_tpu_interpret_mode():
        patch.setattr(norm, "norm_fused_applies", lambda *a, **k: True)
        y_got, g_got = jax.block_until_ready(_norm_both(
            norm.gated_rms_norm, args, ct))
    np.testing.assert_allclose(y_got, y_want, rtol=1e-5, atol=1e-5)
    assert g_got[1].shape == args[1].shape
    for got, want in zip(g_got, g_want):
        assert _relative(got, want) < 1e-5


# -- the layer with every kernel bound, as the chip binds them ----------------


def test_the_mixer_with_its_kernels_bound_is_its_plain_self():
    """Forward, the recomputed forward and the backward pass through a
    Gated DeltaNet of two key heads serving four value heads of 128 with the
    rule's, the convolution's and the gated norm's kernels bound: the rule
    reads ``q``, ``k``, ``v`` off the convolved projection in place and
    norms inside, the norm reads ``z`` off the fused one. (The kernels by
    ``interpret=True``: the layer runs them under ``jax.checkpoint``, which
    cannot take the callbacks of the TPU interpreter.)"""
    from jax.experimental import pallas as pl

    mixer = GatedDeltaNet(2, 4, 128, 128, 4, dtype=jnp.float32)
    u = jax.random.normal(jax.random.key(1), (2, 128, 64))
    variables = mixer.init(jax.random.key(2), u)

    def program():  # a function of its own a trace: jit keeps traces by it
        def loss(v, u):
            out = mixer.apply(v, u, mutable=["mixer_stats"])[0]
            return (out * jnp.cos(jnp.arange(out.shape[-1]))).sum(), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, want), g_want = program()(variables, u)
        with pytest.MonkeyPatch.context() as patch:
            for op, rule in ((conv, "conv_fused_applies"),
                             (delta, "delta_fused_applies"),
                             (norm, "norm_fused_applies")):
                patch.setattr(op, rule, lambda *a, **k: True)
            patch.setattr(pl, "pallas_call", functools.partial(
                pl.pallas_call, interpret=True))
            traced = program().trace(variables, u)
            (_, got), g_got = jax.block_until_ready(
                traced.lower().compile()(variables, u))
    text = str(traced.jaxpr)
    for call in ("_conv_forward", "_rule_forward", "_rule_backward",
                 "_norm_forward", "_norm_backward"):
        assert call in text, call
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    flat_got, _ = jax.tree_util.tree_flatten_with_path(g_got)
    for (path, a), b in zip(flat_got, jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=1e-4 * float(jnp.abs(b).max()) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


# -- causality ---------------------------------------------------------------


def test_moving_token_t_moves_nothing_before_t(f32_task, variables, batch):
    t = 70  # in the second chunk
    moved = dict(batch, input_ids=batch["input_ids"].copy())
    moved["input_ids"][0, t] = (moved["input_ids"][0, t] + 7) % VOCAB
    a = _logits(f32_task, variables, batch)
    b = _logits(f32_task, variables, moved)
    assert float(jnp.abs(a[0, :t] - b[0, :t]).max()) == 0
    assert float(jnp.abs(a[0, t:] - b[0, t:]).min(0).max()) > 0
    assert float(jnp.abs(a[1] - b[1]).max()) == 0  # rows apart


def test_the_rule_and_the_convolution_look_back_only():
    q, k, v, g, beta = _rule_inputs(128)
    t = 70
    chunked = jax.jit(delta.delta_chunked)
    o = chunked(q, k, v, g, beta)[0]
    for i, moved in enumerate((q.at[:, t].add(0.1), k.at[:, t].add(0.1),
                               v.at[:, t].add(1.0), g.at[:, t].add(-1.0),
                               beta.at[:, t].multiply(0.5))):
        args = [q, k, v, g, beta]
        args[i] = moved
        other = chunked(*args)[0]
        assert float(jnp.abs(other[:, :t] - o[:, :t]).max()) == 0, i
        assert float(jnp.abs(other[:, t:] - o[:, t:]).max()) > 0, i
    x = jax.random.normal(jax.random.key(0), (2, 16, 8))
    taps = jax.random.normal(jax.random.key(1), (4, 8))
    y = causal_depthwise_conv(x, taps)
    y_moved = causal_depthwise_conv(x.at[:, 9].add(1.0), taps)
    changed = np.asarray(jnp.abs(y_moved - y).max((0, 2)) > 0)
    assert changed.tolist() == [9 <= i <= 12 for i in range(16)]
    # ``taps[K - 1]`` is this token's, ``taps[0]`` the token three before
    np.testing.assert_allclose(
        y[:, 5], sum(taps[j] * x[:, 5 - 3 + j] for j in range(4)), rtol=1e-5)
    np.testing.assert_allclose(y[:, 0], taps[3] * x[:, 0], rtol=1e-5)
    np.testing.assert_allclose(
        causal_depthwise_conv(x, taps, bias=jnp.ones((8,))), y + 1)
    # the gated norm: a token's output reads its own row of o and of z, and
    # of the row its own head alone through o
    from jax.experimental.pallas import tpu as pltpu

    (o, z, scale), _ = _norm_inputs(rows=1, seq=128, heads=2)
    with pltpu.force_tpu_interpret_mode():
        forms = (norm.gated_rms_norm_plain, functools.partial(
            norm.norm_kernel, block_s=64, block_d=128, step_rows=16))
        for form in forms:
            y = form(o, z, scale).reshape(1, 128, 2, 128)
            for moved in (form(o.at[:, t, 1].add(1.0), z, scale),
                          form(o, z.at[:, t, 256 + 128:].add(1.0), scale)):
                changed = np.asarray(jnp.abs(
                    moved.reshape(y.shape) - y).max((0, 3)) > 0)
                assert changed[t].tolist() == [False, True]
                assert not changed[:t].any() and not changed[t + 1:].any()


# -- the gated attention ------------------------------------------------------


def _gated(attention_fn=None):
    return GatedAttention(4, 2, 32, 8, 1e-6, 1e7, jnp.float32, attention_fn)


def test_the_gated_attention_layer_has_the_parts_it_names():
    """A head's queries and gate side by side in a projection twice as wide;
    one ``1 + w`` scale a head width for queries and one for keys; rotary on
    the first 8 of 32; ``sigmoid(gate)`` on the output."""
    mixer = _gated()
    x = jax.random.normal(jax.random.key(0), (2, 16, 64))
    params = mixer.init(jax.random.key(1), x)["params"]
    assert jax.tree.map(lambda p: p.shape, params) == {
        "query": {"kernel": (64, 4, 64)}, "key": {"kernel": (64, 2, 32)},
        "value": {"kernel": (64, 2, 32)}, "out": {"kernel": (4, 32, 64)},
        "q_norm": {"scale": (32,)}, "k_norm": {"scale": (32,)}}
    assert not np.asarray(params["q_norm"]["scale"]).any()  # w from 0
    seen = {}

    def spy(q, k, v, mask=None, **kw):
        seen.update(q=q, k=k, v=v)
        return jnp.ones_like(q)  # the output is then the gate alone

    params = dict(params, q_norm={"scale": jnp.full((32,), 0.5)})
    out = _gated(spy).apply({"params": params}, x)
    q_gate = jnp.einsum("bsh,hnd->bsnd", x, params["query"]["kernel"])
    q0 = q_gate[..., :32]
    normed = 1.5 * q0 * jax.lax.rsqrt(
        jnp.mean(q0 * q0, -1, keepdims=True) + 1e-6)
    turned = rotary_embedding(normed[..., :8], jnp.arange(16), 1e7)
    np.testing.assert_allclose(seen["q"].transpose(0, 2, 1, 3)[..., :8],
                               turned, atol=1e-5)
    np.testing.assert_allclose(seen["q"].transpose(0, 2, 1, 3)[..., 8:],
                               normed[..., 8:], atol=1e-5)  # left as it is
    assert float(jnp.abs(turned[:, 1:] - normed[:, 1:, :, :8]).max()) > 0.1
    assert seen["k"].shape == (2, 2, 16, 32) == seen["v"].shape
    gate = jax.nn.sigmoid(q_gate[..., 32:])
    np.testing.assert_allclose(
        out, jnp.einsum("bsnd,ndh->bsh", gate, params["out"]["kernel"]),
        atol=1e-5)


def test_rmsnorm_with_a_unit_offset_scales_by_one_plus_w():
    x = jax.random.normal(jax.random.key(0), (3, 16))
    norm = RMSNorm(1e-6, jnp.float32, True)
    params = norm.init(jax.random.key(1), x)
    assert not np.asarray(params["params"]["scale"]).any()
    plain = RMSNorm(1e-6, jnp.float32)
    ones = plain.init(jax.random.key(1), x)
    np.testing.assert_allclose(norm.apply(params, x), plain.apply(ones, x))
    w = jnp.linspace(-0.5, 0.5, 16)
    np.testing.assert_allclose(
        norm.apply({"params": {"scale": w}}, x),
        plain.apply({"params": {"scale": 1 + w}}, x), rtol=1e-6)


# -- parity with the published modules ----------------------------------------


@pytest.fixture(scope="module")
def published():
    """The published classes at the tiny preset's sizes, float32."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    try:
        from transformers.models.qwen3_next import modeling_qwen3_next as hf
        from transformers.models.qwen3_next.configuration_qwen3_next import (
            Qwen3NextConfig,
        )
    except ImportError as e:  # an older transformers
        pytest.skip(f"no qwen3_next in this transformers: {e}")
    config = Qwen3NextConfig(
        hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, linear_conv_kernel_dim=4,
        num_experts=EXPERTS, num_experts_per_tok=TOP_K,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        partial_rotary_factor=0.25, rope_theta=1e7, vocab_size=VOCAB,
        rms_norm_eps=1e-6, norm_topk_prob=True)
    config._attn_implementation = "eager"
    torch.manual_seed(0)
    return torch, hf, config


def _np(t):
    return jnp.asarray(t.detach().numpy())


PARITY = 1e-4  # float32 torch against float32 jax.numpy: summation order


def test_reference_linear_attention_is_the_published_module(published):
    torch, hf, config = published
    ref = _load_reference()
    module = hf.Qwen3NextGatedDeltaNet(config, layer_idx=0).float()
    with torch.no_grad():
        module.A_log.uniform_(-6.0, 2.0)  # decays from slow to fast
        module.norm.weight.uniform_(0.75, 1.25)
    x = torch.randn(2, SEQ, 64)
    with torch.no_grad():
        want = module(x)
    hk, rep, dk, dv = 2, 2, 16, 16
    # the checkpoint's fused projection is interleaved by key head: [q, k,
    # the head's two values, their two gates]; the program's is [q; k; v; z]
    w = _np(module.in_proj_qkvz.weight).T.reshape(64, hk, -1)
    parts = jnp.split(w, [dk, 2 * dk, 2 * dk + rep * dv], axis=-1)
    qkvz = jnp.concatenate([p.reshape(64, -1) for p in parts], axis=-1)
    ba = _np(module.in_proj_ba.weight).T.reshape(64, hk, 2 * rep)
    ba = jnp.concatenate([ba[..., :rep].reshape(64, -1),
                          ba[..., rep:].reshape(64, -1)], axis=-1)
    params = {
        "in_proj_qkvz": qkvz, "in_proj_ba": ba,
        "conv_kernel": _np(module.conv1d.weight)[:, 0, :].T,
        "A_log": _np(module.A_log), "dt_bias": _np(module.dt_bias),
        "norm_scale": _np(module.norm.weight),
        "out_proj": {"kernel": _np(module.out_proj.weight).T}}
    with jax.default_matmul_precision("highest"):
        got, _ = _one_program(
            lambda p, x: ref._linear_attention(x, p, lambda t: t), params,
            _np(x))
    np.testing.assert_allclose(got, _np(want), atol=PARITY)
    assert float(jnp.abs(_np(want)).max()) > 0.05


def test_reference_gated_attention_is_the_published_module(published):
    torch, hf, config = published
    ref = _load_reference()
    module = hf.Qwen3NextAttention(config, layer_idx=3).float()
    with torch.no_grad():
        module.q_norm.weight.uniform_(-0.25, 0.25)
        module.k_norm.weight.uniform_(-0.25, 0.25)
    seq = 24
    x = torch.randn(2, seq, 64)
    positions = torch.arange(seq)[None].expand(2, -1)
    cos_sin = hf.Qwen3NextRotaryEmbedding(config)(x, positions)
    causal = torch.full((seq, seq), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        want, _ = module(x, cos_sin, causal.expand(2, 1, -1, -1))
    params = {
        "query": {"kernel": _np(module.q_proj.weight).T.reshape(64, 4, 64)},
        "key": {"kernel": _np(module.k_proj.weight).T.reshape(64, 2, 32)},
        "value": {"kernel": _np(module.v_proj.weight).T.reshape(64, 2, 32)},
        "out": {"kernel": _np(module.o_proj.weight).T.reshape(4, 32, 64)},
        "q_norm": {"scale": _np(module.q_norm.weight)},
        "k_norm": {"scale": _np(module.k_norm.weight)}}

    def allow_rows(start, n):
        at = start + jnp.arange(n)
        return jnp.broadcast_to(
            jnp.arange(seq)[None, :] <= at[:, None], (2, n, seq))

    with jax.default_matmul_precision("highest"):
        got = ref._gated_attention(_np(x), params, jnp.arange(seq),
                                   allow_rows)
    np.testing.assert_allclose(got, _np(want), atol=PARITY)
    assert float(jnp.abs(_np(want)).max()) > 0.05


def test_reference_expert_layer_is_the_published_module(published):
    torch, hf, config = published
    ref = _load_reference()
    module = hf.Qwen3NextSparseMoeBlock(config).float()
    with torch.no_grad():
        module.gate.weight.mul_(8.0)  # a router that decides
    x = torch.randn(2, SEQ, 64)
    with torch.no_grad():
        want, router_logits = module(x)

    def stack(name):
        return jnp.stack([_np(getattr(e, name).weight).T
                          for e in module.experts])

    shared = module.shared_expert
    params = {
        "router": {"kernel": _np(module.gate.weight).T},
        "w_gate": stack("gate_proj"), "w_up": stack("up_proj"),
        "w_down": stack("down_proj"),
        "shared": {name: {"kernel": _np(getattr(shared, f"{name}_proj"
                                                ).weight).T}
                   for name in ("gate", "up", "down")},
        "shared_gate": {"kernel": _np(module.shared_expert_gate.weight).T}}
    with jax.default_matmul_precision("highest"):
        got, logits, _, chosen = ref._sparse_block(
            _np(x).reshape(-1, 64), params)
    np.testing.assert_allclose(logits, _np(router_logits), atol=PARITY)
    assert int(chosen.sum()) == 2 * SEQ * TOP_K
    np.testing.assert_allclose(got.reshape(2, SEQ, 64), _np(want),
                               atol=PARITY)


# -- the configuration's file against the program ----------------------------


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b-c4.json")) as f:
        return json.load(f)


def test_the_cut_holds_the_parameters_the_file_states(config):
    task = get_task(**config["task"])
    shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
    held = sum(int(np.prod(leaf.shape))
               for leaf in jax.tree.leaves(shapes["params"]))
    assert held == config["held_parameters"] == 625_667_136
    count = {
        (layer, name): sum(int(np.prod(leaf.shape))
                           for leaf in jax.tree.leaves(part))
        for layer in ("layer_0", "layer_3")
        for name, part in shapes["params"][layer].items()}
    assert count == {
        ("layer_0", "gdn"): 33_718_464, ("layer_3", "attn"): 27_263_488,
        ("layer_0", "moe"): 104_859_648, ("layer_3", "moe"): 104_859_648,
        ("layer_0", "ln_attn"): 2048, ("layer_0", "ln_mlp"): 2048,
        ("layer_3", "ln_attn"): 2048, ("layer_3", "ln_mlp"): 2048}
    assert "batch_stats" not in shapes  # no selection bias: no state
    assert task.model.held_kinds == ("D", "D", "D", "A")


def test_every_width_is_the_published_one(config):
    """The catalog row's ``config`` (copied into the test: the guide is not
    part of the repository), key by key, but for the three keys ``reduced``
    names, which the file gives beside their published values."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    reduced = {"num_hidden_layers": 4, "num_experts": 32,
               "vocab_size": 18992}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in published.items():
        assert config[key] == reduced.get(key, value), key
        assert config["model"][key] == reduced.get(key, value), key
        if key in reduced:
            assert config["model"][f"{key}_published"] == value
    model = get_task(**config["task"]).model
    assert (model.hidden_size, model.num_heads, model.expert_dim,
            model.num_experts, model.experts_per_token, model.rope_theta,
            model.norm_eps, model.norm_offset, model.tied_head) == (
        2048, 16, 512, 512, 10, 1e7, 1e-6, True, False)
    assert {p.func: p.keywords for p in model.parts} == {
        GatedDeltaNet: dict(key_heads=16, value_heads=32, key_dim=128,
                            value_dim=128, conv=4),
        GatedAttention: dict(kv_heads=2, head_dim=256, rotary_dim=64)}
    moe = dict(model.moe)
    assert (moe["held_experts"], moe["first_expert"], moe["shared_dim"],
            moe["norm_topk"], moe["shared_gate"]) == (32, 0, 512, True, True)
    assert len(model.layer_kinds) == 48
    assert [i for i, kind in enumerate(model.layer_kinds) if kind == "A"] \
        == list(range(3, 48, 4))
