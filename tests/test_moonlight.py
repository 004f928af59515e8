"""Moonlight-16B-A3B's decoder (latent attention with 192-wide keys and
128-wide values, a leading dense layer, sigmoid top-k routing with a
selection bias, a shared expert, and an expert layer that is told which
experts it holds; here ``moonlight_tiny``: 1 dense + 2 expert layers, 8
experts, 2 a token, 1 shared) against the plain float32 reference the
benchmark keeps in ``benchmark/reference/moonlight-16b-a3b-c4.py``, on seeded
weights, on the CPU.

As for OLMoE (``tests/test_olmoe.py``) two things are asked. *Is the
mathematics right?* The program computed in float32 against the reference,
under the same share of the experts: logits, loss and every parameter
group's gradient to ``F32_TOL``, on the dense path and with the fused kernel
for unequal head widths as the attention (TPU interpret mode); a router in
bf16, weights not renormalised, a forgotten scaling factor, a bias that
enters the weights and a dropped expert each miss it by orders of magnitude.
*Does the share add up?* The routed parts that every share of one expert
layer gives, with the shared expert counted once, are the uncut reference's
layer output. Then the router's state: the bias moves against the load after
a training step and takes no gradient.
"""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import grouped_kernels_are_the_plain_form, register_preset

from lance_distributed_training_tpu.models import get_task, moe, transformer
from lance_distributed_training_tpu.models.moe import DroplessMoE, SwiGLU
from lance_distributed_training_tpu.models.transformer import moonlight_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, ROWS, VOCAB, TOP_K, EXPERTS = 32, 4, 512, 2, 8
SHARE = "1/2"  # experts 4..7 of 8
F32_TOL = 2e-4  # float32 against float32: summation order and grouping only
GROUPS = ("router", "w_gate", "w_up", "w_down", "shared", "mlp", "query",
          "kv_a", "kv_b", "out", "norms", "tok_embed", "lm_head")


def _load_reference(first: int):
    path = os.path.join(ROOT, "benchmark", "reference",
                        "moonlight-16b-a3b-c4.py")
    spec = importlib.util.spec_from_file_location(
        f"moonlight_reference_{first}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.TOP_K, module.NOPE, module.ROPE, module.FIRST = TOP_K, 16, 8, first
    return module


@pytest.fixture(scope="module")
def ref():
    return _load_reference(first=4)


def _register(name, **changes):
    """``moonlight_tiny`` under a name of its own, with fields changed."""
    return register_preset(name, "moonlight_tiny", **changes)


@pytest.fixture(scope="module")
def f32_task():
    _register("moonlight_tiny_f32", dtype=jnp.float32)
    try:
        yield get_task("causal_lm", model_name="moonlight_tiny_f32",
                       seq_len=SEQ, expert_share=SHARE)
    finally:
        del transformer.CAUSAL_LMS["moonlight_tiny_f32"]


@pytest.fixture(scope="module")
def bf16_task():
    return get_task("causal_lm", model_name="moonlight_tiny", seq_len=SEQ,
                    expert_share=SHARE)


@pytest.fixture(scope="module")
def variables(ref, bf16_task):
    return ref.perturb(bf16_task.init_variables(jax.random.key(3)),
                       jax.random.key(4))


@pytest.fixture(scope="module")
def batch():
    ids = np.random.default_rng(5).integers(2, VOCAB, (ROWS, SEQ))
    mask = np.ones((ROWS, SEQ), np.int8)
    mask[-1, SEQ - 5:] = 0  # a padded tail: live tokens only in the losses
    return {"input_ids": ids.astype(np.int32), "attention_mask": mask}


def _groups(tree) -> dict:
    """Parameter groups: the router, the held experts' three matrices, the
    shared expert, the dense layer, latent attention's four projections, the
    norms, the embedding, the head (layers together)."""
    out: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [k.key for k in path if hasattr(k, "key")]
        name = next(k for k in (
            "router", "w_gate", "w_up", "w_down", "shared", "mlp", "query",
            "kv_a", "kv_b", "out", "tok_embed", "lm_head", "scale")
            if k in keys)
        out.setdefault("norms" if name == "scale" else name, []).append(
            jnp.ravel(leaf))
    return {k: jnp.concatenate(v) for k, v in out.items()}


def _relative(got, want) -> float:
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _eager(fn, *args):
    return fn(*args)


def _one_program(fn, *args):
    """One jitted program, waited for: an interpreted kernel's callbacks and
    an eager caller can wait for each other on the CPU's one execution queue
    (``tests/test_olmoe.py``)."""
    return jax.block_until_ready(jax.jit(fn)(*args))


def _spread_error(task, ref, variables, batch, run=_eager) -> float:
    """The benchmark's statistic (``benchmark/run.py`` ``check_model``)."""
    got = run(lambda v: task.forward(v, batch, False, None)[0][0], variables)
    want = ref.forward(variables, batch)
    live = ref.live(batch, want)[..., None]
    n = live.sum() * want.shape[-1]
    mean = jnp.where(live, want, 0).sum() / n
    spread = jnp.sqrt(jnp.where(live, (want - mean) ** 2, 0).sum() / n)
    return float(jnp.where(live, jnp.abs(got - want), 0).max() / spread)


def _program_loss(task, batch):
    def loss(v):
        outputs, _ = task.forward(v, batch, True, None)
        return task.loss(outputs, batch)

    return loss


def _program_grads(task, variables, batch, run=_eager):
    grads = run(jax.grad(_program_loss(task, batch)), variables)
    return _groups(grads["params"]), grads["batch_stats"]


# -- the mathematics, float32 against float32 --------------------------------


def test_logits_match_reference_in_float32(ref, f32_task, variables, batch):
    assert _spread_error(f32_task, ref, variables, batch) < F32_TOL


def test_loss_with_the_balance_term_matches_reference(ref, f32_task,
                                                      variables, batch):
    outputs, _ = f32_task.forward(variables, batch, True, None)
    got, want = f32_task.loss(outputs, batch), ref.loss(variables, batch)
    assert abs(float(got) - float(want)) < F32_TOL * float(want)
    # two expert layers x 0.0001 x (about 1, at even routing)
    assert 1e-4 < float(outputs[1]) < 1e-3


@pytest.fixture(scope="module")
def reference_grads(ref, variables, batch):
    grads = jax.grad(lambda v: ref.loss(v, batch))(variables)
    return _groups(grads["params"])


@pytest.fixture(scope="module")
def f32_grads(f32_task, variables, batch):
    return _program_grads(f32_task, variables, batch)


@pytest.mark.parametrize("group", GROUPS)
def test_gradient_matches_reference_in_float32(group, f32_grads,
                                               reference_grads):
    assert _relative(f32_grads[0][group], reference_grads[group]) < F32_TOL


def test_the_selection_bias_takes_no_gradient(f32_grads):
    for leaf in jax.tree.leaves(f32_grads[1]):
        assert not np.asarray(leaf).any()


def test_logits_of_the_program_as_it_runs(ref, bf16_task, variables, batch):
    assert _spread_error(bf16_task, ref, variables, batch) < ref.TOLERANCE


def test_reference_in_the_precision_below_fails_the_benchmark_comparison(
        ref, variables, batch):
    """The reference with the router's values, the bias and the logits in
    bf16 reads over ``TOLERANCE`` against itself in float32: ``perturb``'s
    shared ``OFFSET`` on the bias is invisible to a float32 choice, and bf16
    cannot carry a score beside it."""
    want = ref.forward(variables, batch)
    live = ref.live(batch, want)[..., None]
    low = ref.forward(variables, batch, dtype=jnp.bfloat16)
    n = live.sum() * want.shape[-1]
    mean = jnp.where(live, want, 0).sum() / n
    spread = jnp.sqrt(jnp.where(live, (want - mean) ** 2, 0).sum() / n)
    assert float(jnp.where(live, jnp.abs(low - want), 0).max()
                 / spread) > 2 * ref.TOLERANCE
    assert float(variables["batch_stats"]["layer_1"]["moe"]["bias"].mean()) \
        == pytest.approx(ref.OFFSET, abs=0.05)


BROKEN = {
    "weights_not_renormalised": {"moe": {"norm_topk": False}},
    "scaling_factor_forgotten": {"moe": {"routed_scale": 1.0}},
    "no_selection_bias": {"moe": {"bias_update_rate": 0.0}},
    "no_shared_expert": {"moe": {"shared_dim": 0}},
    "one_expert_fewer": {"experts_per_token": 1},
    "rotary_theta_of_another_model": {"rope_theta": 10000.0},
}


@pytest.mark.parametrize("variant", sorted(BROKEN))
def test_broken_variant_fails_the_float32_comparison(variant, ref, variables,
                                                     batch):
    """``norm_topk_prob`` and ``routed_scaling_factor`` hold, the bias
    chooses, and so on: each departure from the published layer misses the
    reference by orders of magnitude more than the program does."""
    name = f"moonlight_tiny_{variant}"
    _register(name, dtype=jnp.float32, **{
        k: dict(v) if isinstance(v, dict) else v
        for k, v in BROKEN[variant].items()})
    try:
        task = get_task("causal_lm", model_name=name, seq_len=SEQ,
                        expert_share=SHARE)
    finally:
        del transformer.CAUSAL_LMS[name]
    # the same parameters: what a variant does not use, it does not read
    got = task.forward(variables, batch, False, None)[0][0]
    want = ref.forward(variables, batch)
    assert float(jnp.abs(got - want).max() / jnp.std(want)) > 10 * F32_TOL


def test_a_router_in_bf16_fails_the_float32_comparison(ref, f32_task,
                                                       variables, batch,
                                                       monkeypatch):
    """What holds the router to float32 here (the chip's comparison leaves
    out the tokens a bf16 router would move: the reference's note on
    ``MARGIN``)."""
    good = jax.nn.sigmoid

    def sigmoid_of_bf16(z):
        return good(z.astype(jnp.bfloat16)).astype(z.dtype)

    monkeypatch.setattr(jax.nn, "sigmoid", sigmoid_of_bf16)
    try:
        want = ref.forward(variables, batch)
    finally:
        monkeypatch.undo()
    got = f32_task.forward(variables, batch, False, None)[0][0]
    assert float(jnp.abs(got - want).max() / jnp.std(want)) > 10 * F32_TOL


# -- the fused kernel for heads of unequal width -----------------------------

KERNEL_SEQ = 128  # the kernel's blocks are multiples of 128 lanes


@pytest.fixture(scope="module")
def kernel_task():
    """``moonlight_tiny`` in float32 with ``ops/flash.py``'s kernel path as
    its attention, as the rule binds it on a TPU: queries and keys of 24,
    values of 16, so ``unequal_attention`` (the library's splash kernel);
    calls run under ``force_tpu_interpret_mode``."""
    from lance_distributed_training_tpu.ops import flash

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        attention = flash.make_flash_attention(causal=True)
    _register("moonlight_tiny_f32_kernel", dtype=jnp.float32)
    try:
        yield get_task("causal_lm", model_name="moonlight_tiny_f32_kernel",
                       seq_len=KERNEL_SEQ, attention_fn=attention,
                       expert_share=SHARE)
    finally:
        del transformer.CAUSAL_LMS["moonlight_tiny_f32_kernel"]


@pytest.fixture(scope="module")
def kernel_batch():
    ids = np.random.default_rng(6).integers(2, VOCAB, (2, KERNEL_SEQ))
    mask = np.ones((2, KERNEL_SEQ), np.int8)
    mask[-1, KERNEL_SEQ - 9:] = 0
    return {"input_ids": ids.astype(np.int32), "attention_mask": mask}


@pytest.fixture(scope="module")
def kernel_grads(kernel_task, variables, kernel_batch):
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        return _program_grads(kernel_task, variables, kernel_batch,
                              run=_one_program)[0]


@pytest.fixture(scope="module")
def kernel_reference_grads(ref, variables, kernel_batch):
    return _groups(jax.grad(lambda v: ref.loss(v, kernel_batch))(
        variables)["params"])


def test_logits_and_loss_with_the_kernel_match_reference(
        ref, kernel_task, variables, kernel_batch):
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        assert _spread_error(kernel_task, ref, variables, kernel_batch,
                             run=_one_program) < F32_TOL
        got = _one_program(_program_loss(kernel_task, kernel_batch),
                           variables)
    want = ref.loss(variables, kernel_batch)
    assert abs(float(got) - float(want)) < F32_TOL * float(want)


@pytest.mark.parametrize("group", GROUPS)
def test_gradient_with_the_kernel_matches_reference(
        group, kernel_grads, kernel_reference_grads):
    assert _relative(kernel_grads[group],
                     kernel_reference_grads[group]) < F32_TOL


def test_two_programs_share_the_cached_kernel_without_a_leaked_tracer():
    """The kernel object is built once per shape and kept; the first build
    happens while some program is being traced, and what it keeps must not
    be that trace's values (on the chip the model check traced it first and
    ``train``'s step then met an ``UnexpectedTracerError``: PR 30, call 1)."""
    from jax.experimental.pallas import tpu as pltpu

    from lance_distributed_training_tpu.ops import flash

    flash._splash_kernel.cache_clear()
    q, v = jnp.ones((1, 2, 128, 24)), jnp.ones((1, 2, 128, 16))
    with pltpu.force_tpu_interpret_mode():
        first = _one_program(lambda q, k, v: flash.unequal_attention(
            q, k, v, causal=True).sum(), q, q, v)
        # with ids: another trace of the jitted function, the same kernel
        second = _one_program(lambda q, k, v: flash.unequal_attention(
            q, k, v, jnp.ones((1, 128), jnp.int32), causal=True).mean(),
            q, q, v)
    assert float(first) == pytest.approx(128 * 2 * 16)
    assert float(second) == pytest.approx(1.0)
    assert flash._splash_kernel.cache_info().hits >= 1


def test_unequal_heads_take_the_splash_path_and_the_rule_sees_them():
    from lance_distributed_training_tpu.ops import flash

    assert flash.fused_attention_applies(8192, 192, platform="tpu",
                                         value_dim=128) == (
        jax.device_count() == 1)
    assert not flash.fused_attention_applies(8192, 192, platform="tpu",
                                             value_dim=100)
    assert not flash.fused_attention_applies(8192, 192, platform="cpu",
                                             value_dim=128)
    asked = []
    attention = flash.make_flash_attention(causal=True, forced=False)
    attention.fused = lambda *shape: asked.append(shape) or False
    model = moonlight_tiny(vocab_size=VOCAB, attention_fn=attention)
    assert model.kernels(SEQ) == {"attention": False}
    # queries and keys 16 without position + 8 rotary, values 16
    assert set(asked) == {(SEQ, 24, 16)}


# -- the grouped products' kernel form ---------------------------------------


def test_the_grouped_products_kernels_are_the_plain_form_and_the_gauge_says(
        f32_task, variables, batch, monkeypatch):
    """The share's stack, as the cell's shape runs on the chip since PR 50:
    logits, loss and every group's gradient."""
    grouped_kernels_are_the_plain_form(
        f32_task, variables, batch, lambda v: _groups(v["params"]), F32_TOL,
        monkeypatch)


# -- the share ---------------------------------------------------------------


def _expert_layer(**kw):
    fields = dict(moonlight_tiny.keywords["moe"])
    return DroplessMoE(num_experts=EXPERTS, expert_dim=32,
                       experts_per_token=TOP_K, dtype=jnp.float32,
                       **{**fields, **kw})


@pytest.fixture(scope="module")
def whole_layer():
    x = jax.random.normal(jax.random.key(0), (ROWS, SEQ, 64))
    variables = _expert_layer().init(jax.random.key(1), x)
    variables = {"params": variables["params"],
                 "router_state": {"bias": 0.05 * jax.random.normal(
                     jax.random.key(2), (EXPERTS,))}}
    return x, variables


@pytest.mark.parametrize("ranks", [4, 2, 1])
def test_shares_add_up_to_the_uncut_reference_layer(whole_layer, ranks):
    """Every rank's routed part, plus what every rank computes alike (the
    shared expert) counted once, is the whole layer as the reference
    computes it uncut: all 8 experts on every token under the top-2 mask."""
    x, variables = whole_layer
    params = variables["params"]
    ref = _load_reference(first=0)
    y = x.reshape(-1, 64)
    s = jax.nn.sigmoid(y @ params["router"]["kernel"])
    chosen = ref._rank(s + variables["router_state"]["bias"]) < TOP_K
    weights = ref.ROUTED_SCALE * s * chosen / (s * chosen).sum(
        -1, keepdims=True)
    shared_part = ref._swiglu(y, params["shared"])
    with jax.default_matmul_precision("highest"):
        want = ref._experts(y, params, weights) + shared_part

    held = EXPERTS // ranks
    total = shared_part
    for rank in range(ranks):
        layer = _expert_layer(first_expert=rank * held, held_experts=held)
        mine = dict(params, **{
            name: params[name][rank * held:(rank + 1) * held]
            for name in ("w_gate", "w_up", "w_down")})
        out, sown = layer.apply(dict(variables, params=mine), x,
                                mutable=["moe_stats", "aux_loss"])
        total = total + (out.reshape(-1, 64) - shared_part)
        sizes = sown["moe_stats"]
        np.testing.assert_array_equal(
            np.asarray(sizes["held_sizes"][0]),
            np.asarray(sizes["group_sizes"][0])[rank * held:
                                                (rank + 1) * held])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_all_held_without_being_told_equals_the_told_whole(whole_layer):
    x, variables = whole_layer
    told = _expert_layer(held_experts=EXPERTS).apply(variables, x)
    untold = _expert_layer().apply(variables, x)
    np.testing.assert_array_equal(np.asarray(told), np.asarray(untold))


def test_no_token_is_dropped_when_every_assignment_lands_here(whole_layer):
    """The sorted list's bound is T x k, the worst case: a bias that sends
    every token's two experts to the two held ones fills it exactly, and the
    result is still the reference's."""
    x, variables = whole_layer
    bias = jnp.zeros((EXPERTS,)).at[2:4].set(10.0)
    variables = dict(variables, router_state={"bias": bias})
    layer = _expert_layer(first_expert=2, held_experts=2, shared_dim=0)
    params = {k: v for k, v in variables["params"].items() if k != "shared"}
    mine = dict(params, **{name: params[name][2:4]
                           for name in ("w_gate", "w_up", "w_down")})
    out, sown = layer.apply(dict(variables, params=mine), x,
                            mutable=["moe_stats", "aux_loss"])
    assert int(sown["moe_stats"]["held_sizes"][0].sum()) == ROWS * SEQ * TOP_K
    ref = _load_reference(first=0)
    y = x.reshape(-1, 64)
    s = jax.nn.sigmoid(y @ params["router"]["kernel"])
    chosen = ref._rank(s + bias) < TOP_K
    weights = ref.ROUTED_SCALE * s * chosen / (s * chosen).sum(
        -1, keepdims=True)
    with jax.default_matmul_precision("highest"):
        want = ref._experts(y, mine, weights[:, 2:4])
    np.testing.assert_allclose(np.asarray(out.reshape(-1, 64)),
                               np.asarray(want), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("bias_on_held,over", [(0.0, 0.0), (10.0, 1.0)])
def test_usual_list_and_worst_case_list_are_both_exact(bias_on_held, over):
    """1,024 tokens, 2 of 8 experts held: the list has 1,024 rows (twice an
    even routing's 512) and, in a step that sends more than that here (a
    bias that sends everything), all 2,048: output and every gradient are
    the reference's either way, and the layer says which it built."""
    x = jax.random.normal(jax.random.key(0), (2, 512, 64))
    bias = jnp.zeros((EXPERTS,)).at[2:4].set(bias_on_held)
    layer = _expert_layer(first_expert=2, held_experts=2, shared_dim=0)
    params = layer.init(jax.random.key(1), x)["params"]
    ref = _load_reference(first=0)

    def program(params, x):
        out, sown = layer.apply(
            {"params": params, "router_state": {"bias": bias}}, x,
            mutable=["moe_stats", "aux_loss"])
        return (out ** 2).sum(), (out, sown["moe_stats"])

    def reference(params, x):
        y = x.reshape(-1, 64)
        s = jax.nn.sigmoid(y @ params["router"]["kernel"])
        chosen = ref._rank(s + bias) < TOP_K
        weights = ref.ROUTED_SCALE * s * chosen / (s * chosen).sum(
            -1, keepdims=True)
        with jax.default_matmul_precision("highest"):
            out = ref._experts(y, params, weights[:, 2:4]).reshape(x.shape)
        return (out ** 2).sum(), out

    (_, (got, stats)), got_grads = jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True)(params, x)
    (_, want), want_grads = jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True)(params, x)
    assert float(stats["over_usual"][0]) == over
    held_rows = int(stats["held_sizes"][0].sum())
    assert (held_rows > 1024) == bool(over) and held_rows <= 2048
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        assert _relative(g, w) < F32_TOL


def test_absent_assignments_pass_no_gradient_to_their_tokens(whole_layer):
    """A token none of whose experts is held gets nothing from the routed
    part, and gives it no gradient."""
    x, variables = whole_layer
    layer = _expert_layer(first_expert=0, held_experts=2, shared_dim=0)
    params = {k: v for k, v in variables["params"].items() if k != "shared"}
    mine = dict(params, **{name: params[name][:2]
                           for name in ("w_gate", "w_up", "w_down")})
    fed = dict(variables, params=mine)
    y = x.reshape(-1, 64)
    s = jax.nn.sigmoid(y @ params["router"]["kernel"])
    top = jax.lax.top_k(s + variables["router_state"]["bias"], TOP_K)[1]
    absent = np.asarray((top >= 2).all(-1))
    assert 0 < absent.sum() < absent.size
    out = layer.apply(fed, x).reshape(-1, 64)
    assert not np.asarray(out)[absent].any()
    grad = jax.grad(lambda x: (layer.apply(fed, x) ** 2).sum())(x)
    assert not np.asarray(grad.reshape(-1, 64))[absent].any()
    assert np.isfinite(np.asarray(grad)).all()


# -- between the tokens and the built rows ------------------------------------

WAY_T, WAY_K, WAY_E, WAY_HELD, WAY_H = 64, 6, 16, 8, 16


def _way_of(top_e, rows):
    """The layer's own dispatch of a routing ``top_e`` [T, k] when it holds
    the first ``WAY_HELD`` experts: the first ``rows`` of the sorted list."""
    t, k = top_e.shape
    flat = jnp.minimum(top_e.reshape(t * k), WAY_HELD)
    order = jnp.argsort(flat, stable=True)
    pos = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype)).reshape(t, k)
    live_rows = (flat < WAY_HELD).sum()
    return moe._Way(order[:rows], jnp.arange(rows) < live_rows, pos,
                    pos < live_rows)


def _a_routing(case):
    top_e = jax.lax.top_k(jax.random.uniform(
        jax.random.key(3), (WAY_T, WAY_E)), WAY_K)[1]
    top_e = top_e.at[0].set(jnp.arange(WAY_K))  # all six on held experts
    top_e = top_e.at[1].set(WAY_HELD + jnp.arange(WAY_K))  # none
    rows = WAY_T * WAY_K if case == "every_row_built" else 256
    way = _way_of(top_e, rows)
    live = int(way.live.sum())
    assert live == int(way.valid.sum())
    count = way.valid.sum(1)
    assert {"dead_rows_at_the_end": 0 < live < rows and not bool(
                way.live[live:].any()),
            "all_six_slots_held": int(count[0]) == WAY_K,
            "no_slot_held": int(count[1]) == 0,
            "every_row_built": rows == WAY_T * WAY_K}[case]
    return way, rows


@pytest.mark.parametrize("case", ["dead_rows_at_the_end", "all_six_slots_held",
                                  "no_slot_held", "every_row_built"])
def test_the_two_ways_are_each_others_transposes(case):
    """What autodiff makes of tokens -> rows as it is written (a scatter-add)
    is rows -> tokens, and of rows -> tokens (k gathers, scatter-adds) tokens
    -> rows: f32, the order of at most six terms apart."""
    way, rows = _a_routing(case)
    x = jax.random.normal(jax.random.key(4), (WAY_T, WAY_H))
    r = jax.random.normal(jax.random.key(5), (rows, WAY_H))
    plain_out, plain_back = moe._tokens_to_rows.fun, moe._rows_to_tokens.fun
    np.testing.assert_allclose(
        np.asarray(jax.vjp(lambda x: plain_out(x, way), x)[1](r)[0]),
        np.asarray(moe._rows_to_tokens(r, way)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jax.vjp(lambda r: plain_back(r, way), r)[1](x)[0]),
        np.asarray(moe._tokens_to_rows(x, way)), rtol=1e-5, atol=1e-5)
    # and the rules as they stand: <out(x), r> = <x, back(r)>
    assert float((moe._tokens_to_rows(x, way) * r).sum()) == pytest.approx(
        float((x * moe._rows_to_tokens(r, way)).sum()), rel=1e-4)
    # a token's rows are those of its own held assignments, nothing else
    want = np.zeros((WAY_T, WAY_H), np.float32)
    live, token = np.asarray(way.live), np.asarray(way.head) // WAY_K
    np.add.at(want, token[live], np.asarray(r)[live])
    np.testing.assert_allclose(np.asarray(moe._rows_to_tokens(r, way)), want,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", ["dead_rows_at_the_end", "every_row_built"])
def test_weighted_sum_back_has_the_gradients_of_its_own_arithmetic(case):
    from jax.test_util import check_grads

    way, rows = _a_routing(case)
    out = jax.random.normal(jax.random.key(6), (rows, WAY_H))
    top_p = jax.random.uniform(jax.random.key(7), (WAY_T, WAY_K),
                               minval=0.1)
    check_grads(lambda out, top_p: moe._sum_back(out, top_p, way),
                (out, top_p), order=1, modes=["rev"], atol=2e-2, rtol=2e-2)
    want = (jnp.where(way.valid[..., None], jnp.take(
        out, jnp.minimum(way.pos, rows - 1), axis=0), 0)
        * top_p[..., None]).sum(1)  # each token gathers its k results
    np.testing.assert_allclose(np.asarray(moe._sum_back(out, top_p, way)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def _shapes_in(jaxpr, seen):
    """Every value's shape in a jaxpr and in the jaxprs of its equations
    (a ``cond``'s branches, a jitted call, a differentiation rule)."""
    def inner(value):
        if hasattr(value, "jaxpr") and hasattr(value, "consts"):
            yield value.jaxpr
        elif hasattr(value, "eqns"):
            yield value
        elif isinstance(value, (tuple, list)):
            for v in value:
                yield from inner(v)

    for eqn in jaxpr.eqns:
        for var in (*eqn.invars, *eqn.outvars):
            if hasattr(var.aval, "shape"):
                seen.add(tuple(var.aval.shape))
        for value in eqn.params.values():
            for sub in inner(value):
                _shapes_in(sub, seen)
    return seen


def test_nothing_under_a_share_is_tokens_by_slots_by_width():
    """The forward and the backward pass of the layer under a share hold no
    value shaped [T, k, H], whatever its type: a token does not gather its k
    results, and no cotangent is broadcast over the slots."""
    t, h = 2 * 512, 64
    x = jax.random.normal(jax.random.key(0), (2, 512, h))
    layer = _expert_layer(first_expert=2, held_experts=2, shared_dim=0)
    variables = layer.init(jax.random.key(1), x)

    def program(params, x):
        return (layer.apply(dict(variables, params=params), x) ** 2).sum()

    traced = jax.make_jaxpr(jax.value_and_grad(program, argnums=(0, 1)))(
        variables["params"], x)
    shapes = _shapes_in(traced.jaxpr, set())
    assert (1024, h) in shapes  # the list twice an even share long: it saw
    assert (t * TOP_K, h) in shapes  # the branches, the worst case's too
    assert (t, TOP_K, h) not in shapes
    assert (t, TOP_K, EXPERTS) in shapes  # what it would have looked like
    forward = jax.make_jaxpr(program)(variables["params"], x)
    assert (t, TOP_K, h) not in _shapes_in(forward.jaxpr, set())


ALL_HELD_TEXT = (
    "daf3e6c178e24877f7941af9e55f75595196b236e423bd23bf04b48e8627cafc")


def test_layer_that_holds_all_its_experts_lowers_as_it_did():
    """OLMoE's branch (every expert held) is left as it was: the lowered
    text of its forward and backward pass hashes as at PR 30. Pin it anew
    only with a change that means to touch that branch."""
    import hashlib

    layer = DroplessMoE(num_experts=8, expert_dim=32, experts_per_token=2,
                        dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.key(0), jnp.zeros(x.shape))["params"])

    def program(params, x):
        return (layer.apply({"params": params}, x) ** 2).sum()

    text = jax.jit(jax.value_and_grad(program, argnums=(0, 1))).lower(
        params, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == ALL_HELD_TEXT


@pytest.mark.parametrize("bias_on_held,built", [(10.0, 2048), (0.0, 1024)])
def test_row_fill_is_the_live_rows_over_the_rows_built(bias_on_held, built):
    """A step that sends every assignment here builds all T x k rows, and
    the fill is the live rows over those; the usual list's is over its own
    length."""
    x = jax.random.normal(jax.random.key(0), (2, 512, 64))
    layer = _expert_layer(first_expert=2, held_experts=2, shared_dim=0)
    params = layer.init(jax.random.key(1), x)["params"]
    bias = jnp.zeros((EXPERTS,)).at[2:4].set(bias_on_held)
    _, sown = layer.apply({"params": params, "router_state": {"bias": bias}},
                          x, mutable=["moe_stats", "aux_loss"])
    stats = sown["moe_stats"]
    live = int(stats["held_sizes"][0].sum())
    assert float(stats["over_usual"][0]) == float(built == 2048)
    assert 0 < live <= built
    assert float(stats["row_fill"][0]) == pytest.approx(100 * live / built)


def test_shared_expert_and_dense_layer_are_one_module():
    x = jax.random.normal(jax.random.key(0), (2, 8, 64))
    module = SwiGLU(32, jnp.float32)
    params = module.init(jax.random.key(1), x)["params"]
    want = (jax.nn.silu(x @ params["gate"]["kernel"])
            * (x @ params["up"]["kernel"])) @ params["down"]["kernel"]
    np.testing.assert_allclose(np.asarray(module.apply({"params": params}, x)),
                               np.asarray(want), rtol=1e-5, atol=1e-6)
    tree = get_task("causal_lm", model_name="moonlight_tiny",
                    seq_len=SEQ).init_variables(jax.random.key(0))["params"]
    assert set(tree["layer_0"]["mlp"]) == set(
        tree["layer_1"]["moe"]["shared"]) == {"gate", "up", "down"}


# -- the router's state ------------------------------------------------------


def test_bias_moves_against_the_load_after_a_training_step(bf16_task,
                                                           variables, batch):
    outputs, new_state = bf16_task.forward(variables, batch, True, None)
    assert bf16_task.forward(variables, batch, False, None)[1] is None
    live = np.asarray(batch["attention_mask"]).reshape(-1) > 0
    for name in ("layer_1", "layer_2"):
        old = np.asarray(variables["batch_stats"][name]["moe"]["bias"])
        new = np.asarray(new_state["batch_stats"][name]["moe"]["bias"])
        step = np.round((new - old) / 0.001).astype(int)
        assert set(step) <= {-1, 0, 1} and step.any()
        # the load the step saw is the load under the old bias: recompute
        # it through the model's own router, captured
        _, state = bf16_task.model.apply(
            {"params": variables["params"],
             "router_state": variables["batch_stats"]},
            batch["input_ids"], batch["attention_mask"], train=True,
            mutable=["intermediates", "aux_loss", "moe_stats",
                     "router_state"],
            capture_intermediates=lambda m, _: m.name == "router")
        logits = state["intermediates"][name]["moe"]["router"]["__call__"][0]
        top = np.asarray(jax.lax.top_k(
            jax.nn.sigmoid(logits) + old, TOP_K)[1])[live]
        load = np.bincount(top.ravel(), minlength=EXPERTS)
        np.testing.assert_array_equal(step, np.sign(load.mean() - load))
    stats = bf16_task.stats(outputs)
    assert float(stats["moe_router_bias_abs_max"]) == pytest.approx(float(max(
        np.abs(np.asarray(leaf)).max()
        for leaf in jax.tree.leaves(variables["batch_stats"]))))


def test_step_reports_the_share_and_no_drop_counter(bf16_task, variables,
                                                    batch):
    outputs, _ = bf16_task.forward(variables, batch, True, None)
    stats = bf16_task.stats(outputs)
    assert set(stats) == {
        "moe_assignments_total", "moe_expert_load_max", "moe_expert_load_mean",
        "moe_local_assignments_total", "moe_local_load_max",
        "moe_local_load_mean", "moe_local_fallback_total",
        "moe_local_row_fill_pct", "moe_router_bias_abs_max"}
    layers, tokens = 2, ROWS * SEQ
    assert float(stats["moe_assignments_total"]) == tokens * TOP_K * layers
    assert 0 < float(stats["moe_local_assignments_total"]) < float(
        stats["moe_assignments_total"])
    assert float(stats["moe_local_load_mean"]) == pytest.approx(
        float(stats["moe_local_assignments_total"]) / (layers * 4))
    # 128 tokens x 2, of which a half lands here at even routing: 128 live
    # rows in the 256 each layer builds, 50% give or take this routing
    assert float(stats["moe_local_row_fill_pct"]) == pytest.approx(
        100 * float(stats["moe_local_assignments_total"]) / (layers * 256))
    assert 25 < float(stats["moe_local_row_fill_pct"]) < 75


def test_train_step_carries_the_bias_in_the_train_state(bf16_task, variables,
                                                        batch):
    """``make_train_step`` keeps the routers' bias where a train state keeps
    a model's non-trainable collection, and each step leaves it moved."""
    import optax

    from lance_distributed_training_tpu.parallel import get_mesh
    from lance_distributed_training_tpu.trainer import (
        TrainState,
        make_train_step,
    )

    mesh = get_mesh(jax.devices()[:1])
    state = TrainState.create(apply_fn=None, params=variables["params"],
                              batch_stats=variables["batch_stats"],
                              tx=optax.sgd(0.0))
    step = make_train_step(bf16_task, mesh, donate=False, stats=True)
    new_state, loss, stats = step(state, batch, jax.random.key(0))
    assert np.isfinite(float(loss)) and "moe_local_load_max" in stats
    before = jax.tree.leaves(state.batch_stats)
    after = jax.tree.leaves(new_state.batch_stats)
    assert any(np.asarray(a != b).any() for a, b in zip(after, before))
    assert all(float(jnp.abs(a - b).max()) <= 0.001 + 1e-5
               for a, b in zip(after, before))


# -- the task, the presets and the entry point -------------------------------


def test_presets_share_flags_and_their_errors():
    with pytest.raises(ValueError, match="moonlight_16b_a3b"):
        get_task("causal_lm", model_name="nope")
    with pytest.raises(ValueError, match="rank in"):
        get_task("causal_lm", model_name="moonlight_tiny", expert_share="4/4")
    with pytest.raises(ValueError, match="ranks divides"):
        get_task("causal_lm", model_name="moonlight_tiny", expert_share="0/3")
    with pytest.raises(ValueError, match="rank/ranks"):
        get_task("causal_lm", model_name="moonlight_tiny", expert_share="x")
    with pytest.raises(ValueError, match="dropless"):
        get_task("causal_lm", model_name="gpt_small", expert_share="0/2")
    with pytest.raises(ValueError, match="expert_share applies"):
        get_task("masked_lm", expert_share="0/2")
    # the benchmark's configuration: one chip's share of an 8-way job
    full = get_task("causal_lm", model_name="moonlight_16b_a3b", seq_len=8192,
                    num_layers=6, vocab_size=20480, expert_share="0/8")
    shapes = jax.eval_shape(full.init_variables, jax.random.key(0))
    params = shapes["params"]

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    assert count(params["layer_0"]["attn"]) == 13_763_072  # 13.77 M
    assert count(params["layer_0"]["mlp"]) == 3 * 2048 * 11264
    assert params["layer_1"]["moe"]["w_gate"].shape == (8, 2048, 1408)
    assert params["layer_1"]["moe"]["router"]["kernel"].shape == (2048, 64)
    assert count(params["layer_1"]["moe"]["shared"]) == 3 * 2048 * 2816
    assert count(params) == 668_890_112  # the issue's 668.9 M
    assert shapes["batch_stats"]["layer_5"]["moe"]["bias"].shape == (64,)
    # OLMoE takes a share too: the same layer
    olmoe = get_task("causal_lm", model_name="olmoe_tiny", seq_len=16,
                     expert_share="1/4")
    tree = jax.eval_shape(olmoe.init_variables, jax.random.key(0))["params"]
    assert tree["layer_0"]["moe"]["w_up"].shape == (2, 64, 32)


def test_olmoe_under_a_share_keeps_its_auxiliary_terms():
    """The softmax router's two terms are over all 64 outputs whatever is
    held: the same values as with every expert here."""
    x = jax.random.normal(jax.random.key(0), (ROWS, SEQ, 64))
    whole = DroplessMoE(num_experts=8, expert_dim=32, experts_per_token=2,
                        dtype=jnp.float32)
    params = whole.init(jax.random.key(1), x)["params"]
    _, want = whole.apply({"params": params}, x,
                          mutable=["aux_loss", "moe_stats"])
    part = DroplessMoE(num_experts=8, expert_dim=32, experts_per_token=2,
                       dtype=jnp.float32, first_expert=4, held_experts=4)
    mine = dict(params, **{n: params[n][4:] for n in
                           ("w_gate", "w_up", "w_down")})
    _, got = part.apply({"params": mine}, x,
                        mutable=["aux_loss", "moe_stats"])
    for name in ("load_balance", "router_z"):
        assert float(got["aux_loss"][name][0]) == pytest.approx(
            float(want["aux_loss"][name][0]), rel=1e-5)
    np.testing.assert_array_equal(
        np.asarray(got["moe_stats"]["group_sizes"][0]),
        np.asarray(want["moe_stats"]["group_sizes"][0]))


def test_configuration_file_states_the_share_and_the_catalog_keys():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "moonlight-16b-a3b-c4.json")) as f:
        config = json.load(f)
    assert sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (config["n_routed_experts"], config["vocab_size"]) == (8, 20480)
    for key, value in config["model"].items():
        if key in config:  # the catalog's keys, at the top level for the
            assert config[key] == value  # driver and under model for run.py
    task, flags = config["task"], config["train_flags"]
    for key in ("model_name", "seq_len", "vocab_size", "num_layers",
                "expert_share"):
        assert str(task[key]) == flags[flags.index(f"--{key}") + 1]
    assert "Muon" in " ".join(config["changed"])


def test_three_steps_of_train_through_the_cli(tmp_path, monkeypatch):
    from lance_distributed_training_tpu import cli
    from lance_distributed_training_tpu.data import create_text_token_dataset
    from lance_distributed_training_tpu.obs.registry import default_registry

    docs = [np.random.default_rng(0).integers(2, 64, 32).tolist()] * 60
    uri = str(tmp_path / "tok")
    create_text_token_dataset(uri, docs, seq_len=32, fragment_size=64)
    metrics_path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("LDT_METRICS_PATH", str(metrics_path))
    results = cli.main([
        "train", "--dataset_path", uri, "--task_type", "causal_lm",
        "--model_name", "moonlight_tiny", "--expert_share", "0/4",
        "--seq_len", "32", "--vocab_size", "64", "--batch_size", "8",
        "--epochs", "1", "--max_steps", "3", "--optimizer", "adamw", "--lr",
        "3e-3", "--weight_decay", "0.1", "--grad_clip", "1.0", "--log_every",
        "1", "--remat", "--no_ddp", "--no_wandb", "--no_eval_at_end",
        "--no_autotune"])
    records = [json.loads(line) for line in open(metrics_path)
               if '"images_per_sec_dispatch"' in line]
    losses = [r["loss"] for r in records]
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert losses[0] > losses[2]
    assert np.isfinite(results["loss"])
    # the bias has moved by a step's rate a step
    assert [r["moe_router_bias_abs_max"] for r in records] == pytest.approx(
        [0.0, 0.001, 0.002], abs=1e-6)
    registry = default_registry().metrics()
    assert registry["moe_assignments_total"].value >= 3 * 8 * 32 * TOP_K * 2
    assert 0 < registry["moe_local_assignments_total"].value < \
        registry["moe_assignments_total"].value
    assert registry["moe_local_load_max"].value >= \
        registry["moe_local_load_mean"].value > 0
    assert not [n for n in registry if "drop" in n and n.startswith("moe")]
