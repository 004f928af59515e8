"""SmallThinker-21BA3B's decoder layers (one full attention layer without a
position term to three rotary ones in a window, grouped heads that are not
``hidden / heads`` wide, ReGLU experts chosen by a router that reads the
layer's input ahead of attention; here ``smallthinker_tiny``: 4 layers, 4
query heads over 2 key/value heads of 32 on a stream of 64, a window of 16,
16 experts of 32 with 2 a token) against the plain float32 reference the
benchmark keeps in ``benchmark/reference/smallthinker-21b-a3b-c4.py``, on
seeded weights, on the CPU.

*Is the program's mathematics the reference's?* The program computed in
float32 against the reference, whole and under a share of the experts:
logits, loss and every parameter group's gradient to ``F32_TOL`` (float32
against float32: summation order and grouping only). *Does each mechanism
show?* Each of four wrong programs misses the reference by orders of
magnitude more. *Does the share add up?* The four quarters' routed parts are
the uncut layer. Then what only these layers have: the router's logits are
made of the layer's input and nothing attention adds reaches them, while its
gradient reaches ``W_r`` through the weights; a window layer sees exactly
``window`` keys; the full layer ignores ``position_ids``; the kernel path
(interpret mode) at a window wider than a block equals dense attention; and
the configuration's file holds the published widths and the parameters the
program counts.
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
from lance_distributed_training_tpu.models.moe import DroplessMoE
from lance_distributed_training_tpu.models.transformer import (
    LAYER_KINDS,
    GroupedAttention,
    smallthinker_layers,
)
from lance_distributed_training_tpu.ops import flash

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, ROWS, VOCAB, EXPERTS, TOP_K, WINDOW = 48, 2, 512, 16, 2, 16
F32_TOL = 2e-4  # float32 against float32: summation order and grouping only
GROUPS = ("router", "w_gate", "w_up", "w_down", "query", "key", "value",
          "out", "scales", "tok_embed", "lm_head")
SHARES = (None, "1/4")  # whole; experts 4..7 of 16


def _load_reference(first: int = 0):
    path = os.path.join(ROOT, "benchmark", "reference",
                        "smallthinker-21b-a3b-c4.py")
    spec = importlib.util.spec_from_file_location(
        f"smallthinker_reference_{first}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.TOP_K, module.WINDOW, module.FIRST = TOP_K, WINDOW, first
    return module


@pytest.fixture(scope="module", params=SHARES, ids=["whole", "share"])
def share(request):
    return request.param


@pytest.fixture(scope="module")
def ref(share):
    return _load_reference(first=4 if share else 0)


def _task(share, seq=SEQ, **changes):
    if not changes:
        return get_task("causal_lm", model_name="smallthinker_tiny",
                        seq_len=seq, expert_share=share)
    presets = register_preset("smallthinker_tiny_changed",
                              "smallthinker_tiny", **changes)
    try:
        return get_task("causal_lm", model_name="smallthinker_tiny_changed",
                        seq_len=seq, expert_share=share)
    finally:
        del presets["smallthinker_tiny_changed"]


@pytest.fixture(scope="module")
def f32_task(share):
    return _task(share, dtype=jnp.float32)


@pytest.fixture(scope="module")
def bf16_task(share):
    return _task(share)


@pytest.fixture(scope="module")
def variables(ref, bf16_task):
    """Seeded, and perturbed as the benchmark's check perturbs them."""
    return ref.perturb(jax.jit(bf16_task.init_variables)(jax.random.key(3)),
                       jax.random.key(4))


@pytest.fixture(scope="module")
def batch():
    ids = np.random.default_rng(5).integers(2, VOCAB, (ROWS, SEQ))
    mask = np.ones((ROWS, SEQ), np.int8)
    mask[-1, SEQ - 5:] = 0  # a padded tail: live tokens only in the losses
    return {"input_ids": ids.astype(np.int32), "attention_mask": mask}


def _groups(tree) -> dict:
    """Parameter groups, layers together: the router, the held experts'
    three, attention's four projections, every learned scale, the embedding
    and the head."""
    out: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [k.key for k in path if hasattr(k, "key")]
        name = "scales" if keys[-1] == "scale" else next(
            k for k in GROUPS if k in keys)
        out.setdefault(name, []).append(jnp.ravel(leaf))
    return {k: jnp.concatenate(v) for k, v in out.items()}


def _relative(got, want) -> float:
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _one_program(fn, *args):
    """One jitted program, waited for (``tests/test_olmoe.py`` tells why)."""
    return jax.block_until_ready(jax.jit(fn)(*args))


def _reference(ref, variables, batch, dtype=None):
    """``(logits, the tokens the comparison keeps)`` in one program, as
    ``benchmark/run.py`` makes them."""
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


def test_logits_of_the_program_as_it_runs(ref, bf16_task, variables, batch,
                                          want):
    """bf16 at this width, under the chip's ``TOLERANCE`` (whose two
    readings are the chip's, at the published widths: PERF.md section 6)."""
    reading = _spread_error(_logits(bf16_task, variables, batch), want)
    print(f"program in bf16 reads {reading:.3f}")
    assert reading < ref.TOLERANCE


N_ROTARY = "N+rotary"  # a full layer that turns its queries and keys
BROKEN = {
    "window_layers_without_the_window": {
        "parts": {GroupedAttention: {"window": 0}}},
    "rotary_in_the_full_layer": {
        "layer_kinds": tuple(N_ROTARY if k == "N" else k
                             for k in smallthinker_layers(4))},
    "router_reads_the_normed_stream_after_attention": {"router_early": False},
    "silu_for_relu": {"moe": {"activation": "silu"}},
    "rotary_theta_of_another_model": {"rope_theta": 10000.0},
    "a_window_one_key_short": {
        "parts": {GroupedAttention: {"window": WINDOW - 1}}},
}


@pytest.mark.parametrize("variant", sorted(BROKEN))
def test_broken_variant_fails_the_float32_comparison(variant, share,
                                                     variables, batch, want,
                                                     monkeypatch):
    """Each departure from the layer as written misses the reference by
    orders of magnitude more than the program does."""
    monkeypatch.setitem(LAYER_KINDS, N_ROTARY,
                        LAYER_KINDS["N"]._replace(fixed=(("window", 0),)))
    task = _task(share, dtype=jnp.float32, **{
        k: dict(v) if isinstance(v, dict) else v
        for k, v in BROKEN[variant].items()})
    if "router_early" in BROKEN[variant]:  # where a late router looks
        variables = dict(variables, params={
            name: ({**{k: v for k, v in layer.items() if k != "router"},
                    "moe": {**layer["moe"], "router": layer["router"]}}
                   if name.startswith("layer_") else layer)
            for name, layer in variables["params"].items()})
    got = _logits(task, variables, batch)
    assert _spread_error(got, want) > 10 * F32_TOL


# -- the router: ahead of attention ------------------------------------------


def _sown(task, variables, batch, collection):
    def run(v):
        _, sown = task.model.apply(
            {"params": v["params"]}, batch["input_ids"],
            batch["attention_mask"], train=True,
            mutable=["intermediates", "moe_stats", "aux_loss",
                     "mixer_stats"])
        return sown[collection]

    return _one_program(run, variables)


def test_the_routers_choice_does_not_depend_on_attentions_output(
        f32_task, variables, batch):
    """``r = x W_r`` of the stream entering the layer: with layer 0's ``W_o``
    doubled, layer 0's assignment counts stay what they were and its output
    does not (layer 1's router reads a stream that moved, and may move)."""
    doubled = dict(variables, params=jax.tree_util.tree_map_with_path(
        lambda path, x: 2 * x + 0.1 if [
            getattr(k, "key", "") for k in path][:3] == [
                "layer_0", "attn", "out"] else x, variables["params"]))
    before = _sown(f32_task, variables, batch, "moe_stats")
    after = _sown(f32_task, doubled, batch, "moe_stats")
    np.testing.assert_array_equal(before["layer_0"]["moe"]["group_sizes"][0],
                                  after["layer_0"]["moe"]["group_sizes"][0])
    assert float(before["layer_0"]["router_early"][0]) == 1.0
    moved = jnp.abs(_logits(f32_task, doubled, batch)
                    - _logits(f32_task, variables, batch)).max()
    assert float(moved) > 1e-2


def test_a_late_router_would_depend_on_attentions_output(share, variables,
                                                         batch):
    """The same perturbation moves the counts of a router that reads
    ``ln_mlp``'s output: what the test above holds is the early router's."""
    task = _task(None, dtype=jnp.float32, router_early=False)
    late = jax.jit(task.init_variables)(jax.random.key(3))
    late = dict(late, params=jax.tree.map(lambda x: 8 * x, late["params"]))
    doubled = dict(late, params=jax.tree_util.tree_map_with_path(
        lambda path, x: 2 * x + 0.1 if [
            getattr(k, "key", "") for k in path][:3] == [
                "layer_0", "attn", "out"] else x, late["params"]))
    before = _sown(task, late, batch, "moe_stats")
    after = _sown(task, doubled, batch, "moe_stats")
    assert "router" in late["params"]["layer_0"]["moe"]
    assert (np.asarray(before["layer_0"]["moe"]["group_sizes"][0])
            != np.asarray(after["layer_0"]["moe"]["group_sizes"][0])).any()


def test_the_routers_gradient_reaches_w_r_through_the_weights():
    """The choice is an argmax and passes no gradient; the six-way softmax
    over the chosen logits does. With every weight held at 1/k (a stopped
    gradient in their place) ``W_r`` gets none; as written it gets one, and
    it is the reference's (the gradient tests above hold the group)."""
    ref = _load_reference()
    x = jax.random.normal(jax.random.key(0), (ROWS, SEQ, 64))
    layer = DroplessMoE(num_experts=EXPERTS, expert_dim=32,
                        experts_per_token=TOP_K, dtype=jnp.float32,
                        norm_topk=True, activation="relu")
    params = jax.tree.map(lambda w: 8 * w, layer.init(
        jax.random.key(3), x)["params"])
    w_r = params.pop("router")["kernel"]
    ct = jax.random.normal(jax.random.key(5), x.shape)

    def program(w_r, stop=False):
        logits = x @ w_r
        if stop:
            logits = jax.lax.stop_gradient(logits)
        return (layer.apply({"params": params}, x, None, logits) * ct).sum()

    def reference(w_r):
        tokens = x.reshape(-1, 64)
        return (ref._sparse_block(tokens, tokens @ w_r, params)[0].reshape(
            x.shape) * ct).sum()

    with jax.default_matmul_precision("highest"):
        got = _one_program(jax.grad(program), w_r)
        none = _one_program(jax.grad(functools.partial(program, stop=True)),
                            w_r)
        want = _one_program(jax.grad(reference), w_r)
    assert not np.asarray(none).any()
    assert float(jnp.linalg.norm(want)) > 0
    assert _relative(got, want) < F32_TOL


# -- the share ---------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """The four ranks' routed parts are the whole expert layer as the
    reference computes it uncut: all 16 experts on every token under the
    top-2 mask, weighted by a softmax over the chosen two. (No rank adds
    anything that all compute alike: the layer has no shared expert.)"""
    ref = _load_reference(first=0)
    x = jax.random.normal(jax.random.key(0), (ROWS, SEQ, 64))

    def layer(**kw):
        return DroplessMoE(num_experts=EXPERTS, expert_dim=32,
                           experts_per_token=TOP_K, dtype=jnp.float32,
                           norm_topk=True, activation="relu", **kw)

    whole = layer().init(jax.random.key(3), x)["params"]
    whole = jax.tree.map(lambda w: 8 * w, whole)  # a router that decides
    router = whole.pop("router")["kernel"]
    tokens = x.reshape(-1, 64)
    logits = jnp.dot(tokens, router, precision="highest")
    want = _one_program(lambda p: ref._sparse_block(tokens, logits, p)[0],
                        whole).reshape(x.shape)
    np.testing.assert_allclose(_one_program(
        lambda p: layer().apply({"params": p}, x, None, logits), whole), want,
                               rtol=2e-5, atol=2e-4)
    parts = []
    for rank in range(4):
        held = slice(4 * rank, 4 * rank + 4)
        params = {name: whole[name][held] for name in whole}
        parts.append(_one_program(
            lambda p: layer(first_expert=4 * rank, held_experts=4).apply(
                {"params": p}, x, None, logits), params))
        assert float(jnp.abs(parts[-1]).max()) > 0
    np.testing.assert_allclose(sum(parts), want, rtol=2e-5, atol=2e-4)


def test_silu_experts_are_what_they_were():
    """``activation`` defaults to SiLU: a layer that does not name it computes
    what it did before the field, and ReLU is another function."""
    x = jax.random.normal(jax.random.key(0), (1, 32, 64))
    plain = DroplessMoE(num_experts=8, expert_dim=32, experts_per_token=2,
                        dtype=jnp.float32)
    params = plain.init(jax.random.key(1), x)
    named = plain.clone(activation="silu").apply(params, x)
    np.testing.assert_array_equal(plain.apply(params, x), named)
    relu = plain.clone(activation="relu").apply(params, x)
    assert float(jnp.abs(relu - named).max()) > 1e-3
    with pytest.raises(KeyError):
        plain.clone(activation="gelu").apply(params, x)


# -- the two kinds of attention ----------------------------------------------


def _mixer(**fields):
    return GroupedAttention(**{**dict(
        num_heads=4, kv_heads=2, head_dim=32, rope_theta=1.5e6,
        dtype=jnp.float32), **fields})


def test_a_window_layer_sees_exactly_window_keys():
    """Moving the value that token t reads from key t - window + 1 moves its
    output; moving key t - window moves nothing at t. In the full layer
    (N's fields) the same far key does."""
    x = jax.random.normal(jax.random.key(0), (1, SEQ, 64))
    t = 40
    for far, window, rotary, moves in (
            (t - WINDOW + 1, WINDOW, True, True),
            (t - WINDOW, WINDOW, True, False), (t - WINDOW, 0, False, True)):
        mixer = _mixer(window=window, rotary=rotary)
        params = mixer.init(jax.random.key(1), x)
        moved = x.at[0, far].add(1.0)
        before, after = mixer.apply(params, x), mixer.apply(params, moved)
        assert (float(jnp.abs(after - before)[0, t].max()) > 1e-4) is moves
        # and nothing before the moved token moves at all: causal
        np.testing.assert_array_equal(before[0, :far], after[0, :far])


def test_the_full_layer_ignores_position_ids_and_a_window_layer_does_not():
    x = jax.random.normal(jax.random.key(0), (ROWS, SEQ, 64))
    shifted = jnp.broadcast_to(jnp.arange(SEQ) * 3 + 7, (ROWS, SEQ))
    kinds = {"N": dict(LAYER_KINDS["N"].fixed), "W": {"window": WINDOW}}
    assert kinds["N"] == {"window": 0, "rotary": False}
    for kind, fields in kinds.items():
        mixer = _mixer(**fields)
        params = mixer.init(jax.random.key(1), x)
        assert set(params["params"]) == {"query", "key", "value", "out"}
        plain = mixer.apply(params, x)
        turned = mixer.apply(params, x, position_ids=shifted)
        if kind == "N":
            np.testing.assert_array_equal(plain, turned)
        else:  # rotary attention depends on differences of positions
            assert float(jnp.abs(plain - turned).max()) > 1e-3
    assert smallthinker_layers(8) == ("N", "W", "W", "W") * 2


def test_the_layer_has_the_parts_it_names():
    """28 heads of 128 are not the stream's 2,560: the projections' shapes
    are the heads', keys and values come in their own four heads to the
    attention function, the window rides the call, and the scores are over
    ``sqrt(head_dim)``."""
    seen = {}

    def attention_fn(q, k, v, mask=None, window=0, segment_ids=None):
        seen.update(q=q.shape, k=k.shape, v=v.shape, window=window)
        return jnp.zeros_like(q) + v.mean(1, keepdims=True)

    x = jax.random.normal(jax.random.key(0), (ROWS, SEQ, 64))
    mixer = _mixer(window=WINDOW, attention_fn=attention_fn)
    params = mixer.init(jax.random.key(1), x)
    assert jax.tree.map(lambda p: p.shape, params["params"]) == {
        "query": {"kernel": (64, 4, 32)}, "key": {"kernel": (64, 2, 32)},
        "value": {"kernel": (64, 2, 32)}, "out": {"kernel": (4, 32, 64)}}
    _, sown = mixer.apply(params, x, mutable=["mixer_stats"])
    assert seen == {"q": (ROWS, 4, SEQ, 32), "k": (ROWS, 2, SEQ, 32),
                    "v": (ROWS, 2, SEQ, 32), "window": WINDOW}
    assert float(sown["mixer_stats"]["attn_window"][0]) == WINDOW
    assert mixer.kernels(SEQ, 64) == {"attention": False}


def test_the_kernel_path_in_interpret_mode_is_the_dense_layer():
    """The mixer bound to ``unequal_attention`` (interpret mode, one device)
    at 512 tokens and a window of 300, wider than the 128-blocks it runs in:
    values and every parameter's gradient are the dense layer's."""
    from jax.experimental.pallas import tpu as pltpu

    seq, window = 512, 300
    x = jax.random.normal(jax.random.key(0), (1, seq, 64))
    ct = jax.random.normal(jax.random.key(2), x.shape)

    def kernel(q, k, v, mask=None, window=0):
        return flash.unequal_attention(q, k, v, causal=True, window=window,
                                       tiling=flash._square(128))

    def both(params):
        def run(mixer):
            return jax.value_and_grad(
                lambda p: (mixer.apply(p, x) * ct).sum())(params)
        return (run(_mixer(window=window, head_dim=64)),
                run(_mixer(window=window, head_dim=64, attention_fn=kernel)))

    params = {"params": _mixer(window=window, head_dim=64).init(
        jax.random.key(1), x)["params"]}  # not what the layer sows at init
    with pltpu.force_tpu_interpret_mode():
        (want, want_g), (got, got_g) = _one_program(both, params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert _relative(g, w) < 1e-5


# -- what a step reports ------------------------------------------------------


def test_a_training_step_reports_its_gauges(bf16_task, variables, batch,
                                            share):
    def step(v):
        outputs, _ = bf16_task.forward(v, batch, True, None)
        return bf16_task.stats(outputs)

    stats = {k: float(v) for k, v in _one_program(step, variables).items()}
    assert {"router_early", "attn_window", "moe_assignments_total",
            "moe_expert_load_max"} <= set(stats)
    assert stats["router_early"] == 1 and stats["attn_window"] == WINDOW
    assert stats["moe_assignments_total"] == 4 * ROWS * SEQ * TOP_K
    assert ("moe_local_fallback_total" in stats) is bool(share)
    if share:
        assert {"moe_local_load_max", "moe_local_load_mean",
                "moe_local_row_fill_pct"} <= set(stats)


def test_the_first_log_line_names_the_attention_path():
    from lance_distributed_training_tpu import trainer

    config = trainer.TrainConfig(
        dataset_path="", task_type="causal_lm",
        model_name="smallthinker_tiny", seq_len=SEQ)
    assert trainer._kernel_paths(_task(None), config) == {
        "attention": "dense"}


def test_the_stack_holds_the_period_and_refuses_nothing_in_it():
    task = get_task("causal_lm", model_name="smallthinker_21b_a3b",
                    seq_len=16384, layer_span="4:8", expert_share="3/4")
    assert task.model.held_kinds == ("N", "W", "W", "W")
    assert dict(task.model.moe)["first_expert"] == 48
    assert task.model.router_early


# The lowered text of the whole forward and backward pass of the one decoder
# preset ``tests/test_zaya.py``'s table does not hold, hashed on the parent
# of PR 46 (commit 2cf898d) with that file's function: the experts'
# ``activation``, the block's ``router_early`` and the public name of the
# router's product leave its program letter for letter as it was.
QWEN3_NEXT_ON_THE_PARENT = {
    (None, False): "245602d5351030b7",
    ("0/16", False): "aa30dadb4ddde0ce",
    ("0/16", True): "98898ec4a4984d9e",
}


@pytest.mark.parametrize("share,remat", sorted(QWEN3_NEXT_ON_THE_PARENT,
                                               key=str))
def test_qwen3_nexts_step_lowers_as_before_this_model(share, remat):
    from test_zaya import _lowered_hash

    assert _lowered_hash("qwen3_next_tiny", share, remat) == \
        QWEN3_NEXT_ON_THE_PARENT[share, remat]


# -- the configuration's file against the program ----------------------------


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b-c4.json")) as f:
        return json.load(f)


def test_the_cut_holds_the_parameters_the_file_states(config):
    task = get_task(**config["task"])
    shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
    held = sum(int(np.prod(leaf.shape))
               for leaf in jax.tree.leaves(shapes["params"]))
    assert held == config["held_parameters"] == 559_290_880
    count = {
        (layer, name): sum(int(np.prod(leaf.shape))
                           for leaf in jax.tree.leaves(part))
        for layer in ("layer_0", "layer_3")
        for name, part in shapes["params"][layer].items()}
    assert count == {
        (layer, name): n for layer in ("layer_0", "layer_3")
        for name, n in (("attn", 20_971_520), ("router", 163_840),
                        ("moe", 94_371_840), ("ln_attn", 2560),
                        ("ln_mlp", 2560))}
    assert "batch_stats" not in shapes  # no selection bias: no state
    assert task.model.held_kinds == ("N", "W", "W", "W")


def test_every_width_is_the_published_one(config):
    """The catalog row's ``config`` (copied into the test: the guide is not
    part of the repository), key by key, but for the three keys ``reduced``
    names, which the file gives beside their published values."""
    layout = [0, 1, 1, 1] * 13
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": layout, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": layout, "sliding_window_size": 4096,
        "tie_word_embeddings": False, "vocab_size": 151936}
    reduced = {"num_hidden_layers": 4, "moe_num_primary_experts": 16,
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
            model.norm_eps, model.norm_offset, model.tied_head,
            model.router_early) == (
        2560, 28, 768, 64, 6, 1.5e6, 1e-6, False, False, True)
    assert {p.func: p.keywords for p in model.parts} == {
        GroupedAttention: dict(kv_heads=4, head_dim=128, window=4096)}
    assert dict(model.moe) == {
        "norm_topk": True, "activation": "relu", "first_expert": 0,
        "held_experts": 16}
    # the two layouts are one list, and the program's kinds are that list
    assert tuple("W" if turn else "N" for turn in layout) == \
        smallthinker_layers(52) == model.layer_kinds
    assert config["task"]["seq_len"] == published["max_position_embeddings"]


def test_the_flops_file_counts_the_pairs_each_mask_lets_through(config):
    spec = importlib.util.spec_from_file_location(
        "smallthinker_flops", os.path.join(
            ROOT, "benchmark", "flops", "smallthinker-21b-a3b-c4.py"))
    flops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops)
    model = config["model"]
    assert flops._pairs(16384, 0) == 134_225_920
    assert flops._pairs(16384, 4096) == 58_722_304
    band = np.tril(np.ones((64, 64), bool)) & ~np.tril(
        np.ones((64, 64), bool), -16)
    assert flops._pairs(64, 16) == band.sum()
    per_token = flops.forward_flops(model, 1, 16384) / 16384
    assert per_token == pytest.approx(608.7e6, rel=1e-3)
    assert flops.attention_flops(model, 1, 16384, "N") / flops.attention_flops(
        model, 1, 16384, "W") == pytest.approx(134_225_920 / (3 * 58_722_304))
    # keys and values in their own four heads, eight tensors of queries'
    assert flops.attention_bytes(model, 1, 16384, "N") == \
        16384 * 128 * 2 * (6 * 28 + 6 * 4)


# -- the cell's readers -------------------------------------------------------

_FWD = "jit(step)/jvp(forward)/TransformerDecoder/"
_BWD = "jit(step)/transpose(jvp(forward))/TransformerDecoder/"
# op_name -> ps in one run of the step: a hand-made plane with the scopes
# these layers name (the block's router ahead of attention, the mixer's
# three, the expert layer's four)
_OPS = {
    _FWD + "layer_0/moe.router/router/dot_general": 300_000_000,
    _FWD + "layer_0/moe/moe.router/top_k": 200_000_000,
    _FWD + "layer_0/attention/attn/attn.project/query/dot_general":
        2_000_000_000,
    _FWD + "layer_0/attention/attn/attn.full/splash_mha_fwd": 20_000_000_000,
    _BWD + "layer_0/attention/attn/attn.full/splash_mha_dkv": 40_000_000_000,
    _FWD + "layer_1/attention/attn/attn.window/splash_mha_fwd":
        10_000_000_000,
    _BWD + "layer_1/attention/attn/attn.window/splash_mha_dq":
        15_000_000_000,
    _BWD + "layer_1/attention/attn/attn.out/out/dot_general": 1_000_000_000,
    _FWD + "layer_1/moe/moe.dispatch/sort": 400_000_000,
    _BWD + "layer_1/moe/checkpoint/moe.experts/mul": 4_000_000_000,
    "ragged-dot-none": 12_000_000_000,
    _BWD + "layer_1/moe/moe.combine/mul": 250_000_000,
    "jit(step)/optimizer/add": 1_000_000_000,
}
_READS = {  # ms a step, or the share the reader makes of them
    "st_attention_ms": 88.0, "st_router_ms": 0.5, "st_routed_ms": 16.65,
    "st_load_max_over_mean": 1.5, "st_window_kernel_roofline_pct": None,
    "st_full_kernel_roofline_pct": None, "st_experts_roofline_pct": None,
}


def _reader_ctx(ops: dict, config: dict) -> tuple:
    """What ``benchmark/run.py`` hands a reader, around a plane with two
    runs of ``jit_step(7)`` whose operations are ``ops``: the plane
    ``tests/test_bringup.py`` makes for the Moonlight cell's readers, under
    this cell's configuration, shapes and counters."""
    from test_bringup import _moonlight_ctx

    ctx = _moonlight_ctx(ops)
    import run  # benchmark/run.py: on the path since _moonlight_ctx

    for point in ctx["log_points"]:
        point["counters"] = {"moe_local_load_max": 2304.0,
                             "moe_local_load_mean": 1536.0}
    ctx.update(
        cell={"name": "c4-smallthinker-ep4-prepacked-16k", "config": config},
        flops=run.load_module("flops", "smallthinker-21b-a3b-c4"),
        counters={"moe_local_assignments_total": 100 * 4 * 24576.0},
        step_shapes=[{"input_ids": (1, 16384)}])
    return ctx, run


@pytest.mark.parametrize("metric", sorted(_READS))
def test_a_reader_reads_the_scopes_the_layers_name(metric, config):
    ctx, run = _reader_ctx(_OPS, config)
    value = run.load_module("layer_metrics", metric).read(ctx)
    want = _READS[metric]
    model, flops = config["model"], ctx["flops"]
    if metric == "st_window_kernel_roofline_pct":
        want = 100 * flops.attention_flops(model, 1, 16384, "W") / 197e12 \
            / 0.025
    if metric == "st_full_kernel_roofline_pct":
        want = 100 * flops.attention_flops(model, 1, 16384, "N") / 197e12 \
            / 0.060
    if metric == "st_experts_roofline_pct":
        want = 100 * flops.expert_flops(model, 4 * 24576.0) / 197e12 / 0.016
    assert value == pytest.approx(want, rel=1e-6)
    # on a program without these scopes and counters (the parent, or another
    # model's step): nothing, and no error
    bare, _ = _reader_ctx({_FWD + "layer_0/attn/dot_general": 1_000_000},
                          config)
    bare["counters"], bare["log_points"] = {}, [{"t": 20, "counters": {}}]
    assert run.load_module("layer_metrics", metric).read(bare) is None


def test_the_manifest_lists_the_cell_and_its_seven_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"]
                if w["name"] == "c4-smallthinker-ep4-prepacked-16k")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b-c4", "c4-prepacked-16k-ep4", 1)
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [cell["name"]]]
    assert sorted(m["name"] for m in mine) == sorted(_READS)
    first = manifest["per_layer"].index(mine[0])  # appended in a block
    assert manifest["per_layer"][first:first + len(mine)] == mine
    assert {m["moves"] for m in mine} == {"samples_per_s_chip"}
    config = next(c for c in manifest["configs"]
                  if c["name"] == "smallthinker-21b-a3b-c4")
    assert config["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]


def test_the_cell_rehearses_end_to_end_on_the_cpu():
    """``benchmark/run.py``'s whole path for the cell at the tiny preset,
    untraced and traced: the generator at rows of its own length, the model
    check against the reference under a share, ``train`` with ``--layer_span``
    and ``--expert_share``, the log-point clock, the stop, the readers."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--cells", "c4-smallthinker-ep4-prepacked-16k", "--checks", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "rehearsal ok"
    assert "st_load_max_over_mean" in proc.stdout
