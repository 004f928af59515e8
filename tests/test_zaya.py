"""ZAYA1-8B's decoder layer (compressed convolutional attention: 8 query
heads over 2 key/value heads in a latent, two causal convolutions, a value
shift, rotary on half a head; an MLP router whose state rides from layer to
layer, one expert a token under a selection bias; learned scales on both
residual sums; the head tied under RMSNorm; here ``zaya_tiny``: 3 layers, 4
query heads over 2 of 16, 8 experts of 32) against the plain float32
reference the benchmark keeps in ``benchmark/reference/zaya1-8b-c4.py``, on
seeded weights, on the CPU.

As for Moonlight (``tests/test_moonlight.py``): *is the mathematics right?*
The program computed in float32 against the reference, whole and under a
share of the experts: logits, loss and every parameter group's gradient to
``F32_TOL``; a departure from the layer as written misses it by orders of
magnitude. *Does the share add up?* The two halves' routed parts are the
uncut layer. Then what only this layer has: nothing before token t moves
when token t does (the convolutions, the value shift), the rotary turn takes
half a head, the one-expert half-held layer builds one list and no ``cond``,
and factoring the router out left the other models' steps as they were.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import grouped_kernels_are_the_plain_form, register_preset

from lance_distributed_training_tpu.models import get_task, transformer
from lance_distributed_training_tpu.models.moe import DroplessMoE
from lance_distributed_training_tpu.models.transformer import (
    ConvolutionalAttention,
    rotary_embedding,
    zaya_tiny,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, ROWS, VOCAB, EXPERTS = 32, 4, 512, 8
F32_TOL = 2e-4  # float32 against float32: summation order and grouping only
GROUPS = ("router", "w_gate", "w_up", "w_down", "query", "key", "value",
          "conv0", "conv1", "out", "scales", "shifts", "tok_embed")
SHARES = (None, "1/2")  # whole; experts 4..7 of 8


def _load_reference(first: int):
    path = os.path.join(ROOT, "benchmark", "reference", "zaya1-8b-c4.py")
    spec = importlib.util.spec_from_file_location(
        f"zaya_reference_{first}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.ROTARY, module.FIRST = 8, first
    return module


@pytest.fixture(scope="module", params=SHARES, ids=["whole", "share"])
def share(request):
    return request.param


@pytest.fixture(scope="module")
def ref(share):
    return _load_reference(first=4 if share else 0)


def _register(name, **changes):
    """``zaya_tiny`` under a name of its own, with fields changed."""
    return register_preset(name, "zaya_tiny", **changes)


def _task(share, name="zaya_tiny", seq=SEQ, **changes):
    if not changes:
        return get_task("causal_lm", model_name=name, seq_len=seq,
                        expert_share=share)
    attention_fn = changes.pop("attention_fn", None)
    _register("zaya_tiny_changed", **changes)
    try:
        return get_task("causal_lm", model_name="zaya_tiny_changed",
                        seq_len=seq, expert_share=share,
                        attention_fn=attention_fn)
    finally:
        del transformer.CAUSAL_LMS["zaya_tiny_changed"]


@pytest.fixture(scope="module")
def f32_task(share):
    return _task(share, dtype=jnp.float32)


@pytest.fixture(scope="module")
def bf16_task(share):
    return _task(share)


@pytest.fixture(scope="module")
def variables(ref, bf16_task):
    """Seeded, perturbed as the benchmark's check perturbs them, and with
    every expert's last matrix 128 times as large: at these widths an expert
    adds 2% of the stream's scale where at the published ones (32 times as
    wide, same 0.02) it adds several times the stream, and a token that
    takes another expert has to show."""
    variables = ref.perturb(
        jax.jit(bf16_task.init_variables)(jax.random.key(3)),
        jax.random.key(4))
    return dict(variables, params=jax.tree_util.tree_map_with_path(
        lambda path, x: 128 * x if path[-1].key == "w_down" else x,
        variables["params"]))


@pytest.fixture(scope="module")
def batch():
    ids = np.random.default_rng(5).integers(2, VOCAB, (ROWS, SEQ))
    mask = np.ones((ROWS, SEQ), np.int8)
    mask[-1, SEQ - 5:] = 0  # a padded tail: live tokens only in the losses
    return {"input_ids": ids.astype(np.int32), "attention_mask": mask}


def _groups(tree) -> dict:
    """Parameter groups: the router's matrices, the held experts' three, the
    attention's projections and its two convolutions, every learned scale
    (norms, residual sums, temperatures, depth mix), the residual sums'
    shifts, the embedding (layers together)."""
    out: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [k.key for k in path if hasattr(k, "key")]
        last = keys[-1]
        if last.endswith("scale") or last in ("key_temperature", "depth_mix"):
            name = "scales"
        elif last.endswith("shift"):
            name = "shifts"
        elif last.endswith(("conv0", "conv1")):
            name = last[2:]
        else:
            name = next(k for k in ("router", "w_gate", "w_up", "w_down",
                                    "query", "key", "value", "out",
                                    "tok_embed") if k in keys)
        out.setdefault(name, []).append(jnp.ravel(leaf))
    return {k: jnp.concatenate(v) for k, v in out.items()}


def _relative(got, want) -> float:
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _one_program(fn, *args):
    """One jitted program, waited for (``tests/test_olmoe.py`` tells why; on
    the CPU a bf16 product with f32 sums also runs only compiled)."""
    return jax.block_until_ready(jax.jit(fn)(*args))


def _spread_error(got, ref, want, batch) -> float:
    """The benchmark's statistic (``benchmark/run.py`` ``check_model``)."""
    live = ref.live(batch, want)[..., None]
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
    return ref.forward(variables, batch)


def test_logits_match_reference_in_float32(ref, f32_task, variables, batch,
                                           want):
    assert _spread_error(_logits(f32_task, variables, batch), ref, want,
                         batch) < F32_TOL
    # the routers decide (``ROUTER_GAIN``): the one weight differs by token
    sel = ref._NOTES["scores"][0]
    assert float(jnp.std(sel.max(-1))) > 0.01


def test_loss_matches_reference(ref, f32_task, variables, batch):
    got = _one_program(_program_loss(f32_task, batch), variables)
    want = ref.loss(variables, batch)
    assert abs(float(got) - float(want)) < F32_TOL * float(want)


@pytest.fixture(scope="module")
def reference_grads(ref, variables, batch):
    grads = jax.grad(lambda v: ref.loss(v, batch))(variables)
    return _groups(grads["params"])


@pytest.fixture(scope="module")
def f32_grads(f32_task, variables, batch):
    grads = _one_program(jax.grad(_program_loss(f32_task, batch)), variables)
    return _groups(grads["params"]), grads["batch_stats"]


@pytest.mark.parametrize("group", GROUPS)
def test_gradient_matches_reference_in_float32(group, f32_grads,
                                               reference_grads):
    assert _relative(f32_grads[0][group], reference_grads[group]) < F32_TOL


# -- the grouped products' kernel form ---------------------------------------


@pytest.mark.slow  # the stack twice in interpret mode: 17-54 s a case
def test_the_grouped_products_kernels_are_the_plain_form_and_the_gauge_says(
        f32_task, variables, batch, monkeypatch):
    """Whole and under the share, as the cell's shape runs on the chip since
    PR 52."""
    grouped_kernels_are_the_plain_form(
        f32_task, variables, batch, lambda v: _groups(v["params"]), F32_TOL,
        monkeypatch)


def test_the_selection_bias_takes_no_gradient(f32_grads):
    for leaf in jax.tree.leaves(f32_grads[1]):
        assert not np.asarray(leaf).any()


def test_logits_of_the_program_as_it_runs(ref, bf16_task, variables, batch,
                                          want):
    assert _spread_error(_logits(bf16_task, variables, batch), ref, want,
                         batch) < ref.TOLERANCE


def test_reference_in_the_precision_below_fails_the_benchmark_comparison(
        ref, variables, batch, want):
    """The reference with the router's values, the bias and the logits in
    bf16 reads over ``TOLERANCE`` against itself in float32, on the tokens
    the comparison keeps (a quarter of these 123): ``perturb``'s shared
    ``OFFSET`` on the bias is invisible to a float32 choice, and bf16 cannot
    carry a score beside it."""
    low = ref.forward(variables, batch, dtype=jnp.bfloat16)
    ref.forward(variables, batch)  # the notes ``live`` reads: float32's
    assert _spread_error(low, ref, want, batch) > ref.TOLERANCE
    assert float(variables["batch_stats"]["layer_1"]["moe"]["bias"].mean()) \
        == pytest.approx(ref.OFFSET, abs=0.05)


def test_a_token_near_a_tie_marks_the_tokens_that_read_it_layer_by_layer():
    """The comparison's ``left_out``: a token within the margin of a held tie
    in layer l is marked, and each later layer's value shift and convolutions
    carry its changed output to the ``reach`` tokens after it, in its own row
    only; a tie between two absent experts marks nothing."""
    ref = _load_reference(first=0)
    rooms = np.full((3, 2 * 16), 1.0, np.float32)
    either = np.ones_like(rooms, bool)
    rooms[0, 3] = ref.MARGIN / 2  # row 0, token 3, layer 0
    rooms[2, 16 + 14] = ref.MARGIN / 2  # row 1, token 14, the last layer
    rooms[1, 9] = ref.MARGIN / 2  # between two absent experts:
    either[1, 9] = False  # nothing here moves

    def marked(reach=None):
        return np.asarray(ref.left_out(
            jnp.asarray(rooms), jnp.asarray(either), rows=2,
            reach=reach)).reshape(2, 16)

    assert ref.REACH == 1
    assert list(np.nonzero(marked()[0])[0]) == [3, 4, 5]  # two more layers
    assert list(np.nonzero(marked(reach=2)[0])[0]) == [3, 4, 5, 6, 7]
    assert list(np.nonzero(marked()[1])[0]) == [14]
    assert not marked(reach=0)[0, 4]
    # at a row's end the mark does not run into the next row
    rooms[0, 3], rooms[0, 15] = 1.0, 0.0
    assert list(np.nonzero(marked(reach=2)[0])[0]) == [15]
    assert list(np.nonzero(marked(reach=2)[1])[0]) == [14]


BROKEN = {
    "no_selection_bias": {"moe": {"bias_update_rate": 0.0}},
    "two_experts_a_token": {"experts_per_token": 2},
    "rotary_over_the_whole_head": {
        "parts": {ConvolutionalAttention: {"rotary_dim": 16}}},
    "rotary_theta_of_another_model": {"rope_theta": 10000.0},
}


@pytest.mark.parametrize("variant", sorted(BROKEN))
def test_broken_variant_fails_the_float32_comparison(variant, share, ref,
                                                     variables, batch, want):
    """The bias chooses, one expert a token, half a head turns: each
    departure from the layer as written misses the reference by orders of
    magnitude more than the program does."""
    task = _task(share, dtype=jnp.float32, **{
        k: dict(v) if isinstance(v, dict) else v
        for k, v in BROKEN[variant].items()})
    # the same parameters: what a variant does not use, it does not read
    got = _logits(task, variables, batch)
    assert float(jnp.abs(got - want).max() / jnp.std(want)) > 10 * F32_TOL


@pytest.mark.parametrize("leaf,why", [
    ("key_temperature", "tau_g scales the keys after their norm"),
    ("depth_mix", "the router's state reaches the next layer"),
    ("attn_stream_shift", "b_r enters the residual sum"),
    ("mlp_branch_scale", "a_o scales the expert sub-layer's branch"),
    ("k_conv0", "the keys pass the depthwise convolution"),
    ("q_conv1", "the queries pass the convolution within a head"),
])
def test_every_new_parameter_reaches_the_logits(leaf, why, f32_task,
                                                variables, batch):
    """Doubled in every layer, each of the parameters this layer adds moves
    the logits: none is declared and left out of the mathematics."""
    def doubled(path, x):
        return 2 * x + 0.5 if path[-1].key == leaf else x

    changed = dict(variables, params=jax.tree_util.tree_map_with_path(
        doubled, variables["params"]))
    before = _logits(f32_task, variables, batch)
    after = _logits(f32_task, changed, batch)
    assert float(jnp.abs(after - before).max()) > 1e-3, why


# -- the router's state, the stats and the bias's update ---------------------


def test_a_training_step_reports_its_gauges_and_moves_the_bias(
        bf16_task, variables, batch, share):
    def step(v):
        outputs, state = bf16_task.forward(v, batch, True, None)
        return bf16_task.stats(outputs), state

    stats, state = _one_program(step, variables)
    assert {"cca_key_temperature_max", "residual_scale_min",
            "residual_scale_max", "router_top1_prob_mean",
            "moe_router_bias_abs_max", "moe_assignments_total"} <= set(stats)
    assert float(stats["moe_assignments_total"]) == 3 * ROWS * SEQ  # k = 1
    assert 0.75 <= float(stats["residual_scale_min"]) \
        <= float(stats["residual_scale_max"]) <= 1.25  # ``perturb``'s
    assert 0.75 <= float(stats["cca_key_temperature_max"]) <= 1.25
    assert 1 / EXPERTS < float(stats["router_top1_prob_mean"]) < 1
    assert ("moe_local_fallback_total" in stats) is bool(share)
    if share:  # one list, whatever the routing
        assert float(stats["moe_local_fallback_total"]) == 0
    rate = dict(zaya_tiny.keywords["moe"])["bias_update_rate"]
    for layer, new in state["batch_stats"].items():
        moved = np.asarray(new["moe"]["bias"]
                           - variables["batch_stats"][layer]["moe"]["bias"])
        # f32 beside ``OFFSET``: one ulp at 64 is 7.6e-6
        assert np.allclose(np.abs(moved), rate, atol=2e-5)
        assert (moved > 0).any() and (moved < 0).any()


# -- the share ---------------------------------------------------------------


def test_the_two_shares_add_up_to_the_uncut_reference_layer():
    """Rank 0's routed part plus rank 1's is the whole expert layer as the
    reference computes it uncut: all 8 experts on every token under the
    top-1 mask of ``s + b``, weighted by ``s``. (Nothing is computed by both
    ranks alike: the layer has no shared expert.)"""
    ref = _load_reference(first=0)
    x = jax.random.normal(jax.random.key(0), (ROWS, SEQ, 64))
    logits = 3.0 * jax.random.normal(jax.random.key(1), (ROWS, SEQ, EXPERTS))
    bias = 0.05 * jax.random.normal(jax.random.key(2), (EXPERTS,))

    def layer(**kw):
        return DroplessMoE(num_experts=EXPERTS, expert_dim=32,
                           experts_per_token=1, dtype=jnp.float32,
                           bias_update_rate=1e-4, **kw)

    whole = layer().init(jax.random.key(3), x, None, logits)["params"]
    assert "router" not in whole  # the logits were given
    s = jax.nn.softmax(logits.reshape(-1, EXPERTS), -1)
    chosen = ref._rank(s + bias) < 1
    want = ref._experts(x.reshape(-1, 64), whole, s * chosen).reshape(x.shape)
    parts = []
    for rank in range(2):
        held = slice(4 * rank, 4 * rank + 4)
        params = {name: w[held] for name, w in whole.items()}
        parts.append(layer(first_expert=4 * rank, held_experts=4).apply(
            {"params": params, "router_state": {"bias": bias}}, x, None,
            logits))
        assert float(jnp.abs(parts[-1]).max()) > 0
    np.testing.assert_allclose(parts[0] + parts[1], want, atol=2e-5)
    # a token's one expert is on one rank: the other's part is exactly zero
    silent = (jnp.abs(parts[0]).max(-1) == 0) ^ (
        jnp.abs(parts[1]).max(-1) == 0)
    assert bool(silent.all())


def test_one_expert_a_token_and_half_of_them_held_builds_one_list():
    """``usual = min(2 T k held / E, T k)`` is ``T k``: the usual list is the
    worst case's, so the layer builds that one and there is no ``cond``
    (forward, backward or recomputation), where Moonlight's share keeps its
    choice."""
    def jaxpr_of(name, share, seq=128):  # 512 tokens: four tiles of rows
        task = get_task("causal_lm", model_name=name, seq_len=seq,
                        expert_share=share)
        shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
        batch = {"input_ids": jnp.zeros((ROWS, seq), jnp.int32),
                 "attention_mask": jnp.ones((ROWS, seq), jnp.int8)}
        return str(jax.make_jaxpr(jax.grad(_program_loss(task, batch)))(
            shapes))

    assert "cond[" not in jaxpr_of("zaya_tiny", "0/2")
    assert "cond[" not in jaxpr_of("zaya_tiny", None)
    assert "cond[" in jaxpr_of("zaya_tiny", "0/4")  # a quarter: two lists
    assert "cond[" in jaxpr_of("moonlight_tiny", "0/4")


# The lowered text of the whole forward and backward pass of three presets,
# hashed on the parent of PR 38 (commit d46911a) with the function below:
# factoring the router out of ``DroplessMoE``, the third entry of ``handed``
# and the fields this PR adds to the stack leave their programs letter for
# letter as they were. A later change to those layers changes the hash it
# means to change: run ``_lowered_hash`` on the parent and on the change.
LOWERED_ON_THE_PARENT = {
    ("olmoe_tiny", None, False): "8efa1c07930f6b37",
    ("moonlight_tiny", "0/4", False): "9d7e754cafd8205f",
    ("moonlight_tiny", "0/4", True): "4dbd5ac53ff2846d",
    ("phi4_mini_flash_tiny", None, False): "45e3ff30ae4e19bf",
    # hashed on the parent of PR 41 (commit b1c1a41): the depthwise causal
    # convolution that ``MambaMixer`` and ``GatedDeltaNet`` share, RMSNorm's
    # ``1 + w`` form and ``DroplessMoE``'s ``shared_gate`` and its
    # ``norm_topk`` under softmax scores leave these, and the four above,
    # as they were. The two Phi-4 texts were hashed again on PR 43's tree:
    # against the parent's (3e8dd34eac6275c2, be55b124ba580e46 at commit
    # 35dcd86) they hold one more returned constant, the gauge
    # ``conv_fused`` = 0, and the slice of ``z`` out of ``MambaMixer``'s
    # projection stands at its use and not beside ``x``'s; with the values'
    # numbers taken out, the same operations line for line otherwise
    ("phi4_mini_flash_tiny", None, True): "4b282ffbc5aed10e",
    ("zaya_tiny", None, False): "045f187a6df6e5a9",
    ("zaya_tiny", "0/2", False): "b3a8ef5439110232",
    ("zaya_tiny", "0/2", True): "0c33640d60b168c7",
    # hashed on the parent of PR 47 (commit f79fff4): the rows -> tokens sum
    # as an op of its own (``ops/rows.py``: the plain form off the TPU, a
    # kernel on it) leaves these two, whose cells the kernel's gain is
    # claimed in, and the eight above, as they were
    ("qwen3_next_tiny", "0/16", False): "aa30dadb4ddde0ce",
    ("smallthinker_tiny", "0/4", False): "15726310e4ab1cd1",
}


def _lowered_hash(name, share, remat):
    task = get_task("causal_lm", model_name=name, seq_len=32,
                    expert_share=share, remat=remat)
    shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
    batch = {"input_ids": jax.ShapeDtypeStruct((4, 32), jnp.int32),
             "attention_mask": jax.ShapeDtypeStruct((4, 32), jnp.int8)}

    def step(v, b):
        def loss(p):
            out, state = task.forward({**v, "params": p}, b, True, None)
            return task.loss(out, b), (out[2], state)
        return jax.value_and_grad(loss, has_aux=True)(v["params"])

    text = jax.jit(step).lower(shapes, batch).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name,share,remat", sorted(
    LOWERED_ON_THE_PARENT, key=str))
def test_the_other_models_steps_lower_as_before_the_router_was_factored_out(
        name, share, remat):
    assert _lowered_hash(name, share, remat) == LOWERED_ON_THE_PARENT[
        name, share, remat]


# -- causality: the convolutions and the value shift -------------------------


def test_moving_token_t_moves_nothing_before_t_and_the_token_after(
        f32_task, variables, batch):
    """The convolutions and the value shift look one token back and never
    ahead: with another id at position t, every logit before t is bit for
    bit what it was, and position t + 1 moves through more than attention
    (it moves with attention cut off too: next test)."""
    t = 11
    moved = dict(batch, input_ids=batch["input_ids"].copy())
    moved["input_ids"][:, t] = (moved["input_ids"][:, t] + 7) % VOCAB
    before = _logits(f32_task, variables, batch)
    after = _logits(f32_task, variables, moved)
    np.testing.assert_array_equal(before[:, :t], after[:, :t])
    assert float(jnp.abs(after[:, t:] - before[:, t:]).max()) > 1e-3


def _mixer(attention_fn=None):
    return ConvolutionalAttention(4, 2, 16, 8, 5e6, jnp.float32,
                                  attention_fn)


def test_the_mix_reaches_one_token_back_twice_and_the_values_once():
    """What attention is given, with attention itself replaced by a function
    that hands the values straight through: token t's output depends on
    tokens t and t - 1 only (the value shift), and the queries and keys that
    reach the attention function on tokens t - 2 to t (two 2-tap
    convolutions), never on a later one."""
    seen = {}

    def passthrough(q, k, v, mask=None):
        seen.update(q=q, k=k, v=v)
        return jnp.repeat(v, q.shape[1] // v.shape[1], axis=1)

    x = jax.random.normal(jax.random.key(0), (1, SEQ, 64))
    mixer = _mixer(passthrough)
    params = mixer.init(jax.random.key(1), x)

    def parts(x):
        out = mixer.apply(params, x, mutable=["mixer_stats"])[0]
        return out, seen["q"], seen["k"], seen["v"]

    t = 9
    base = parts(x)
    moved = parts(x.at[:, t].add(1.0))
    reach = {"out": 1, "q": 2, "k": 2, "v": 1}
    for name, a, b, axis in zip(reach, base, moved, (1, 2, 2, 2)):
        changed = np.asarray(jnp.abs(a - b).max(
            tuple(i for i in range(a.ndim) if i != axis)) > 0)
        assert changed[t:t + reach[name] + 1].all(), name
        assert not changed[:t].any(), name
        assert not changed[t + reach[name] + 1:].any(), name
    # head 0 of the values is this token's, head 1 the one before's
    v_changed = np.asarray(jnp.abs(base[3] - moved[3]).max((0, 3)) > 0)
    assert v_changed[0, t] and not v_changed[0, t + 1]
    assert v_changed[1, t + 1] and not v_changed[1, t]
    # queries and keys reach the kernel at sqrt(d) (times tau = 1) a head
    np.testing.assert_allclose(jnp.linalg.norm(base[1], axis=-1), 4.0,
                               rtol=1e-5)
    np.testing.assert_allclose(jnp.linalg.norm(base[2], axis=-1), 4.0,
                               rtol=1e-5)


# -- half-rotary -------------------------------------------------------------


def test_a_partial_turn_is_the_full_turn_on_its_part_and_nothing_elsewhere():
    x = jax.random.normal(jax.random.key(0), (2, SEQ, 4, 16))
    pos = jnp.arange(SEQ)
    half = rotary_embedding(x, pos, 5e6, 8)
    np.testing.assert_array_equal(half[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(half[..., :8],
                                  rotary_embedding(x[..., :8], pos, 5e6))
    assert float(jnp.abs(half[:, 1:, :, :8] - x[:, 1:, :, :8]).max()) > 0.1
    # the whole head, asked for by its width or not at all, is as before
    np.testing.assert_array_equal(rotary_embedding(x, pos, 1e4, 16),
                                  rotary_embedding(x, pos, 1e4))
    np.testing.assert_array_equal(rotary_embedding(x, pos, 1e4, 0),
                                  rotary_embedding(x, pos, 1e4))
    # a turn keeps each pair's length: element i with element i + 4
    np.testing.assert_allclose(
        half[..., :4] ** 2 + half[..., 4:8] ** 2,
        x[..., :4] ** 2 + x[..., 4:8] ** 2, rtol=1e-4, atol=1e-5)


# -- the configuration's file against the program ----------------------------


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "zaya1-8b-c4.json")) as f:
        return json.load(f)


def test_the_cut_holds_the_parameters_the_file_states(config):
    task = get_task(**config["task"])
    shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
    held = sum(int(np.prod(leaf.shape))
               for leaf in jax.tree.leaves(shapes["params"]))
    assert held == config["held_parameters"] == 708_644_876
    layer = shapes["params"]["layer_0"]
    count = {name: sum(int(np.prod(leaf.shape))
                       for leaf in jax.tree.leaves(layer[name]))
             for name in ("attn", "router", "moe")}
    assert count == {"attn": 5_242_880 + 330_240 + 2, "router": 659_968,
                     "moe": 100_663_296}
    # the selection bias is state, not a parameter: 16 a layer, all outputs
    bias = jax.tree.leaves(shapes["batch_stats"])
    assert [leaf.shape for leaf in bias] == [(16,)] * 6


def test_every_width_is_the_published_one(config):
    """The catalog row's ``config`` (copied into the test: the guide is not
    part of the repository), key by key, but for the three keys ``reduced``
    names, which the file gives beside their published values."""
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "lm_head_bias": False, "max_position_embeddings": 131072,
        "model_type": "zaya", "moe_intermediate_size": 2048,
        "num_attention_heads": 8, "num_experts": 16,
        "num_experts_per_tok": 1, "num_hidden_layers": 40,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
        "rms_norm_eps": 1e-05, "router_hidden_size": 256,
        "sliding_window": None, "tie_word_embeddings": True,
        "vocab_size": 262272}
    reduced = {"num_hidden_layers": 6, "num_experts": 8, "vocab_size": 32784}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in published.items():
        assert config[key] == reduced.get(key, value), key
        assert config["model"][key] == reduced.get(key, value), key
        if key in reduced:
            assert config["model"][f"{key}_published"] == value
    assert config["layer_types"] == ["hybrid"] * 40
    assert config["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
    model = get_task(**config["task"]).model
    assert (model.hidden_size, model.num_heads, model.expert_dim,
            model.num_experts, model.experts_per_token, model.rope_theta,
            model.norm_eps, model.tied_head) == (
        2048, 8, 2048, 16, 1, 5e6, 1e-5, True)
    assert {p.func.__name__: p.keywords for p in model.parts} == {
        "ConvolutionalAttention": dict(kv_heads=2, head_dim=128,
                                       rotary_dim=64),
        "StateRouter": dict(width=256)}
    assert dict(model.moe)["held_experts"] == 8
