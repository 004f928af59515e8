"""Laguna-S-2.1's decoder layers (three window layers of more query heads to
one full layer of fewer, all over the same key/value heads, a sigmoid gate a
head, two rotary schemes in one stack with YaRN's table on half a head in the
full layers, a leading dense layer, sigmoid-scored experts beside a shared
one; here ``laguna_tiny``: 5 layers, 4 query heads in the full layers and 6
in the window ones over 2 key/value heads of 16 on a stream of 64, a window
of 16, 16 experts of 32 with 4 a token) against the plain float32 reference
the benchmark keeps in ``benchmark/reference/laguna-s-2.1-c4.py``, on seeded
weights, on the CPU.

*Is the program's mathematics the reference's?* The program computed in
float32 against the reference, whole and under a share of the experts:
logits, loss and every parameter group's gradient to ``F32_TOL``. *Does each
mechanism show?* Each of ten wrong programs misses the reference by orders of
magnitude more. *Does the share add up?* The four quarters' routed parts and
the shared expert once are the uncut layer. Then what only these layers have:
sizes found by a layer's kind; YaRN's table and factor against
``transformers``' own; the band's edge at the published window; the gate one
scalar a head; and the configuration's file against the catalog row and the
parameters the program counts.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import register_preset

from lance_distributed_training_tpu.models import get_task
from lance_distributed_training_tpu.models.moe import DroplessMoE
from lance_distributed_training_tpu.models.transformer import (
    CAUSAL_LMS,
    LAYER_KINDS,
    GroupedAttention,
    laguna_layers,
    rotary_embedding,
    yarn_frequencies,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, ROWS, VOCAB, EXPERTS, TOP_K, WINDOW = 64, 2, 512, 16, 4, 16
TINY_YARN = (8.0, 64, 4.0, 1.0, 1.2079441541679836)
PUBLISHED_YARN = (128.0, 8192, 32.0, 1.0, 1.4852030263919618)
SHARPER = 8.0
F32_TOL = 2e-4  # float32 against float32: summation order and grouping only
GROUPS = ("router", "w_gate", "w_up", "w_down", "query", "key", "value",
          "out", "head_gate", "dense", "shared", "scales", "tok_embed",
          "lm_head")
SHARES = (None, "1/4")  # whole; experts 4..7 of 16
CELL = "c4-laguna-ep32-prepacked-8k"


def _load(folder: str, first: int = 0):
    path = os.path.join(ROOT, "benchmark", folder, "laguna-s-2.1-c4.py")
    spec = importlib.util.spec_from_file_location(
        f"laguna_{folder}_{first}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _load_reference(first: int = 0):
    """The reference at ``laguna_tiny``'s constants."""
    ref = _load("reference", first)
    ref.TOP_K, ref.WINDOW, ref.FIRST = TOP_K, WINDOW, first
    ref.THETA_FULL, ref.ROTARY_FULL, ref.YARN = 64.0, 8, TINY_YARN
    return ref


@pytest.fixture(scope="module", params=SHARES, ids=["whole", "share"])
def share(request):
    return request.param


@pytest.fixture(scope="module")
def ref(share):
    return _load_reference(first=4 if share else 0)


def _task(share, seq=SEQ, **changes):
    if not changes:
        return get_task("causal_lm", model_name="laguna_tiny", seq_len=seq,
                        expert_share=share)
    presets = register_preset("laguna_tiny_changed", "laguna_tiny", **changes)
    try:
        return get_task("causal_lm", model_name="laguna_tiny_changed",
                        seq_len=seq, expert_share=share)
    finally:
        del presets["laguna_tiny_changed"]


@pytest.fixture(scope="module")
def f32_task(share):
    return _task(share, dtype=jnp.float32)


@pytest.fixture(scope="module")
def bf16_task(share):
    return _task(share)


@pytest.fixture(scope="module")
def variables(ref, bf16_task):
    """Seeded, and perturbed as the benchmark's check perturbs them; then
    ``W_q`` and ``W_k`` times ``SHARPER`` more: on a stream of 64 the scores'
    spread is a fiftieth of what it is at the published 3,072, and a softmax
    that is nearly a mean shows neither rotary scheme."""
    perturbed = ref.perturb(
        jax.jit(bf16_task.init_variables)(jax.random.key(3)),
        jax.random.key(4))
    return jax.tree_util.tree_map_with_path(
        lambda path, x: SHARPER * x if [
            getattr(k, "key", "") for k in path][-2] in ("query", "key")
        else x, perturbed)


@pytest.fixture(scope="module")
def batch():
    ids = np.random.default_rng(5).integers(2, VOCAB, (ROWS, SEQ))
    mask = np.ones((ROWS, SEQ), np.int8)
    mask[-1, SEQ - 5:] = 0  # a padded tail: live tokens only in the losses
    return {"input_ids": ids.astype(np.int32), "attention_mask": mask}


def _groups(tree) -> dict:
    """Parameter groups, layers together: the router, the held experts'
    three, attention's four projections and its gate, the dense layer, the
    shared expert, every learned scale, the embedding and the head."""
    out: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [k.key for k in path if hasattr(k, "key")]
        if keys[-1] == "scale":
            name = "scales"
        elif "mlp" in keys:
            name = "dense"
        elif "shared" in keys:
            name = "shared"
        elif keys[-3:-1] == ["attn", "gate"]:
            name = "head_gate"
        else:
            name = next(k for k in GROUPS if k in keys)
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
    """The model's loss has no balance term: the sequence-wise term the
    sigmoid-scored layers sow takes no weight."""
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


_BOTH = ("GW", "GF")
BROKEN = {
    "no_gate": {"parts": {k: {"head_gate": False} for k in _BOTH}},
    "full_layers_without_yarn": {"parts": {"GF": {"yarn": ()}}},
    "yarn_without_its_factor": {
        "parts": {"GF": {"yarn": TINY_YARN[:4] + (1.0,)}}},
    "full_layers_turn_the_whole_head": {"parts": {"GF": {"rotary_dim": 0}}},
    "window_layers_under_the_full_layers_theta": {
        "parts": {"GW": {"rope_theta": 64.0}}},
    "window_layers_without_the_window": {"parts": {"GW": {"window": 0}}},
    "a_window_one_key_short": {"parts": {"GW": {"window": WINDOW - 1}}},
    "softmax_scores": {"moe": {"scoring": "softmax"}},
    "scores_not_renormalised": {"moe": {"norm_topk": False}},
    "routed_scale_of_one": {"moe": {"routed_scale": 1.0}},
}


@pytest.mark.parametrize("variant", sorted(BROKEN))
def test_broken_variant_fails_the_float32_comparison(variant, share,
                                                     variables, batch, want):
    """Each departure from the layer as written misses the reference by
    orders of magnitude more than the program does."""
    task = _task(share, dtype=jnp.float32, **{
        k: dict(v) for k, v in BROKEN[variant].items()})
    got = _logits(task, variables, batch)
    assert _spread_error(got, want) > 10 * F32_TOL


# -- sizes by kind ------------------------------------------------------------


def test_a_layer_takes_the_sizes_made_for_its_kind():
    """One class, two sets of sizes: the block finds the entry of ``parts``
    made for its kind, and what the entry states wins over the stack's one
    ``num_heads`` and ``rope_theta``."""
    model = get_task("causal_lm", model_name="laguna_tiny",
                     seq_len=SEQ).model
    assert model.held_kinds == laguna_layers(5) == (
        "GF", "GW", "GW", "GW", "GF")
    mixers = [model._layer(i, kind, parent=None).mixer(parent=None)
              for i, kind in enumerate(model.held_kinds)]
    assert all(type(m) is GroupedAttention for m in mixers)
    assert [(m.num_heads, m.window, m.rotary_dim, m.rope_theta, bool(m.yarn),
             m.head_gate) for m in mixers] == [
        (4, 0, 8, 64.0, True, True), (6, 16, 0, 10000.0, False, True),
        (6, 16, 0, 10000.0, False, True), (6, 16, 0, 10000.0, False, True),
        (4, 0, 8, 64.0, True, True)]
    assert LAYER_KINDS["GW"].mixer is LAYER_KINDS["GF"].mixer
    assert model.yarn == TINY_YARN
    shapes = jax.eval_shape(
        get_task("causal_lm", model_name="laguna_tiny",
                 seq_len=SEQ).init_variables, jax.random.key(0))["params"]
    assert [shapes[f"layer_{i}"]["attn"]["query"]["kernel"].shape
            for i in range(5)] == [(64, 4, 16), (64, 6, 16), (64, 6, 16),
                                   (64, 6, 16), (64, 4, 16)]
    assert "mlp" in shapes["layer_0"] and "moe" in shapes["layer_4"]


def test_a_later_stage_holds_no_dense_layer():
    """``dense_layers`` counts published layers: the span 4:8 (the ladder's
    second rung) starts with a sparse full layer."""
    task = get_task("causal_lm", model_name="laguna_s_2_1", seq_len=8192,
                    layer_span="4:8", expert_share="0/32", vocab_size=12544)
    assert task.model.held_kinds == ("GF", "GW", "GW", "GW")
    shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
    assert all("moe" in shapes["params"][f"layer_{i}"] for i in range(4))
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(
        shapes["params"])) == 653_577_216


# -- YaRN ---------------------------------------------------------------------


def _transformers_yarn(theta, head_dim, partial, yarn):
    torch = pytest.importorskip("torch")
    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")

    class Stub:  # the keys that version's function reads (``rope_scaling``;
        # the row writes them as ``rope_parameters`` by layer type)
        rope_theta = theta
        partial_rotary_factor = partial
        hidden_size, num_attention_heads = head_dim, 1
        max_position_embeddings = 1048576
        rope_scaling = {
            "rope_type": "yarn", "factor": yarn[0],
            "original_max_position_embeddings": yarn[1],
            "beta_fast": yarn[2], "beta_slow": yarn[3],
            "attention_factor": yarn[4]}

    Stub.head_dim = head_dim
    inv, factor = rope_utils._compute_yarn_parameters(
        Stub(), torch.device("cpu"))
    return inv.numpy(), factor


def test_yarn_is_transformers_at_the_published_five_numbers():
    """Inverse frequencies and the factor on cos and sin, the program's and
    the reference's, against ``_compute_yarn_parameters``; the ramp runs from
    pair 9 to pair 18 of 32."""
    want, factor = _transformers_yarn(500000, 128, 0.5, PUBLISHED_YARN)
    assert want.shape == (32,)
    assert factor == PUBLISHED_YARN[4] == 0.1 * math.log(128) + 1
    ours = np.array(yarn_frequencies(64, 500000.0, *PUBLISHED_YARN[:4]))
    theirs = np.array(_load("reference").yarn_inverse_frequencies(
        64, 500000.0, *PUBLISHED_YARN[:4]))
    np.testing.assert_allclose(ours, want, rtol=2e-6)
    np.testing.assert_allclose(theirs, want, rtol=2e-6)
    own = 500000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(ours[:10], own[:10], rtol=1e-12)  # low = 9
    np.testing.assert_allclose(ours[18:], own[18:] / 128, rtol=1e-12)
    assert np.all((ours[10:18] < own[10:18])
                  & (ours[10:18] > own[10:18] / 128))  # high = 18
    # and the preset carries those five numbers, half a head, theta 5e5
    full = dict(CAUSAL_LMS["laguna_s_2_1"].ctor.keywords["parts"])["GF"]
    assert (full.keywords["yarn"], full.keywords["rotary_dim"],
            full.keywords["rope_theta"]) == (PUBLISHED_YARN, 64, 500000.0)


def test_the_rotary_turn_under_a_table_and_a_factor():
    """``rotary_embedding`` with the table and the factor is the plain turn
    at those frequencies, times the factor, on the first ``width`` elements;
    with neither it is what it was."""
    x = jax.random.normal(jax.random.key(0), (1, 12, 2, 16))
    pos = jnp.arange(12) * 5
    table = yarn_frequencies(8, 64.0, *TINY_YARN[:4])
    got = rotary_embedding(x, pos, 64.0, 8, table, 1.25)
    angle = np.asarray(pos, np.float64)[:, None] * np.asarray(table)
    cos, sin = (1.25 * f(angle)[None, :, None, :] for f in (np.cos, np.sin))
    a, b = np.asarray(x[..., :4], np.float64), np.asarray(x[..., 4:8],
                                                         np.float64)
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin,
                           np.asarray(x[..., 8:])], -1)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    plain = rotary_embedding(x, pos, 64.0, 8)
    own = tuple(64.0 ** (-j / 4) for j in range(4))
    np.testing.assert_allclose(rotary_embedding(x, pos, 64.0, 8, own),
                               plain, rtol=1e-6, atol=1e-6)


# -- the band, the gate -------------------------------------------------------


def _mixer(**fields):
    return GroupedAttention(**{**dict(num_heads=2, kv_heads=1, head_dim=8,
                                      dtype=jnp.float32), **fields})


def test_the_band_ends_at_the_published_window():
    """At ``sliding_window`` 512: the key 511 before a query is seen, the
    key 512 before it is not."""
    window = dict(CAUSAL_LMS["laguna_s_2_1"].ctor.keywords["parts"])[
        "GW"].keywords["window"]
    assert window == 512
    seq, far = 640, 20
    x = jax.random.normal(jax.random.key(0), (1, seq, 16))
    mixer = _mixer(window=window)
    params = mixer.init(jax.random.key(1), x)
    before = mixer.apply(params, x)
    after = mixer.apply(params, x.at[0, far].add(1.0))
    moved = jnp.abs(after - before)[0].max(-1)
    assert float(moved[far + 511]) > 1e-5
    assert float(moved[far + 512:].max()) == 0.0
    np.testing.assert_array_equal(before[0, :far], after[0, :far])  # causal


def test_the_gate_is_one_scalar_a_head():
    """``W_g`` is stream by heads; at ``W_g`` = 0 every head's output is
    halved; a column of ``W_g`` moves its own head's part of the output
    projection's input and no other's; the mean rides ``mixer_stats``."""
    x = jax.random.normal(jax.random.key(0), (ROWS, SEQ, 64))
    seen = []

    def attention_fn(q, k, v, mask=None, window=0, segment_ids=None):
        seen.append((q.shape, k.shape, window))
        return jnp.ones_like(q)

    gated = _mixer(num_heads=6, kv_heads=2, head_dim=16, window=WINDOW,
                   head_gate=True, attention_fn=attention_fn)
    params = gated.init(jax.random.key(1), x)["params"]
    assert params["gate"]["kernel"].shape == (64, 6)
    assert set(params) == {"query", "key", "value", "out", "gate"}
    plain = _mixer(num_heads=6, kv_heads=2, head_dim=16, window=WINDOW,
                   attention_fn=attention_fn)
    rest = {k: v for k, v in params.items() if k != "gate"}
    zero = {**rest, "gate": {"kernel": jnp.zeros((64, 6))}}
    np.testing.assert_allclose(
        gated.apply({"params": zero}, x),
        0.5 * plain.apply({"params": rest}, x), rtol=1e-5, atol=1e-6)
    assert seen[-1] == ((ROWS, 6, SEQ, 16), (ROWS, 2, SEQ, 16), WINDOW)
    # with a kernel that returns ones the output is sum_n g_n (sum_d W_o[n, d])
    # : head 3's column of W_g moves it along head 3's vector alone

    def run(p):
        out, sown = gated.apply({"params": p}, x, mutable=["mixer_stats"])
        return out, sown["mixer_stats"]["attn_gate"][0]

    base, mean = run(params)
    moved = {**params, "gate": {"kernel": params["gate"]["kernel"].at[
        :, 3].add(0.5)}}
    delta = (run(moved)[0] - base).reshape(-1, 64)
    along = params["out"]["kernel"][3].sum(0)  # [64]
    along = along / jnp.linalg.norm(along)
    across = delta - (delta @ along)[:, None] * along
    assert float(jnp.abs(delta).max()) > 1e-3
    assert float(jnp.abs(across).max()) < 1e-5
    assert 0.3 < float(mean) < 0.7


# -- the share ----------------------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """Every rank's routed part, plus what every rank computes alike (the
    shared expert) counted once, is the whole layer as the reference computes
    it uncut: all 16 experts on every token under the top-4 mask."""
    ref = _load_reference(first=0)
    fields = dict(num_experts=EXPERTS, expert_dim=32, experts_per_token=TOP_K,
                  dtype=jnp.float32, scoring="sigmoid", norm_topk=True,
                  routed_scale=2.5, shared_dim=32)
    x = jax.random.normal(jax.random.key(0), (ROWS, SEQ, 64))
    whole = DroplessMoE(**fields)
    params = whole.init(jax.random.key(1), x)["params"]
    params = dict(params, router={"kernel": 8 * params["router"]["kernel"]})
    y = x.reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        weights, _ = ref._route(y @ params["router"]["kernel"])
        shared_part = ref._swiglu(y, params["shared"])
        want = ref._experts(y, params, weights) + shared_part
    held = EXPERTS // 4
    total = shared_part
    for rank in range(4):
        layer = DroplessMoE(**fields, first_expert=rank * held,
                            held_experts=held)
        mine = dict(params, **{
            name: params[name][rank * held:(rank + 1) * held]
            for name in ("w_gate", "w_up", "w_down")})
        out, sown = layer.apply({"params": mine}, x,
                                mutable=["moe_stats", "aux_loss"])
        total = total + (out.reshape(-1, 64) - shared_part)
        sizes = sown["moe_stats"]
        np.testing.assert_array_equal(
            np.asarray(sizes["held_sizes"][0]),
            np.asarray(sizes["group_sizes"][0])[rank * held:
                                                (rank + 1) * held])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(whole.apply({"params": params}, x)
                                          ).reshape(-1, 64),
                               np.asarray(want), rtol=2e-4, atol=2e-5)


# -- what a step reports ------------------------------------------------------


def test_a_training_step_reports_its_gauges(bf16_task, variables, batch,
                                            share):
    def step(v):
        outputs, _ = bf16_task.forward(v, batch, True, None)
        return bf16_task.stats(outputs)

    stats = {k: float(v) for k, v in _one_program(step, variables).items()}
    assert {"attn_gate_mean", "attn_window", "moe_assignments_total",
            "moe_expert_load_max"} <= set(stats)
    assert 0.2 < stats["attn_gate_mean"] < 0.8
    assert stats["attn_window"] == WINDOW
    assert stats["moe_assignments_total"] == 4 * ROWS * SEQ * TOP_K
    assert ("moe_local_fallback_total" in stats) is bool(share)
    if share:
        assert {"moe_local_load_max", "moe_local_load_mean",
                "moe_local_row_fill_pct"} <= set(stats)


def test_the_first_log_line_names_the_attention_path_and_yarn():
    from lance_distributed_training_tpu import trainer

    config = trainer.TrainConfig(dataset_path="", task_type="causal_lm",
                                 model_name="laguna_tiny", seq_len=SEQ)
    assert trainer._kernel_paths(_task(None), config) == {
        "attention": "dense",
        "yarn": "factor 8 over 64 positions, beta 4/1, cos and sin x 1.2079"}
    other = get_task("causal_lm", model_name="smallthinker_tiny", seq_len=SEQ)
    assert "yarn" not in trainer._kernel_paths(other, config)


def test_the_published_shapes_have_their_tilings():
    """Both of the cell's attention calls find a timed entry, so
    ``attention_tiling_fallback_total`` stays 0 in the cell."""
    from lance_distributed_training_tpu.ops import flash

    for heads, window in ((72, 512), (48, 0)):
        tiling, timed = flash.splash_tiling(8192, 128, 128, heads, True,
                                            window)
        assert timed, (heads, window)
        assert all(8192 % b == 0 for b in tiling.blocks)


# -- the configuration's file against the program ----------------------------


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-s-2.1-c4.json")) as f:
        return json.load(f)


def test_the_cut_holds_the_parameters_the_file_states(config):
    task = get_task(**config["task"], remat=True)
    shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
    held = sum(int(np.prod(leaf.shape))
               for leaf in jax.tree.leaves(shapes["params"]))
    assert held == config["held_parameters"] == 811_017_216
    count = {
        (layer, name): sum(int(np.prod(leaf.shape))
                           for leaf in jax.tree.leaves(part))
        for layer in ("layer_0", "layer_1", "layer_4")
        for name, part in shapes["params"][layer].items()}
    norms = {"ln_attn": 3072, "ln_mlp": 3072}
    assert count == {
        **{("layer_0", k): v for k, v in {
            "attn": 44_187_648, "mlp": 113_246_208, **norms}.items()},
        **{("layer_1", k): v for k, v in {
            "attn": 63_135_744, "moe": 85_721_088, **norms}.items()},
        **{("layer_4", k): v for k, v in {
            "attn": 44_187_648, "moe": 85_721_088, **norms}.items()}}
    assert "batch_stats" not in shapes  # no selection bias: no state
    assert task.model.held_kinds == ("GF", "GW", "GW", "GW", "GF")
    assert config["train_flags"][config["train_flags"].index(
        "--expert_share") + 1] == "0/32"


def test_every_width_is_the_published_one(config):
    """The catalog row's ``config`` (copied into the test: the guide is not
    part of the repository), key by key, but for the three keys ``reduced``
    names, which the file gives beside their published values."""
    types = ["full_attention", "sliding_attention", "sliding_attention",
             "sliding_attention"] * 12
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
        "intermediate_size": 12288, "num_hidden_layers": 48,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "layer_types": types,
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
        "moe_router_logit_softcapping": 0}
    reduced = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 12544}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in published.items():
        assert config[key] == reduced.get(key, value), key
        assert config["model"][key] == reduced.get(key, value), key
        if key in reduced:
            assert config["model"][f"{key}_published"] == value
    model = get_task(**config["task"]).model
    assert (model.hidden_size, model.num_heads, model.expert_dim,
            model.num_experts, model.experts_per_token, model.norm_eps,
            model.norm_offset, model.tied_head, model.dense_layers,
            model.dense_dim) == (
        3072, 48, 1024, 256, 10, 1e-6, False, False, 1, 12288)
    parts = {kind: part.keywords for kind, part in model.parts}
    assert parts == {
        "GW": dict(num_heads=72, kv_heads=8, head_dim=128, window=512,
                   rope_theta=10000.0, head_gate=True),
        "GF": dict(kv_heads=8, head_dim=128, rope_theta=500000.0,
                   rotary_dim=64, yarn=PUBLISHED_YARN, head_gate=True)}
    assert dict(model.moe) == {
        "scoring": "sigmoid", "norm_topk": True, "routed_scale": 2.5,
        "shared_dim": 1024, "first_expert": 0, "held_experts": 8}
    assert CAUSAL_LMS["laguna_s_2_1"].aux_weights == {}
    # the three per-layer lists are one layout, and the program's kinds are it
    assert tuple("GF" if t == "full_attention" else "GW" for t in types) == \
        laguna_layers(48) == model.layer_kinds
    assert [72 if k == "GW" else 48 for k in model.layer_kinds] == \
        published["num_attention_heads_per_layer"]
    assert config["model"]["heads_held"] == [48, 72, 72, 72, 48]
    assert config["task"]["seq_len"] == published["rope_parameters"][
        "full_attention"]["original_max_position_embeddings"]


def test_the_flops_file_counts_the_band_as_a_band(config):
    flops = _load("flops")
    model = config["model"]
    assert flops._pairs(8192, 0) == 33_558_528
    assert flops._pairs(8192, 512) == 4_063_488
    band = np.tril(np.ones((64, 64), bool)) & ~np.tril(
        np.ones((64, 64), bool), -16)
    assert flops._pairs(64, 16) == band.sum()
    per_token = flops.forward_flops(model, 1, 8192) / 8192
    assert per_token == pytest.approx(1220.7e6, rel=1e-4)
    assert flops.attention_flops(model, 1, 8192, "F") / flops.attention_flops(
        model, 1, 8192, "W") == pytest.approx(
            2 * 48 * 33_558_528 / (3 * 72 * 4_063_488))
    # keys and values in their own eight heads, six tensors of the queries'
    assert flops.attention_bytes(model, 1, 8192, "W") == \
        3 * 8192 * 128 * 2 * (6 * 72 + 6 * 8)
    assert flops.attention_bytes(model, 1, 8192, "F") == \
        2 * 8192 * 128 * 2 * (6 * 48 + 6 * 8)
    # operations bound both: the band by 6.8 ms against 3.7, the triangle by
    # 25.1 against 1.7
    for mask, least_ms in (("W", 6.84), ("F", 25.12)):
        by_flops = flops.attention_flops(model, 1, 8192, mask) / 197e12
        assert by_flops > flops.attention_bytes(model, 1, 8192, mask) / 819e9
        assert by_flops * 1e3 == pytest.approx(least_ms, abs=0.01)


# -- the cell's readers -------------------------------------------------------

_FWD = "jit(step)/jvp(forward)/TransformerDecoder/"
_BWD = "jit(step)/transpose(jvp(forward))/TransformerDecoder/"
# op_name -> ps in one run of the step: a hand-made plane with the scopes
# these layers name (the mixer's five, the dense layer's, the expert layer's)
_OPS = {
    _FWD + "layer_0/attention/attn/attn.project/query/dot_general":
        2_000_000_000,
    _FWD + "layer_0/attention/attn/attn.full/splash_mha_fwd": 20_000_000_000,
    _BWD + "layer_0/attention/attn/attn.full/splash_mha_dkv": 40_000_000_000,
    _FWD + "layer_0/attention/attn/attn.gate/gate/dot_general": 300_000_000,
    _BWD + "layer_0/attention/attn/attn.gate/mul": 700_000_000,
    _FWD + "layer_0/mlp.dense/mlp/gate/dot_general": 9_000_000_000,
    _FWD + "layer_1/attention/attn/attn.window/splash_mha_fwd":
        10_000_000_000,
    _BWD + "layer_1/attention/attn/attn.window/splash_mha_dq":
        15_000_000_000,
    _BWD + "layer_1/attention/attn/attn.out/out/dot_general": 1_000_000_000,
    _FWD + "layer_1/moe/moe.router/top_k": 200_000_000,
    _FWD + "layer_1/moe/moe.dispatch/sort": 400_000_000,
    _BWD + "layer_1/moe/checkpoint/moe.experts/mul": 4_000_000_000,
    "ragged-dot-none": 12_000_000_000,
    _BWD + "layer_1/moe/moe.combine/mul": 250_000_000,
    _FWD + "layer_1/moe/moe.shared/shared/up/dot_general": 600_000_000,
    "jit(step)/optimizer/add": 1_000_000_000,
}
_READS = {  # ms a step, or the share the reader makes of them
    "lg_attention_ms": 89.0, "lg_gate_ms": 1.0, "lg_dense_ms": 9.0,
    "lg_routed_ms": 16.65, "lg_load_max_over_mean": 1.5,
    "lg_window_kernel_roofline_pct": None,
    "lg_full_kernel_roofline_pct": None, "lg_experts_roofline_pct": None,
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
        point["counters"] = {"moe_local_load_max": 480.0,
                             "moe_local_load_mean": 320.0}
    ctx.update(
        cell={"name": CELL, "config": config},
        flops=run.load_module("flops", "laguna-s-2.1-c4"),
        counters={"moe_local_assignments_total": 100 * 4 * 2560.0},
        step_shapes=[{"input_ids": (1, 8192)}])
    return ctx, run


@pytest.mark.parametrize("metric", sorted(_READS))
def test_a_reader_reads_the_scopes_the_layers_name(metric, config):
    ctx, run = _reader_ctx(_OPS, config)
    value = run.load_module("layer_metrics", metric).read(ctx)
    want = _READS[metric]
    model, flops = config["model"], ctx["flops"]
    if metric == "lg_window_kernel_roofline_pct":
        want = 100 * flops.attention_flops(model, 1, 8192, "W") / 197e12 \
            / 0.025
    if metric == "lg_full_kernel_roofline_pct":
        want = 100 * flops.attention_flops(model, 1, 8192, "F") / 197e12 \
            / 0.060
    if metric == "lg_experts_roofline_pct":  # their bytes bound them
        want = 100 * flops.expert_bytes(model, 4 * 2560.0) / 819e9 / 0.016
    assert value == pytest.approx(want, rel=1e-6)
    assert 0 < value < 100 or metric.endswith("_ms") or "load" in metric
    # on a program without these scopes and counters (the parent, or another
    # model's step): nothing, and no error
    bare, _ = _reader_ctx({_FWD + "layer_0/attn/dot_general": 1_000_000},
                          config)
    bare["counters"], bare["log_points"] = {}, [{"t": 20, "counters": {}}]
    assert run.load_module("layer_metrics", metric).read(bare) is None


def test_the_recorded_trace_holds_every_scope_the_readers_name():
    """``benchmark/fixtures/scopes/laguna_1chip_v5e.json.gz``, two runs of
    the cell's step cut from a traced run on the v5e (PR 53): each scope a
    ``lg_*`` reader names has operations in it, the mixer's parts add up to
    the ``attention`` scope, and the kernels' names are the splash kernel's."""
    import sys

    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from reduce import named_scopes, scopes

    raw = scopes.load_fixture(os.path.join(
        ROOT, "benchmark", "fixtures", "scopes", "laguna_1chip_v5e.json.gz"))
    ms = {name: named_scopes.ms_of(raw, (name,)) for name in (
        "attention", "attn.project", "attn.window", "attn.full", "attn.gate",
        "attn.out", "mlp.dense", "moe.router", "moe.dispatch", "moe.experts",
        "moe.combine", "moe.shared", "lm_head")}
    assert all(value and value > 0 for value in ms.values()), ms
    parts = sum(ms[name] for name in ("attn.project", "attn.window",
                                      "attn.full", "attn.gate", "attn.out"))
    assert parts == pytest.approx(ms["attention"], rel=1e-6)
    assert ms["attn.full"] > ms["attn.window"] > ms["attn.gate"]
    assert named_scopes.ms_of(raw, (), also=named_scopes.GROUPED_PRODUCTS) > 0
    kernels = {meta["name"].split(" = ")[0] for meta, _, _ in scopes.step_ops(
        raw)[1] if named_scopes.under(meta["tf_op"], ("attn.window",))
        and "splash" in meta["name"]}
    assert kernels, "no splash kernel under attn.window"


def test_the_manifest_lists_the_cell_and_its_eight_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-s-2.1-c4", "c4-prepacked-8k-vp8-12544", 1)
    assert manifest["workloads"][-1] == cell and len(cell["why"]) <= 200
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [cell["name"]]]
    assert sorted(m["name"] for m in mine) == sorted(_READS)
    assert manifest["per_layer"][-len(mine):] == mine  # appended in a block
    assert {m["moves"] for m in mine} == {"samples_per_s_chip"}
    config = manifest["configs"][-1]
    assert config["name"] == "laguna-s-2.1-c4"
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]


def test_the_cell_rehearses_end_to_end_on_the_cpu():
    """``benchmark/run.py``'s whole path for the cell at the tiny preset,
    untraced and traced: the generator, the model check against the
    reference under a share, ``train`` with ``--layer_span`` and
    ``--expert_share``, the log-point clock, the stop, the readers."""
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
    assert "lg_load_max_over_mean" in proc.stdout
