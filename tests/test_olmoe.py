"""OLMoE's decoder layer (rotary, RMSNorm, bias-free attention, 64 dropless
SwiGLU experts with 8 a token; here ``olmoe_tiny``: 8 experts, 2 a token)
against the plain float32 reference the benchmark keeps in
``benchmark/reference/olmoe-1b-7b-c4.py``, on seeded weights, on the CPU.

Two comparisons, because two things are being asked. *Is the mathematics
right?* The program computed in float32 (``olmoe_tiny`` with ``dtype``
float32, registered for the test) against the reference: both are float32 and
differ in the order of their sums and in how the experts' products are
grouped, so logits, loss and every parameter group's gradient agree to
``F32_TOL`` = 2e-4 of their scale (measured 1e-6 to 3e-5), and a bf16 router,
a renormalised top-k and a dropped expert each miss it by orders of magnitude.
*Does the program as it runs (bf16 compute) stay near it?* The benchmark's own
comparison: worst logit difference over the logits' spread under the
reference's ``TOLERANCE``, tokens within ``MARGIN`` of a routing tie left out;
a renormalised top-k fails that too, here as at published widths, and so does
a bf16 router, because the reference's ``perturb`` gives every token's router
logits a shared offset that a float32 softmax and top-k do not see and bf16
logits cannot carry (the reference's note on ``OFFSET``; without it bf16
activations perturb the router's input by more than a bf16 router adds).
The same comparisons run once more with the Pallas flash kernel as the
attention (TPU interpret mode), the path the benchmark's cell trains on and
``benchmark/run.py``'s model check does not build.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import grouped_kernels_are_the_plain_form, register_preset

from lance_distributed_training_tpu.models import get_task
from lance_distributed_training_tpu.models.moe import DroplessMoE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, ROWS, VOCAB, TOP_K = 32, 4, 512, 2
F32_TOL = 2e-4  # float32 against float32: summation order and grouping only
# bf16 compute against float32, a parameter group's gradient relative to its
# norm. Outside the expert layer it is the rounding of bf16 operands: 0.6-1.2%
# measured. In it, one token in twenty takes another k-th expert than the
# reference does (the reference's note on MARGIN), so that share of the
# contributions to an expert's gradient is another token's: 6% (router) to
# 16% (the experts' matrices) measured here, and no smaller at published
# widths, where the share of such tokens is the same.
BF16_GRAD_TOL = {"router": 0.15, "w_gate": 0.35, "w_up": 0.35, "w_down": 0.35}
BF16_GRAD_TOL_ELSEWHERE = 0.03


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "olmoe-1b-7b-c4.py")
    spec = importlib.util.spec_from_file_location("olmoe_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.TOP_K = TOP_K
    return module


@pytest.fixture(scope="module")
def f32_task():
    """The same task computed in float32, under a preset name of its own."""
    name = "olmoe_tiny_f32"
    presets = register_preset(name, "olmoe_tiny", dtype=jnp.float32)
    try:
        yield get_task("causal_lm", model_name=name, seq_len=SEQ)
    finally:
        del presets[name]


@pytest.fixture(scope="module")
def bf16_task():
    return get_task("causal_lm", model_name="olmoe_tiny", seq_len=SEQ)


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
    """Parameter groups as the issue names them: the router, each of the
    experts' three matrices, each of attention's four, the norms, the
    embedding, the head (layers together)."""
    out: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [k.key for k in path if hasattr(k, "key")]
        name = next(k for k in (
            "router", "w_gate", "w_up", "w_down", "query", "key", "value",
            "out", "tok_embed", "lm_head", "scale") if k in keys)
        out.setdefault("norms" if name == "scale" else name, []).append(
            jnp.ravel(leaf))
    return {k: jnp.concatenate(v) for k, v in out.items()}


def _relative(got, want) -> float:
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _eager(fn, *args):
    return fn(*args)


def _one_program(fn, *args):
    """``fn`` as one jitted program, waited for. For the interpreted
    kernel: its callbacks dispatch operations of their own, an eager caller
    goes on dispatching from the test's thread meanwhile, and on the CPU's
    one execution queue the two can wait for each other for good (one run
    in five of the whole suite under six workers stopped here)."""
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


# -- the mathematics, float32 against float32 --------------------------------


def test_logits_match_reference_in_float32(ref, f32_task, variables, batch):
    assert _spread_error(f32_task, ref, variables, batch) < F32_TOL


def test_loss_with_both_auxiliary_terms_matches_reference(
        ref, f32_task, variables, batch):
    outputs, _ = f32_task.forward(variables, batch, True, None)
    got, want = f32_task.loss(outputs, batch), ref.loss(variables, batch)
    assert abs(float(got) - float(want)) < F32_TOL * float(want)
    # both terms are in it: the loss without them is smaller by their sum
    assert float(outputs[1]) > 0.01  # 0.01 * ~1 + 0.001 * logsumexp^2
    assert float(got) - float(f32_task.loss(
        (outputs[0], jnp.zeros(())), batch)) == pytest.approx(
            float(outputs[1]), rel=1e-4)


@pytest.fixture(scope="module")
def reference_grads(ref, variables, batch):
    return _groups(jax.grad(lambda v: ref.loss(v, batch))(variables))


def _program_loss(task, batch):
    def loss(v):
        outputs, _ = task.forward(v, batch, True, None)
        return task.loss(outputs, batch)

    return loss


def _program_grads(task, variables, batch, run=_eager):
    return _groups(run(jax.grad(_program_loss(task, batch)), variables))


GROUPS = ("router", "w_gate", "w_up", "w_down", "query", "key", "value",
          "out", "norms", "tok_embed", "lm_head")


@pytest.fixture(scope="module")
def f32_grads(f32_task, variables, batch):
    return _program_grads(f32_task, variables, batch)


@pytest.mark.parametrize("group", GROUPS)
def test_gradient_matches_reference_in_float32(group, f32_grads,
                                               reference_grads):
    assert _relative(f32_grads[group], reference_grads[group]) < F32_TOL


def test_gradients_of_the_program_as_it_runs(bf16_task, variables, batch,
                                             reference_grads):
    """bf16 compute, every group, against the float32 reference."""
    got = _program_grads(bf16_task, variables, batch)
    assert set(got) == set(GROUPS)
    worst = {g: _relative(got[g], reference_grads[g]) for g in GROUPS}
    over = {g: e for g, e in worst.items()
            if e > BF16_GRAD_TOL.get(g, BF16_GRAD_TOL_ELSEWHERE)}
    assert not over, worst


# -- the benchmark's comparison, bf16 against float32 ------------------------


def test_logits_of_the_program_as_it_runs(ref, bf16_task, variables, batch):
    assert _spread_error(bf16_task, ref, variables, batch) < ref.TOLERANCE


def _bf16_router(ref):
    def route(y, kernel):
        logits = (y.astype(jnp.bfloat16) @ kernel.astype(jnp.bfloat16))
        probs = jax.nn.softmax(logits, -1).astype(jnp.float32)
        logits = logits.astype(jnp.float32)
        return logits, probs * (ref._rank(logits) < ref.TOP_K)
    return route


def _renormalised(ref):
    good = ref._route

    def route(y, kernel):
        logits, weights = good(y, kernel)
        return logits, weights / weights.sum(-1, keepdims=True)
    return route


def _one_expert_dropped(ref):
    def route(y, kernel):
        logits = y @ kernel
        return logits, jax.nn.softmax(logits, -1) * (
            ref._rank(logits) < ref.TOP_K - 1)
    return route


BROKEN = {"bf16_router": _bf16_router, "renormalised_top_k": _renormalised,
          "one_expert_dropped": _one_expert_dropped}


@pytest.mark.parametrize("variant", sorted(BROKEN))
def test_broken_variant_fails_the_float32_comparison(
        variant, ref, f32_task, variables, batch, monkeypatch):
    monkeypatch.setattr(ref, "_route", BROKEN[variant](ref))
    assert _spread_error(f32_task, ref, variables, batch) > 10 * F32_TOL


def test_renormalised_top_k_fails_the_benchmark_comparison(
        ref, bf16_task, variables, batch, monkeypatch):
    """At this preset's sizes one of two experts is a smaller share of the
    residual stream than one of eight is at the published ones, so a dropped
    expert reads 0.06 here, over the correct program's 0.03 and under
    ``TOLERANCE``, and a bf16 router 0.05; at published widths they read 1.2
    and 1.8 to 1.9 on the chip (PERF.md section 6, PR 26)."""
    monkeypatch.setattr(ref, "_route", _renormalised(ref))
    assert _spread_error(bf16_task, ref, variables, batch) > ref.TOLERANCE


@pytest.mark.parametrize("offset", [0.0, None])
def test_router_offset_shows_a_bf16_router_at_the_published_shape(
        ref, offset, monkeypatch):
    """The router as published (2,048 wide, 64 experts, 8 a token) on inputs
    a norm would hand it, its kernel through the reference's ``perturb``:
    the layer takes the reference's eight experts for every token that
    ``MARGIN`` keeps, and a router computed in bf16 takes others for over a
    fifth of them (0.37 measured), but only where ``perturb`` has given the
    logits their shared ``OFFSET``; without it (``offset`` 0) it takes the
    same experts, which is why the comparison could not see it before."""
    monkeypatch.setattr(ref, "TOP_K", 8)
    if offset is not None:
        monkeypatch.setattr(ref, "OFFSET", offset)
    tokens, width = 1024, 2048
    layer = DroplessMoE(num_experts=64, expert_dim=8, experts_per_token=8)
    x = jax.random.normal(jax.random.key(0), (1, tokens, width))
    x = x.astype(jnp.bfloat16).astype(jnp.float32)
    params = ref.perturb(layer.init(jax.random.key(1), x), jax.random.key(2))
    _, state = layer.apply(
        params, x, mutable=["intermediates", "aux_loss", "moe_stats"],
        capture_intermediates=lambda module, _: module.name == "router")
    program = state["intermediates"]["router"]["__call__"][0]
    kernel = params["params"]["router"]["kernel"]
    want, _ = ref._route(x[0], kernel)
    rank = ref._rank(want)
    gap = (jnp.where(rank == 7, want, 0).sum(-1)
           - jnp.where(rank == 8, want, 0).sum(-1))
    kept = gap >= ref.MARGIN * jnp.sqrt(jnp.var(want, -1).mean())
    assert 0.3 < float(kept.mean()) < 0.7

    def others(logits):  # share of the kept tokens that take other experts
        differs = ((ref._rank(logits) < 8) != (rank < 8)).any(-1)
        return float((differs & kept).sum() / kept.sum())

    assert others(program) == 0.0
    in_bf16 = others(_bf16_router(ref)(x[0], kernel)[0])
    assert in_bf16 == 0.0 if offset == 0.0 else in_bf16 > 0.2


# -- the attention kernel the cell trains on ----------------------------------

KERNEL_SEQ = 128  # the kernel's key blocks are multiples of 128 lanes


@pytest.fixture(scope="module")
def kernel_task():
    """``olmoe_tiny`` in float32 with ``ops/flash.py``'s Pallas kernel as its
    attention, as ``--flash_attention`` builds it on a TPU (causal, blocks no
    longer than the row); calls run under ``force_tpu_interpret_mode``."""
    from lance_distributed_training_tpu.ops import flash

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        attention = flash.make_flash_attention(causal=True)
    name = "olmoe_tiny_f32_kernel"
    presets = register_preset(name, "olmoe_tiny", dtype=jnp.float32)
    with pytest.MonkeyPatch.context() as patch:
        # the cell's 4,096 tokens run the library's blocked kernel; keep
        # these 128 on it (tests/test_attention_choice.py holds the
        # short-sequence kernel that would take them)
        patch.setattr(flash, "SHORT_SEQ", 0)
        try:
            yield get_task("causal_lm", model_name=name, seq_len=KERNEL_SEQ,
                           attention_fn=attention)
        finally:
            del presets[name]


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
                              run=_one_program)


@pytest.fixture(scope="module")
def kernel_reference_grads(ref, variables, kernel_batch):
    return _groups(jax.grad(lambda v: ref.loss(v, kernel_batch))(variables))


def test_logits_and_loss_with_the_flash_kernel_match_reference(
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
def test_gradient_with_the_flash_kernel_matches_reference(
        group, kernel_grads, kernel_reference_grads):
    assert _relative(kernel_grads[group],
                     kernel_reference_grads[group]) < F32_TOL


# -- the dropless property ---------------------------------------------------


# -- the grouped products' kernel form ---------------------------------------


def test_the_grouped_products_kernels_are_the_plain_form_and_the_gauge_says(
        f32_task, variables, batch, monkeypatch):
    """As the cell's shape runs on the chip since PR 52; all experts are
    held, so every built row is live."""
    grouped_kernels_are_the_plain_form(f32_task, variables, batch, _groups,
                                       F32_TOL, monkeypatch)


def _layer(dtype=jnp.float32):
    return DroplessMoE(num_experts=8, expert_dim=32, experts_per_token=TOP_K,
                       dtype=dtype)


def test_every_token_reaches_exactly_k_distinct_experts():
    layer = _layer()
    x = jax.random.normal(jax.random.key(0), (ROWS, SEQ, 64))
    params = layer.init(jax.random.key(1), x)["params"]
    _, sown = layer.apply({"params": params}, x,
                          mutable=["aux_loss", "moe_stats"])
    sizes = sown["moe_stats"]["group_sizes"][0]
    assert int(sizes.sum()) == ROWS * SEQ * TOP_K  # nothing dropped, ever
    logits = x.reshape(-1, 64) @ params["router"]["kernel"]
    top = np.asarray(jax.lax.top_k(logits, TOP_K)[1])
    assert all(len(set(row)) == TOP_K for row in top)
    np.testing.assert_array_equal(
        np.bincount(top.ravel(), minlength=8), np.asarray(sizes))
    # all tokens to one expert is still exact: no capacity to overflow
    skewed = dict(params, router={"kernel": jnp.zeros((64, 8)).at[:, 3].set(
        jnp.sign(x.reshape(-1, 64).mean(0)))})
    _, sown = layer.apply({"params": skewed}, jnp.abs(x),
                          mutable=["moe_stats"])
    sizes = sown["moe_stats"]["group_sizes"][0]
    assert int(sizes.sum()) == ROWS * SEQ * TOP_K and int(sizes.max()) >= 100


def test_output_follows_a_permutation_of_the_tokens():
    layer = _layer()
    x = jax.random.normal(jax.random.key(2), (1, ROWS * SEQ, 64))
    params = layer.init(jax.random.key(1), x)
    perm = np.random.default_rng(0).permutation(ROWS * SEQ)
    y = layer.apply(params, x)
    y_perm = layer.apply(params, x[:, perm])
    np.testing.assert_allclose(np.asarray(y[:, perm]), np.asarray(y_perm),
                               rtol=1e-5, atol=1e-6)


def test_step_reports_expert_load_and_no_drop_counter(bf16_task, variables,
                                                      batch):
    outputs, _ = bf16_task.forward(variables, batch, True, None)
    stats = bf16_task.stats(outputs)
    assert set(stats) == {"moe_assignments_total", "moe_expert_load_max",
                          "moe_expert_load_mean"}
    layers = 2
    assert float(stats["moe_assignments_total"]) == ROWS * SEQ * TOP_K * layers
    assert float(stats["moe_expert_load_mean"]) == ROWS * SEQ * TOP_K / 8
    assert float(stats["moe_expert_load_max"]) >= float(
        stats["moe_expert_load_mean"])


# -- packed rows: each document as if alone ----------------------------------


@pytest.mark.parametrize("flash_seam", [False, True])
def test_packed_documents_get_the_logits_they_get_alone(f32_task, variables,
                                                        flash_seam):
    """Rotary positions restart and no attention crosses the junction, by
    the dense block mask and by an ``attention_fn`` that takes the segment
    ids itself (off a TPU ``make_flash_attention`` is exact dense attention
    behind the kernel's interface)."""
    task = f32_task
    if flash_seam:
        from lance_distributed_training_tpu.ops.flash import (
            make_flash_attention,
        )
        task_model = task.model.clone(
            attention_fn=make_flash_attention(causal=True))
    else:
        task_model = task.model
    gen = np.random.default_rng(9)
    a, b = gen.integers(2, VOCAB, 13), gen.integers(2, VOCAB, 15)
    packed = np.zeros((1, SEQ), np.int32)
    packed[0, :13], packed[0, 13:28] = a, b
    seg = np.zeros((1, SEQ), np.int32)
    seg[0, :13], seg[0, 13:28] = 1, 2
    pos = np.zeros((1, SEQ), np.int32)
    pos[0, :13], pos[0, 13:28] = np.arange(13), np.arange(15)
    together = task_model.apply(
        variables, packed, (seg > 0).astype(np.int8), train=False,
        segment_ids=seg, position_ids=pos)
    for doc, at in ((a, 0), (b, 13)):
        ids = np.zeros((1, SEQ), np.int32)
        ids[0, :len(doc)] = doc
        mask = (np.arange(SEQ) < len(doc)).astype(np.int8)[None]
        alone = task_model.apply(variables, ids, mask, train=False)
        np.testing.assert_allclose(
            np.asarray(together[0, at:at + len(doc)]),
            np.asarray(alone[0, :len(doc)]), rtol=2e-4, atol=2e-5)


# -- the task and the entry point --------------------------------------------


@pytest.mark.parametrize("task_type,model_name,depth", [
    ("masked_lm", "bert_small", 4), ("causal_lm", "gpt_small", 4),
    ("causal_lm", "olmoe_tiny", 2)])
def test_num_layers_states_a_depth(task_type, model_name, depth):
    def layers(**kw):
        task = get_task(task_type, model_name=model_name, seq_len=16,
                        vocab_size=64, **kw)
        shapes = jax.eval_shape(task.init_variables, jax.random.key(0))
        return sum(k.startswith("layer_") for k in shapes["params"])

    assert layers() == layers(num_layers=0) == depth
    assert layers(num_layers=1) == 1
    assert layers(num_layers=depth + 1) == depth + 1


def test_presets_and_their_errors():
    with pytest.raises(ValueError, match="olmoe_1b_7b.*olmoe_tiny"):
        get_task("causal_lm", model_name="nope")
    with pytest.raises(ValueError, match="own expert layers"):
        get_task("causal_lm", model_name="olmoe_tiny", num_experts=4)
    with pytest.raises(ValueError, match="num_layers applies"):
        get_task("classification", num_layers=2)
    tiny = get_task("causal_lm", model_name="olmoe_tiny", seq_len=16)
    assert tiny.model.vocab_size == 512  # the preset's own, not GPT-2's
    full = get_task("causal_lm", model_name="olmoe_1b_7b", seq_len=4096,
                    num_layers=1)
    shapes = jax.eval_shape(full.init_variables, jax.random.key(0))["params"]
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 625_616_896  # one layer, embedding and head: 625.6 M
    assert shapes["layer_0"]["moe"]["w_gate"].shape == (64, 2048, 1024)
    assert shapes["lm_head"]["kernel"].shape == (2048, 50304)


def test_configuration_file_holds_the_catalog_keys_twice_alike():
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmoe-1b-7b-c4.json")) as f:
        config = json.load(f)
    assert config["num_hidden_layers"] == 1 and list(config["reduced"]) == [
        "num_hidden_layers"]
    for key, value in config["model"].items():
        if key in config:  # the catalog's keys, at the top level for the
            assert config[key] == value  # driver and under model for run.py


def test_three_steps_of_train_through_the_cli(tmp_path, monkeypatch):
    from lance_distributed_training_tpu import cli
    from lance_distributed_training_tpu.data import create_text_token_dataset
    from lance_distributed_training_tpu.obs.registry import default_registry

    # one document over and over: something three steps can learn
    docs = [np.random.default_rng(0).integers(2, 64, 32).tolist()] * 60
    uri = str(tmp_path / "tok")
    create_text_token_dataset(uri, docs, seq_len=32, fragment_size=64)
    metrics_path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("LDT_METRICS_PATH", str(metrics_path))
    results = cli.main([
        "train", "--dataset_path", uri, "--task_type", "causal_lm",
        "--model_name", "olmoe_tiny", "--num_layers", "1", "--seq_len", "32",
        "--vocab_size", "64", "--batch_size", "8", "--epochs", "1",
        "--max_steps", "3", "--optimizer", "adamw", "--lr", "1e-2",
        "--weight_decay", "0.1", "--grad_clip", "1.0", "--log_every", "1",
        "--no_ddp", "--no_wandb", "--no_eval_at_end", "--no_autotune"])
    import json

    losses = [json.loads(line)["loss"] for line in open(metrics_path)
              if '"images_per_sec_dispatch"' in line]
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert losses[0] > losses[1] > losses[2]
    assert np.isfinite(results["loss"])
    registry = default_registry().metrics()
    assert registry["moe_assignments_total"].value >= 3 * 8 * 32 * TOP_K
    assert registry["moe_expert_load_max"].value >= \
        registry["moe_expert_load_mean"].value == 8 * 32 * TOP_K / 8
    assert not [n for n in registry if "drop" in n and n.startswith("moe")]
