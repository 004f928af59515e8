"""The masked-LM head on the masked positions only (ISSUE 27).

In training ``_masked_lm_task.forward`` gathers the hidden states of the
masked positions into a buffer of static capacity and applies the tied head
and the cross-entropy to that buffer; a batch that masks more than the
capacity takes the full-logits branch. Whichever branch runs, loss and
gradients are those of the full-logits path written out below as the
reference: the step as it was before the change, on the same ``rng``.
"""

from __future__ import annotations

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from lance_distributed_training_tpu.models import tasks
from lance_distributed_training_tpu.models.tasks import get_task

ROWS, SEQ, VOCAB = 4, 512, 96
RNG = jax.random.PRNGKey(27)


def make_task(num_experts: int = 0):
    return get_task("masked_lm", model_name="bert_small", seq_len=SEQ,
                    vocab_size=VOCAB, num_layers=2, num_experts=num_experts)


def padded_batch(lengths=(512, 300, 17, 0)):
    ids = jax.random.randint(jax.random.PRNGKey(1), (ROWS, SEQ), 2, VOCAB)
    live = jnp.arange(SEQ)[None, :] < jnp.asarray(lengths)[:, None]
    return {"input_ids": jnp.where(live, ids, 0),
            "attention_mask": live.astype(jnp.int8)}


def packed_batch():
    """Rows of several documents: segments from 1, 0 on the padding, and
    positions that restart with each document."""
    batch = padded_batch((512, 480, 256, 40))
    cuts = [(0, 200, 512), (0, 30, 480), (0, 256, 256), (0, 8, 40)]
    seg = np.zeros((ROWS, SEQ), np.int32)
    pos = np.zeros((ROWS, SEQ), np.int32)
    for row, (a, b, end) in enumerate(cuts):
        seg[row, a:b], seg[row, b:end] = 1, 2
        pos[row, a:b], pos[row, b:end] = np.arange(b - a), np.arange(end - b)
    return dict(batch, segment_ids=jnp.asarray(seg),
                position_ids=jnp.asarray(pos))


def full_logits_loss(task, params, batch, rng, num_experts):
    """The step's loss as it was before the change: the same mask from the
    same ``rng``, logits for every position, weights ``mlm_mask``."""
    ids = batch["input_ids"].astype(jnp.int32)
    mask = batch["attention_mask"]
    mlm_mask = jax.random.bernoulli(rng, 0.15, ids.shape) & (mask > 0)
    corrupted = jnp.where(mlm_mask, 1, ids)
    kwargs = dict(train=True, segment_ids=batch.get("segment_ids"),
                  position_ids=batch.get("position_ids"))
    aux = jnp.zeros((), jnp.float32)
    if num_experts:
        logits, sown = task.model.apply({"params": params}, corrupted, mask,
                                        mutable=["aux_loss"], **kwargs)
        aux = sum(jax.tree_util.tree_leaves(sown["aux_loss"]))
    else:
        logits = task.model.apply({"params": params}, corrupted, mask,
                                  **kwargs)
    assert logits.shape == ids.shape + (VOCAB,)
    raw = optax.softmax_cross_entropy_with_integer_labels(logits, ids)
    w = mlm_mask.astype(jnp.float32)
    return (raw * w).sum() / jnp.maximum(w.sum(), 1.0) + 0.01 * aux, mlm_mask


def step_loss(task, params, batch, rng):
    outputs, _ = task.forward({"params": params}, batch, True, rng)
    return task.loss(outputs, batch), task.stats(outputs)


CASES = {
    # name: (batch, experts, forced capacity or None, fallback expected)
    "padded": (padded_batch, 0, None, 0.0),
    "packed": (packed_batch, 0, None, 0.0),
    "experts": (padded_batch, 4, None, 0.0),
    "no_masked_position": (lambda: padded_batch((0, 0, 0, 0)), 0, None, 0.0),
    "overflow_takes_the_full_branch": (padded_batch, 0, 32, 1.0),
    "packed_overflow": (packed_batch, 0, 64, 1.0),
    "capacity_just_holds": (lambda: padded_batch((512, 512, 64, 8)), 0, 96,
                            None),
    # a row no longer than its capacity goes the same way: all of it fits
    "short_row": (lambda: {k: v[:, :64] for k, v in padded_batch(
        (64, 64, 17, 0)).items()}, 0, None, 0.0),
}


@pytest.fixture(scope="module")
def compared():
    """Each case run once: ``(reference loss, mask, grads), (loss, stats,
    grads), capacity`` of a row."""
    done = {}

    def run(name):
        if name not in done:
            make_batch, experts, forced, _ = CASES[name]
            task, batch = make_task(experts), make_batch()
            params = task.init_variables(jax.random.PRNGKey(0))["params"]
            with pytest.MonkeyPatch.context() as patch:
                if forced is not None:
                    patch.setattr(tasks, "_mlm_head_capacity",
                                  lambda seq_len: forced)
                (want, mlm_mask), want_grads = jax.jit(jax.value_and_grad(
                    lambda p: full_logits_loss(task, p, batch, RNG, experts),
                    has_aux=True))(params)
                (got, stats), got_grads = jax.jit(jax.value_and_grad(
                    lambda p: step_loss(task, p, batch, RNG),
                    has_aux=True))(params)
            done[name] = ((want, mlm_mask, want_grads),
                          (got, stats, got_grads),
                          forced or tasks._mlm_head_capacity(
                              batch["input_ids"].shape[1]))
        return done[name]

    return run


def test_capacity_is_a_quarter_of_the_row_in_multiples_of_128():
    law = tasks._mlm_head_capacity
    assert [law(s) for s in (32, 128, 256, 512, 640, 1024, 4096)] == [
        32, 128, 128, 128, 256, 256, 1024]


@pytest.mark.parametrize("name", sorted(CASES))
def test_loss_equals_the_full_logits_path(compared, name):
    (want, _, _), (got, _, _), _ = compared(name)
    assert np.isfinite(float(got))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    if name == "no_masked_position":
        assert float(got) == 0.0


@pytest.mark.parametrize("name", sorted(CASES))
def test_every_gradient_equals_the_full_logits_path(compared, name):
    (_, _, want), (_, _, got), _ = compared(name)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        g = got
        for key in path:
            g = g[key.key]
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-5 * scale + 1e-12,
            err_msg=jax.tree_util.keystr(path))
    if name != "no_masked_position":  # the head's gradient is really there
        assert float(jnp.abs(got["tok_embed"]["embedding"]).max()) > 0


@pytest.mark.parametrize("name", sorted(CASES))
def test_stats_read_what_the_batch_held(compared, name):
    (_, mlm_mask, _), (_, stats, _), capacity = compared(name)
    assert set(stats) == {"mlm_selected_tokens_total",
                          "mlm_head_fallback_total",
                          "mlm_head_capacity_tokens", "mlm_head_fill_pct"}
    held = int(mlm_mask.sum())
    assert float(stats["mlm_selected_tokens_total"]) == held
    assert float(stats["mlm_head_capacity_tokens"]) == ROWS * capacity
    np.testing.assert_allclose(float(stats["mlm_head_fill_pct"]),
                               100.0 * held / (ROWS * capacity), rtol=1e-6)
    overflows = float((mlm_mask.sum(-1) > capacity).any())
    assert float(stats["mlm_head_fallback_total"]) == overflows
    expected = CASES[name][3]
    if expected is not None:
        assert overflows == expected


@pytest.mark.parametrize("train,rng", [(False, None), (True, None)],
                         ids=["eval", "train_without_rng"])
def test_forward_without_a_masking_rng_keeps_full_logits(train, rng):
    task, batch = make_task(), padded_batch()
    variables = task.init_variables(jax.random.PRNGKey(0))
    (logits, mlm_mask, _), _ = task.forward(variables, batch, train, rng)
    assert logits.shape == (ROWS, SEQ, VOCAB) and logits.dtype == jnp.float32
    assert mlm_mask.shape == (ROWS, SEQ)
    assert task.metric((logits, mlm_mask, 0.0), batch).shape == (ROWS,)
    assert task.stats((logits, mlm_mask, 0.0)) == {}  # no gathered head ran
    assert np.isfinite(float(task.loss((logits, mlm_mask, 0.0), batch)))


def test_the_two_halves_of_the_encoder_are_the_whole():
    """``return_hidden`` then ``hidden=`` is ``__call__``: one module, the
    same parameters under the same names."""
    task, batch = make_task(), padded_batch()
    variables = task.init_variables(jax.random.PRNGKey(0))
    ids, mask = batch["input_ids"], batch["attention_mask"]
    whole = task.model.apply(variables, ids, mask, train=False)
    hidden = task.model.apply(variables, ids, mask, train=False,
                              return_hidden=True)
    assert hidden.shape == (ROWS, SEQ, 256)
    head_only = {"params": {"tok_embed": variables["params"]["tok_embed"]}}
    again = task.model.apply(head_only, None, hidden=hidden[:, :7])
    np.testing.assert_allclose(np.asarray(again), np.asarray(whole[:, :7]),
                               rtol=1e-5, atol=1e-5)
    assert sorted(variables["params"]) == sorted(
        ["tok_embed", "pos_embed", "ln_final", "layer_0", "layer_1"])


COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "collective-permute",
               "reduce-scatter", "collective-broadcast")


def collectives_of(text):
    """Every collective of a compiled program as ``{computation: [(kind,
    elements)]}``, and the names of the computations that are a
    ``conditional``'s branches."""
    found, branches, computation = {}, set(), None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", line)
        if m:
            computation = m.group(1)
        if " conditional(" in line:
            branches.update(re.findall(
                r"(?:true|false)_computation=%?([\w.\-]+)", line))
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                branches.update(n.strip().lstrip("%")
                                for n in group.split(","))
        m = re.search(r"= (.+?) (%s)(-start)?\(" % "|".join(COLLECTIVES),
                      line)
        if m:
            sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
                     for dims in re.findall(r"\[([\d,]*)\]", m.group(1))]
            found.setdefault(computation, []).append((m.group(2), sum(sizes)))
    return found, branches


def sharded_step(mesh, rules=(), batch_spec=None, attention_fn=None,
                 fsdp_axis=None):
    """One SGD step (lr 1, so a parameter moves by its gradient) of a
    one-layer model on 16 padded rows of 512 over ``mesh``: the compiled
    text, the loss, the stats and every parameter's change."""
    from lance_distributed_training_tpu import trainer
    from lance_distributed_training_tpu.parallel import make_global_batch

    task = get_task("masked_lm", model_name="bert_small", seq_len=SEQ,
                    vocab_size=VOCAB, num_layers=1, attention_fn=attention_fn)
    config = trainer.TrainConfig(dataset_path="unused", lr=1.0, momentum=0.0)
    state, sharding = trainer.create_sharded_train_state(
        jax.random.PRNGKey(0), task, config, mesh, rules, fsdp_axis=fsdp_axis)
    step = trainer.make_train_step(task, mesh, state_sharding=sharding,
                                   batch_spec=batch_spec, donate=False,
                                   stats=True)
    gen = np.random.default_rng(0)
    live = np.arange(SEQ)[None, :] < gen.integers(100, SEQ + 1, 16)[:, None]
    ids = np.where(live, gen.integers(2, VOCAB, (16, SEQ)), 0)
    batch = make_global_batch(
        {"input_ids": ids.astype(np.int32),
         "attention_mask": live.astype(np.int8)}, mesh,
        seq_axis="seq" if batch_spec is not None else None)
    text = step.lower(state, batch, RNG).compile().as_text()
    new_state, loss, stats = step(state, batch, RNG)
    moved = jax.tree.map(lambda new, old: np.asarray(new) - np.asarray(old),
                         new_state.params, state.params)
    return text, float(loss), jax.tree.map(float, stats), moved


@pytest.fixture(scope="module")
def data_parallel_step():
    from lance_distributed_training_tpu.parallel import get_mesh

    assert len(jax.devices()) == 8
    return sharded_step(get_mesh())


def test_over_the_mesh_the_gathered_head_adds_no_collective(
        data_parallel_step):
    """Per row, so the batch axis stays sharded: the step reduces gradients
    and scalars (the loss, the overflow flag, the counts) and moves no
    hidden state between devices. The tied head's gradient (with the loss
    beside it) is reduced inside the branch taken, so once in each branch's
    text; every parameter's gradient once outside, the table's share from
    the embedding lookup among them, as on the full path of before the
    change, which reduced the head's product apart from the lookup's too."""
    text, _, stats, moved = data_parallel_step
    found, branches = collectives_of(text)
    assert len(branches) == 2 and stats["mlm_head_fallback_total"] == 0.0
    kinds = {kind for cs in found.values() for kind, _ in cs}
    assert kinds == {"all-reduce"}, found
    head = VOCAB * 256 + 1
    for branch in branches:
        assert found[branch] == [("all-reduce", head)]
    outside = [n for name, cs in found.items() if name not in branches
               for _, n in cs if n > 16]
    assert outside == [sum(leaf.size for leaf in jax.tree.leaves(moved))]


def _tensor_parallel():
    from lance_distributed_training_tpu.parallel import get_mesh
    from lance_distributed_training_tpu.parallel.sharding import (
        TRANSFORMER_RULES,
    )

    return dict(mesh=get_mesh(model_parallelism=2), rules=TRANSFORMER_RULES)


def _tensor_and_sequence_parallel():
    from lance_distributed_training_tpu.parallel import get_mesh
    from lance_distributed_training_tpu.parallel.ring_attention import (
        make_ring_attention,
    )
    from lance_distributed_training_tpu.parallel.sharding import (
        TRANSFORMER_RULES,
        batch_partition_spec,
    )

    mesh = get_mesh(model_parallelism=2, seq_parallelism=2)
    return dict(mesh=mesh, rules=TRANSFORMER_RULES,
                batch_spec=batch_partition_spec(2, seq_axis="seq"),
                attention_fn=make_ring_attention(mesh))


def _fully_sharded():
    from lance_distributed_training_tpu.parallel import get_mesh

    return dict(mesh=get_mesh(), fsdp_axis="data")


# name: (the layout, the kinds of collective its conditional's branches may
# hold). The table is sharded over 'model' inside the differentiation rule;
# under 'seq' the per-row sort and both gathers run along a sharded axis.
LAYOUTS = {
    "dp4_tp2": (_tensor_parallel, {"all-reduce"}),
    "dp2_tp2_sp2": (_tensor_and_sequence_parallel,
                    {"all-reduce", "all-to-all", "collective-permute"}),
    "fsdp8": (_fully_sharded, {"all-reduce", "all-gather"}),
}


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_rows_of_512_on_a_sharded_mesh_equal_data_parallel(
        data_parallel_step, name):
    """The gathered branch itself (capacity 128 of 512, no overflow) under
    tensor, sequence and fully-sharded data parallelism: the loss and every
    parameter's gradient are the data-parallel step's, to what bf16
    activations allow between two layouts."""
    layout, allowed = LAYOUTS[name]
    _, want_loss, want_stats, want = data_parallel_step
    text, loss, stats, moved = sharded_step(**layout())
    assert stats == want_stats and stats["mlm_head_fallback_total"] == 0.0
    assert stats["mlm_head_capacity_tokens"] == 16 * 128
    np.testing.assert_allclose(loss, want_loss, rtol=1e-3)
    largest = max(float(np.abs(w).max()) for w in jax.tree.leaves(want))
    for path, w in jax.tree_util.tree_leaves_with_path(want):
        g = moved
        for key in path:
            g = g[key.key]
        np.testing.assert_allclose(
            g, w, rtol=0, atol=4e-2 * float(np.abs(w).max()) + 1e-3 * largest,
            err_msg=jax.tree_util.keystr(path))
    found, branches = collectives_of(text)
    assert len(branches) == 2
    inside = {kind for b in branches for kind, _ in found.get(b, ())}
    assert inside <= allowed, found


STEP_TASKS = {
    # name: (the task, a batch, the names its stats come under)
    "masked_lm": (
        lambda: get_task("masked_lm", model_name="bert_small", seq_len=16,
                         vocab_size=VOCAB, num_layers=1),
        {"input_ids": jax.ShapeDtypeStruct((8, 16), jnp.int32),
         "attention_mask": jax.ShapeDtypeStruct((8, 16), jnp.int8)},
        {"mlm_selected_tokens_total", "mlm_head_fallback_total",
         "mlm_head_capacity_tokens", "mlm_head_fill_pct"}),
    "causal_lm": (
        lambda: get_task("causal_lm", model_name="olmoe_1b_7b", seq_len=16,
                         vocab_size=VOCAB, num_layers=1),
        {"input_ids": jax.ShapeDtypeStruct((8, 16), jnp.int32),
         "attention_mask": jax.ShapeDtypeStruct((8, 16), jnp.int8)},
        {"moe_assignments_total", "moe_expert_load_max",
         "moe_expert_load_mean"}),
    "classification": (
        lambda: get_task("classification", num_classes=4,
                         model_name="resnet18", image_size=16),
        {"image": jax.ShapeDtypeStruct((8, 16, 16, 3), jnp.uint8),
         "label": jax.ShapeDtypeStruct((8,), jnp.int32)},
        set()),
}


@pytest.mark.parametrize("name", sorted(STEP_TASKS))
def test_what_the_step_returns_is_the_callers_choice(name):
    """``(state, loss)`` for every task, the gradient norm and the task's
    stats (none: an empty dictionary) only where the caller asks: a task
    that gains stats changes no caller's unpacking."""
    from lance_distributed_training_tpu import trainer
    from lance_distributed_training_tpu.parallel import get_mesh

    make_task, batch, names = STEP_TASKS[name]
    task, mesh = make_task(), get_mesh()
    state = jax.eval_shape(lambda r: trainer.create_train_state(
        r, task, trainer.TrainConfig(dataset_path="unused")), RNG)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)

    def returned(**asked):
        step = trainer.make_train_step(task, mesh, donate=False, **asked)
        return jax.eval_shape(step, state, batch, rng)

    new_state, loss = returned()
    assert loss.shape == () and jax.tree.structure(
        new_state) == jax.tree.structure(state)
    _, _, stats = returned(stats=True)
    assert set(stats) == names
    assert all(v.shape == () for v in stats.values())
    _, _, norm, stats = returned(grad_norm=True, stats=True)
    assert norm.shape == () and set(stats) == names
    assert len(returned(grad_norm=True)) == 3


def test_train_publishes_the_counters_and_the_gauge(tmp_path, monkeypatch):
    from lance_distributed_training_tpu import cli
    from lance_distributed_training_tpu.data import create_text_token_dataset
    from lance_distributed_training_tpu.obs.registry import default_registry

    rng = np.random.default_rng(0)
    docs = [rng.integers(2, 64, 256).tolist() for _ in range(40)]
    uri = str(tmp_path / "tok")
    create_text_token_dataset(uri, docs, seq_len=256, fragment_size=64)
    metrics_path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("LDT_METRICS_PATH", str(metrics_path))
    names = ("mlm_selected_tokens_total", "mlm_head_fallback_total")
    before = {n: getattr(default_registry().metrics().get(n), "value", 0.0)
              for n in names}
    cli.main([
        "train", "--dataset_path", uri, "--task_type", "masked_lm",
        "--model_name", "bert_small", "--num_layers", "1", "--seq_len", "256",
        "--vocab_size", "64", "--batch_size", "8", "--epochs", "1",
        "--max_steps", "4", "--log_every", "2", "--no_ddp", "--no_wandb",
        "--no_eval_at_end", "--no_autotune"])
    lines = [json.loads(line) for line in open(metrics_path)
             if '"images_per_sec_dispatch"' in line]
    assert len(lines) == 2
    registry = default_registry().metrics()
    # a row of 256 has room for 128 masked positions: 8 rows, 1,024 slots
    assert registry["mlm_head_capacity_tokens"].value == 8 * 128
    selected = registry["mlm_selected_tokens_total"].value - before[
        "mlm_selected_tokens_total"]
    # four steps of 8 x 256 real tokens at 15%: 1,229 on average, sd 32
    assert 1000 < selected < 1460
    assert registry["mlm_head_fallback_total"].value == before[
        "mlm_head_fallback_total"]
    for line in lines:  # the share of the capacity used rides the log line
        assert line["mlm_head_capacity_tokens"] == 8 * 128
        assert 20 < line["mlm_head_fill_pct"] < 40
        assert "mlm_selected_tokens_total" not in line
