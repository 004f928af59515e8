"""Switch-MoE layer: routing/capacity semantics, expert-parallel sharding
over the 'model' axis, aux-loss plumbing, end-to-end training."""

import pytest
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from lance_distributed_training_tpu.models import get_task
from lance_distributed_training_tpu.models.moe import MoEMLP
from lance_distributed_training_tpu.parallel import get_mesh
from lance_distributed_training_tpu.parallel.sharding import (
    TRANSFORMER_RULES,
    partition_specs,
)

pytestmark = pytest.mark.slow  # heavy integration tier (see conftest); gate commits with -m fast

VOCAB, SEQ = 256, 16


def test_moe_forward_and_aux_loss():
    model = MoEMLP(num_experts=4, mlp_dim=32, capacity_factor=2.0,
                   dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 8, 16)),
                    jnp.float32)
    variables = {"params": model.init(jax.random.key(0), x)["params"]}
    y, sown = model.apply(variables, x, mutable=["aux_loss"])
    assert y.shape == x.shape
    (aux,) = jax.tree_util.tree_leaves(sown["aux_loss"])
    # Load-balance loss is ~1 for near-uniform routing, >=1 by Cauchy-Schwarz.
    assert float(aux) >= 0.99


def test_moe_capacity_drops_overflow():
    """With capacity_factor tiny, most tokens overflow → output ~zero rows
    (they pass through the residual in the encoder block)."""
    model = MoEMLP(num_experts=2, mlp_dim=8, capacity_factor=0.01,
                   dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 64, 16)),
                    jnp.float32)
    variables = {"params": model.init(jax.random.key(0), x)["params"]}
    y, _ = model.apply(variables, x, mutable=["aux_loss"])
    # capacity = max(1, int(0.01*64/2)) = 1 → at most 2 non-zero rows.
    nonzero_rows = int((np.abs(np.asarray(y[0])).sum(-1) > 1e-6).sum())
    assert nonzero_rows <= 2


def test_moe_params_shard_over_model_axis():
    task = get_task("masked_lm", model_name="bert_small", seq_len=SEQ,
                    vocab_size=VOCAB, num_experts=4)
    mesh = get_mesh(model_parallelism=2)
    variables = jax.eval_shape(task.init_variables, jax.random.key(0))
    specs = partition_specs(variables["params"], TRANSFORMER_RULES, mesh)
    # bert_small has 4 layers; moe_every=2 → layers 1 and 3 are MoE.
    moe = specs["layer_1"]["moe"]
    assert moe["w_in"] == P("model")
    assert moe["w_out"] == P("model")
    assert moe["b_in"] == P("model")
    assert moe["router"]["kernel"] == P()
    # Layer 0 stays dense.
    assert "moe" not in specs["layer_0"]
    assert specs["layer_0"]["mlp_in"]["kernel"] == P(None, "model")


def test_moe_train_step_on_tp_mesh():
    """One step of an expert-parallel masked-LM model on dp=4×tp=2; loss
    finite and includes the aux term."""
    from lance_distributed_training_tpu.parallel import make_global_batch
    from lance_distributed_training_tpu.trainer import (
        TrainConfig,
        create_sharded_train_state,
        make_train_step,
    )

    task = get_task("masked_lm", model_name="bert_small", seq_len=SEQ,
                    vocab_size=VOCAB, num_experts=4)
    mesh = get_mesh(model_parallelism=2)
    cfg = TrainConfig(dataset_path="", lr=0.1)
    state, sharding = create_sharded_train_state(
        jax.random.key(0), task, cfg, mesh, TRANSFORMER_RULES
    )
    step = make_train_step(task, mesh, state_sharding=sharding, donate=False)
    gen = np.random.default_rng(0)
    batch = make_global_batch(
        {
            "input_ids": gen.integers(2, VOCAB, (16, SEQ)).astype(np.int32),
            "attention_mask": np.ones((16, SEQ), np.int8),
        },
        mesh,
    )
    _, loss = step(state, batch, jax.random.key(1))
    assert np.isfinite(float(loss))


def test_moe_end_to_end_train(tmp_path):
    from lance_distributed_training_tpu.data import create_text_token_dataset
    from lance_distributed_training_tpu.trainer import TrainConfig, train

    gen = np.random.default_rng(0)
    docs = [gen.integers(2, VOCAB, 24).tolist() for _ in range(80)]
    uri = str(tmp_path / "tok")
    create_text_token_dataset(uri, docs, seq_len=SEQ, fragment_size=64)
    results = train(TrainConfig(
        dataset_path=uri, task_type="masked_lm", model_name="bert_small",
        vocab_size=VOCAB, seq_len=SEQ, batch_size=16, epochs=1,
        num_experts=2, model_parallelism=2, no_wandb=True, eval_at_end=False,
    ))
    assert np.isfinite(results["loss"])


def test_dropless_layer_with_the_grouped_kernels_bound_is_its_plain_self(
        monkeypatch):
    """``DroplessMoE`` holding all its experts (OLMoE's branch), f32: the
    three grouped products by ``ops/grouped.py``'s kernel form (the
    library's Pallas grouped matmul, interpret mode) against
    ``jax.lax.ragged_dot``, output and every gradient, and the gauge."""
    from jax.experimental.pallas import tpu as pltpu

    from lance_distributed_training_tpu.models.moe import DroplessMoE
    from lance_distributed_training_tpu.obs.registry import default_registry
    from lance_distributed_training_tpu.ops import grouped

    layer = DroplessMoE(8, 256, 2, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (1, 128, 128))
    variables = layer.init(jax.random.key(1), x)

    def run(params, x):
        y, _ = layer.apply({"params": params}, x,
                           mutable=["aux_loss", "moe_stats"])
        return (y * y).sum(), y

    gauge = default_registry().gauge("grouped_products_fused")
    gauge.set(0.0)
    step = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)
    want = jax.jit(step)(variables["params"], x)
    assert gauge.value == 0.0
    monkeypatch.setattr(grouped, "grouped_tiling", lambda *shape, **_:
                        grouped.Tiling(*((128, 128, 128),) * 3))
    with pltpu.force_tpu_interpret_mode():
        got = jax.block_until_ready(jax.jit(lambda p, x: step(p, x))(
            variables["params"], x))
    assert gauge.value == 1.0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
