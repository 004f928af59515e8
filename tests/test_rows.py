"""Rows -> tokens under a rank's share (``ops/rows.py``): each token the sum
of its live built rows, in f32.

*Is the kernel the plain form?* The Pallas kernel in interpret mode on one
device against ``sum_slots`` in f32, weighted into f32 and unweighted into
the rows' type, at 2, 6 and 10 slots a token, bf16 and f32 rows, with a token
of no live slot, a token of k live slots, positions past the built rows, a
last partial block of tokens and blocks no live row falls into. *Does the
rule choose as it says?* Off the TPU, under a mesh, at one slot a token, at a
list of more than half the assignments, at ragged shapes. *Does the layer
call it?* ``DroplessMoE`` under a share with the kernel bound against its
plain self, values and gradients.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lance_distributed_training_tpu.models import moe
from lance_distributed_training_tpu.ops import rows as ops


def _share(tokens, k, experts, held, width, dtype, seed, built=None):
    """The share path's integers as ``DroplessMoE`` makes them, for ids drawn
    without replacement: ``(rows [R, H], way, weights [T, k])``. Token 0
    chooses k held experts, token 1 none."""
    rng = np.random.default_rng(seed)
    top_e = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    top_e[0] = np.arange(k) % held
    top_e[1] = held + np.arange(k) % (experts - held)
    flat = np.where(top_e.reshape(-1) < held, top_e.reshape(-1), held)
    order = np.argsort(flat, kind="stable")
    inverse = np.zeros_like(order)
    inverse[order] = np.arange(tokens * k)
    live = int((flat < held).sum())
    built = built or min(-(-2 * tokens * k * held // (experts * 128)) * 128,
                         tokens * k)
    assert live <= built, (live, built)
    pos = inverse.reshape(tokens, k)  # the absent ones' lie past the built
    way = ops.Way(jnp.asarray(order[:built], jnp.int32),
                  jnp.arange(built) < live, jnp.asarray(pos, jnp.int32),
                  jnp.asarray(pos < live))
    assert way.valid[0].all() and not way.valid[1].any()
    assert int(way.pos.max()) >= built or built == tokens * k
    rows = jnp.asarray(rng.standard_normal((built, width)), dtype)
    return rows, way, jnp.asarray(rng.random((tokens, k)), jnp.float32)


def _kernel(rows, way, weights, **kwargs):
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        return jax.block_until_ready(
            ops.rows_kernel(rows, way, weights, **kwargs))


# slots a token (of 16, 16 and 64 experts, a quarter, a quarter and an eighth
# held), the rows' type, weighted into f32 or plain into the rows' type
CASES = list(itertools.product((2, 6, 10), (jnp.bfloat16, jnp.float32),
                               (True, False)))


@pytest.mark.parametrize(
    "k,dtype,weighted", CASES,
    ids=[f"k{k}-{jnp.dtype(d).name}-{'weighted' if w else 'plain'}"
         for k, d, w in CASES])
def test_the_kernel_in_interpret_mode_is_the_plain_form(k, dtype, weighted):
    experts, held = (64, 8) if k == 10 else (16, 4)
    # 200 tokens: a last block of 72 after one of 128
    rows, way, weights = _share(200, k, experts, held, 256, dtype, seed=k)
    weights = weights if weighted else None
    out = jnp.float32 if weighted else dtype
    want = ops.sum_slots(rows, way, weights)
    got = _kernel(rows, way, weights, dtype=out, block_t=128)
    assert got.dtype == out and got.shape == want.shape
    if not weighted:  # sums of at most k bf16 or f32 rows: the same sums
        np.testing.assert_array_equal(got, want.astype(out))
    else:  # three bf16 pieces of a weight add up to it: f32's own rounding
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    assert not np.asarray(got[1]).any()  # the token of no live slot


@pytest.mark.parametrize("block_t,block_r", [(8, 128), (64, 128), (256, 256),
                                             (512, 128)])
def test_the_kernel_at_other_blocks_and_a_list_with_empty_blocks(
        block_t, block_r):
    """Blocks of eight tokens (many hold no live row, and are zeros), blocks
    wider than the tokens, chunks of 256: the grid's tables at their edges."""
    rows, way, weights = _share(328, 6, 64, 4, 128, jnp.bfloat16, seed=3,
                                built=256)
    want = ops.sum_slots(rows, way, weights)
    got = _kernel(rows, way, weights, block_t=block_t, block_r=block_r)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


def test_a_list_with_no_live_row_and_one_that_is_all_live():
    rows, way, weights = _share(128, 2, 8, 4, 128, jnp.bfloat16, seed=5)
    none = way._replace(live=jnp.zeros_like(way.live),
                        valid=jnp.zeros_like(way.valid))
    assert not np.asarray(_kernel(rows, none, weights)).any()
    # every assignment held: row r stands for assignment r, all of them live
    each = jnp.arange(256, dtype=jnp.int32)
    every = ops.Way(each, jnp.ones_like(way.live), each.reshape(128, 2),
                    jnp.ones_like(way.valid))
    np.testing.assert_allclose(
        _kernel(rows, every, weights), ops.sum_slots(rows, every, weights),
        rtol=2e-6, atol=2e-6)


def test_dead_rows_that_are_not_finite_reach_no_token():
    """The plain form masks a dead row away; the kernel never multiplies
    one by a zero."""
    rows, way, weights = _share(200, 6, 16, 4, 128, jnp.float32, seed=7)
    rows = jnp.where(way.live[:, None], rows, jnp.nan)
    got = _kernel(rows, way, weights, block_t=64)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, ops.sum_slots(rows, way, weights),
                               rtol=2e-6, atol=2e-6)


def test_the_kernel_refuses_shapes_it_cannot_tile():
    rows, way, weights = _share(128, 2, 8, 4, 128, jnp.bfloat16, seed=1)
    with pytest.raises(ValueError, match="whole chunks"):
        ops.rows_kernel(rows[:100], way._replace(
            head=way.head[:100], live=way.live[:100]), weights)
    with pytest.raises(ValueError, match="whole chunks"):
        ops.rows_kernel(rows[:, :100], way, weights)
    with pytest.raises(ValueError, match="whole chunks"):
        ops.rows_kernel(rows.astype(jnp.float16), way, weights)


class _Mesh:
    def __init__(self, size):
        self.size = size


@pytest.mark.parametrize("says,args,kwargs", [
    # the three cells' calls: Qwen3-Next, SmallThinker, Moonlight
    (True, (16384, 20480, 2048, 10), {}),
    (True, (16384, 49152, 2560, 6), {}),
    (True, (8192, 12288, 2048, 6), {}),
    (True, (8192, 12288, 2048, 6), {"mesh": _Mesh(1)}),
    # off the TPU; under a mesh (a 'model' axis over the rows among them)
    (False, (16384, 20480, 2048, 10), {"platform": "cpu"}),
    (False, (16384, 20480, 2048, 10), {"mesh": _Mesh(4)}),
    # one expert a token (ZAYA1: one gather, no loop)
    (False, (8192, 8192, 2048, 1), {}),
    # the worst-case list, every assignment: a second copy of itself
    (False, (16384, 98304, 2560, 6), {}),
    (False, (8192, 49152, 2048, 6), {}),
    # ragged: rows, width, tokens
    (False, (16384, 20400, 2048, 10), {}),
    (False, (16384, 20480, 2000, 10), {}),
    (False, (16380, 20480, 2048, 10), {}),
], ids=["qwen3_next", "smallthinker", "moonlight", "mesh_of_one", "off_tpu",
        "mesh", "one_slot", "worst_case_smallthinker", "worst_case_moonlight",
        "ragged_rows", "ragged_width", "ragged_tokens"])
def test_the_rule(says, args, kwargs, monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    kwargs = {"platform": "tpu", **kwargs}
    assert ops.rows_sum_applies(*args, **kwargs) is says
    if says:  # several devices and no mesh: the plain form
        monkeypatch.setattr(jax, "device_count", lambda *a: 4)
        assert ops.rows_sum_applies(*args, **kwargs) is False


def test_off_the_tpu_the_chooser_is_the_plain_form_and_the_gauge_stays():
    from lance_distributed_training_tpu.obs.registry import default_registry

    assert ops.rows_sum_applies(16384, 20480, 2048, 10) is False  # a CPU
    default_registry().gauge("rows_sum_fused").set(0.0)
    rows, way, weights = _share(128, 2, 8, 4, 128, jnp.bfloat16, seed=2)
    np.testing.assert_array_equal(ops.sum_rows(rows, way, weights),
                                  ops.sum_slots(rows, way, weights))
    got = ops.sum_rows(rows, way, dtype=rows.dtype)
    assert got.dtype == rows.dtype
    np.testing.assert_array_equal(got, ops.sum_slots(rows, way).astype(
        rows.dtype))
    assert default_registry().gauge("rows_sum_fused").value == 0.0


def _bound(monkeypatch, block_t=64):
    """``sum_rows`` choosing the kernel (in interpret mode) wherever its
    shapes allow, as on one TPU device."""
    from jax.experimental.pallas import tpu as pltpu

    rule = ops.rows_sum_applies
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    monkeypatch.setattr(
        ops, "rows_sum_applies",
        lambda tokens, rows, width, k: rule(tokens, rows, width, k,
                                            platform="tpu"))
    monkeypatch.setattr(ops, "BLOCK_T", block_t)
    return pltpu.force_tpu_interpret_mode()


@pytest.mark.parametrize("k,experts,held", [(2, 16, 4), (6, 64, 8)])
def test_the_layer_under_a_share_with_the_kernel_bound_is_its_plain_self(
        k, experts, held, monkeypatch):
    """``DroplessMoE`` holding a share, f32 throughout: the output and the
    gradients to the input, the router and the three expert matrices with
    ``sum_rows`` running the kernel (forward, recomputed forward and the
    cotangent of tokens -> rows) against the same layer on the plain form."""
    from lance_distributed_training_tpu.obs.registry import default_registry

    layer = moe.DroplessMoE(experts, 32, k, dtype=jnp.float32,
                            first_expert=held, held_experts=held)
    x = jax.random.normal(jax.random.key(0), (2, 128, 128))
    ct = jax.random.normal(jax.random.key(1), x.shape)
    variables = layer.init(jax.random.key(2), x)

    def run(params, x):
        y, sown = layer.apply({"params": params}, x,
                              mutable=["aux_loss", "moe_stats"])
        return (y * ct).sum(), (y, sown["moe_stats"]["over_usual"])

    step = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)
    (_, (want, over)), want_grads = step(variables["params"], x)
    assert float(over[0]) == 0.0  # the usual list: the kernel's branch
    default_registry().gauge("rows_sum_fused").set(0.0)
    with _bound(monkeypatch):
        (_, (got, _)), got_grads = step(variables["params"], x)
    assert default_registry().gauge("rows_sum_fused").value == 1.0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
