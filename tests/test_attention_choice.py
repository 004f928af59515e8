"""The attention path a sequence model gets when nobody chose one
(``ops/flash.py``: ``fused_attention_applies``, ``make_flash_attention(
forced=False)``, bound by ``get_task``), and the short-sequence kernel it
runs up to 1,024 tokens (``short_attention``).

On the CPU the rule keeps dense attention, so the kernel's side is built
under a stubbed platform and run in TPU interpret mode; each such call is one
jitted program that is waited for (``tests/test_olmoe.py`` tells why). All
comparisons are float32 against float32: the two paths differ in the order
of their sums only.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lance_distributed_training_tpu.models import get_task, tasks
from lance_distributed_training_tpu.models.transformer import (
    bert_small,
    dot_product_attention,
)
from lance_distributed_training_tpu.ops import flash
from lance_distributed_training_tpu.parallel import get_mesh

F32_TOL = 2e-4  # summation order only (measured 1e-7 to 2e-6)
SEQ, ROWS, VOCAB = 256, 4, 512
RNG = jax.random.PRNGKey(29)


def _one_program(fn, *args):
    return jax.block_until_ready(jax.jit(fn)(*args))


def _meshes():
    return {"none": None,
            "one device": get_mesh(jax.devices()[:1]),
            "data": get_mesh(jax.devices()[:4]),
            "data x model": get_mesh(jax.devices()[:4], model_parallelism=2)}


# -- the rule ----------------------------------------------------------------

RULE = [
    # platform, mesh, seq, head width, fused?
    ("cpu", "one device", 512, 64, False),  # every tier-1 test
    ("tpu", "one device", 512, 64, True),  # the BERT cells
    ("tpu", "one device", 4096, 128, True),  # OLMoE's shapes, no flag
    ("tpu", "data", 512, 64, True),  # four chips: shard_map over 'data'
    ("tpu", "one device", 256, 64, True),  # the lower edge, as timed:
    ("tpu", "one device", 128, 64, False),  # here dense is level with it
    ("tpu", "one device", 64, 64, False),  # under one block of keys
    ("tpu", "one device", 197, 64, False),  # ViT-B/16: not whole blocks
    ("tpu", "one device", 77, 64, False),  # CLIP's text tower
    ("tpu", "one device", 512, 48, False),  # a head that fills no half tile
    ("tpu", "data x model", 512, 64, False),  # 'model' has met no chip
    ("tpu", "none", 512, 64, False),  # eight devices, nobody said how split
    ("gpu", "one device", 512, 64, False),
]


@pytest.mark.parametrize("platform,mesh,seq,head_dim,fused", RULE)
def test_the_rule(platform, mesh, seq, head_dim, fused):
    assert flash.fused_attention_applies(
        seq, head_dim, _meshes()[mesh], platform) is fused


@pytest.mark.parametrize("seq,head_dim,value_dim,fused", [
    (8192, 192, 128, True),  # latent attention: Moonlight's cell
    (8192, 64, 128, True),  # differential attention, window, full and cross
    (8192, 64, 96, False),  # a value that fills no half tile
    (8192, 48, 128, False),
    (200, 64, 128, False),  # not whole blocks
])
def test_the_rule_for_values_of_another_width(seq, head_dim, value_dim,
                                              fused):
    assert flash.fused_attention_applies(
        seq, head_dim, _meshes()["one device"], "tpu", value_dim) is fused


def test_without_a_mesh_one_device_is_fused(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert flash.fused_attention_applies(512, 64, None, "tpu")


@pytest.mark.parametrize("forced", [True, False])
def test_the_flag_still_forces(monkeypatch, forced):
    """``--flash_attention`` is the kernel whatever the shapes (or the
    kernel's own error); without it the same shapes stay dense."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    attention = flash.make_flash_attention(
        mesh=_meshes()["one device"], forced=forced)
    assert attention.fused(197, 64) is forced
    assert attention.fused(512, 64) is True
    q = jnp.zeros((1, 2, 197, 64), jnp.float32)
    traced = str(jax.make_jaxpr(attention)(q, q, q))
    assert ("pallas_call" in traced) is forced  # the chip's compiler judges


def test_off_the_tpu_nothing_is_fused_flag_or_not():
    for forced in (True, False):
        attention = flash.make_flash_attention(forced=forced)
        assert attention.fused(512, 64) is False


@pytest.mark.parametrize("task_type,model,causal", [
    ("masked_lm", "bert_small", False),
    ("causal_lm", "gpt_small", True),
    ("causal_lm", "olmoe_tiny", True),
])
def test_get_task_binds_the_choice_and_carries_causality(
        monkeypatch, task_type, model, causal):
    seen = {}
    make = flash.make_flash_attention

    def spy(**kwargs):
        seen.update(kwargs)
        return make(**kwargs)

    monkeypatch.setattr(flash, "make_flash_attention", spy)
    mesh = _meshes()["one device"]
    task = get_task(task_type, model_name=model, seq_len=SEQ, mesh=mesh)
    assert seen == {"causal": causal, "mesh": mesh, "forced": False}
    assert task.model.attention_fn.fused(SEQ, 64) is False  # the CPU


def test_a_chosen_attention_function_is_left_alone(monkeypatch):
    monkeypatch.setattr(flash, "make_flash_attention", lambda **_: 1 / 0)
    given = functools.partial(dot_product_attention, dtype=jnp.float32)
    task = get_task("masked_lm", model_name="bert_small", seq_len=SEQ,
                    attention_fn=given)
    assert task.model.attention_fn is given
    get_task("classification", model_name="resnet18", num_classes=10)


# -- the kernel against the dense function -----------------------------------


def _segments(seq):
    pos = np.arange(seq)
    ids = np.where(pos < seq // 3, 1, np.where(pos < seq - seq // 5, 2, 0))
    return jnp.asarray(np.stack([ids, np.ones(seq, np.int64)]), jnp.int32)


@pytest.mark.parametrize("block_q", [256, 128])  # one block; accumulated
@pytest.mark.parametrize("ids", [True, False])
@pytest.mark.parametrize("causal", [False, True])
def test_short_attention_forward_and_backward_equal_dense(
        causal, ids, block_q):
    from jax.experimental.pallas import tpu as pltpu

    shape = (2, 2, 256, 64)
    q, k, v, w = (jax.random.normal(key, shape, jnp.float32)
                  for key in jax.random.split(jax.random.key(0), 4))
    seg = _segments(shape[2]) if ids else None
    mask = flash.segment_attention_mask(seg) if ids else None
    live = ((seg > 0)[:, None, :, None] if ids
            else jnp.ones((2, 1, 256, 1), bool))  # dead queries mean nothing

    def dense(q, k, v):
        return jnp.where(live, dot_product_attention(
            q, k, v, mask=mask, dtype=jnp.float32, causal=causal), 0)

    def kernel(q, k, v):
        return jnp.where(live, flash.short_attention(
            q, k, v, seg, causal=causal, block_q=block_q), 0)

    def both(q, k, v):
        return [(fn(q, k, v), jax.grad(lambda *a: (fn(*a) * w).sum(),
                                       argnums=(0, 1, 2))(q, k, v))
                for fn in (dense, kernel)]

    with pltpu.force_tpu_interpret_mode():
        (want, want_grads), (got, got_grads) = _one_program(both, q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-6)
    for g, wnt in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, wnt, atol=2e-5, rtol=1e-5)


def test_short_attention_refuses_what_it_cannot_tile():
    q = jnp.zeros((1, 2, 200, 64), jnp.float32)
    with pytest.raises(ValueError, match="multiples of 128"):
        jax.eval_shape(flash.short_attention, q, q, q)


# -- the chip's compiler, without the chip -----------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e host (a compile-only client: nothing runs
    and no backend is registered). Described inside the fixture, never at
    import: only the worker that is given this file loads the library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("TPU_LOG_DIR", "disabled")  # or it logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu here, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape,causal", [
    ((32, 12, 512, 64), False),  # c4-bert-prepacked's step
    ((24, 12, 512, 64), False),  # c4-bert-ragged's larger grid
    ((16, 12, 1024, 64), False),  # two query blocks: the f32 scratch
    ((8, 16, 1024, 128), True),  # heads of a whole tile, causal
    ((64, 12, 256, 64), True),  # the rule's lower edge
])
def test_short_attention_compiles_for_a_v5e_at_real_widths(one_chip, shape,
                                                           causal):
    """Mosaic's own checks (tiling, lane slices of a head, VMEM) on the
    forward and the backward kernel, which interpret mode does not make."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((shape[0], shape[2]), jnp.int32,
                               sharding=one_chip)

    def loss(q, k, v, ids):
        return flash.short_attention(q, k, v, ids, causal=causal).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, ids).compile()  # the gradient alone needs no forward
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("rows,with_ids", [(1, True), (2, False)])
def test_unequal_attention_compiles_for_a_v5e_at_moonlights_widths(
        one_chip, rows, with_ids):
    """Latent attention's heads as the Moonlight cell runs them: 16 heads,
    8,192 tokens, queries and keys of 192, values of 128, unpadded, at the
    tiling the function chooses for these shapes (one the chip timed): the
    forward kernel and the backward's, with and without segment ids, and no
    ``[B, H, S, S]`` tensor anywhere in the program."""
    qk = jax.ShapeDtypeStruct((rows, 16, 8192, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((rows, 16, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    ids = jax.ShapeDtypeStruct((rows, 8192), jnp.int32, sharding=one_chip)
    tiling, timed = flash.splash_tiling(8192, 192, 128, 16, True)
    assert timed

    def loss(q, k, v, ids):
        out = flash.unequal_attention(q, k, v, ids if with_ids else None,
                                      causal=True)
        assert out.shape == v.shape
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v, ids).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= (3 if tiling.dq else 2) * rows
    assert "8192,8192]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("window", [512, 0])
def test_unequal_attention_compiles_for_a_v5e_at_phi4_flashs_widths(
        one_chip, window):
    """Differential attention's heads as the Phi-4-mini-flash cell runs
    them: 40 query heads of 64 over 20 key heads and 10 values of 128, 8,192
    tokens, in a band of 512 (S) and causal (F*, X), each at the tiling the
    function chooses for it (timed, and not the same): no ``[B, H, S, S]``
    tensor, and the band's block tables are its own."""
    def spec(heads, width):
        return jax.ShapeDtypeStruct((1, heads, 8192, width), jnp.bfloat16,
                                    sharding=one_chip)

    tiling, timed = flash.splash_tiling(8192, 64, 128, 40, True, window)
    assert timed

    def loss(q, k, v):
        out = flash.unequal_attention(q, k, v, causal=True, window=window)
        assert out.shape == (1, 40, 8192, 128)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        spec(40, 64), spec(20, 64), spec(10, 128)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= (3 if tiling.dq else 2)
    assert "8192,8192]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    shape = flash._Shape(8192, 64, 128, 40, True, window)
    assert flash._splash_kernel(shape) is flash._splash_kernel(shape)
    assert flash._splash_kernel(shape) is not flash._splash_kernel(
        shape._replace(window=512 - window))


@pytest.mark.parametrize("heads,groups,width,scale,is_timed,window", [
    (8, 2, 128, 0.0, True, 0),  # c4-zaya1-ep2-prepacked-8k's
    # c4-granite4h-vp8-prepacked-8k's, scores / 64; no sweep has timed it
    (32, 8, 64, 0.015625, False, 0),
    # c4-laguna-ep32-prepacked-8k's: the window layers' nine query heads a
    # key head in a band of 512, the full layers' six over the causal row
    (72, 8, 128, 0.0, True, 512),
    (48, 8, 128, 0.0, True, 0),
], ids=["zaya1", "granite4h", "laguna_window", "laguna_full"])
def test_unequal_attention_compiles_for_a_v5e_at_grouped_heads_of_equal_widths(
        one_chip, heads, groups, width, scale, is_timed, window):
    """Heads in groups whose values are as wide as their keys, 8,192 tokens,
    causal, at the tiling timed for the shape: compressed convolutional
    attention's 8 query heads over 2 key and value heads of 128 as the ZAYA1
    cell runs them, grouped attention's 32 over 8 of 64 as the Granite
    cell does (at the rule's square 512s: attention is 3.4% of that cell's
    work and no sweep has timed its shape), its score scale folded into the
    queries as ``GroupedAttention`` folds it (a power of two: no second
    rounding), and Laguna's 72 over 8 of 128 in a band of 512 and 48 over 8
    causal. The three kernels take the keys and values in their own heads
    (Mosaic's checks on the index maps that find a block's key head and on
    dkv's scratch kept over a group), no ``[B, H, S, S]`` tensor, and the
    cotangents of keys and values come back in the heads they went in."""
    def spec(heads):
        return jax.ShapeDtypeStruct((1, heads, 8192, width), jnp.bfloat16,
                                    sharding=one_chip)

    tiling, timed = flash.splash_tiling(8192, width, width, heads, True,
                                        window)
    assert timed is is_timed and tiling.dq is not None

    def loss(q, k, v):
        if scale:
            q = q * (scale * width ** 0.5)
        out = flash.unequal_attention(q, k, v, causal=True, window=window)
        assert out.shape == (1, heads, 8192, width)
        return out.astype(jnp.float32).sum()

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        spec(heads), spec(groups), spec(groups))
    _, (_, dk, dv) = lowered.out_info
    assert dk.shape == dv.shape == (1, groups, 8192, width)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert "8192,8192]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_unequal_attention_compiles_for_a_v5e_at_qwen3_nexts_widths(
        one_chip):
    """Gated attention's heads as the Qwen3-Next cell runs them: 16 query
    heads over 2 key and value heads, all 256 wide, 8,192 tokens, causal, at
    the tiling timed for the shape: the three kernels, and no ``[B, H, S,
    S]`` tensor."""
    def spec(heads):
        return jax.ShapeDtypeStruct((1, heads, 8192, 256), jnp.bfloat16,
                                    sharding=one_chip)

    tiling, timed = flash.splash_tiling(8192, 256, 256, 16, True)
    assert timed and tiling.dq is not None

    def loss(q, k, v):
        out = flash.unequal_attention(q, k, v, causal=True)
        assert out.shape == (1, 16, 8192, 256)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        spec(16), spec(2), spec(2)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert "8192,8192]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("window", [0, 4096], ids=["full", "window"])
def test_unequal_attention_compiles_for_a_v5e_at_smallthinkers_widths(
        one_chip, window):
    """Grouped attention's heads as the SmallThinker cell runs them: 28 query
    heads over 4 key and value heads of 128 at a 16,384-token row, once over
    the whole causal row and once in a band of 4,096, each at the tiling
    timed for its shape: the three kernels, and no ``[B, H, S, S]``
    tensor."""
    def spec(heads):
        return jax.ShapeDtypeStruct((1, heads, 16384, 128), jnp.bfloat16,
                                    sharding=one_chip)

    tiling, timed = flash.splash_tiling(16384, 128, 128, 28, True, window)
    assert timed and tiling.dq is not None

    def loss(q, k, v):
        out = flash.unequal_attention(q, k, v, causal=True, window=window)
        assert out.shape == (1, 28, 16384, 128)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        spec(28), spec(4), spec(4)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    assert "16384,16384]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_the_delta_rules_kernel_compiles_for_a_v5e_at_qwen3_nexts_widths(
        one_chip):
    """A Gated DeltaNet's rule as the Qwen3-Next cell calls it (two rows of
    8,192 tokens, 16 key heads serving 32 value heads of 128, every head in
    one call), forward and backward: the three kernels (the forward, the
    forward again for what the backward pass starts from, the backward), and
    outside them nothing of a chunk's preparation. XLA held the decay mask, ``A``, its
    inverse and the steps of the inversion in float32, ``[.., 64, 64]`` a
    chunk and head (``[.., 8192, 64]`` a head), 3.5 GiB a row at these
    widths and a group of eight heads at a time for it; the kernels make
    them in VMEM, and what they keep for the backward pass (a chunk's
    starting state, two chunks' inverses side by side) leaves the program's
    temporaries under the limit one group of eight had."""
    from lance_distributed_training_tpu.ops import delta

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, g, beta):
        o, last = delta.delta_kernel(q, k, v, g, beta)
        assert o.shape == v.shape and last.shape == (2, 32, 128, 128)
        return o.astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=range(5))).lower(
        spec(2, 8192, 16, 128), spec(2, 8192, 16, 128),
        spec(2, 8192, 32, 128), spec(2, 8192, 32, dtype=jnp.float32),
        spec(2, 8192, 32, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert "while(" not in text  # no loop over groups of heads
    assert not re.search(r"f32\[[0-9,]*(64,64|8192,64)\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 7 << 27


def test_the_delta_rule_norms_in_its_kernels_for_a_v5e_at_qwen3_nexts_widths(
        one_chip):
    """The rule as the Qwen3-Next cell calls it since PR 48: ``q``, ``k``
    and ``v`` read off the convolved projection ``[2, 8192, 8192]`` where
    they lie, the unit norms of ``q`` and ``k`` made by the three kernels.
    No slice of the projection is written out in front of them, nothing of
    ``[8192, 2048]`` exists in f32 (the plain norm kept both of ``q`` and
    ``k`` so), and beside what the kernels keep for the backward pass the
    program holds the three cotangents and their concatenation."""
    from lance_distributed_training_tpu.ops import delta

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(qkv, g, beta):
        o, last = delta.delta_kernel_packed(qkv, g, beta, key_heads=16,
                                            key_dim=128, qk_norm=True)
        assert o.shape == (2, 8192, 32, 128) and o.dtype == jnp.bfloat16
        return o.astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=range(3))).lower(
        spec(2, 8192, 8192), spec(2, 8192, 32, dtype=jnp.float32),
        spec(2, 8192, 32, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert all(line.count("bf16[2,8192,8192]{2,1,0}") == 3 for line in calls)
    assert not re.search(r"bf16\[2,8192,\d+\]\S* slice\(", text)
    assert not re.search(r"f32\[2,8192,(2048|16,128)\]", text)
    assert "rsqrt" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 7 << 27


def test_the_gated_norms_kernels_compile_for_a_v5e_at_qwen3_nexts_widths(
        one_chip):
    """The gated RMSNorm as the Qwen3-Next cell calls it, forward and
    backward: two kernels, the gate read from the last 4,096 of the fused
    projection's 12,288 columns where they lie, nothing of ``[8192, 4096]``
    kept in f32 (the plain form held ``o``, its norm and the gate so)."""
    from lance_distributed_training_tpu.ops import norm

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(o, z, scale, ct):
        y = norm.norm_kernel(o.reshape(2, 8192, 32, 128), z, scale)
        assert y.shape == (2, 8192, 4096) and y.dtype == jnp.bfloat16
        return (y * ct).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        spec(2, 8192, 4096), spec(2, 8192, 12288),
        spec(128, dtype=jnp.float32), spec(2, 8192, 4096)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert all("bf16[2,8192,12288]" in line for line in calls)
    assert not re.search(r"bf16\[2,8192,\d+\]\S* slice\(", text)
    # the output, its cotangent's product, do and dz before the unread
    # columns' zeros, each bf16
    assert compiled.memory_analysis().temp_size_in_bytes < 4.5 * (
        2 * 8192 * 4096 * 2)


def test_the_duals_kernels_compile_for_a_v5e_at_granites_widths(one_chip):
    """The state-space dual as the Granite cell calls it: one row of 8,192
    tokens, 64 heads of 64 over 128 states, ``x``, ``b`` and ``c`` read from
    the 4,352 convolved columns where they lie, forward and backward: two
    kernels, no slice of the projection in front of them, and nothing of
    ``[chunks, heads, 128, 128]`` in HBM (the plain form's decay masks: 268
    MB a layer in float32)."""
    from lance_distributed_training_tpu.ops import ssd

    def spec(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(xbc, dt, a, d, ct):
        y, last = ssd.ssd_kernel_packed(xbc, dt, a, d, head_dim=64)
        assert y.shape == (1, 8192, 64, 64) and y.dtype == jnp.bfloat16
        assert last.shape == (1, 64, 64, 128)
        return (y * ct).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).lower(
        spec(1, 8192, 4352, dtype=jnp.bfloat16), spec(1, 8192, 64), spec(64),
        spec(64), spec(1, 8192, 64, 64, dtype=jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    calls = [line for line in text.splitlines() if " custom-call(" in line]
    assert all("bf16[1,8192,4352]" in line for line in calls)
    assert not re.search(r"bf16\[1,8192,\d+\]\S* slice\(", text)
    assert not re.search(r"\[(1,)?64,64,128,128\]", text)
    # the state a chunk starts from, float32: 64 chunks of 2 MiB, and little
    # else (dx, db, dc and the per-token scalars' two layouts)
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * (
        64 * 64 * 64 * 128 * 4)


@pytest.mark.parametrize("rows,wide,width,has_bias", [
    (2, 12288, 8192, False),  # c4-qwen3next-ep16-prepacked-8k's projection
    (1, 10240, 5120, True),  # c4-phi4flash-vp8-prepacked-8k's
    (1, 8448, 4352, True),  # c4-granite4h-vp8-prepacked-8k's: 34 lane groups
])
def test_the_convolutions_kernels_compile_for_a_v5e_at_the_cells_widths(
        one_chip, rows, wide, width, has_bias):
    """The depthwise causal convolution and its SiLU as the two cells call
    them, forward and backward: two kernels, each reading the first columns
    of the fused projection where they lie. No slice of the projection is
    written out in front of them, and nothing of ``[8192, width]`` is kept
    in f32: the plain form's padded copy, its four shifted f32 products and their
    cotangents were some ten passes over HBM."""
    from lance_distributed_training_tpu.ops import conv

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, taps, bias, ct):
        y = conv.conv_kernel(x, taps, bias)
        assert y.shape == (rows, 8192, width) and y.dtype == jnp.bfloat16
        return (y * ct).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2)[:2 + has_bias])).lower(
        spec(rows, 8192, wide), spec(4, width, dtype=jnp.float32),
        spec(width, dtype=jnp.float32) if has_bias else None,
        spec(rows, 8192, width)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    calls = [line for line in text.splitlines() if " custom-call(" in line]
    assert all(f"bf16[{rows},8192,{wide}]" in line for line in calls)
    assert not re.search(rf"bf16\[{rows},8192,\d+\]\S* slice\(", text)
    # the output, its cotangent and dx before the unread columns' zeros, each
    # bf16; one f32 array of that shape would be two more
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5 * (
        rows * 8192 * width * 2)


@pytest.mark.parametrize("tokens,k,built,width", [
    (16384, 10, 20480, 2048),  # c4-qwen3next-ep16-prepacked-8k's usual list
    (16384, 6, 49152, 2560),  # c4-smallthinker-ep4-prepacked-16k's
    (8192, 6, 12288, 2048),  # c4-moonlight-ep8-prepacked-8k's
])
def test_the_rows_kernel_compiles_for_a_v5e_at_the_cells_shapes(
        one_chip, tokens, k, built, width):
    """Rows -> tokens as the three share cells call it, weighted into f32
    (the sum back) and plain into bf16 (the cotangent of tokens -> rows): one
    kernel each, no loop over the slots, nothing with a slot axis beside
    ``[T, H]``, and beside the rows in their tokens' order no scratch."""
    from lance_distributed_training_tpu.ops import rows as ops

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(rows, head, live, weights):
        way = ops.Way(head, live, jnp.zeros((tokens, k), jnp.int32),
                      jnp.zeros((tokens, k), bool))
        return (ops.rows_kernel(rows, way, weights),
                ops.rows_kernel(rows, way, dtype=rows.dtype))

    compiled = jax.jit(both).lower(
        spec(built, width), spec(built, dtype=jnp.int32),
        spec(built, dtype=jnp.bool_),
        spec(tokens, k, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "while(" not in text
    assert not re.search(rf"\[({k},{tokens}|{tokens},{k}),{width}\]", text)
    # the ordered rows, bf16, and nothing else of the rows' or tokens' size
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * (
        built * width * 2)


def _grouped_entries():
    from lance_distributed_training_tpu.ops import grouped

    return sorted(grouped.TILINGS.items())


@pytest.mark.parametrize("shape,tiling", _grouped_entries(),
                         ids=["x".join(map(str, s))
                              for s, _ in _grouped_entries()])
def test_the_grouped_products_kernels_compile_for_a_v5e_at_every_entry(
        one_chip, shape, tiling):
    """A grouped product and both its cotangents at each shape and tiling of
    ``ops/grouped.py``'s table, bf16 as the cells call it: three kernels
    (``gmm``, ``gmm`` against the transposed matrices, ``tgmm``) inside
    Mosaic's default VMEM, no ``ragged-dot`` and no copy of the matrices in
    another layout."""
    from lance_distributed_training_tpu.ops import grouped

    rows, groups, k, n = shape

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(xs, w, sizes, ct):
        y, back = jax.vjp(lambda xs, w: grouped.kernel_product(
            xs, w, sizes, tiling), xs, w)
        return y, *back(ct)

    compiled = jax.jit(both).lower(
        spec(rows, k), spec(groups, k, n), spec(groups, dtype=jnp.int32),
        spec(rows, n)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert "ragged-dot" not in text
    if n != k:  # a square expert's transpose has the matrices' own shape
        assert not re.search(rf"bf16\[{groups},{n},{k}\]", text)


@pytest.mark.parametrize("rows,seq,hidden,vocab,tied", [
    (1, 8192, 2560, 25008, True),  # c4-phi4flash-vp8-prepacked-8k's head
    (2, 4096, 2048, 50304, False),  # c4-olmoe-prepacked-4k's
])
def test_the_causal_loss_never_re_lays_the_logits_for_a_v5e(
        one_chip, rows, seq, hidden, vocab, tied):
    """The head's product, the causal task's own loss and their gradients at
    two cells' shapes: the logits are read on the grid the head wrote. A
    shift applied to them made ``[.., seq - 1, vocab]``, which XLA re-laid in
    two ``while`` loops of ``dynamic-update-slice`` where the vocabulary is no
    multiple of 128 (Phi-4's eighth, 25,008) and in a sliced copy where it is
    (OLMoE). The cotangent is still written once (``_last_position_unlearned``
    tells why)."""
    task = get_task("causal_lm", model_name="gpt_small", seq_len=16,
                    vocab_size=64)  # the loss knows no model

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, table, batch):
        logits = jnp.einsum("bsh,vh->bsv" if tied else "bsh,hv->bsv", x,
                            table.astype(x.dtype),
                            preferred_element_type=jnp.float32)
        return task.loss((logits, jnp.zeros((), jnp.float32)), batch)

    ids = spec((rows, seq), jnp.int32)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        spec((rows, seq, hidden), jnp.bfloat16),
        spec((vocab, hidden) if tied else (hidden, vocab), jnp.float32),
        {"input_ids": ids, "attention_mask": spec((rows, seq), jnp.int8),
         "segment_ids": ids}).compile().as_text()
    assert " while(" not in text
    # the soft-max's exponentials are taken for its sum and for the one
    # cotangent, not once more inside each of the head's backward products
    assert text.count(" exponential(") == 2
    assert not re.search(rf"\[[0-9,]*\b({seq - 1},{vocab}|{vocab},{seq - 1})\b",
                         text)


# -- the tiling a call's shapes get -------------------------------------------

CELL_SHAPES = [  # seq, d_qk, d_v, heads, causal, window
    (8192, 192, 128, 16, True, 0),  # Moonlight's latent attention
    (8192, 64, 128, 40, True, 0),  # Phi-4-mini-flash, F* and X
    (8192, 64, 128, 40, True, 512),  # Phi-4-mini-flash, S
    (8192, 128, 128, 8, True, 0),  # ZAYA1's heads in groups, equal widths
    (16384, 128, 128, 28, True, 0),  # SmallThinker's full layer, 28 over 4
    (16384, 128, 128, 28, True, 4096),  # and its band, eight 512-blocks wide
]
UNTIMED_SHAPES = [  # and the square block each runs
    ((8192, 64, 64, 32, True, 0), 512),  # Granite 4.0-H's 32 over 8 of 64
    ((4096, 192, 128, 16, True, 0), 512),  # a shorter row of the same heads
    ((8192, 128, 64, 8, True, 0), 512),  # other widths
    ((1024, 64, 128, 8, True, 256), 512),  # another band
    ((8192, 64, 128, 40, False, 0), 512),  # no causal mask was timed
    ((640, 64, 128, 4, True, 0), 128),  # neither 512 nor 256 divides it
    ((768, 64, 128, 4, True, 0), 256),
    ((128, 24, 16, 2, True, 0), 128),  # the tiny presets' one block
]


@pytest.mark.parametrize("shape,square", [(s, 0) for s in CELL_SHAPES]
                         + UNTIMED_SHAPES)
def test_the_tiling_of_a_shape_is_one_the_kernels_take(shape, square):
    """Every block divides the sequence in whole 128-lane tiles, a compute
    block divides its key block, and the choice is a pure function: the
    shapes' own timed entry, else square blocks that every shape so far has
    compiled with. A timed entry keeps dq's f32 accumulation: two backward
    kernels, never the fused one with its partials in the queries' dtype."""
    seq = shape[0]
    tiling, timed = flash.splash_tiling(*shape)
    assert timed is (shape in CELL_SHAPES)
    assert all(b % 128 == 0 and seq % b == 0 for b in tiling.blocks), tiling
    assert tiling.fwd[1] % tiling.fwd[2] == 0
    assert tiling.dkv[1] % tiling.dkv[2] == 0
    assert tiling.dq is not None
    if not timed:
        assert tiling == flash._square(square)
    assert flash.splash_tiling(*shape) == (tiling, timed)


@pytest.mark.parametrize(
    "shape", CELL_SHAPES + [s for s, _ in UNTIMED_SHAPES[:3]])
def test_a_shapes_kernel_is_built_once_and_a_window_has_its_own(shape):
    from lance_distributed_training_tpu.obs.registry import default_registry

    key = flash._Shape(*shape)
    fallbacks = default_registry().counter("attention_tiling_fallback_total")
    flash._splash_kernel.cache_clear()
    flash.splash_tilings_built()
    before = fallbacks.value
    kernel = flash._splash_kernel(key)
    assert flash._splash_kernel(key) is kernel
    other = flash._splash_kernel(key._replace(window=key.window or 256))
    banded = key.window > 0
    assert (other is kernel) is banded
    # a kernel built by the rule counts once, a cached one never again
    untimed = shape not in CELL_SHAPES
    assert fallbacks.value - before == untimed + (not banded)
    lines = flash.splash_tilings_built()
    assert len(lines) == 2 - banded
    assert lines[0]["source"] == ("rule" if untimed else "timed")
    assert f"seq={key.seq} d_qk={key.d_qk} d_v={key.d_v}" in lines[0][
        "attention_tiling"]
    assert flash.splash_tilings_built() == []


def _windowed_grouped_heads_equal_dense(window, tiling, heads=(4, 2, 1),
                                        widths=(64, 128)):
    """4 query heads of 64 over 2 key heads and 1 value of 128 (differential
    attention's form: the kernels get four heads of each, repeated outside
    them), or other ``heads`` and ``widths``; 512 tokens,
    segment ids: forward and all three gradients, each in the heads its
    array came in, against dense attention under the same mask on keys and
    values repeated a head a query head."""
    from jax.experimental.pallas import tpu as pltpu

    seq = 512
    (h, hk, hv), (d_qk, d_v) = heads, widths
    keys = jax.random.split(jax.random.key(window), 4)
    q = jax.random.normal(keys[0], (2, h, seq, d_qk), jnp.float32)
    k = jax.random.normal(keys[1], (2, hk, seq, d_qk), jnp.float32)
    v = jax.random.normal(keys[2], (2, hv, seq, d_v), jnp.float32)
    w = jax.random.normal(keys[3], (2, h, seq, d_v), jnp.float32)
    seg = _segments(seq)
    live = (seg > 0)[:, None, :, None]  # dead queries mean nothing
    at = jnp.arange(seq)
    mask = flash.segment_attention_mask(seg)
    if window:
        mask = mask & (at[:, None] - at[None, :] < window)[None, None]

    def dense(q, k, v):
        return jnp.where(live, dot_product_attention(
            q, jnp.repeat(k, h // hk, 1), jnp.repeat(v, h // hv, 1),
            mask=mask, dtype=jnp.float32, causal=True), 0)

    def kernel(q, k, v):
        return jnp.where(live, flash.unequal_attention(
            q, k, v, seg, causal=True, window=window, tiling=tiling), 0)

    def both(q, k, v):
        return [(fn(q, k, v), jax.grad(lambda *a: (fn(*a) * w).sum(),
                                       argnums=(0, 1, 2))(q, k, v))
                for fn in (dense, kernel)]

    with pltpu.force_tpu_interpret_mode():
        (want, want_grads), (got, got_grads) = _one_program(both, q, k, v)
    np.testing.assert_allclose(got, want, atol=5e-6)
    for g, wnt in zip(got_grads, want_grads):
        assert g.shape == wnt.shape
        np.testing.assert_allclose(g, wnt, atol=5e-5, rtol=1e-5)


@pytest.mark.parametrize("window,heads,widths", [
    *((window, (4, 2, 1), (64, 128)) for window in (0, 100, 128, 300)),
    # keys and values in as many heads, four query heads to each: nothing
    # is repeated, the kernels find a block's key head and sum its dK and dV
    (0, (8, 2, 2), (128, 128)),
    (128, (8, 2, 2), (128, 128)),
    (100, (6, 2, 2), (64, 64)),  # Laguna's tiny preset: three to a head
])
def test_unequal_attention_in_a_window_over_grouped_heads_equals_dense(
        window, heads, widths):
    """In square blocks of 128 (a window of 100 leaves whole blocks out)."""
    _windowed_grouped_heads_equal_dense(window, flash._square(128), heads,
                                        widths)


@pytest.mark.parametrize("window,tiling", [
    # a kernel's blocks are its own, none of them square
    (0, flash.SplashTiling((128, 256, 128), (256, 512, 128), (256, 128))),
    (100, flash.SplashTiling((256, 512, 256), (128, 256, 128), (512, 256))),
    # the fused backward, as the causal cells run it: dq in partials
    (0, flash.SplashTiling((256, 256, 128), (128, 256, 256), None)),
    (300, flash.SplashTiling((128, 128, 128), (256, 128, 128), None)),
])
def test_unequal_attention_in_blocks_of_each_kernels_own_equals_dense(
        window, tiling):
    _windowed_grouped_heads_equal_dense(window, tiling)


@pytest.mark.parametrize("heads,d_qk", [((4, 2, 1), 64), ((2, 2, 2), 192)])
def test_the_fused_backward_rounds_dq_where_two_kernels_do_not(heads, d_qk):
    """bf16 arrays, as the cells feed them, against dense attention in f32
    on the same values. Two backward kernels give the same dq whatever their
    blocks: dq is summed over the key blocks in f32 and rounded once. The
    library's fused backward writes a partial a key block in the queries'
    dtype (four here), so its dq alone lies further out: why no timed entry
    takes it (PERF.md section 6, PR 34, has the chip's reading). If the
    last line fails, the library keeps those partials wider than it did."""
    from jax.experimental.pallas import tpu as pltpu

    seq = 512
    keys = jax.random.split(jax.random.key(d_qk), 4)
    q, k, v, w = (jax.random.normal(key, (1, h, seq, d), jnp.bfloat16)
                  for key, h, d in zip(keys, (*heads, heads[0]),
                                       (d_qk, d_qk, 128, 128)))
    tilings = {
        "square": flash._square(128),
        "two kernels": flash.SplashTiling((256, 256, 128), (128, 256, 128),
                                          (256, 128)),
        "fused": flash.SplashTiling((256, 256, 128), (128, 128, 128), None)}

    def gradients(fn, *arrays):
        return jax.grad(lambda *a: (fn(*a).astype(jnp.float32) * w).sum(),
                        argnums=(0, 1, 2))(*arrays)

    def dense(q, k, v):
        return dot_product_attention(
            q, jnp.repeat(k, heads[0] // heads[1], 1),
            jnp.repeat(v, heads[0] // heads[2], 1), dtype=jnp.float32,
            causal=True)

    def all_forms(q, k, v):
        want = gradients(dense, *(t.astype(jnp.float32) for t in (q, k, v)))
        return want, {name: gradients(functools.partial(
            flash.unequal_attention, causal=True, tiling=tiling), q, k, v)
            for name, tiling in tilings.items()}

    with pltpu.force_tpu_interpret_mode():
        want, got = _one_program(all_forms, q, k, v)
    error = {name: [_relative(g.astype(jnp.float32), wnt)
                    for g, wnt in zip(grads, want)]
             for name, grads in got.items()}
    assert all(e < 0.01 for es in error.values() for e in es), error
    np.testing.assert_allclose(error["two kernels"], error["square"],
                               rtol=0.01)
    np.testing.assert_allclose(error["fused"][1:], error["square"][1:],
                               rtol=0.01)
    assert error["fused"][0] > 1.02 * error["two kernels"][0], error


def _kernel_operands(jaxpr) -> list:
    """The operands' shapes of every ``pallas_call`` under ``jaxpr``, a list
    a call."""
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append([tuple(var.aval.shape) for var in eqn.invars])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            calls += _kernel_operands(sub)
    return calls


@pytest.mark.parametrize("heads,window", [
    ((72, 8, 8), 128), ((48, 8, 8), 0),  # Laguna's W and F layers
    ((28, 4, 4), 128), ((28, 4, 4), 0),  # SmallThinker's W and N layers
    # Phi-4-mini-flash's S and F*: 20 key heads and 10 value heads, and the
    # kernels want as many of each, so both still go a head a query head
    ((40, 20, 10), 128), ((40, 20, 10), 0),
    ((16, 16, 16), 0),  # Moonlight's: nothing to repeat, before or now
], ids=str)
def test_the_kernels_take_keys_and_values_in_the_heads_they_have(heads,
                                                                 window):
    """The cells' head counts at 256 tokens, queries and keys of 64 and
    values of 128 so that a shape names its array: each of the call's three
    kernels (forward, dq, dkv) takes one ``[H, S, 64]`` operand, the
    queries, and keys and values of ``Hk == Hv`` heads; ``[H, S, 128]`` is
    the output's cotangent alone, backward, and nothing forward. No key or
    value reaches a kernel a head a query head, so no such copy is made for
    it and none is summed over on the way back."""
    (h, hk, hv), seq = heads, 256
    q, k, v = (jax.ShapeDtypeStruct((1, n, seq, d), jnp.bfloat16)
               for n, d in ((h, 64), (hk, 64), (hv, 128)))
    held = hk if hk == hv else h

    def loss(q, k, v):
        return flash.unequal_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32).sum()

    traced = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        q, k, v)
    assert [g.shape for g in traced.out_avals[1:]] == [
        q.shape, k.shape, v.shape]
    calls = _kernel_operands(traced.jaxpr)
    assert len(calls) == 3
    for n, shapes in enumerate(calls):
        assert shapes.count((held, seq, 64)) == 1 + (held == h), shapes
        assert shapes.count((held, seq, 128)) == 1 + (held == h and n > 0)
        if held < h:
            assert shapes.count((h, seq, 64)) == 1, shapes
            assert shapes.count((h, seq, 128)) == (n > 0), shapes


@pytest.mark.parametrize("heads,widths,window,on_the_parent", [
    ((16, 16, 16), (192, 128), 0, "e0e3986c039c5b10"),  # Moonlight's
    ((40, 20, 10), (64, 128), 512, "4d1287acfe1186ad"),  # Phi-4's S
    ((40, 20, 10), (64, 128), 0, "28f04e047fb1b3db"),  # and its F* and X
], ids=["moonlight", "phi4_window", "phi4_causal"])
def test_a_call_that_groups_nothing_new_is_traced_as_before(
        heads, widths, window, on_the_parent):
    """Moonlight's 16 heads of 192 over 16 and 16 values of 128 (nothing was
    repeated and nothing is) and Phi-4-mini-flash's 40 of 64 over 20 and 10
    values of 128 (both repeated to 40, as before), at 8,192 tokens with
    segment ids, forward and backward. The jaxpr's text (every operation
    around the three kernels and inside them) hashed on the parent of PR 54
    (commit 43a824d) with these lines; the lowered text differs by the line
    numbers of this repo's frames inside Mosaic's serialized modules and by
    nothing else, so it is the jaxpr that is held."""
    import hashlib

    def loss(q, k, v, ids):
        return flash.unequal_attention(
            q, k, v, ids, causal=True, window=window).astype(
                jnp.float32).sum()

    (h, hk, hv), (d_qk, d_v) = heads, widths
    q, k, v = (jax.ShapeDtypeStruct((1, n, 8192, d), jnp.bfloat16)
               for n, d in ((h, d_qk), (hk, d_qk), (hv, d_v)))
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    text = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        q, k, v, ids))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == on_the_parent


@pytest.mark.parametrize("heads,kv_heads,repeat", [
    ((6, 2, 2), 2, 1),  # grouped: the kernels' own index maps
    ((6, 3, 1), 6, 6),  # values in fewer heads than keys: a head a query
    ((6, 1, 3), 6, 6),  # head for both, or keys in fewer than values
    ((6, 6, 6), 6, 1),  # a head a query head as they come
], ids=str)
def test_a_call_says_its_key_heads_and_counts_what_it_still_repeats(
        heads, kv_heads, repeat):
    """``attention_kv_repeat_total`` counts the traced calls that copy keys
    or values outside the kernels, and the call's log line says into how
    many heads they went and the most either was repeated: 1 in the grouped
    cells, 4 in Phi-4-mini-flash's (20 key heads twice, 10 value heads four
    times, to its 40 query heads). (A row of 384 tokens
    that no other test of this file traces: the function is jitted, and a
    call traced before builds nothing and says nothing.)"""
    from lance_distributed_training_tpu.obs.registry import default_registry

    repeats = default_registry().counter("attention_kv_repeat_total")
    fallbacks = default_registry().counter("attention_tiling_fallback_total")
    flash._splash_kernel.cache_clear()
    flash.splash_tilings_built()
    before = repeats.value, fallbacks.value
    q, k, v = (jax.ShapeDtypeStruct((1, n, 384, 64), jnp.float32)
               for n in heads)
    out = jax.eval_shape(functools.partial(flash.unequal_attention,
                                           causal=True), q, k, v)
    assert out.shape == q.shape
    assert repeats.value - before[0] == (repeat > 1)
    assert fallbacks.value - before[1] == 1  # nobody timed 384 tokens
    (line,) = flash.splash_tilings_built()
    assert (line["kv_heads"], line["repeat"]) == (kv_heads, repeat)
    assert line["attention_tiling"] == (
        "seq=384 d_qk=64 d_v=64 heads=6 causal=True window=0")
    assert list(line)[:3] == ["attention_tiling", "kv_heads", "repeat"]


@pytest.mark.parametrize("shape,causal", [
    ((32, 12, 512, 64), False),  # c4-bert-prepacked
    ((24, 12, 512, 64), False),  # c4-bert-ragged
    ((2, 16, 4096, 128), True),  # c4-olmoe-prepacked-4k
])
def test_heads_of_equal_width_never_reach_the_splash_kernel(
        monkeypatch, shape, causal):
    """The cells whose heads are as wide in values as in keys run
    ``short_attention`` or the library's blocked kernel: no entry of the
    splash kernels' table, and no later edit to it, can move them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(flash, "_splash_kernel", lambda *_: 1 / 0)
    monkeypatch.setattr(flash, "splash_tiling", lambda *_: 1 / 0)
    attention = flash.make_flash_attention(
        causal=causal, mesh=_meshes()["one device"], forced=False)
    assert attention.fused(*shape[2:])
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((shape[0], shape[2]), jnp.int32)
    traced = str(jax.make_jaxpr(
        lambda q, k, v, ids: attention(q, k, v, segment_ids=ids))(
            q, q, q, ids))
    assert "pallas_call" in traced and "splash" not in traced
    with pytest.raises(ZeroDivisionError):  # the guard guards
        k = jax.ShapeDtypeStruct((*shape[:3], 2 * shape[3]), jnp.bfloat16)
        jax.make_jaxpr(lambda q, k, v: attention(k, k, v))(q, k, q)


# -- heads in groups, as wide in values as in keys ----------------------------


@pytest.mark.parametrize("heads,width", [(8, 128), (16, 256)],
                         ids=["zaya1", "qwen3_next"])
@pytest.mark.parametrize("platform,kernel", [("tpu", True), ("cpu", False)])
def test_grouped_heads_of_equal_width_take_the_kernel_on_a_tpu_alone(
        monkeypatch, platform, kernel, heads, width):
    """8 query heads over 2 key and value heads of 128 at 8,192 tokens (ZAYA1's
    cell), 16 over 2 of 256 (Qwen3-Next's): the rule says kernel from the
    shapes, the call obeys it with the splash kernels (this shape raised
    ``NotImplementedError`` before PR 38), and off the chip the same call is
    dense attention over repeated heads."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    attention = flash.make_flash_attention(
        causal=True, mesh=_meshes()["one device"], forced=False)
    assert attention.fused(8192, width, width) is kernel
    q = jax.ShapeDtypeStruct((1, heads, 8192, width), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2, 8192, width), jnp.bfloat16)
    traced = str(jax.make_jaxpr(attention)(q, kv, kv))
    assert ("pallas_call" in traced and "splash" in traced) is kernel
    assert jax.eval_shape(attention, q, kv, kv).shape == q.shape


@pytest.mark.parametrize("width", [128, 256])
def test_grouped_heads_of_equal_width_in_the_kernel_equal_dense(monkeypatch,
                                                                width):
    """The call the rule makes on a TPU, in interpret mode, against dense
    attention over repeated keys and values: forward and gradients, with a
    key-validity mask (the last row's tail is padding); heads of 128
    (ZAYA1's) and of 256 (Qwen3-Next's)."""
    from jax.experimental.pallas import tpu as pltpu

    seq = 256
    keys = jax.random.split(jax.random.key(38), 4)
    q = jax.random.normal(keys[0], (2, 4, seq, width), jnp.float32)
    k = jax.random.normal(keys[1], (2, 2, seq, width), jnp.float32)
    v = jax.random.normal(keys[2], (2, 2, seq, width), jnp.float32)
    w = jax.random.normal(keys[3], q.shape, jnp.float32)
    valid = jnp.arange(seq)[None, :] < jnp.asarray([seq, seq - 40])[:, None]
    mask = valid[:, None, None, :]
    live = valid[:, None, :, None]  # dead queries mean nothing
    with monkeypatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        chosen = flash.make_flash_attention(
            causal=True, mesh=_meshes()["one device"], forced=False)

    def dense(q, k, v):
        return jnp.where(live, dot_product_attention(
            q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1), mask=mask,
            dtype=jnp.float32, causal=True), 0)

    def kernel(q, k, v):
        return jnp.where(live, chosen(q, k, v, mask=mask), 0)

    def both(q, k, v):
        return [(fn(q, k, v), jax.grad(lambda *a: (fn(*a) * w).sum(),
                                       argnums=(0, 1, 2))(q, k, v))
                for fn in (dense, kernel)]

    assert "splash" in str(jax.make_jaxpr(kernel)(q, k, v))
    with pltpu.force_tpu_interpret_mode():
        (want, want_grads), (got, got_grads) = _one_program(both, q, k, v)
    np.testing.assert_allclose(got, want, atol=5e-6)
    for g, wnt in zip(got_grads, want_grads):
        assert g.shape == wnt.shape
        np.testing.assert_allclose(g, wnt, atol=5e-5, rtol=1e-5)


# -- BERT's encoder with the chosen kernel -----------------------------------


def _padded(lengths):
    ids = jax.random.randint(jax.random.PRNGKey(1), (ROWS, SEQ), 2, VOCAB)
    live = jnp.arange(SEQ)[None, :] < jnp.asarray(lengths)[:, None]
    return {"input_ids": jnp.where(live, ids, 0),
            "attention_mask": live.astype(jnp.int8)}


def _packed():
    """Rows of two documents: segments from 1, 0 on the padding, positions
    that restart with each document."""
    batch = _padded((256, 240, 128, 10))
    cuts = [(100, 256), (7, 240), (127, 128), (2, 10)]
    seg = np.zeros((ROWS, SEQ), np.int32)
    pos = np.zeros((ROWS, SEQ), np.int32)
    for row, (b, end) in enumerate(cuts):
        seg[row, :b], seg[row, b:end] = 1, 2
        pos[row, :b], pos[row, b:end] = np.arange(b), np.arange(end - b)
    return dict(batch, segment_ids=jnp.asarray(seg),
                position_ids=jnp.asarray(pos))


BATCHES = {"all-ones mask": lambda: _padded((SEQ,) * ROWS),
           "padded rows": lambda: _padded((256, 180, 17, 1)),
           "packed rows": _packed}
GROUPS = ("query", "key", "value", "out", "mlp_in", "mlp_out", "norms",
          "tok_embed", "pos_embed")


def _groups(tree) -> dict:
    out: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [k.key for k in path if hasattr(k, "key")]
        name = next((k for k in GROUPS if k in keys), "norms")
        out.setdefault(name, []).append(jnp.ravel(leaf))
    return {k: jnp.concatenate(v) for k, v in out.items()}


def _relative(got, want) -> float:
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.fixture(scope="module")
def encoders():
    """``bert_small``'s widths (two layers) in float32, twice on the same
    parameters: dense attention named outright (the path before PR 29), and
    what ``get_task`` binds by itself on a one-chip TPU."""
    make = functools.partial(get_task, "masked_lm", model_name="bert_small",
                             seq_len=SEQ, vocab_size=VOCAB, num_layers=2)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(tasks, "bert_small",
                      functools.partial(bert_small, dtype=jnp.float32))
        dense = make(attention_fn=functools.partial(
            dot_product_attention, dtype=jnp.float32))
        patch.setattr(jax, "default_backend", lambda: "tpu")
        chosen = make(mesh=get_mesh(jax.devices()[:1]))
    assert chosen.model.attention_fn.fused(SEQ, 64)
    return dense, chosen, dense.init_variables(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def compared(encoders):
    """Each batch run once, one program: eval logits, the train loss and
    its gradient, on both paths."""
    from jax.experimental.pallas import tpu as pltpu

    dense, chosen, variables = encoders
    done = {}

    def run(name):
        if name not in done:
            batch = BATCHES[name]()

            def one(task, v):
                logits = task.forward(v, batch, False, None)[0][0]
                live = (batch["attention_mask"] > 0)[..., None]

                def loss(v):
                    return task.loss(task.forward(v, batch, True, RNG)[0],
                                     batch)

                value, grads = jax.value_and_grad(loss)(v)
                return jnp.where(live, logits, 0), value, grads

            with pltpu.force_tpu_interpret_mode():
                done[name] = _one_program(
                    lambda v: (one(dense, v), one(chosen, v)), variables)
        return done[name]

    return run


@pytest.mark.parametrize("name", BATCHES)
def test_encoder_logits_and_loss_with_the_chosen_kernel_equal_dense(
        compared, name):
    (want, want_loss, _), (got, got_loss, _) = compared(name)
    spread = float(jnp.std(want))
    assert float(jnp.abs(got - want).max()) < F32_TOL * spread
    assert abs(float(got_loss) - float(want_loss)) < F32_TOL * float(
        want_loss)


@pytest.mark.parametrize("group", GROUPS)
@pytest.mark.parametrize("name", BATCHES)
def test_encoder_gradient_with_the_chosen_kernel_equals_dense(
        compared, name, group):
    (_, _, want), (_, _, got) = compared(name)
    assert _relative(_groups(got)[group], _groups(want)[group]) < F32_TOL


# -- train() says which path it took -----------------------------------------


def _train_a_few_steps(tmp_path, monkeypatch) -> list:
    """``bert_small``'s one layer for four steps on the CPU, logged every
    two; the run's JSONL lines."""
    import json

    from lance_distributed_training_tpu import cli
    from lance_distributed_training_tpu.data import create_text_token_dataset

    rng = np.random.default_rng(0)
    docs = [rng.integers(2, 64, 128).tolist() for _ in range(40)]
    uri = str(tmp_path / "tok")
    create_text_token_dataset(uri, docs, seq_len=128, fragment_size=64)
    metrics_path = tmp_path / "metrics.jsonl"
    monkeypatch.setenv("LDT_METRICS_PATH", str(metrics_path))
    cli.main([
        "train", "--dataset_path", uri, "--task_type", "masked_lm",
        "--model_name", "bert_small", "--num_layers", "1", "--seq_len", "128",
        "--vocab_size", "64", "--batch_size", "8", "--epochs", "1",
        "--max_steps", "4", "--log_every", "2", "--no_ddp", "--no_wandb",
        "--no_eval_at_end", "--no_autotune"])
    return [json.loads(line) for line in open(metrics_path)]


def test_train_logs_the_attention_path_and_sets_the_gauge(tmp_path,
                                                          monkeypatch):
    from lance_distributed_training_tpu.obs.registry import default_registry

    default_registry().gauge("attention_fused").set(-1.0)
    lines = _train_a_few_steps(tmp_path, monkeypatch)
    assert default_registry().gauge("attention_fused").value == 0.0
    assert [ln["attention"] for ln in lines if "attention" in ln] == ["dense"]
    steps = [ln for ln in lines if "images_per_sec_dispatch" in ln]
    assert len(steps) == 2  # the gauge rides every log line
    assert all(ln["attention_fused"] == 0.0 for ln in steps)


def test_train_logs_the_tiling_of_each_kernel_built_once(tmp_path,
                                                         monkeypatch):
    """Kernels are built while a step is traced; the loop's next log point
    says what each got (here two are built by hand: the CPU's attention is
    dense), before that point's own line and never again."""
    flash._splash_kernel.cache_clear()
    flash.splash_tilings_built()
    flash._splash_kernel(flash._Shape(8192, 192, 128, 16, True, 0))
    flash._splash_kernel(flash._Shape(1024, 192, 128, 16, True, 0))
    lines = _train_a_few_steps(tmp_path, monkeypatch)
    said = [n for n, ln in enumerate(lines) if "attention_tiling" in ln]
    first = next(n for n, ln in enumerate(lines)
                 if "images_per_sec_dispatch" in ln)
    assert said == [first - 2, first - 1]
    assert [lines[n]["source"] for n in said] == ["timed", "rule"]
    assert lines[said[0]]["attention_tiling"] == (
        "seq=8192 d_qk=192 d_v=128 heads=16 causal=True window=0")
    assert lines[said[0]]["dq"] == "1024/1024"
    assert lines[said[1]]["fwd"] == lines[said[1]]["dkv"] == "512/512/512"
