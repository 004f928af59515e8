"""The seam a new model goes through (models/transformer.py): a layer kind
is its mixer's class, an entry in ``LAYER_KINDS`` and a preset record in
``CAUSAL_LMS``; what the stack says of its kernels (``Task.kernels``) is what
its mixers say, and both the step's gauges and the first log line are made
from that one answer.

*Does every preset answer with the kernels its layers have, and does the
first log line say them?* The eighteen presets and the five spans the
benchmark's cells hold. *Does a kind nobody wrote into the task or the
trainer train?* A toy mixer with sizes, a kernel and a sown gauge of its
own, patched into the two tables here, through ``get_task`` and
``make_train_step``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from lance_distributed_training_tpu import trainer
from lance_distributed_training_tpu.models import get_task, transformer
from lance_distributed_training_tpu.ops import (
    conv,
    delta,
    flash,
    norm,
    scan,
    ssd,
)

ATTENTION = {"attention"}
SAMBAY = {"attention", "scan", "conv"}
QWEN3_NEXT = {"attention", "delta", "conv", "norm"}
GRANITE4 = {"attention", "ssd", "conv", "norm"}
PLAIN = {"attention": "dense", "scan": "chunked", "delta": "chunked",
         "ssd": "chunked", "conv": "plain", "norm": "plain"}
# a kernel a mixer names and no rule can choose: Mamba-2's gated norm has the
# gate inside its statistic and the plain lines alone (ops/norm.py)
PLAIN_ONLY = {"granite4_h_micro": {"norm"}, "granite4_h_tiny": {"norm"}}
# what the first line says beside the kernels' forms: the YaRN rule of a
# stack whose full layers turn under one (trainer._kernel_paths)
ALSO_SAID = {
    "laguna_s_2_1": {"yarn": "factor 128 over 8192 positions, beta 32/1, "
                             "cos and sin x 1.4852"},
    "laguna_tiny": {"yarn": "factor 8 over 64 positions, beta 4/1, "
                            "cos and sin x 1.2079"}}
KERNELS = {
    ("gpt_base", None): ATTENTION, ("gpt_small", None): ATTENTION,
    ("olmoe_1b_7b", None): ATTENTION, ("olmoe_tiny", None): ATTENTION,
    ("moonlight_16b_a3b", None): ATTENTION,
    ("moonlight_tiny", None): ATTENTION,
    ("phi4_mini_flash", None): SAMBAY,
    ("phi4_mini_flash_tiny", None): SAMBAY,
    ("zaya1_8b", None): ATTENTION, ("zaya_tiny", None): ATTENTION,
    ("qwen3_next_80b_a3b", None): QWEN3_NEXT,
    ("qwen3_next_tiny", None): QWEN3_NEXT,
    ("smallthinker_21b_a3b", None): ATTENTION,
    ("smallthinker_tiny", None): ATTENTION,
    ("granite4_h_micro", None): GRANITE4,
    ("granite4_h_tiny", None): GRANITE4,
    ("laguna_s_2_1", None): ATTENTION, ("laguna_tiny", None): ATTENTION,
    # the spans of the cells c4-phi4flash-vp8-prepacked-8k,
    # c4-qwen3next-ep16-prepacked-8k, c4-smallthinker-ep4-prepacked-16k,
    # c4-granite4h-vp8-prepacked-8k and c4-laguna-ep32-prepacked-8k
    ("phi4_mini_flash", "14:20"): SAMBAY,
    ("qwen3_next_80b_a3b", "0:4"): QWEN3_NEXT,
    ("smallthinker_21b_a3b", "0:4"): ATTENTION,
    ("granite4_h_micro", "0:10"): GRANITE4,
    ("laguna_s_2_1", "0:5"): ATTENTION,
    # a span of Mamba-2 layers alone has no attention to report
    ("granite4_h_micro", "6:10"): GRANITE4 - {"attention"},
}


@pytest.mark.parametrize("model,span", sorted(KERNELS, key=str))
def test_a_preset_answers_with_its_kernels_and_the_first_line_says_them(
        model, span, monkeypatch):
    config = trainer.TrainConfig(dataset_path="", task_type="causal_lm",
                                 model_name=model, seq_len=8192)
    task = get_task("causal_lm", model_name=model, seq_len=8192,
                    layer_span=span)
    assert set(task.kernels) == KERNELS[model, span]
    # here, on the CPU, every op's own rule says no
    assert trainer._kernel_paths(task, config) == {
        **{name: PLAIN[name] for name in KERNELS[model, span]},
        **ALSO_SAID.get(model, {})}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for op, rule in ((flash, "fused_attention_applies"),
                     (scan, "scan_fused_applies"),
                     (delta, "delta_fused_applies"),
                     (ssd, "ssd_fused_applies"),
                     (conv, "conv_fused_applies"),
                     (norm, "norm_fused_applies")):
        monkeypatch.setattr(op, rule, lambda *a, **k: True)
    task = get_task("causal_lm", model_name=model, seq_len=8192,
                    layer_span=span)
    fused = {name: name not in PLAIN_ONLY.get(model, ())
             for name in KERNELS[model, span]}
    assert task.kernels == fused
    assert trainer._kernel_paths(task, config) == {
        **{name: "fused kernel" if on else PLAIN[name]
           for name, on in fused.items()}, **ALSO_SAID.get(model, {})}


def test_the_table_holds_the_presets_listed_here():
    assert set(transformer.CAUSAL_LMS) == {model for model, _ in KERNELS}


class ToyMixer(nn.Module):
    """The mean of the last ``taps`` tokens under a learned gate: a mixer
    with a size, a kernel to report and a scalar to sow, which ``tasks.py``
    and ``trainer.py`` have never heard of."""

    taps: int
    dtype: Any = jnp.bfloat16
    kernel_init: Callable = nn.linear.default_kernel_init

    def kernels(self, seq_len: int, width: int) -> dict:
        return {"toy": seq_len % self.taps == 0}

    @nn.compact
    def __call__(self, u):
        gate = nn.sigmoid(self.param("gate", nn.initializers.zeros_init(),
                                     (), jnp.float32))
        self.sow("mixer_stats", "toy_gate_max", gate)
        padded = jnp.pad(u, ((0, 0), (self.taps - 1, 0), (0, 0)))
        mean = sum(padded[:, i:i + u.shape[1]]
                   for i in range(self.taps)) / self.taps
        return nn.Dense(u.shape[-1], use_bias=False, dtype=self.dtype,
                        kernel_init=self.kernel_init, name="out")(
            (gate * mean).astype(self.dtype))


def test_a_kind_written_here_trains_through_get_task_and_the_train_step(
        monkeypatch):
    from lance_distributed_training_tpu.parallel import get_mesh

    monkeypatch.setitem(transformer.LAYER_KINDS, "T", transformer.LayerKind(
        ToyMixer, "toy", "toy_mixer"))
    monkeypatch.setitem(transformer.CAUSAL_LMS, "toy_tiny", transformer.Preset(
        partial(transformer.TransformerDecoder, hidden_size=32, num_layers=2,
                num_heads=2, expert_dim=0, num_experts=0,
                experts_per_token=0, dense_layers=2, dense_dim=64, kind="T",
                parts=(partial(ToyMixer, taps=4),)), 64, {}))
    task = get_task("causal_lm", model_name="toy_tiny", seq_len=16)
    assert task.kernels == {"toy": True}
    assert get_task("causal_lm", model_name="toy_tiny",
                    seq_len=18).kernels == {"toy": False}
    config = trainer.TrainConfig(dataset_path="", task_type="causal_lm",
                                 model_name="toy_tiny", seq_len=16)
    assert trainer._kernel_paths(task, config) == {"toy": "fused kernel"}

    variables = task.init_variables(jax.random.key(0))
    assert variables["params"]["layer_1"]["toy"]["gate"].shape == ()
    state = trainer.TrainState.create(
        apply_fn=None, params=variables["params"], tx=optax.sgd(0.1))
    step = trainer.make_train_step(task, get_mesh(jax.devices()[:1]),
                                   donate=False, stats=True)
    ids = np.random.default_rng(0).integers(2, 64, (2, 16))
    batch = {"input_ids": ids, "attention_mask": np.ones((2, 16), np.int8)}
    new_state, loss, stats = step(state, batch, jax.random.key(1))
    assert np.isfinite(float(loss))
    # the kernel's gauge by its name, the sown scalar by its own, reduced
    # over the two layers as its ending says: sigmoid(0) in both
    assert {k: float(v) for k, v in stats.items()} == {
        "toy_fused": 1.0, "toy_gate_max": 0.5}
    assert float(new_state.params["layer_0"]["toy"]["gate"]) != 0.0
