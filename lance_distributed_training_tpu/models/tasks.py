"""Task abstraction: model + forward + loss + metric as one unit.

Generalises the reference's ``get_model_and_loss(task_type, num_classes) →
(model, loss_fn, eval_fn)`` contract
(``/root/reference/modelling/get_model_and_loss.py:4-11``) so ONE jitted
train step serves every task family. Each task owns:

* ``init_variables`` — parameter/state init,
* ``forward(variables, batch, train, rng)`` — including device-side input
  prep (normalize/augment for images, on-device MLM masking for text: all
  work that the reference did per-row on host is fused into the step here),
* ``loss(outputs, batch)`` and ``metric(outputs, batch)``.

Registered: ``classification`` (reference parity), ``masked_lm`` (BASELINE
C4/BERT config), ``contrastive`` (BASELINE LAION/CLIP config).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from ..ops.image import normalize_images, random_flip
from . import resnet as _resnet
from .clip import CLIP, clip_contrastive_loss, clip_resnet50_bert, clip_tiny
from .transformer import (
    CAUSAL_LMS,
    TransformerDecoder,
    bert_base,
    bert_small,
)

__all__ = ["Task", "get_task", "TASK_REGISTRY"]


@dataclasses.dataclass(frozen=True)
class Task:
    name: str
    model: Any
    init_variables: Callable  # (rng) -> variables
    forward: Callable  # (variables, batch, train, rng) -> (outputs, new_state|None)
    loss: Callable  # (outputs, batch) -> scalar
    metric: Callable  # (outputs, batch) -> per-example float array
    metric_name: str = "accuracy"
    stats: Optional[Callable] = None  # (outputs) -> {name: scalar} that the
    # train step returns beside the loss, under the names the trainer
    # publishes them by: a ``*_total`` is this step's share of a counter,
    # any other name a gauge (the expert layer's load, the masked-LM head's
    # fill); None for a task with nothing to report. ``loss`` and ``stats``
    # take the outputs of any ``forward``; ``metric`` those of an eval
    # forward (``train=False``), which are what they were for every task
    kernels: dict = dataclasses.field(default_factory=dict)  # which kernels
    # a sequence model's layers run at the task's seq_len, by name: True the
    # fused kernel, False the plain form (the model's ``kernels``: each mixer
    # asks its op's own rule); empty for a task with none to choose


# ---------------------------------------------------------------- classification
_RESNETS = {
    "resnet18": _resnet.resnet18,
    "resnet34": _resnet.resnet34,
    "resnet50": _resnet.resnet50,
    "resnet101": _resnet.resnet101,
    "resnet152": _resnet.resnet152,
}


def _classifiers() -> dict:
    from . import vit as _vit

    return {
        **_RESNETS,
        "vit_tiny": _vit.vit_tiny,
        "vit_small": _vit.vit_small,
        "vit_base": _vit.vit_base,
    }


def _classification_task(num_classes: int, model_name: str, image_size: int,
                         augment: bool, param_dtype=None) -> Task:
    registry = _classifiers()
    try:
        ctor = registry[model_name]
    except KeyError:
        raise ValueError(
            f"Invalid model name: {model_name} (have {sorted(registry)})"
        ) from None
    kwargs = {"num_classes": num_classes}
    if param_dtype is not None:
        if model_name not in _RESNETS:
            raise ValueError(
                f"param_dtype override supports the ResNet family; got "
                f"{model_name!r}"
            )
        kwargs["param_dtype"] = param_dtype
    model = ctor(**kwargs)

    def init_variables(rng):
        return model.init(
            rng, jnp.zeros((1, image_size, image_size, 3), jnp.float32),
            train=False,
        )

    def forward(variables, batch, train, rng):
        images = normalize_images(batch["image"])
        if train and augment and rng is not None:
            images = random_flip(rng, images)
        if train:
            logits, new_state = model.apply(
                variables, images, train=True, mutable=["batch_stats"]
            )
            return logits, new_state
        logits = model.apply(variables, images, train=False)
        return logits, None

    def loss(logits, batch):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]
        ).mean()

    def metric(logits, batch):
        return (jnp.argmax(logits, -1) == batch["label"]).astype(jnp.float32)

    return Task("classification", model, init_variables, forward, loss, metric)


# ---------------------------------------------------------------- masked LM
def _depth(num_layers: int) -> dict:
    """``num_layers`` as a constructor argument; 0 keeps the preset's."""
    if num_layers < 0:
        raise ValueError(f"num_layers must be >= 0, got {num_layers}")
    return {"num_layers": num_layers} if num_layers else {}


class _MaskedHeadLoss(NamedTuple):
    """What the masked-LM forward returns in place of logits while it
    trains: the mean cross-entropy over the masked positions, and the
    step's stats under the names they are published by (``Task.stats``)."""

    xent: jax.Array
    stats: dict


def _mlm_head_capacity(seq_len: int) -> int:
    """Masked positions of one row that the gathered head has room for: a
    quarter of the row, rounded up to a multiple of 128, at most the row.
    A function of the batch's shape alone. At 15% masking a row of 512
    holds 77 on average with a standard deviation of 8.1; 128 is 6.3 of
    them above."""
    return min(seq_len, -(-seq_len // 512) * 128)


def _xent_sum(logits, targets, weights):
    return (optax.softmax_cross_entropy_with_integer_labels(logits, targets)
            * weights).sum()


def _masked_head_loss(head, head_params, hidden, targets,
                      mlm_mask) -> _MaskedHeadLoss:
    """Mean over the masked positions of the cross-entropy of
    ``head(head_params, hidden)`` against ``targets``, with the head and the
    soft-max applied to those positions only: per row (so the batch axis
    stays sharded over ``'data'``) the masked positions' hidden states and
    targets are gathered, in order, into ``_mlm_head_capacity`` slots, and
    the slots past the row's count weigh 0. Bernoulli masking has no upper
    count, so a batch in which some row masks more than the capacity takes
    the full-logits branch of a ``lax.cond``: loss and gradients are the
    full path's for every batch, and no masked position is ever dropped.

    The loss is its own differentiation rule: the branch taken computes the
    gradients with the value, inside the one ``cond``. Differentiated from
    outside, a ``cond`` hands every branch's residuals to the backward pass,
    filled with zeros by the branch not taken: 2 GB of them a BERT-base
    step, and 1.3 GiB on the step's peak (compiled for a v5e, PR 27).

    Stats: ``mlm_selected_tokens_total`` (the masked positions),
    ``mlm_head_fallback_total`` (1 where the batch overflowed),
    ``mlm_head_capacity_tokens`` (rows x capacity) and ``mlm_head_fill_pct``
    (the share of those slots used)."""
    rows, seq_len = mlm_mask.shape
    capacity = _mlm_head_capacity(seq_len)
    weights = mlm_mask.astype(jnp.float32)
    count = mlm_mask.sum(-1)  # [B]
    selected = weights.sum()
    total = jnp.maximum(selected, 1.0)

    def on_all(head_params, h):
        return _xent_sum(head(head_params, h), targets, weights) / total

    def on_masked(head_params, h):
        # a row's masked positions first, in their order
        slots = jnp.argsort(jnp.where(mlm_mask, 0, 1), axis=-1,
                            stable=True)[:, :capacity]
        w = jnp.arange(capacity)[None, :] < count[:, None]
        return _xent_sum(
            head(head_params, jnp.take_along_axis(h, slots[..., None], 1)),
            jnp.take_along_axis(targets, slots, 1), w) / total

    with jax.named_scope("mlm_head"):
        # never on a row no longer than its capacity
        overflow = (count > capacity).any()

        @jax.custom_vjp
        def xent_of(head_params, hidden):
            return jax.lax.cond(overflow, on_all, on_masked,
                                head_params, hidden)

        def xent_fwd(head_params, hidden):
            return jax.lax.cond(
                overflow, *(jax.value_and_grad(f, argnums=(0, 1))
                            for f in (on_all, on_masked)),
                head_params, hidden)

        def xent_bwd(grads, ct):
            return jax.tree.map(lambda g: (ct * g).astype(g.dtype), grads)

        xent_of.defvjp(xent_fwd, xent_bwd)
        xent = xent_of(head_params, hidden)
        slots = rows * capacity
        return _MaskedHeadLoss(xent, {
            "mlm_selected_tokens_total": selected,
            "mlm_head_fallback_total": overflow.astype(jnp.float32),
            "mlm_head_capacity_tokens": jnp.float32(slots),
            "mlm_head_fill_pct": 100.0 * selected / slots})


def _masked_lm_task(vocab_size: Optional[int], model_name: str, seq_len: int,
                    mask_prob: float = 0.15, mask_id: int = 1,
                    attention_fn: Optional[Callable] = None,
                    remat: bool = False, num_experts: int = 0,
                    moe_every: int = 2,
                    aux_loss_weight: float = 0.01,
                    num_layers: int = 0) -> Task:
    ctor = {"bert_base": bert_base, "bert_small": bert_small}.get(model_name)
    if ctor is None:
        raise ValueError(f"Invalid model name: {model_name} "
                         "(have ['bert_base', 'bert_small'])")
    model = ctor(vocab_size=vocab_size or 30522, max_len=seq_len,
                 attention_fn=attention_fn, remat=remat,
                 num_experts=num_experts, moe_every=moe_every,
                 **_depth(num_layers))

    def init_variables(rng):
        ids = jnp.zeros((1, seq_len), jnp.int32)
        return model.init(rng, ids, jnp.ones((1, seq_len), jnp.int8),
                          train=False)

    def forward(variables, batch, train, rng):
        ids = batch["input_ids"].astype(jnp.int32)
        mask = batch["attention_mask"]
        # Packed batches (the ragged token plane, ops/token_device.py):
        # segment ids gate attention at sequence boundaries and position
        # ids restart the positional embedding per packed sequence. Absent
        # (the padded arm) the model runs its historical row-wise path.
        seg = batch.get("segment_ids")
        pos = batch.get("position_ids")
        if train and rng is not None:
            # On-device BERT masking: static shapes, no host RNG. The masked
            # positions double as the loss targets.
            mlm_mask = (
                jax.random.bernoulli(rng, mask_prob, ids.shape)
                & (mask > 0)
            )
        else:
            # Eval: deterministic mask (every ~1/mask_prob-th position) so
            # masked-token accuracy measures real infilling, not copying.
            stride = max(int(round(1.0 / mask_prob)), 1)
            positions = jnp.arange(ids.shape[1])
            mlm_mask = ((positions % stride) == 0)[None, :] & (mask > 0)
        corrupted = jnp.where(mlm_mask, mask_id, ids)
        gathered = train and rng is not None  # the head on the targets only
        aux = jnp.zeros((), jnp.float32)
        if train and num_experts > 0:
            # MoE blocks sow their switch load-balance terms; collect them.
            out, sown = model.apply(
                variables, corrupted, mask, train=True, mutable=["aux_loss"],
                segment_ids=seg, position_ids=pos, return_hidden=gathered,
            )
            for leaf in jax.tree_util.tree_leaves(sown.get("aux_loss", {})):
                aux = aux + leaf
        else:
            out = model.apply(variables, corrupted, mask, train=train,
                              segment_ids=seg, position_ids=pos,
                              return_hidden=gathered)
        if gathered:
            # ``out`` is the final hidden states: 85% of the positions are
            # no target, so the vocabulary projection and the soft-max run
            # on the masked ones alone (the published model gathers
            # ``max_predictions_per_seq`` positions before its head too).
            out = _masked_head_loss(
                lambda p, h: model.apply({"params": p}, None, hidden=h),
                {"tok_embed": variables["params"]["tok_embed"]},  # the
                # tied head's own parameters
                out, ids, mlm_mask)
        return (out, mlm_mask, aux), None

    def loss(outputs, batch):
        head, mlm_mask, aux = outputs
        if isinstance(head, _MaskedHeadLoss):
            xent = head.xent
        else:  # full logits [B, S, V]: eval, or a train call without rng
            w = mlm_mask.astype(jnp.float32)
            targets = batch["input_ids"].astype(jnp.int32)
            xent = _xent_sum(head, targets, w) / jnp.maximum(w.sum(), 1.0)
        return xent + aux_loss_weight * aux

    def metric(outputs, batch):
        logits, mlm_mask, _aux = outputs
        targets = batch["input_ids"].astype(jnp.int32)
        hit = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
        w = mlm_mask.astype(jnp.float32)
        # Per-example masked-token accuracy.
        return (hit * w).sum(-1) / jnp.maximum(w.sum(-1), 1.0)

    return Task("masked_lm", model, init_variables, forward, loss, metric,
                metric_name="masked_token_accuracy",
                stats=lambda outputs: getattr(outputs[0], "stats", {}),
                kernels=model.kernels(seq_len))


# ---------------------------------------------------------------- causal LM
def _weighted_aux(sown: dict, weights: dict):
    """Σ over the terms' names of weight × (that term summed over the
    layers that sowed it); a term without a weight is left out."""
    sums: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(sown):
        name = next((k.key for k in path
                     if getattr(k, "key", None) in weights), None)
        if name is not None:
            sums[name] = sums.get(name, 0.0) + leaf
    return sum((weights[n] * v for n, v in sums.items()),
               jnp.zeros((), jnp.float32))


_OVER_LAYERS = {"max": jnp.max, "min": jnp.min, "total": jnp.sum}
# The scalars sown before PR 44's rule, by the name each was sown under: the
# names it is published by (where they are not its own), and the order in
# which the step has always reduced them, so that its lowered text stays
# what tests/test_zaya.py holds by hash. The expert layers' (``moe_stats``):
# assignment counts ([E] a layer), under a share the same of the experts held
# here, how many layers built the worst-case list and how full the built
# lists were; a selection bias's largest magnitude; with one expert a token
# the mean weight it got; a shared expert's gate. The mixers'
# (``mixer_stats``): the gated delta rule's state, decay and write strength,
# the attention layers' output gate, a state-space layer's state,
# differential lambda, ZAYA's key temperature and residual scales.
_SOWN_AS = {
    "group_sizes": ("moe_assignments_total", "moe_expert_load_max",
                    "moe_expert_load_mean"),
    "held_sizes": ("moe_local_assignments_total", "moe_local_load_max",
                   "moe_local_load_mean"),
    "over_usual": ("moe_local_fallback_total",),
    "row_fill": ("moe_local_row_fill_pct",),
    "bias_abs_max": ("moe_router_bias_abs_max",),
    "top1_prob": ("router_top1_prob_mean",),
    "shared_gate_mean": ("shared_gate_mean",),
    "delta_state_abs_max": ("delta_state_abs_max",),
    "delta_decay_min": ("delta_decay_min",),
    "delta_beta_mean": ("delta_beta_mean",),
    "attn_gate": ("attn_gate_mean",),
    "ssm_state_abs_max": ("ssm_state_abs_max",),
    "diff_lambda": ("diff_lambda_min", "diff_lambda_max"),
    "cca_key_temperature": ("cca_key_temperature_max",),
    "residual_scale": ("residual_scale_min", "residual_scale_max"),
}


def _step_stats(sown: dict) -> dict:
    """The step's scalars from what the layers sowed into ``moe_stats`` and
    ``mixer_stats``: each is published under the name it was sown by, its
    values (one a layer that sowed it) reduced as the name ends, ``_max`` to
    the largest, ``_min`` the least, ``_total`` the sum, anything else
    (``_mean``, ``_pct``) the mean. So a layer that sows a new scalar edits
    nothing here."""
    by_name: dict = {}
    for collection in ("moe_stats", "mixer_stats"):
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                sown.get(collection, {})):
            by_name.setdefault(path[-2].key, []).append(leaf)
    out = {}
    for sown_as in [*(n for n in _SOWN_AS if n in by_name),
                    *(n for n in by_name if n not in _SOWN_AS)]:
        values = jnp.stack(by_name[sown_as]).astype(jnp.float32)
        for name in _SOWN_AS.get(sown_as, (sown_as,)):
            out[name] = _OVER_LAYERS.get(name.rsplit("_", 1)[-1],
                                         jnp.mean)(values)
    return out


def _layer_span(span: Optional[str], model) -> dict:
    """``--layer_span first:end`` as the stack's fields: the published
    layers ``[first, end)`` of a preset whose layers differ by kind."""
    if span is None:
        return {}
    kinds = getattr(model, "layer_kinds", ())
    if not kinds:
        raise ValueError("layer_span states which published layers of a "
                         "preset with layers of several kinds are held; "
                         "--num_layers cuts the others")
    try:
        first, end = (int(x) for x in span.split(":"))
    except ValueError:
        raise ValueError(f"layer_span is 'first:end', got {span!r}") from None
    if not 0 <= first < end <= len(kinds):
        raise ValueError(f"layer_span {span} is not inside the preset's "
                         f"{len(kinds)} layers")
    return {"first_layer": first, "num_layers": end - first}


def _expert_share(share: Optional[str], experts: int) -> tuple:
    """``--expert_share r/n`` as DroplessMoE's fields: rank ``r`` of ``n``
    that share each expert layer holds the ``E / n`` experts from ``r * E /
    n`` on."""
    if share is None:
        return ()
    try:
        rank, ranks = (int(x) for x in share.split("/"))
    except ValueError:
        raise ValueError(f"expert_share is 'rank/ranks', got {share!r}") \
            from None
    if not 0 <= rank < ranks or experts % ranks:
        raise ValueError(
            f"expert_share {share}: rank in [0, ranks), and ranks divides "
            f"the preset's {experts} experts")
    held = experts // ranks
    return (("first_expert", rank * held), ("held_experts", held))


@jax.custom_vjp
def _last_position_unlearned(logits):
    """``[B, S, V]`` logits as they are; their cotangent with the last
    position's row at exactly 0. That position has no target, and what the
    log-sum-exp's backward makes of a weight of 0 there is 0 x softmax: nan
    where the logits are not finite. Written as an update of that one row,
    in place, and not as a select over the grid: XLA fuses a select into both
    of the head's backward products and takes the softmax's exponentials
    once more in each (2 ms of the OLMoE cell's step; chip, PR 36), where
    the update leaves one cotangent written once in the products' bf16."""
    return logits


_last_position_unlearned.defvjp(
    lambda logits: (logits, None),
    lambda _, ct: (ct.at[:, -1].set(0),))


def _causal_lm_task(vocab_size: Optional[int], model_name: str, seq_len: int,
                    attention_fn: Optional[Callable] = None,
                    remat: bool = False, num_experts: int = 0,
                    moe_every: int = 2, num_layers: int = 0,
                    expert_share: Optional[str] = None,
                    layer_span: Optional[str] = None) -> Task:
    """Decoder-only next-token prediction (``CAUSAL_LMS``: the GPT presets
    on the encoder trunk, the others on the decoder stack) over the same packed
    token columns as masked-LM (``create_text_token_dataset``) — the text arm
    beyond the reference's vision-only scope, sharing the trainer, samplers
    and storage unchanged. The shift by one token is applied to the targets
    and their weights and never to the ``[B, S, V]`` logits: a slice of
    ``S - 1`` rows makes XLA re-lay them (and their cotangent) off the
    layout the head wrote, in loops where ``V`` is no multiple of 128."""
    if model_name not in CAUSAL_LMS:
        raise ValueError(f"Invalid model name: {model_name} "
                         f"(have {sorted(CAUSAL_LMS)})")
    ctor, own_vocab, aux_weights = CAUSAL_LMS[model_name]
    model = ctor(vocab_size=vocab_size or own_vocab,
                 attention_fn=attention_fn, remat=remat)
    decoder = isinstance(model, TransformerDecoder)  # no table to size by
    # seq_len. Every layer but the leading dense ones has dropless experts
    dropless = decoder and model.num_experts > 0
    if num_layers and layer_span is not None:
        raise ValueError("num_layers and layer_span both state the depth")
    changes = {**_depth(num_layers), **_layer_span(layer_span, model)}
    if expert_share is not None and not dropless:
        raise ValueError("expert_share states which of a dropless "
                         "preset's experts are held")
    if decoder:
        if num_experts:
            raise ValueError(
                f"{model_name} has its own "
                f"{'expert ' if dropless else ''}layers; --num_experts "
                "adds switch experts to the BERT/GPT presets only")
        if dropless:
            changes["moe"] = model.moe + _expert_share(
                expert_share, model.num_experts)
    else:
        changes.update(max_len=seq_len, num_experts=num_experts,
                       moe_every=moe_every)
    model = model.clone(**changes)
    kernels = model.kernels(seq_len)  # a span that cannot run is refused here
    # All but attention's (a gauge the trainer sets once, on the host) and
    # the gated norm's (the first log line says it; no gauge) ride the
    # step's stats as constants: ``<name>_fused``, the scan's under the name
    # it has had
    fused = {{"scan": "ssm_scan_fused"}.get(name, f"{name}_fused"): on
             for name, on in kernels.items()
             if name not in ("attention", "norm")}
    sows = (["aux_loss", "moe_stats", "router_state", "mixer_stats"]
            if decoder else ["aux_loss"] if num_experts > 0 else [])

    def init_variables(rng):
        ids = jnp.zeros((1, seq_len), jnp.int32)
        variables = model.init(rng, ids, jnp.ones((1, seq_len), jnp.int8),
                               train=False)
        if not decoder:
            return variables
        # what the layers sow at init is not state; the routers' selection
        # bias is, and rides where a train state keeps a model's
        # non-trainable collection
        return {"params": variables["params"],
                **({"batch_stats": variables["router_state"]}
                   if "router_state" in variables else {})}

    def forward(variables, batch, train, rng):
        ids = batch["input_ids"].astype(jnp.int32)
        mask = batch["attention_mask"]
        # Packed batches: segments gate the (already causal) attention at
        # sequence boundaries; positions restart per packed sequence.
        seg = batch.get("segment_ids")
        pos = batch.get("position_ids")
        if dropless and "batch_stats" in variables:
            variables = {"params": variables["params"],
                         "router_state": variables["batch_stats"]}
        if train and sows:
            logits, sown = model.apply(
                variables, ids, mask, train=True, mutable=sows,
                segment_ids=seg, position_ids=pos,
            )
            aux = _weighted_aux(sown.get("aux_loss", {}), aux_weights)
            if not decoder:
                return (logits, aux), None
            # the bias as the routers left it: the step's new state
            state = ({"batch_stats": sown["router_state"]}
                     if "router_state" in sown else None)
            stats = {name: jnp.float32(on) for name, on in fused.items()}
            return (logits, aux, {**stats, **_step_stats(sown)}), state
        logits = model.apply(variables, ids, mask, train=train,
                             segment_ids=seg, position_ids=pos)
        return (logits, jnp.zeros((), jnp.float32)), None

    def _shifted(outputs, batch):
        logits, aux = outputs[:2]
        ids = batch["input_ids"].astype(jnp.int32)
        # Predict token t+1 from positions <= t; weight by the target's
        # validity so padding after a final partial pack contributes nothing.
        targets = ids[:, 1:]
        w = batch["attention_mask"][:, 1:].astype(jnp.float32)
        seg = batch.get("segment_ids")
        if seg is not None:
            # Packed rows: a position whose target belongs to a DIFFERENT
            # packed sequence is a junction, not a prediction — weight it
            # out, so the packed loss matches per-sequence semantics.
            w = w * (seg[:, 1:] == seg[:, :-1]).astype(jnp.float32)
        # The shift lives in these two [B, S] arrays: the last position has
        # no target, so it gets one of weight 0 and the logits stay whole.
        last = ((0, 0), (0, 1))
        return logits, jnp.pad(targets, last), jnp.pad(w, last), aux

    def loss(outputs, batch):
        logits, targets, w, aux = _shifted(outputs, batch)
        raw = optax.softmax_cross_entropy_with_integer_labels(
            _last_position_unlearned(logits), targets)
        # selected, not multiplied: the last position's term is not the
        # loss's even where its logits are not finite
        return (jnp.where(w > 0, raw * w, 0.0).sum()
                / jnp.maximum(w.sum(), 1.0) + aux)

    def metric(outputs, batch):
        logits, targets, w, _aux = _shifted(outputs, batch)
        hit = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
        return (hit * w).sum(-1) / jnp.maximum(w.sum(-1), 1.0)

    return Task("causal_lm", model, init_variables, forward, loss, metric,
                metric_name="next_token_accuracy",
                stats=(lambda outputs: outputs[2]) if decoder else None,
                kernels=kernels)


# ------------------------------------------------------- pipelined masked LM
_BERT_DIMS = {
    # (hidden, layers, heads, mlp_dim) — mirrors bert_base / bert_small.
    "bert_base": (768, 12, 12, 3072),
    "bert_small": (256, 4, 4, 1024),
}


def _pipelined_masked_lm_task(
    vocab_size: Optional[int],
    model_name: str,
    seq_len: int,
    mesh,
    n_microbatches: int,
    mask_prob: float = 0.15,
    mask_id: int = 1,
    dtype=jnp.bfloat16,
) -> Task:
    """Masked-LM with the encoder stack run through the GPipe pipeline
    (:mod:`..parallel.pipeline_parallel`) over the mesh's ``'pipe'`` axis.

    The L encoder blocks' params are stacked ``[L, ...]`` and sharded
    ``P('pipe')`` (each stage holds ``L/pp`` layers and scans them);
    embedding/head stay replicated outside the pipeline. Designed for PACKED
    sequences (the C4 config,
    :func:`..data.authoring.create_text_token_dataset` with ``pack=True``):
    attention runs unmasked inside the pipeline, so padded rows should be
    rare (only a dataset's final partial pack); the MLM loss still respects
    ``attention_mask``.
    """
    from ..parallel.pipeline_parallel import pipeline_apply, stack_stage_params
    from .transformer import EncoderBlock

    if model_name not in _BERT_DIMS:
        raise ValueError(f"Invalid model name: {model_name} "
                         f"(have {sorted(_BERT_DIMS)})")
    hidden, layers, heads, mlp_dim = _BERT_DIMS[model_name]
    vocab = vocab_size or 30522
    pp = mesh.shape.get("pipe", 1)
    if layers % pp:
        raise ValueError(f"{layers} layers not divisible by pipe={pp}")
    block = EncoderBlock(num_heads=heads, mlp_dim=mlp_dim, dtype=dtype)

    def init_variables(rng):
        rngs = jax.random.split(rng, layers + 2)
        dummy = jnp.zeros((1, seq_len, hidden), dtype)
        blocks = stack_stage_params(
            [block.init(rngs[i], dummy)["params"] for i in range(layers)]
        )
        init = jax.nn.initializers.normal(0.02)
        return {
            "params": {
                "blocks": blocks,
                "tok_embed": init(rngs[-2], (vocab, hidden), jnp.float32),
                "pos_embed": init(rngs[-1], (seq_len, hidden), jnp.float32),
                "ln_scale": jnp.ones((hidden,), jnp.float32),
                "ln_bias": jnp.zeros((hidden,), jnp.float32),
            }
        }

    def stage_fn(stage_params, h):
        return jax.lax.scan(
            lambda carry, q: (block.apply({"params": q}, carry, None), None),
            h,
            stage_params,
        )[0]

    def forward(variables, batch, train, rng):
        p = variables["params"]
        ids = batch["input_ids"].astype(jnp.int32)
        valid = batch["attention_mask"] > 0
        if train and rng is not None:
            mlm_mask = jax.random.bernoulli(rng, mask_prob, ids.shape) & valid
        else:
            stride = max(int(round(1.0 / mask_prob)), 1)
            positions = jnp.arange(ids.shape[1])
            mlm_mask = ((positions % stride) == 0)[None, :] & valid
        corrupted = jnp.where(mlm_mask, mask_id, ids)
        x = p["tok_embed"][corrupted].astype(dtype)
        x = x + p["pos_embed"][None, : ids.shape[1]].astype(dtype)
        x = pipeline_apply(stage_fn, p["blocks"], x, mesh, n_microbatches)
        x32 = x.astype(jnp.float32)
        mean = x32.mean(-1, keepdims=True)
        var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
        x32 = (x32 - mean) / jnp.sqrt(var + 1e-6) * p["ln_scale"] + p["ln_bias"]
        logits = x32 @ p["tok_embed"].T  # tied head
        return (logits, mlm_mask, jnp.zeros((), jnp.float32)), None

    def loss(outputs, batch):
        logits, mlm_mask, _aux = outputs
        targets = batch["input_ids"].astype(jnp.int32)
        raw = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        w = mlm_mask.astype(jnp.float32)
        return (raw * w).sum() / jnp.maximum(w.sum(), 1.0)

    def metric(outputs, batch):
        logits, mlm_mask, _aux = outputs
        targets = batch["input_ids"].astype(jnp.int32)
        hit = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
        w = mlm_mask.astype(jnp.float32)
        return (hit * w).sum(-1) / jnp.maximum(w.sum(-1), 1.0)

    return Task("masked_lm_pp", block, init_variables, forward, loss, metric,
                metric_name="masked_token_accuracy")


# ---------------------------------------------------------------- contrastive
def _contrastive_task(model_name: str, image_size: int, seq_len: int,
                      vocab_size: Optional[int], augment: bool = True) -> Task:
    ctor = {"clip_resnet50_bert": clip_resnet50_bert, "clip_tiny": clip_tiny}.get(
        model_name
    )
    if ctor is None:
        raise ValueError(f"Invalid model name: {model_name} "
                         "(have ['clip_resnet50_bert', 'clip_tiny'])")
    # vocab_size=None → the preset's own default (clip_tiny: 1000,
    # clip_resnet50_bert: 30522); an explicit value always wins.
    kwargs = {"max_len": seq_len}
    if vocab_size is not None:
        kwargs["vocab_size"] = vocab_size
    model: CLIP = ctor(**kwargs)

    def init_variables(rng):
        return model.init(
            rng,
            jnp.zeros((2, image_size, image_size, 3), jnp.float32),
            jnp.zeros((2, seq_len), jnp.int32),
            jnp.ones((2, seq_len), jnp.int8),
            train=False,
        )

    def forward(variables, batch, train, rng):
        images = normalize_images(batch["image"])
        if train and augment and rng is not None:
            images = random_flip(rng, images)
        if train:
            out, new_state = model.apply(
                variables, images, batch["input_ids"].astype(jnp.int32),
                batch["attention_mask"], train=True, mutable=["batch_stats"],
            )
            return out, new_state
        out = model.apply(
            variables, images, batch["input_ids"].astype(jnp.int32),
            batch["attention_mask"], train=False,
        )
        return out, None

    def loss(outputs, batch):
        img_emb, txt_emb, scale = outputs
        return clip_contrastive_loss(img_emb, txt_emb, scale)

    def metric(outputs, batch):
        img_emb, txt_emb, scale = outputs
        logits = img_emb @ txt_emb.T
        return (jnp.argmax(logits, -1) == jnp.arange(logits.shape[0])).astype(
            jnp.float32
        )

    return Task("contrastive", model, init_variables, forward, loss, metric,
                metric_name="retrieval_top1")


def get_task(
    task_type: str,
    *,
    num_classes: int = 101,
    model_name: Optional[str] = None,
    image_size: int = 224,
    seq_len: int = 128,
    vocab_size: Optional[int] = None,
    augment: bool = True,
    attention_fn: Optional[Callable] = None,
    remat: bool = False,
    num_experts: int = 0,
    moe_every: int = 2,
    pipeline_parallelism: int = 1,
    pp_microbatches: int = 4,
    mesh=None,
    param_dtype=None,
    num_layers: int = 0,
    expert_share: Optional[str] = None,
    layer_span: Optional[str] = None,
) -> Task:
    """``vocab_size=None`` means "the model's own default" (bert_*: 30522,
    a causal_lm preset: its record's in ``transformer.CAUSAL_LMS``,
    clip_tiny: 1000, clip_resnet50_bert: 30522);
    explicit values always apply verbatim.
    ``param_dtype`` overrides the parameter/optimizer-state dtype (ResNet
    family only; e.g. ``jnp.bfloat16`` halves weight HBM). ``num_layers``
    overrides a transformer preset's depth (0 keeps it): one chip's share
    of a published model is a few of its layers at every published width,
    and ``expert_share`` (``"rank/ranks"``, the dropless causal_lm presets)
    the experts of each layer that this rank of an expert-parallel job
    holds; ``layer_span`` (``"first:end"``, the presets whose layers differ
    by kind) the published layers a pipeline stage holds;
    with ``vocab_size`` as its slice of the vocabulary that is the share a
    configuration states."""
    if expert_share is not None and task_type != "causal_lm":
        raise ValueError("expert_share applies to the causal_lm presets "
                         "with dropless expert layers")
    if layer_span is not None and task_type != "causal_lm":
        raise ValueError("layer_span applies to the causal_lm presets whose "
                         "layers differ by kind")
    if num_layers and (task_type not in ("masked_lm", "causal_lm")
                       or pipeline_parallelism > 1):
        raise ValueError(
            "num_layers applies to the masked_lm and causal_lm transformer "
            "presets (without pipeline_parallelism)")
    if task_type == "classification":
        return _classification_task(
            num_classes, model_name or "resnet50", image_size, augment,
            param_dtype=param_dtype,
        )
    if (task_type in ("masked_lm", "causal_lm") and attention_fn is None
            and pipeline_parallelism <= 1):
        # Nobody chose an attention path (no --flash_attention, no ring):
        # the fused kernel where the shapes each call sees and the mesh
        # allow it, dense attention elsewhere (ops/flash.py has the rule).
        from ..ops.flash import make_flash_attention

        attention_fn = make_flash_attention(
            causal=task_type == "causal_lm", mesh=mesh, forced=False)
    if task_type == "masked_lm":
        if pipeline_parallelism > 1:
            if attention_fn is not None or num_experts:
                raise ValueError(
                    "pipeline_parallelism composes with dp only "
                    "(not seq/flash/moe) in this release"
                )
            return _pipelined_masked_lm_task(
                vocab_size, model_name or "bert_base", seq_len, mesh,
                pp_microbatches,
            )
        return _masked_lm_task(vocab_size, model_name or "bert_base", seq_len,
                               attention_fn=attention_fn, remat=remat,
                               num_experts=num_experts, moe_every=moe_every,
                               num_layers=num_layers)
    if task_type == "causal_lm":
        if pipeline_parallelism > 1:
            raise ValueError(
                "pipeline_parallelism supports masked_lm only in this release"
            )
        return _causal_lm_task(vocab_size, model_name or "gpt_base", seq_len,
                               attention_fn=attention_fn, remat=remat,
                               num_experts=num_experts, moe_every=moe_every,
                               num_layers=num_layers,
                               expert_share=expert_share,
                               layer_span=layer_span)
    if task_type == "contrastive":
        return _contrastive_task(
            model_name or "clip_resnet50_bert", image_size, seq_len,
            vocab_size, augment=augment,
        )
    # Error-message parity: modelling/get_model_and_loss.py:10-11.
    raise ValueError(f"Invalid task type: {task_type}")


TASK_REGISTRY = ("classification", "masked_lm", "causal_lm", "contrastive")
