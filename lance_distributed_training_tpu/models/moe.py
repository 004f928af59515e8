"""Feed-forward layers of the transformer stacks: a plain SwiGLU and two
Mixture-of-Experts layers.

* :class:`SwiGLU` — ``down(silu(gate(x)) · up(x))``, no biases: a decoder's
  dense layer and an expert layer's shared expert.
* :class:`MoEMLP` — the **sharded** form (``--num_experts`` on the BERT/GPT
  presets): top-1 switch routing with a per-expert capacity, expressed
  entirely as einsums over a dense ``[T, E, C]`` dispatch tensor, so the SPMD
  partitioner shards the expert dimension over the mesh's ``'model'`` axis
  (``MOE_RULES`` in :mod:`..parallel.sharding`) and the dispatch einsum
  becomes the expert all-to-all. Overflow tokens skip the layer, and the
  dispatch tensors grow with ``T * E * C``: right for a few experts over a
  mesh, impossible at a published sparse model's shape (8,192 tokens, 64
  experts, 8 a token: terabytes).
* :class:`DroplessMoE` — the **published-shape** form (the OLMoE,
  Moonlight, ZAYA1, Qwen3-Next and SmallThinker presets): top-k routing
  with no capacity and no dropped token; the ``T * k`` assignments are sorted
  by expert, the tokens gathered, the
  three SwiGLU products run as grouped matrix multiplications over the
  ragged groups (:func:`..ops.grouped.grouped_product`:
  ``jax.lax.ragged_dot``, or on one TPU device, at a shape its table has, the
  Pallas grouped matmul and the gauge ``grouped_products_fused`` says), and
  each token's k results
  gathered back through the inverse permutation and summed with their
  weights. Nothing larger than ``[T * k, width]`` exists. It holds all of
  its experts (OLMoE on one chip) or is told which contiguous range of them
  it holds (one rank's share of an expert-parallel layer: the router stays
  whole, the layer computes what its own experts give and leaves out what
  the absent ones would add; the exchange that brings a deployment's rank
  the other ranks' tokens is not here: ROADMAP D12; its rows -> tokens sum
  is :func:`..ops.rows.sum_rows`: a loop over the k slots off the TPU, under
  a mesh, with one expert a token and for the worst-case list, one sort, one
  gather and a Pallas kernel that reads each built row once for the usual
  list on one TPU device). Its router is one
  product of its own, or the logits the caller hands it:
* :class:`StateRouter` — ZAYA1's, an MLP over a narrow state that is mixed
  with the previous layer's and handed on to the next.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.grouped import grouped_product
from ..ops.rows import Way as _Way, sum_rows

__all__ = ["SwiGLU", "MoEMLP", "DroplessMoE", "StateRouter",
           "router_product"]


class SwiGLU(nn.Module):
    """``down(silu(gate(x)) · up(x))`` of width ``mlp_dim``, no biases: bf16
    operands on the matrix unit, f32 parameters."""

    mlp_dim: int
    dtype: Any = jnp.bfloat16
    kernel_init: Callable = nn.linear.default_kernel_init

    @nn.compact
    def __call__(self, x):
        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            param_dtype=jnp.float32,
                            kernel_init=self.kernel_init, name=name)

        y = nn.silu(dense(self.mlp_dim, "gate")(x)) * dense(
            self.mlp_dim, "up")(x)
        return dense(x.shape[-1], "down")(y)


class MoEMLP(nn.Module):
    """Switch-routed expert MLP: ``[B, S, H] -> [B, S, H]``.

    Capacity note: tokens beyond an expert's queue contribute zero to the
    output (their dispatch weight is masked), which with the transformer's
    residual connection means they simply skip the MLP — the standard
    overflow behavior.
    """

    num_experts: int
    mlp_dim: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, h = x.shape
        t = b * s
        e = self.num_experts
        capacity = max(1, int(self.capacity_factor * t / e))
        tokens = x.reshape(t, h)

        # Router in f32 for a stable softmax.
        logits = nn.Dense(e, dtype=jnp.float32, param_dtype=jnp.float32,
                          name="router")(tokens.astype(jnp.float32))
        probs = nn.softmax(logits, axis=-1)  # [T, E]
        expert_index = jnp.argmax(probs, axis=-1)  # [T]
        expert_prob = jnp.max(probs, axis=-1)  # gate value of the winner

        onehot = jax.nn.one_hot(expert_index, e, dtype=jnp.float32)  # [T, E]
        # Position of each token in its expert's queue (1-based), then mask
        # out tokens past capacity — all static shapes.
        position = jnp.cumsum(onehot, axis=0) * onehot  # [T, E]
        within = (position > 0) & (position <= capacity)
        pos_onehot = jax.nn.one_hot(
            (position - 1.0).astype(jnp.int32), capacity, dtype=jnp.float32
        )  # [T, E, C]
        dispatch = pos_onehot * within[..., None].astype(jnp.float32)
        combine = dispatch * expert_prob[:, None, None]

        # Expert queues: [E, C, H] — the einsum the partitioner turns into
        # the expert all-to-all when E is sharded.
        expert_in = jnp.einsum(
            "tec,th->ech", dispatch.astype(self.dtype), tokens.astype(self.dtype)
        )
        w_in = self.param(
            "w_in", nn.initializers.lecun_normal(), (e, h, self.mlp_dim),
            jnp.float32,
        )
        b_in = self.param("b_in", nn.initializers.zeros_init(),
                          (e, self.mlp_dim), jnp.float32)
        w_out = self.param(
            "w_out", nn.initializers.lecun_normal(), (e, self.mlp_dim, h),
            jnp.float32,
        )
        b_out = self.param("b_out", nn.initializers.zeros_init(), (e, h),
                           jnp.float32)
        hidden = nn.gelu(
            jnp.einsum("ech,ehm->ecm", expert_in, w_in.astype(self.dtype))
            + b_in[:, None, :].astype(self.dtype)
        )
        expert_out = (
            jnp.einsum("ecm,emh->ech", hidden, w_out.astype(self.dtype))
            + b_out[:, None, :].astype(self.dtype)
        )
        y = jnp.einsum(
            "tec,ech->th", combine.astype(self.dtype), expert_out
        ).reshape(b, s, h)

        # Switch load-balance loss: E * Σ_e (fraction routed to e) ×
        # (mean router prob of e); minimised by uniform routing.
        frac = onehot.mean(axis=0)
        mean_prob = probs.mean(axis=0)
        self.sow("aux_loss", "load_balance",
                 e * jnp.sum(frac * mean_prob))
        return y


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """``x[perm]`` for a permutation ``perm`` of the rows with its
    ``inverse``: the cotangent is ``g[inverse]``, a gather again, where the
    transpose of a plain gather is a scatter-add that cannot know its
    indices are distinct."""
    return jnp.take(x, perm, axis=0)


_permute_rows.defvjp(
    lambda x, perm, inverse: (jnp.take(x, perm, axis=0), (perm, inverse)),
    lambda res, g: (jnp.take(g, res[1], axis=0), None, None))


@jax.custom_vjp
def _tokens_to_rows(x, way):
    """The built rows from the tokens ``x`` [T, H]: row r is the token its
    assignment belongs to where the row is live, zeros elsewhere. The
    transpose of :func:`_rows_to_tokens`, and the other way round: each is
    the other's cotangent."""
    k = way.pos.shape[1]
    return jnp.where(way.live[:, None], jnp.take(x, way.head // k, axis=0), 0)


@jax.custom_vjp
def _rows_to_tokens(rows, way):
    """Each token the sum of its live rows, [R, H] -> [T, H], summed in f32
    and returned in the rows' type."""
    return sum_rows(rows, way, dtype=rows.dtype)


_tokens_to_rows.defvjp(
    lambda x, way: (_tokens_to_rows(x, way), way),
    lambda way, g: (_rows_to_tokens(g, way), None))
_rows_to_tokens.defvjp(
    lambda rows, way: (_rows_to_tokens(rows, way), way),
    lambda way, g: (_tokens_to_rows(g, way), None))


@jax.custom_vjp
def _sum_back(out, top_p, way):
    """The weighted sum back, [T, H] f32: the sorted rows ``out`` [R, H]
    each times its assignment's ``top_p`` [T, k] in f32, summed to their
    tokens. Its cotangents come from the R rows that :func:`_tokens_to_rows`
    makes of the ``[T, H]`` one, and ``top_p``'s is a gather of R dot
    products: nothing is ``[T, k, H]``."""
    return sum_rows(out, way, top_p)


def _sum_back_bwd(res, g):
    out, top_p, way = res
    g_rows = _tokens_to_rows(g, way)  # [R, H] f32
    d_out = jnp.take(top_p.reshape(-1), way.head)[:, None] * g_rows
    d_row = (out.astype(jnp.float32) * g_rows).sum(-1)  # [R]
    d_top_p = jnp.where(way.valid, jnp.take(
        d_row, jnp.minimum(way.pos, out.shape[0] - 1)), 0)
    return d_out.astype(out.dtype), d_top_p.astype(top_p.dtype), None


_sum_back.defvjp(
    lambda out, top_p, way: (_sum_back(out, top_p, way), (out, top_p, way)),
    _sum_back_bwd)


def router_product(features: int, kernel_init, name: str):
    """A bias-free product of a router (this file's, and the one a decoder
    block makes of its input where the router reads ahead of attention), f32
    throughout: on a TPU an f32 product at default precision is one bf16
    pass, and the eighth and ninth expert of a token are often closer than
    that."""
    return nn.Dense(features, use_bias=False, dtype=jnp.float32,
                    param_dtype=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST,
                    kernel_init=kernel_init, name=name)


class _RouterMLP(nn.Module):
    """:class:`StateRouter`'s norm and three layers, f32: ``r -> logits``."""

    num_experts: int
    width: int
    norm_eps: float
    kernel_init: Callable

    @nn.compact
    def __call__(self, r):
        from .transformer import RMSNorm

        y = RMSNorm(self.norm_eps, jnp.float32, name="norm")(r)
        for name in ("mlp_1", "mlp_2"):
            y = nn.gelu(router_product(self.width, self.kernel_init, name)(y),
                        approximate=False)
        return router_product(self.num_experts, self.kernel_init, "mlp_3")(y)


class StateRouter(nn.Module):
    """ZAYA's router (arXiv:2511.17127): the normed tokens go down to
    ``width`` dimensions, the state the previous layer's router left is
    mixed in channel by channel, and a three-layer MLP over the normed state
    gives the ``num_experts`` logits:

        r_l = u W_r + g_l * r_{l-1}
        logits = W_3 gelu(W_2 gelu(W_1 rms(r_l)))

    ``(u [B, S, H], r_{l-1} [B, S, width] or None: zeros) -> (logits [B, S,
    E], r_l)``, both f32; no biases. :class:`DroplessMoE` takes the logits in
    place of its own product's; the caller hands ``r_l`` to the next layer.
    The backward pass keeps ``r_l`` and makes the MLP's six f32 arrays of
    ``[T, width]`` again (0.23 GiB of a six-layer stack at 8,192 tokens)."""

    num_experts: int
    width: int
    norm_eps: float = 1e-5
    kernel_init: Callable = nn.linear.default_kernel_init

    @nn.compact
    def __call__(self, u, carried=None):
        r = router_product(self.width, self.kernel_init, "down")(
            u.astype(jnp.float32))
        mix = self.param("depth_mix", nn.initializers.ones_init(),
                         (self.width,), jnp.float32)
        if carried is not None:  # the first layer held: r_{-1} = 0
            r = r + mix * carried
        return nn.remat(_RouterMLP)(self.num_experts, self.width,
                                    self.norm_eps, self.kernel_init,
                                    name="mlp")(r), r


class DroplessMoE(nn.Module):
    """Top-k routed SwiGLU experts without capacity: ``[B, S, H] -> [B, S, H]``,
    ``Σ_k w_k · down_k(silu(gate_k(x)) · up_k(x))`` over each token's
    ``experts_per_token`` chosen experts (``activation`` ``"relu"``: ReLU in
    SiLU's place, SmallThinker's ReGLU), no biases, plus ``shared(x)``
    where ``shared_dim`` > 0 (one :class:`SwiGLU` every token passes; under
    ``shared_gate`` times ``sigmoid(x w_g)``, Qwen3-Next's).

    The logits are the layer's own (one bias-free f32 product, ``router``)
    or, where the call is given ``logits`` [B, S, E], the caller's (ZAYA1's
    :class:`StateRouter`, whose state rides between layers; SmallThinker's
    product of the block's input, made ahead of attention). Two scorings.
    ``"softmax"`` (OLMoE, ZAYA1, Qwen3-Next): the k largest router
    probabilities, weighted by the softmax values themselves, divided by
    their sum under ``norm_topk`` (Qwen3-Next). ``"sigmoid"`` (the
    DeepSeek-V3 family, Moonlight): ``s = sigmoid(logits)``, the weights the
    chosen ``s`` divided by their sum under ``norm_topk`` and times
    ``routed_scale``. Under either,
    ``bias_update_rate`` > 0 (Moonlight, ZAYA1) chooses the k largest of ``s +
    b`` where ``b`` is a selection bias that takes no gradient
    (``router_state``/``bias``; it moves against the load by that rate after
    each training step, over the tokens this layer saw); the weights never
    see ``b``.

    ``held_experts`` > 0 tells the layer which experts it holds: that many
    from ``first_expert`` on, one rank's share of an expert-parallel layer.
    The router keeps its ``num_experts`` outputs and its k a token; the
    layer has the held experts' matrices only, sorts the held assignments to
    the front of the list, multiplies those, and gives the rest no weight:
    what the absent experts would add is left out of the result.

    Static in shape: every token has exactly k assignments, so the sorted
    list has ``T * k`` rows whatever the routing; only the group sizes are
    data. Under a share only the rows in a held expert's group are real
    (``k * held / E`` a token at even routing), so the layer builds twice
    that many rows, and in a step whose routing sends more than that here
    it builds all ``T * k`` instead, the bound no routing exceeds (a
    ``lax.cond`` on the count: exact either way, no token is ever dropped;
    ``moe_stats``/``over_usual`` says which, ``row_fill`` how much of the
    built list was live; where twice an even share is already ``T * k``, one
    expert a token with half of them held, there is the one list and no
    ``cond``; where the worst case is more than four usual lists, a
    sixteenth of ten experts a token held, it is built ``usual`` rows at a
    time and no array of ``T * k`` rows exists). Between the ``[T, H]``
    tokens and the ``[R, H]``
    built rows lie two operations that are each other's transposes: tokens
    -> rows (a live row is its token, a dead one zeros) and rows -> tokens
    (a token is the sum of its live rows, in f32: :mod:`..ops.rows`, slot
    after slot in a loop that gathers and masks ``[T, H]`` k times, or, for
    the usual list on one TPU device, the rows sorted by token and summed by
    a kernel that reads each once: ``rows_sum_applies`` chooses from the
    shapes, and the gauge ``rows_sum_fused`` says). The sum
    back weights each row by its assignment's ``top_p`` in f32 on the way, and
    its cotangents come from the R rows of the ``[T, H]`` one: nothing
    shaped ``[T, k, H]`` is gathered, multiplied or broadcast, forward or
    backward. The gather, the products and the sum back are recomputed in
    the backward pass, so that a layer keeps its ``[T, H]`` input and not
    the rows of every intermediate.

    Sows its auxiliary terms into ``aux_loss`` (softmax: the OLMoE paper's ``load_balance`` = ``E · Σ_e f_e
    · P_e`` and ``router_z`` = ``mean(logsumexp(logits)²)``; sigmoid: the
    sequence-wise ``seq_balance``, the same product with ``P`` the scores
    over their sum, per row and averaged; all over live tokens, unweighted)
    and into ``moe_stats`` the experts' assignment counts (``group_sizes``,
    all ``num_experts``), under a share those of the held experts
    (``held_sizes``, ``over_usual``, ``row_fill``), with a bias its
    largest magnitude (``bias_abs_max``), and with one expert a token the mean
    weight it got (``top1_prob``); ``live`` [B, S] marks the tokens that count
    (None: all).
    """

    num_experts: int
    expert_dim: int
    experts_per_token: int
    dtype: Any = jnp.bfloat16
    kernel_init: Callable = nn.initializers.lecun_normal()
    scoring: str = "softmax"
    norm_topk: bool = False
    routed_scale: float = 1.0
    bias_update_rate: float = 0.0  # > 0: the selection bias and its update
    shared_dim: int = 0
    shared_gate: bool = False  # the shared expert times sigmoid(x w_g)
    first_expert: int = 0
    held_experts: int = 0  # 0: all of them
    activation: str = "silu"  # the experts' gate: "silu" (SwiGLU) or "relu"

    @nn.compact
    def __call__(self, x, live=None, logits=None):
        b, s, h = x.shape
        t, e, k = b * s, self.num_experts, self.experts_per_token
        first, held = self.first_expert, self.held_experts or e
        a_share = held < e  # one rank's share: some assignments are absent
        tokens = x.reshape(t, h)
        w_gate, w_up, w_down = (
            self.param(name, self.kernel_init, shape, jnp.float32)
            for name, shape in (("w_gate", (held, h, self.expert_dim)),
                                ("w_up", (held, h, self.expert_dim)),
                                ("w_down", (held, self.expert_dim, h))))
        w = (jnp.ones((t,), jnp.float32) if live is None
             else live.reshape(t).astype(jnp.float32))

        with jax.named_scope("moe.router"):
            if logits is None:  # the layer's own router: one product
                logits = router_product(e, self.kernel_init, "router")(
                    tokens.astype(jnp.float32))
            else:
                logits = logits.reshape(t, e)
            softmax = self.scoring == "softmax"
            probs = (nn.softmax(logits, axis=-1) if softmax
                     else nn.sigmoid(logits))  # [T, E]
            if self.bias_update_rate > 0:
                # chosen by s + b, weighted by s alone
                bias = self.variable("router_state", "bias", jnp.zeros,
                                     (e,), jnp.float32)
                top_e = jax.lax.top_k(probs + bias.value, k)[1]
                top_p = jnp.take_along_axis(probs, top_e, axis=-1)
            else:
                top_p, top_e = jax.lax.top_k(probs, k)  # [T, k]
            if self.norm_topk:
                top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
            if not softmax:
                top_p = top_p * self.routed_scale

        act = {"silu": nn.silu, "relu": nn.relu}[self.activation]

        def experts(xs, group_sizes, w_gate, w_up, w_down):
            with jax.named_scope("moe.experts"):
                gate = grouped_product(xs, w_gate.astype(self.dtype),
                                       group_sizes)
                up = grouped_product(xs, w_up.astype(self.dtype),
                                     group_sizes)
                return grouped_product(act(gate) * up,
                                       w_down.astype(self.dtype),
                                       group_sizes)

        with jax.named_scope("moe.dispatch"):
            # Stable sort of the T*k assignments by expert: row i of the
            # sorted list is assignment order[i], of token order[i] // k.
            # Under a share the held experts' come first, in the held
            # experts' order, and the absent ones' after every group.
            flat_e = top_e.reshape(t * k)
            if a_share:
                flat_e = jnp.where(
                    (flat_e >= first) & (flat_e < first + held),
                    flat_e - first, held)
            order = jnp.argsort(flat_e, stable=True)
            inverse = jnp.zeros_like(order).at[order].set(
                jnp.arange(t * k, dtype=order.dtype), unique_indices=True)
            ends = jnp.searchsorted(jnp.take(flat_e, order), jnp.arange(held),
                                    side="right").astype(jnp.int32)
            group_sizes = jnp.diff(ends, prepend=0)

        over = jnp.zeros((), jnp.float32)
        fill = jnp.full((), 100.0)  # live rows over built rows, in percent
        if not a_share:
            with jax.named_scope("moe.dispatch"):
                xs = _permute_rows(
                    jnp.repeat(tokens.astype(self.dtype), k, axis=0), order,
                    inverse)
            out = experts(xs, group_sizes, w_gate, w_up, w_down)
            with jax.named_scope("moe.combine"):
                # Each token's k rows come back through the inverse
                # permutation and are summed with their weights in f32: the
                # scatter-add of the sorted rows, without the scatter.
                y = _permute_rows(out, inverse, order).reshape(t, k, h)
                y = (y.astype(jnp.float32) * top_p[..., None]).sum(1)
        else:
            # One rank's share: only the rows in a held expert's group are
            # real, ends[-1] of them, first in the list. ``rows`` of the list
            # are built: the rows past the groups enter as zeros, leave as
            # zeros, and pass no gradient to the tokens they stand for.
            pos = inverse.reshape(t, k)
            valid = pos < ends[-1]

            def with_rows(rows):
                if rows > 4 * usual:
                    return in_pieces(-(-rows // usual))

                def run(tokens, top_p, w_gate, w_up, w_down, order, pos,
                        valid, ends, group_sizes):
                    with jax.named_scope("moe.dispatch"):
                        way = _Way(order[:rows], jnp.arange(rows) < ends[-1],
                                   pos, valid)
                        xs = _tokens_to_rows(tokens.astype(self.dtype), way)
                    out = experts(xs, group_sizes, w_gate, w_up, w_down)
                    with jax.named_scope("moe.combine"):
                        return _sum_back(out, top_p, way)
                return run

            # Twice the rows of an even routing, in whole tiles, and in a
            # step whose routing sends more than that here, the worst case:
            # all T*k, which no routing exceeds. Exact either way. The choice
            # is its own differentiation rule: it keeps its inputs, and the
            # branch taken recomputes its rows in the backward pass (from
            # outside, a ``cond`` hands the backward pass every branch's
            # residuals, the worst case's among them, filled with zeros).
            # Everything traced is an argument, the integers too: a rule
            # that closed over them would leak them under ``--remat``.
            usual = min(-(-2 * t * k * held // (e * 128)) * 128, t * k)
            over = (ends[-1] > usual).astype(jnp.float32)
            fill = 100.0 * ends[-1] / jnp.where(over > 0, t * k, usual)

            def in_pieces(pieces):
                """The worst case where it is more than four usual lists (a
                sixteenth of 10 experts a token held: eight): the whole
                sorted list, ``usual`` rows at a time, each piece the groups'
                part that falls inside it, summed in f32. Exact as the one
                long list is, and no array of ``T * k`` rows exists, in a
                branch that sizes the step's memory and hardly ever runs."""
                def run(tokens, top_p, w_gate, w_up, w_down, order, pos,
                        valid, ends, group_sizes):
                    del group_sizes
                    heads = jnp.pad(order, (0, pieces * usual - t * k))

                    @jax.checkpoint
                    def piece(i, tokens, top_p, w_gate, w_up, w_down):
                        lo = i * usual
                        with jax.named_scope("moe.dispatch"):
                            inside = valid & (pos >= lo) & (pos < lo + usual)
                            way = _Way(
                                jax.lax.dynamic_slice_in_dim(heads, lo, usual),
                                lo + jnp.arange(usual) < ends[-1],
                                jnp.clip(pos - lo, 0, usual - 1), inside)
                            sizes = jnp.diff(jnp.clip(ends, lo, lo + usual),
                                             prepend=lo)
                            xs = _tokens_to_rows(tokens.astype(self.dtype),
                                                 way)
                        out = experts(xs, sizes, w_gate, w_up, w_down)
                        with jax.named_scope("moe.combine"):
                            return _sum_back(out, top_p, way)

                    def add(y, i):
                        return y + piece(i, tokens, top_p, w_gate, w_up,
                                         w_down), None

                    return jax.lax.scan(
                        add, jnp.zeros((t, h), jnp.float32),
                        jnp.arange(pieces))[0]
                return run

            def either(make, inputs, *args):
                """``make(rows)(*args)`` with the worst-case list if the
                step's routing is over the usual one (by the arguments' own
                ``ends``), else with the usual; where twice an even share is
                already every assignment (one expert a token, half of them
                held) there is one list and no ``cond``."""
                if usual == t * k:
                    return make(usual)(*args)
                return jax.lax.cond(inputs[8][-1] > usual, make(t * k),
                                    make(usual), *args)

            @jax.custom_vjp
            def routed(*inputs):
                return either(with_rows, inputs, *inputs)

            def routed_bwd(inputs, g):
                def back(rows):
                    def run(inputs, g):
                        diff, ints = inputs[:5], inputs[5:]
                        return jax.vjp(lambda *d: with_rows(rows)(
                            *d, *ints), *diff)[1](g)
                    return run
                return (*either(back, inputs, inputs, g), *(None,) * 5)

            routed.defvjp(lambda *inputs: (routed(*inputs), inputs),
                          routed_bwd)
            y = routed(tokens, top_p, w_gate, w_up, w_down, order, pos, valid,
                       ends, group_sizes)

        n = jnp.maximum(w.sum(), 1.0)
        if a_share or self.scoring != "softmax":
            # [T, E]: which experts each token chose, held here or not
            chose = jax.nn.one_hot(top_e, e, dtype=jnp.float32).sum(1)
            load = chose.sum(0).astype(jnp.int32)
            live_load = (chose * w[:, None]).sum(0)
        else:
            # all held: the sorted rows' groups are the experts' loads, and
            # the live ones a running sum over them read at the groups' ends
            run = jnp.concatenate([jnp.zeros((1,), jnp.float32), jnp.cumsum(
                jnp.take(w, order // k))])
            load, live_load = group_sizes, run[ends] - run[ends - group_sizes]
        if self.scoring == "softmax":
            # f_e: the share of the live tokens' assignments that went to e;
            # P_e: the mean router probability of e over live tokens.
            frac = live_load / (n * k)
            mean_prob = (probs * w[:, None]).sum(0) / n
            lse = jax.nn.logsumexp(logits, axis=-1)
            self.sow("aux_loss", "load_balance",
                     e * jnp.sum(frac * mean_prob))
            self.sow("aux_loss", "router_z", jnp.sum(lse * lse * w) / n)
        else:
            # per row (a sequence): f the experts' share of its live tokens'
            # assignments times E, P the mean over them of the scores over
            # their sum (DeepSeek-V3, arXiv:2412.19437, section 2.1.2)
            rows_n = jnp.maximum(w.reshape(b, s).sum(1), 1.0)[:, None]
            frac = (chose * w[:, None]).reshape(b, s, e).sum(1) / (rows_n * k)
            share = (probs / probs.sum(-1, keepdims=True) * w[:, None]
                     ).reshape(b, s, e).sum(1) / rows_n
            self.sow("aux_loss", "seq_balance",
                     e * jnp.mean(jnp.sum(frac * share, axis=-1)))
        if self.bias_update_rate > 0:
            self.sow("moe_stats", "bias_abs_max", jnp.abs(bias.value).max())
            if (self.is_mutable_collection("router_state")
                    and not self.is_initializing()):
                # after the step: up where the live load was under the mean
                bias.value = bias.value + self.bias_update_rate * jnp.sign(
                    live_load.mean() - live_load)
        self.sow("moe_stats", "group_sizes", load)
        if k == 1:  # the one expert's weight is the whole layer's scale
            self.sow("moe_stats", "top1_prob", (top_p[:, 0] * w).sum() / n)
        if self.held_experts:
            self.sow("moe_stats", "held_sizes", group_sizes)
            self.sow("moe_stats", "over_usual", over)
            self.sow("moe_stats", "row_fill", fill)
        y = y.astype(self.dtype)
        if self.shared_dim:
            with jax.named_scope("moe.shared"):
                shared = SwiGLU(self.shared_dim, self.dtype, self.kernel_init,
                                name="shared")(tokens)
                if self.shared_gate:
                    gate = nn.sigmoid(nn.Dense(
                        1, use_bias=False, dtype=self.dtype,
                        param_dtype=jnp.float32, kernel_init=self.kernel_init,
                        dot_general=partial(
                            jax.lax.dot_general,
                            preferred_element_type=jnp.float32),
                        name="shared_gate")(tokens))
                    self.sow("moe_stats", "shared_gate_mean",
                             (gate[:, 0] * w).sum() / n)
                    shared = (gate * shared.astype(jnp.float32)).astype(
                        self.dtype)
                y = y + shared
        return y.reshape(b, s, h)
