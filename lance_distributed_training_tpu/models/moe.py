"""Mixture-of-Experts feed-forward layers for the transformer stacks.

Two layers, one for each thing the repo does with experts today:

* :class:`MoEMLP` — the **sharded** form (``--num_experts`` on the BERT/GPT
  presets): top-1 switch routing with a per-expert capacity, expressed
  entirely as einsums over a dense ``[T, E, C]`` dispatch tensor, so the SPMD
  partitioner shards the expert dimension over the mesh's ``'model'`` axis
  (``MOE_RULES`` in :mod:`..parallel.sharding`) and the dispatch einsum
  becomes the expert all-to-all. Overflow tokens skip the layer, and the
  dispatch tensors grow with ``T * E * C``: right for a few experts over a
  mesh, impossible at a published sparse model's shape (8,192 tokens, 64
  experts, 8 a token: terabytes).
* :class:`DroplessMoE` — the **one-chip, published-shape** form (the OLMoE
  presets): top-k routing with no capacity and no dropped token; the
  ``T * k`` assignments are sorted by expert, the tokens gathered, the three
  SwiGLU products run as grouped matrix multiplications over the ragged
  groups (``jax.lax.ragged_dot``), and each token's k results gathered back
  through the inverse permutation and summed with their weights.
  Nothing larger than ``[T * k, width]`` exists. One dispatch for both is
  decided where experts first span chips at a published shape (ROADMAP R3).
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

__all__ = ["MoEMLP", "DroplessMoE"]


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


class DroplessMoE(nn.Module):
    """Top-k routed SwiGLU experts without capacity: ``[B, S, H] -> [B, S, H]``,
    ``Σ_k p_k · down_k(silu(gate_k(x)) · up_k(x))`` over each token's
    ``experts_per_token`` largest router probabilities ``p_k`` (the softmax
    values themselves, not renormalised), no biases.

    Static in shape: every token has exactly k assignments, so the sorted
    list has ``T * k`` rows whatever the routing; only the group sizes are
    data. Sows the two auxiliary terms of the OLMoE paper into ``aux_loss``
    (``load_balance`` = ``E · Σ_e f_e · P_e`` and ``router_z`` =
    ``mean(logsumexp(logits)²)``, both over live tokens, unweighted) and the
    experts' assignment counts into ``moe_stats``; ``live`` [B, S] marks the
    tokens that count (None: all).
    """

    num_experts: int
    expert_dim: int
    experts_per_token: int
    dtype: Any = jnp.bfloat16
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x, live=None):
        b, s, h = x.shape
        t, e, k = b * s, self.num_experts, self.experts_per_token
        tokens = x.reshape(t, h)
        w_gate, w_up, w_down = (
            self.param(name, self.kernel_init, shape, jnp.float32)
            for name, shape in (("w_gate", (e, h, self.expert_dim)),
                                ("w_up", (e, h, self.expert_dim)),
                                ("w_down", (e, self.expert_dim, h))))

        with jax.named_scope("moe.router"):
            # f32 throughout: on a TPU an f32 product at default precision
            # is one bf16 pass, and the eighth and ninth expert of a token
            # are often closer than that.
            logits = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                              param_dtype=jnp.float32,
                              precision=jax.lax.Precision.HIGHEST,
                              kernel_init=self.kernel_init, name="router")(
                                  tokens.astype(jnp.float32))
            probs = nn.softmax(logits, axis=-1)  # [T, E]
            top_p, top_e = jax.lax.top_k(probs, k)  # [T, k]

        with jax.named_scope("moe.dispatch"):
            # Stable sort of the T*k assignments by expert: row i of the
            # sorted list is assignment order[i], of token order[i] // k.
            flat_e = top_e.reshape(t * k)
            order = jnp.argsort(flat_e, stable=True)
            inverse = jnp.zeros_like(order).at[order].set(
                jnp.arange(t * k, dtype=order.dtype), unique_indices=True)
            ends = jnp.searchsorted(jnp.take(flat_e, order), jnp.arange(e),
                                    side="right").astype(jnp.int32)
            group_sizes = jnp.diff(ends, prepend=0)
            xs = _permute_rows(
                jnp.repeat(tokens.astype(self.dtype), k, axis=0), order,
                inverse)

        with jax.named_scope("moe.experts"):
            gate = jax.lax.ragged_dot(xs, w_gate.astype(self.dtype),
                                      group_sizes)
            up = jax.lax.ragged_dot(xs, w_up.astype(self.dtype), group_sizes)
            out = jax.lax.ragged_dot(nn.silu(gate) * up,
                                     w_down.astype(self.dtype), group_sizes)

        with jax.named_scope("moe.combine"):
            # Each token's k rows come back through the inverse permutation
            # and are summed with their weights in f32: the scatter-add of
            # the sorted rows, without the scatter.
            y = _permute_rows(out, inverse, order).reshape(t, k, h)
            y = (y.astype(jnp.float32) * top_p[..., None]).sum(1)

        w = (jnp.ones((t,), jnp.float32) if live is None
             else live.reshape(t).astype(jnp.float32))
        n = jnp.maximum(w.sum(), 1.0)
        # f_e: the share of the live tokens' assignments that went to e (a
        # running sum over the sorted rows, read at the groups' ends);
        # P_e: the mean router probability of e over live tokens.
        run = jnp.concatenate([jnp.zeros((1,), jnp.float32), jnp.cumsum(
            jnp.take(w, order // k))])
        frac = (run[ends] - run[ends - group_sizes]) / (n * k)
        mean_prob = (probs * w[:, None]).sum(0) / n
        lse = jax.nn.logsumexp(logits, axis=-1)
        self.sow("aux_loss", "load_balance", e * jnp.sum(frac * mean_prob))
        self.sow("aux_loss", "router_z", jnp.sum(lse * lse * w) / n)
        self.sow("moe_stats", "group_sizes", group_sizes)
        return y.astype(self.dtype).reshape(b, s, h)
