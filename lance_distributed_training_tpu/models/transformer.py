"""Flax transformer encoder (BERT-base family) — the text arm.

Covers the BASELINE text config ("C4 text → on-device tokenize/pack for
BERT-base"; BASELINE.json configs[3]). The reference itself has no text
models (SURVEY.md §5 "vision classification only") — this extends the task
registry the same way ``modelling/get_model_and_loss.py`` would have.

TPU-first: bf16 compute / f32 params, static shapes (packed fixed-length
sequences from :func:`..data.authoring.create_text_token_dataset`), attention
as batched einsums on the MXU, optional remat for long sequences. The
attention core is factored out (:func:`dot_product_attention`) so the
sequence-parallel ring variant (:mod:`..parallel.ring_attention`) can swap in.
"""

from __future__ import annotations

import math
from contextlib import nullcontext
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.conv import causal_conv_silu, causal_depthwise_conv
from .moe import DroplessMoE, MoEMLP, StateRouter, SwiGLU, router_product

__all__ = ["TransformerEncoder", "TransformerDecoder", "bert_base",
           "bert_small", "gpt_base", "gpt_small", "olmoe_1b_7b",
           "olmoe_tiny", "moonlight_16b_a3b", "moonlight_tiny",
           "phi4_mini_flash", "phi4_mini_flash_tiny", "sambay_layers",
           "zaya1_8b", "zaya_tiny", "qwen3_next_80b_a3b", "qwen3_next_tiny",
           "qwen3_next_layers", "smallthinker_21b_a3b", "smallthinker_tiny",
           "smallthinker_layers", "granite4_h_micro", "granite4_h_tiny",
           "granite4_layers", "laguna_s_2_1", "laguna_tiny", "laguna_layers",
           "dot_product_attention", "RMSNorm", "rotary_embedding",
           "yarn_frequencies", "causal_depthwise_conv", "LayerKind",
           "LAYER_KINDS", "Preset", "CAUSAL_LMS"]


def dot_product_attention(q, k, v, mask=None, dtype=jnp.bfloat16,
                          causal=False):
    """Standard softmax attention: q,k,v [B, H, S, D] → [B, H, S, D].

    Softmax statistics in f32 for stability; matmuls in ``dtype`` on the MXU.
    ``causal=True`` adds the autoregressive lower-triangular mask (decoder
    attention) on top of any key-validity ``mask``.
    """
    d = q.shape[-1]  # v's last dimension may differ (latent attention)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        tri = jnp.tril(jnp.ones((s_q, s_k), bool))[None, None]
        scores = jnp.where(tri, scores, jnp.finfo(jnp.float32).min)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    weights = nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(dtype), v)


def _accepts_segment_ids(fn) -> bool:
    """Does this attention_fn take the packed-sequence ``segment_ids``
    kwarg (``ops.flash.make_flash_attention`` does; ring attention and the
    plain einsum path express segments as a dense mask instead)?"""
    import inspect

    try:
        return "segment_ids" in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


def _attention_masks(attention_mask, segment_ids, attention_fn):
    """``(mask, segment_ids)`` as the blocks' attention takes them, for both
    stacks: the key-validity mask [B, 1, 1, S], or for packed rows either the
    ids themselves (an ``attention_fn`` that takes them) or the dense block
    mask [B, 1, S, S]."""
    mask = None
    if attention_mask is not None:
        # [B, S] -> [B, 1, 1, S]: keys masked out, broadcast over queries.
        mask = attention_mask[:, None, None, :].astype(bool)
    if segment_ids is None:
        return mask, None
    if attention_fn is not None and _accepts_segment_ids(attention_fn):
        # Segment-native attention (the Pallas flash kernel): pass the ids
        # straight through; they carry validity too.
        return None, segment_ids
    # Dense path: lower segments to the block mask [B,1,S,S] —
    # same-segment-and-live; supersedes the validity mask.
    from ..ops.flash import segment_attention_mask

    return segment_attention_mask(segment_ids), None


def _attention_kernel(attention_fn, seq_len: int, d_qk: int, d_v: int) -> dict:
    """An attention mixer's answer to ``kernels``: whether a call with these
    shapes runs the fused kernel, asked of the function it was bound
    (``ops.flash.make_flash_attention``'s ``fused``, the test each call
    makes); one without the attribute (ring attention, none) runs dense."""
    fused = getattr(attention_fn, "fused", None)
    return {"attention": bool(fused and fused(seq_len, d_qk, d_v))}


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis: statistics
    in f32, result in ``dtype``, a learned f32 scale and no bias. With
    ``unit_offset`` the scale is ``1 + w`` with ``w`` learned from 0 (the
    Qwen3-Next family's form: weight decay then pulls the scale to 1)."""

    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        if self.unit_offset:
            scale = 1.0 + self.param("scale", nn.initializers.zeros_init(),
                                     (x.shape[-1],), jnp.float32)
        else:
            scale = self.param("scale", nn.initializers.ones_init(),
                               (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.eps) * scale).astype(self.dtype)


def rotary_embedding(x, positions, theta: float, width: int = 0,
                     inv_freq=None, factor: float = 1.0):
    """Rotary position embedding, in f32: ``x`` [B, S, H, D], ``positions``
    [B, S] or [S]. Over the whole head dimension, or with ``width`` > 0 over
    its first ``width`` elements, the others left as they are (a partial
    rotary factor: ``width = factor * D``). Half-rotation pairing (element
    ``i`` turns with element ``i + width/2``, as the published decoder
    implementations pair them), angle ``position * theta^(-2i/width)``, or
    ``position * inv_freq[i]`` where a table of ``width / 2`` inverse
    frequencies is given (:func:`yarn_frequencies`); ``factor`` other than 1
    multiplies cos and sin both (YaRN's attention factor)."""
    if 0 < width < x.shape[-1]:
        return jnp.concatenate([
            rotary_embedding(x[..., :width], positions, theta, 0, inv_freq,
                             factor),
            x[..., width:]], axis=-1)
    half = x.shape[-1] // 2
    if inv_freq is None:
        freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        freq = jnp.asarray(inv_freq, jnp.float32)
    angle = positions.astype(jnp.float32)[..., None] * freq  # [(B,) S, D/2]
    cos = jnp.cos(angle)[..., None, :]
    sin = jnp.sin(angle)[..., None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def yarn_frequencies(width: int, theta: float, factor: float,
                     original_max: int, beta_fast: float = 32.0,
                     beta_slow: float = 1.0) -> tuple:
    """YaRN's ``width / 2`` inverse frequencies (Peng et al.,
    arXiv:2309.00071, as ``transformers`` ``_compute_yarn_parameters`` has
    them): pair ``j``'s own ``f_j = theta^(-2j/width)`` where it turns more
    than ``beta_fast`` times over ``original_max`` positions, ``f_j /
    factor`` where fewer than ``beta_slow``, a linear ramp over the pairs
    between. Python floats: a constant of the program."""
    def pair_that_turns(rotations):
        return width * math.log(original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(beta_slow)), width - 1)
    if low == high:
        high += 0.001  # the ramp's width is never 0
    table = []
    for j in range(width // 2):
        f = theta ** (-2.0 * j / width)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        table.append(f / factor * ramp + f * (1.0 - ramp))
    return tuple(table)


class SelfAttention(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    causal: bool = False  # decoder (GPT) attention; custom attention_fns
    # must bind their own causality (e.g. make_flash_attention(causal=True))
    use_bias: bool = True
    norm_eps: float = 0.0  # >0: RMSNorm with a learned scale on the
    # whole query and key projections, before the split into heads
    rope_theta: float = 0.0  # >0: rotary positions on queries and keys
    kernel_init: Callable = nn.linear.default_kernel_init

    def kernels(self, seq_len: int, width: int) -> dict:
        """Which kernels a call at ``seq_len`` on a stream ``width`` wide
        runs, by name: every mixer class has this method."""
        head_dim = width // self.num_heads
        return _attention_kernel(self.attention_fn, seq_len, head_dim,
                                 head_dim)

    @nn.compact
    def __call__(self, x, mask=None, segment_ids=None, position_ids=None):
        b, s, h = x.shape
        head_dim = h // self.num_heads
        dense = partial(
            nn.DenseGeneral, dtype=self.dtype, param_dtype=jnp.float32,
            use_bias=self.use_bias, kernel_init=self.kernel_init,
        )
        q = dense(features=(self.num_heads, head_dim), name="query")(x)
        k = dense(features=(self.num_heads, head_dim), name="key")(x)
        v = dense(features=(self.num_heads, head_dim), name="value")(x)
        if self.norm_eps > 0:
            norm = partial(RMSNorm, self.norm_eps, self.dtype)
            q = norm(name="q_norm")(q.reshape(b, s, h)).reshape(q.shape)
            k = norm(name="k_norm")(k.reshape(b, s, h)).reshape(k.shape)
        if self.rope_theta > 0:
            # Packed rows carry positions that restart with each document;
            # otherwise a row is one sequence from 0.
            pos = jnp.arange(s) if position_ids is None else position_ids
            q = rotary_embedding(q, pos, self.rope_theta)
            k = rotary_embedding(k, pos, self.rope_theta)
        # [B, S, H, D] -> [B, H, S, D]
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        attn = self.attention_fn or partial(
            dot_product_attention, dtype=self.dtype, causal=self.causal
        )
        if segment_ids is not None:
            # Only reaches here when the fn declares the kwarg (the
            # encoder lowers segments to a dense block mask otherwise).
            out = attn(q, k, v, mask=mask, segment_ids=segment_ids)
        else:
            out = attn(q, k, v, mask=mask)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h)
        return dense(features=h, axis=-1, name="out")(out)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3, Moonlight), without a
    query compression (``q_lora_rank`` null): keys and values are expanded
    from one ``kv_rank``-wide normed latent per token, and position enters
    through ``rope_dim`` rotary elements of each query head and one rotary key
    head that all heads share. A head's queries and keys are ``nope_dim +
    rope_dim`` wide and its values ``v_dim``, so the attention function sees
    ``d_qk != d_v``; scores are over ``sqrt(nope_dim + rope_dim)``."""

    num_heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    kernel_init: Callable = nn.linear.default_kernel_init

    def kernels(self, seq_len: int, width: int) -> dict:
        return _attention_kernel(self.attention_fn, seq_len,
                                 self.nope_dim + self.rope_dim, self.v_dim)

    @nn.compact
    def __call__(self, x, mask=None, segment_ids=None, position_ids=None):
        b, s, h = x.shape
        n, nope = self.num_heads, self.nope_dim
        dense = partial(nn.DenseGeneral, dtype=self.dtype,
                        param_dtype=jnp.float32, use_bias=False,
                        kernel_init=self.kernel_init)
        with jax.named_scope("mla.project"):
            q = dense(features=(n, nope + self.rope_dim), name="query")(x)
            latent = dense(features=self.kv_rank + self.rope_dim,
                           name="kv_a")(x)
            kv = dense(features=(n, nope + self.v_dim), name="kv_b")(
                RMSNorm(self.norm_eps, self.dtype, name="kv_norm")(
                    latent[..., :self.kv_rank]))
            pos = jnp.arange(s) if position_ids is None else position_ids
            q_pe = rotary_embedding(q[..., nope:], pos, self.rope_theta)
            k_pe = rotary_embedding(latent[:, :, None, self.kv_rank:], pos,
                                    self.rope_theta)
            q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                k_pe, (b, s, n, self.rope_dim))], axis=-1)
            # [B, S, H, D] -> [B, H, S, D]
            q, k, v = (t.transpose(0, 2, 1, 3)
                       for t in (q, k, kv[..., nope:]))
        attn = self.attention_fn or partial(
            dot_product_attention, dtype=self.dtype, causal=True)
        with jax.named_scope("mla.kernel"):
            if segment_ids is not None:
                out = attn(q, k, v, mask=mask, segment_ids=segment_ids)
            else:
                out = attn(q, k, v, mask=mask)
        with jax.named_scope("mla.project"):
            return dense(features=h, axis=(-2, -1), name="out")(
                out.transpose(0, 2, 1, 3))


class DifferentialAttention(nn.Module):
    """Differential attention (arXiv:2410.05258) over grouped heads, without
    a position term, as SambaY's attention layers run it (arXiv:2507.06607):
    differential head ``i`` takes the query heads ``(2i, 2i+1)`` and, with
    ``j = i // (num_heads / kv_heads)``, the key heads ``(2j, 2j+1)`` and the
    value ``[V_2j, V_2j+1]``, twice a head wide:

        o_i = (softmax(Q_2i K_2j' / sqrt(d)) - lam softmax(Q_2i+1 K_2j+1'
               / sqrt(d))) V,   o_i <- (1 - lam_init) RMSNorm(o_i)

    with ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init`` from four
    learned vectors and ``lam_init = 0.8 - 0.6 exp(-0.3 depth)``, ``depth``
    the layer's published index. The attention function sees ``num_heads``
    query heads ``head_dim`` wide over ``kv_heads`` key heads and ``kv_heads
    / 2`` value heads twice as wide (grouped heads, ``d_qk != d_v``), with
    ``window`` > 0 for a causal band. ``shared`` None: keys and values are
    this layer's own and are returned for later layers; else they are the
    ``(k, v)`` an earlier layer returned (SambaY's cross-decoder), and this
    layer has no key or value projection."""

    num_heads: int
    kv_heads: int
    depth: int
    window: int = 0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    kernel_init: Callable = nn.linear.default_kernel_init

    def kernels(self, seq_len: int, width: int) -> dict:
        head_dim = width // self.num_heads
        return _attention_kernel(self.attention_fn, seq_len, head_dim,
                                 2 * head_dim)

    @nn.compact
    def __call__(self, x, mask=None, segment_ids=None, shared=None):
        b, s, h = x.shape
        n, g = self.num_heads, self.kv_heads
        d, rep = h // n, n // g
        dense = partial(nn.DenseGeneral, dtype=self.dtype,
                        param_dtype=jnp.float32, use_bias=False,
                        kernel_init=self.kernel_init)
        # query heads in the order (pair of key heads, which softmax, which
        # differential head of the pair's group): every key head then serves
        # `rep` neighbouring query heads, every value 2 x `rep`
        q = dense(features=(n, d), name="query")(x).reshape(
            b, s, g // 2, rep, 2, d).transpose(0, 2, 4, 3, 1, 5).reshape(
            b, n, s, d)
        if shared is None:
            k = dense(features=(g, d), name="key")(x).transpose(0, 2, 1, 3)
            v = dense(features=(g // 2, 2 * d), name="value")(x).transpose(
                0, 2, 1, 3)
        else:
            k, v = shared
        attn = self.attention_fn
        if attn is None:  # dense off a TPU; knows windows and grouped heads
            from ..ops.flash import make_flash_attention

            attn = make_flash_attention(causal=True, forced=False)
        kwargs = {"window": self.window} if self.window else {}
        if segment_ids is not None:
            kwargs["segment_ids"] = segment_ids
        with jax.named_scope("attn.window" if self.window else "attn.full"
                             if shared is None else "attn.cross"):
            out = attn(q, k, v, mask=mask, **kwargs)
        with jax.named_scope("diff.combine"):
            lam_init = 0.8 - 0.6 * math.exp(-0.3 * self.depth)

            def vec(name):
                return self.param(name, nn.initializers.normal(0.1), (d,),
                                  jnp.float32)

            lam = (jnp.exp(jnp.sum(vec("lambda_q1") * vec("lambda_k1")))
                   - jnp.exp(jnp.sum(vec("lambda_q2") * vec("lambda_k2")))
                   + lam_init)
            self.sow("mixer_stats", "diff_lambda", lam)
            out = out.reshape(b, g // 2, 2, rep, s, 2 * d).astype(jnp.float32)
            out = (out[:, :, 0] - lam * out[:, :, 1]).reshape(
                b, n // 2, s, 2 * d).transpose(0, 2, 1, 3)
            out = (1.0 - lam_init) * RMSNorm(
                self.norm_eps, jnp.float32, name="sub_norm")(out)
            out = out.astype(self.dtype).reshape(b, s, h)
        return dense(features=h, axis=-1, name="out")(out), (k, v)


class ConvolutionalAttention(nn.Module):
    """Compressed convolutional attention (CCA, arXiv:2510.04476) as ZAYA1
    runs it: queries, keys and values live in a latent narrower than the
    residual stream (``num_heads`` query heads over ``kv_heads`` key and
    value heads, each ``head_dim`` wide), and are mixed along the sequence
    before the scores:

        q~ = u W_q,  k~ = u W_k,  v = [u_t W_v0 ; u_{t-1} W_v1]
        conv(z) = C1(C0(z)):  C0(z)_t = a0 * z_{t-1} + a1 * z_t   a channel
                              C1(z)_t = z_{t-1} A0_h + z_t A1_h   a head
        q = conv(q~) + (q~ + rep(k~)) / 2
        k = conv(k~) + (k~ + groupmean(q~)) / 2
        q <- sqrt(d) q / |q|,   k <- tau_g sqrt(d) k / |k|

    then rotary positions on the first ``rotary_dim`` elements of each head,
    causal softmax attention over ``sqrt(d)`` with query head ``h`` on key
    and value head ``h // (num_heads / kv_heads)``, and ``W_o`` from the
    latent back to the stream. The value shift: the first half of the value
    heads come from this token, the second half from the one before
    (``u_{-1} = 0``). ``tau_g`` is a learned temperature a key head, from 1.
    No biases. What lies between the projections and the kernel (``cca.mix``)
    is recomputed in the backward pass. Nothing here knows where a document
    ends inside a row: the convolutions and the value shift run across it
    (ROADMAP R4)."""

    num_heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    kernel_init: Callable = nn.linear.default_kernel_init

    def kernels(self, seq_len: int, width: int) -> dict:
        return _attention_kernel(self.attention_fn, seq_len, self.head_dim,
                                 self.head_dim)

    @nn.compact
    def __call__(self, x, mask=None, segment_ids=None, position_ids=None):
        b, s, h = x.shape
        n, g, d = self.num_heads, self.kv_heads, self.head_dim
        dense = partial(nn.DenseGeneral, dtype=self.dtype,
                        param_dtype=jnp.float32, use_bias=False,
                        kernel_init=self.kernel_init)
        with jax.named_scope("cca.project"):
            q0 = dense(features=(n, d), name="query")(x)
            k0 = dense(features=(g, d), name="key")(x)
            v0 = dense(features=(g, d), name="value")(x)

        def taps(name, shape, fan_in):
            edge = 1.0 / math.sqrt(fan_in)  # a Conv1d's own
            return self.param(
                name, lambda key, shape, dtype: jax.random.uniform(
                    key, shape, dtype, -edge, edge), shape, jnp.float32)

        # [tap, head, channel] and [tap, head, in, out]; tap 0 is the token
        # before, tap 1 this token
        convs = {name: (taps(f"{name}_conv0", (2, heads, d), 2),
                        taps(f"{name}_conv1", (2, heads, d, d), 2 * d))
                 for name, heads in (("q", n), ("k", g))}
        temperature = self.param("key_temperature",
                                 nn.initializers.ones_init(), (g,),
                                 jnp.float32)
        self.sow("mixer_stats", "cca_key_temperature", temperature)
        pos = jnp.arange(s) if position_ids is None else position_ids

        def before(t):  # t_{-1} = 0
            return jnp.pad(t, ((0, 0), (1, 0), (0, 0), (0, 0)))[:, :-1]

        def conv(z, depthwise, within):
            """``C1(C0(z))`` in f32: C0's sum in f32, C1 one product a head
            over both taps with operands in ``dtype``."""
            heads = z.shape[2]
            z = z.astype(jnp.float32)
            z = (depthwise[0] * before(z) + depthwise[1] * z).astype(
                self.dtype)
            return jnp.einsum(
                "bsnd,nde->bsne", jnp.concatenate([before(z), z], axis=-1),
                within.transpose(1, 0, 2, 3).reshape(
                    heads, 2 * d, d).astype(self.dtype)).astype(jnp.float32)

        def unit(t):  # |t| = sqrt(d) over a head
            return t * jax.lax.rsqrt(
                jnp.mean(t * t, -1, keepdims=True) + 1e-12)

        @jax.checkpoint  # a dozen f32 passes over the latent: the backward
        # pass makes them again from the three projections and keeps none
        def mix(q0, k0, v0, convs, temperature, pos):
            q32, k32 = q0.astype(jnp.float32), k0.astype(jnp.float32)
            q = conv(q0, *convs["q"]) + 0.5 * (
                q32 + jnp.repeat(k32, n // g, axis=2))
            k = conv(k0, *convs["k"]) + 0.5 * (
                k32 + q32.reshape(b, s, g, n // g, d).mean(3))
            q = rotary_embedding(unit(q), pos, self.rope_theta,
                                 self.rotary_dim).astype(self.dtype)
            k = rotary_embedding(unit(k) * temperature[:, None], pos,
                                 self.rope_theta,
                                 self.rotary_dim).astype(self.dtype)
            v = jnp.concatenate([v0[:, :, :g // 2],
                                 before(v0[:, :, g // 2:])], axis=2)
            # [B, S, H, D] -> [B, H, S, D]
            return tuple(t.transpose(0, 2, 1, 3) for t in (q, k, v))

        with jax.named_scope("cca.mix"):
            q, k, v = mix(q0, k0, v0, convs, temperature, pos)
        attn = self.attention_fn
        if attn is None:  # dense off a TPU; knows grouped heads
            from ..ops.flash import make_flash_attention

            attn = make_flash_attention(causal=True, forced=False)
        kwargs = {} if segment_ids is None else {"segment_ids": segment_ids}
        with jax.named_scope("cca.kernel"):
            out = attn(q, k, v, mask=mask, **kwargs)
        with jax.named_scope("cca.out"):
            return dense(features=h, axis=(-2, -1), name="out")(
                out.transpose(0, 2, 1, 3))


class MambaMixer(nn.Module):
    """Mamba-1's mixer (arXiv:2312.00752): ``[x; z] = u W_in``; ``x <-
    silu(conv(x) + b_c)``, depthwise and causal over ``conv`` tokens; ``[dl;
    B; C] = x W_x``; ``dt = softplus(dl W_dt + b_dt)``; the selective scan
    with ``A = -exp(A_log)`` (:mod:`..ops.scan`); ``m = scan + D x``; ``out =
    (m silu(z)) W_out``. ``dt``, the exponent, the state and the sum over
    the states in float32, the products' operands in ``dtype``. Returns
    ``(out, m)``: SambaY's gated memory units read ``m``, the scan's output
    before the gate. The convolution, its bias and its SiLU are one call,
    :func:`..ops.conv.causal_conv_silu`, on the projection ``[x; z]`` whole:
    on one TPU device at rows of whole 128-token tiles and channels in whole
    lane groups a Pallas kernel pair that reads ``x``'s columns where they
    lie, one pass over HBM forward and one backward; everywhere else the
    plain ``silu(causal_depthwise_conv(x) + b_c)`` in XLA."""

    inner: int
    states: int
    conv: int
    dt_rank: int
    dtype: Any = jnp.bfloat16
    kernel_init: Callable = nn.linear.default_kernel_init

    def kernels(self, seq_len: int, width: int) -> dict:
        from ..ops.conv import conv_fused_applies
        from ..ops.scan import scan_fused_applies

        return {"scan": scan_fused_applies(seq_len, self.inner, self.states),
                "conv": conv_fused_applies(seq_len, self.inner, self.conv)}

    @nn.compact
    def __call__(self, u):
        from ..ops.scan import selective_scan

        n, r = self.states, self.dt_rank
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=jnp.float32, kernel_init=self.kernel_init)
        f32_out = partial(jax.lax.dot_general,
                          preferred_element_type=jnp.float32)
        with jax.named_scope("ssm.project"):
            xz = dense(2 * self.inner, name="in_proj")(u)
        with jax.named_scope("ssm.conv"):
            edge = 1.0 / math.sqrt(self.conv)  # a depthwise Conv1d's own
            taps = self.param(
                "conv_kernel", lambda key, shape, dtype: jax.random.uniform(
                    key, shape, dtype, -edge, edge),
                (self.conv, self.inner), jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros_init(),
                              (self.inner,), jnp.float32)
            # the first ``inner`` columns of ``xz``, read where they lie
            x = causal_conv_silu(xz, taps, bias, dtype=self.dtype)
        with jax.named_scope("ssm.project"):
            dbc = dense(r + 2 * n, name="x_proj", dot_general=f32_out)(x)
            dt = jax.nn.softplus(dense(
                self.inner, name="dt_proj", dot_general=f32_out)(
                dbc[..., :r].astype(self.dtype)) + self.param(
                "dt_bias", _dt_bias_init, (self.inner,), jnp.float32))
        a = -jnp.exp(self.param(
            "A_log", lambda key, shape, dtype: jnp.log(jnp.broadcast_to(
                jnp.arange(1, shape[1] + 1, dtype=dtype), shape)),
            (self.inner, n), jnp.float32))
        skip = self.param("D", nn.initializers.ones_init(), (self.inner,),
                          jnp.float32)
        with jax.named_scope("ssm.scan"):
            y, last = selective_scan(x, dt, a, dbc[..., r:r + n],
                                     dbc[..., r + n:])
        self.sow("mixer_stats", "ssm_state_abs_max", jnp.abs(last).max())
        with jax.named_scope("ssm.gate"):
            m = (y.astype(jnp.float32) + skip * x.astype(jnp.float32)
                 ).astype(self.dtype)
            gated = m * nn.silu(xz[..., self.inner:])
        with jax.named_scope("ssm.project"):
            return dense(u.shape[-1], name="out_proj")(gated), m


def _dt_bias_init(key, shape, dtype):
    """Mamba's: the inverse softplus of a step drawn log-uniformly from
    [1e-3, 1e-1], so that ``softplus(b_dt)`` starts there."""
    step = jnp.exp(jax.random.uniform(key, shape, dtype) * (
        math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return step + jnp.log(-jnp.expm1(-step))


class Mamba2Mixer(nn.Module):
    """Mamba-2's mixer (the state-space dual, arXiv:2405.21060) as Granite
    4.0-H runs it (``GraniteMoeHybridMambaLayer``), ``heads`` heads of
    ``head_dim`` (``inner`` columns in all) over one group of ``states``:

        [xBC; z] = u W_in          dt = softplus(u W_dt + dt_bias)    a head
        [x; B; C] = silu(conv([xBC]) + b_c)       depthwise, causal
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,   A = -exp(A_log) a head
        y_t = h_t C_t + D x_t                           (:mod:`..ops.ssd`)
        out = (w_n * g / sqrt(mean(g^2) + eps)) W_out,  g = y * silu(z)

    a decay that is a scalar a head where :class:`MambaMixer` (Mamba-1) has
    one a channel and state, a state ``[head_dim, states]`` a head, ``B`` and
    ``C`` shared by all heads, and one norm over all ``inner`` columns with
    the gate inside its statistic (:func:`..ops.norm.gate_then_rms_norm`; a
    Gated DeltaNet's norms a head and gates afterwards). The published
    projection is one matrix ``[z; xBC; dt]``; here the convolved columns
    come first, ``[xBC; z]`` (``inner + 2 states`` then ``inner``: whole lane
    groups at the published sizes, so :func:`..ops.conv.causal_conv_silu`
    reads them where they lie, and on the chip the dual's kernel pair reads
    ``x``, ``B`` and ``C`` from the convolved columns in place,
    :func:`..ops.ssd.ssd_packed`), and the ``heads`` columns of ``dt`` are a
    product of their own with float32 output, as a Gated DeltaNet's
    ``in_proj_ba`` is: a loader would permute. ``dt``, the decay, the state
    and the norm in float32, the products' operands in ``dtype``. No biases
    but the convolution's and ``dt``'s. Nothing here knows where a document
    ends inside a row: the convolution and the state run across it (ROADMAP
    R4)."""

    inner: int
    heads: int
    head_dim: int
    states: int
    conv: int
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    kernel_init: Callable = nn.linear.default_kernel_init

    def kernels(self, seq_len: int, width: int) -> dict:
        from ..ops.conv import conv_fused_applies
        from ..ops.ssd import ssd_fused_applies

        return {"ssd": ssd_fused_applies(seq_len, self.heads, self.head_dim,
                                         self.states),
                "conv": conv_fused_applies(
                    seq_len, self.inner + 2 * self.states, self.conv),
                "norm": False}  # the gate inside the statistic: plain lines

    @nn.compact
    def __call__(self, u):
        from ..ops.norm import gate_then_rms_norm
        from ..ops.ssd import ssd_packed

        if self.inner != self.heads * self.head_dim:
            raise ValueError(
                f"a Mamba-2 mixer's {self.heads} heads of {self.head_dim} "
                f"are its inner width, not {self.inner}")
        h = u.shape[-1]
        convolved = self.inner + 2 * self.states
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=jnp.float32, kernel_init=self.kernel_init)
        with jax.named_scope("ssd.project"):
            xbcz = dense(convolved + self.inner, name="in_proj_xbcz")(u)
            dt = jax.nn.softplus(dense(
                self.heads, name="in_proj_dt", dot_general=partial(
                    jax.lax.dot_general, preferred_element_type=jnp.float32)
                )(u) + self.param("dt_bias", nn.initializers.ones_init(),
                                  (self.heads,), jnp.float32))
        with jax.named_scope("ssd.conv"):
            taps = self.param("conv_kernel", self.kernel_init,
                              (self.conv, convolved), jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros_init(),
                              (convolved,), jnp.float32)
            # the first inner + 2 states columns, read where they lie
            mixed = causal_conv_silu(xbcz, taps, bias, dtype=self.dtype)
        a = -jnp.exp(self.param(
            "A_log", lambda key, shape, dtype: jnp.log(
                jnp.arange(1, shape[0] + 1, dtype=dtype)),
            (self.heads,), jnp.float32))
        skip = self.param("D", nn.initializers.ones_init(), (self.heads,),
                          jnp.float32)
        with jax.named_scope("ssd.kernel"):
            # x, B and C where they lie in mixed's columns
            y, last = ssd_packed(mixed, dt, a, skip, head_dim=self.head_dim)
        self.sow("mixer_stats", "ssd_state_abs_max", jnp.abs(last).max())
        self.sow("mixer_stats", "ssd_decay_min", jnp.exp((dt * a).min()))
        self.sow("mixer_stats", "ssd_dt_mean", dt.mean())
        with jax.named_scope("ssd.norm"):
            scale = self.param("norm_scale", nn.initializers.ones_init(),
                               (self.inner,), jnp.float32)
            # z where it lies in the projection's last columns
            gated = gate_then_rms_norm(y, xbcz, scale, eps=self.norm_eps,
                                       dtype=self.dtype)
        with jax.named_scope("ssd.project"):
            return dense(h, name="out_proj")(gated)


class GatedMemoryUnit(nn.Module):
    """SambaY's gated memory unit (arXiv:2507.06607): ``(m silu(u W_1))
    W_2``, with ``m`` an earlier layer's scan output at the same token in
    place of a scan of its own."""

    dtype: Any = jnp.bfloat16
    kernel_init: Callable = nn.linear.default_kernel_init

    def kernels(self, seq_len: int, width: int) -> dict:
        return {}  # two products and a gate: XLA's own

    @nn.compact
    def __call__(self, u, memory):
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=jnp.float32, kernel_init=self.kernel_init)
        gate = nn.silu(dense(memory.shape[-1], name="in_proj")(u))
        return dense(u.shape[-1], name="out_proj")(memory * gate)


class GatedDeltaNet(nn.Module):
    """Qwen3-Next's linear-attention mixer (Gated Delta Networks,
    arXiv:2412.06464), ``key_heads`` key heads of ``key_dim`` serving
    ``value_heads`` value heads of ``value_dim`` (key head ``j`` the value
    heads ``j * value_heads / key_heads`` onwards):

        [q~; k~; v~; z] = u W_qkvz          [b; a] = u W_ba
        [q; k; v] = silu(conv([q~; k~; v~]))    depthwise, causal, no bias
        beta = sigmoid(b)     g = -exp(A_log) * softplus(a + dt_bias)
        q <- q / sqrt(|q|^2 + 1e-6) / sqrt(key_dim),  k <- k / sqrt(|k|^2
        + 1e-6)                                          a head
        o = the gated delta rule over (q, k, v, g, beta)  (:mod:`..ops.delta`)
        out = (rmsnorm(o) * w_n * silu(z)) W_out         a value head

    The fused projection is laid out ``[q; k; v; z]`` (the published
    checkpoint interleaves it by key head: a loader would permute). ``g``,
    ``beta``, the L2 norms, the convolution's sums and the gated norm in
    float32, the products' operands in ``dtype``. No biases. What lies ahead
    of the output projection is made again in the backward pass from the
    normed stream and the rule's output. Nothing here knows where a document
    ends inside a row: the convolution and the state run across it (ROADMAP
    R4). The convolution and its SiLU are one call,
    :func:`..ops.conv.causal_conv_silu`, on the fused projection whole: on
    one TPU device at rows of whole 128-token tiles and channels in whole
    lane groups a Pallas kernel pair that reads the first ``2 keys + values``
    columns where they lie (forward, the recomputed forward and a backward
    pass that keeps nothing but the projection); everywhere else the plain
    ``silu(causal_depthwise_conv(.))`` in XLA, as the rule beside it is
    :func:`..ops.delta.delta_chunked` there. The two norms run where their
    rows already sit. On the chip the unit norms of ``q`` and ``k`` are made
    by the rule's kernels as they load a key head's rows (and their
    derivative by the rule's backward kernel as it writes ``dq`` and
    ``dk``), which read ``q``, ``k`` and ``v`` from the convolved columns in
    place (:func:`..ops.delta.gated_delta_rule_packed`), and the gated
    RMSNorm is one kernel each way that reads ``z`` from the projection's
    last columns and keeps ``o``, ``z`` and the scale
    (:func:`..ops.norm.gated_rms_norm`); off the chip, under a mesh and at
    heads that are not whole lane groups both are the ``jax.numpy`` lines
    they were, float32 with the same epsilons."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv: int
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    kernel_init: Callable = nn.linear.default_kernel_init

    def kernels(self, seq_len: int, width: int) -> dict:
        from ..ops.conv import conv_fused_applies
        from ..ops.delta import delta_fused_applies
        from ..ops.norm import norm_fused_applies

        convolved = (2 * self.key_heads * self.key_dim
                     + self.value_heads * self.value_dim)
        return {"delta": delta_fused_applies(seq_len, self.value_heads,
                                             self.key_dim, self.value_dim),
                "conv": conv_fused_applies(seq_len, convolved, self.conv),
                "norm": norm_fused_applies(seq_len, self.value_heads,
                                           self.value_dim)}

    @nn.compact
    def __call__(self, u):
        from jax.ad_checkpoint import checkpoint_name

        from ..ops.delta import gated_delta_rule_packed
        from ..ops.norm import gated_rms_norm

        h = u.shape[-1]
        hk, hv, dk, dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        keys, values = hk * dk, hv * dv
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=jnp.float32, kernel_init=self.kernel_init)
        w_qkvz = self.param("in_proj_qkvz", self.kernel_init,
                            (h, 2 * keys + 2 * values), jnp.float32)
        w_ba = self.param("in_proj_ba", self.kernel_init, (h, 2 * hv),
                          jnp.float32)
        taps = self.param("conv_kernel", self.kernel_init,
                          (self.conv, 2 * keys + values), jnp.float32)
        a_log = self.param(
            "A_log", lambda key, shape, dtype: jnp.log(jax.random.uniform(
                key, shape, dtype, 1e-3, 16.0)), (hv,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.ones_init(), (hv,),
                             jnp.float32)
        scale = self.param("norm_scale", nn.initializers.ones_init(), (dv,),
                           jnp.float32)

        # Ahead of the output projection everything but the rule is a
        # product of 12,288 columns or elementwise over [S, 8,192] and [S,
        # 4,096], much of it in f32: the backward pass makes it again from
        # the normed stream and the rule's output and keeps nothing else
        # (0.8 GiB a layer and row of 8,192 tokens, for one more product).
        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.save_only_these_names(
                     "gdn_rule_out"))
        def mix(u, w_qkvz, w_ba, taps, a_log, dt_bias, scale):
            with jax.named_scope("gdn.project"):
                qkvz = jnp.dot(u, w_qkvz.astype(self.dtype))
                ba = jnp.dot(u, w_ba.astype(self.dtype),
                             preferred_element_type=jnp.float32)
            with jax.named_scope("gdn.conv"):
                # the first 2 keys + values columns, read where they lie
                mixed = causal_conv_silu(qkvz, taps, dtype=self.dtype)
            with jax.named_scope("gdn.gates"):
                beta = nn.sigmoid(ba[..., :hv])
                g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
            with jax.named_scope("gdn.kernel"):
                # q, k, v where they lie in mixed's columns, q and k raw:
                # their norms are the rule's (off the chip, ahead of it)
                o, last = gated_delta_rule_packed(
                    mixed, g, beta, key_heads=hk, key_dim=dk, qk_norm=True)
            o = checkpoint_name(o, "gdn_rule_out")
            with jax.named_scope("gdn.norm"):
                # z where it lies in the projection's last columns
                gated = gated_rms_norm(o, qkvz, scale, eps=self.norm_eps,
                                       dtype=self.dtype)
            return gated, (jnp.abs(last).max(), jnp.exp(g.min()),
                           beta.mean())

        gated, (state_max, decay_min, beta_mean) = mix(
            u.astype(self.dtype), w_qkvz, w_ba, taps, a_log, dt_bias, scale)
        self.sow("mixer_stats", "delta_state_abs_max", state_max)
        self.sow("mixer_stats", "delta_decay_min", decay_min)
        self.sow("mixer_stats", "delta_beta_mean", beta_mean)
        with jax.named_scope("gdn.project"):
            return dense(h, name="out_proj")(gated)


class GatedAttention(nn.Module):
    """Qwen3-Next's softmax-attention mixer: ``num_heads`` query heads over
    ``kv_heads`` key and value heads, all ``head_dim`` wide (which is not
    ``hidden / num_heads``), a ``1 + w`` RMSNorm over each head's queries
    and keys (one scale a head width, all heads), rotary positions on the
    first ``rotary_dim`` elements of a head, and the attention output times
    ``sigmoid(gate)``, the gate from the second half of a query projection
    twice as wide (a head's ``[q; gate]`` side by side). No biases."""

    num_heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    kernel_init: Callable = nn.linear.default_kernel_init

    def kernels(self, seq_len: int, width: int) -> dict:
        return _attention_kernel(self.attention_fn, seq_len, self.head_dim,
                                 self.head_dim)

    @nn.compact
    def __call__(self, x, mask=None, segment_ids=None, position_ids=None):
        b, s, h = x.shape
        n, g, d = self.num_heads, self.kv_heads, self.head_dim
        dense = partial(nn.DenseGeneral, dtype=self.dtype,
                        param_dtype=jnp.float32, use_bias=False,
                        kernel_init=self.kernel_init)
        norm = partial(RMSNorm, self.norm_eps, self.dtype, True)
        with jax.named_scope("attn.project"):
            q_gate = dense(features=(n, 2 * d), name="query")(x)
            k = dense(features=(g, d), name="key")(x)
            v = dense(features=(g, d), name="value")(x)
            pos = jnp.arange(s) if position_ids is None else position_ids
            q = rotary_embedding(norm(name="q_norm")(q_gate[..., :d]), pos,
                                 self.rope_theta, self.rotary_dim)
            k = rotary_embedding(norm(name="k_norm")(k), pos,
                                 self.rope_theta, self.rotary_dim)
            # [B, S, H, D] -> [B, H, S, D]
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        attn = self.attention_fn
        if attn is None:  # dense off a TPU; knows grouped heads
            from ..ops.flash import make_flash_attention

            attn = make_flash_attention(causal=True, forced=False)
        kwargs = {} if segment_ids is None else {"segment_ids": segment_ids}
        with jax.named_scope("attn.kernel"):
            out = attn(q, k, v, mask=mask, **kwargs)
        with jax.named_scope("attn.gate"):
            gate = nn.sigmoid(q_gate[..., d:].astype(jnp.float32))
            self.sow("mixer_stats", "attn_gate", gate.mean())
            out = (out.transpose(0, 2, 1, 3).astype(jnp.float32) * gate
                   ).astype(self.dtype)
        with jax.named_scope("attn.project"):
            return dense(features=h, axis=(-2, -1), name="out")(out)


class GroupedAttention(nn.Module):
    """SmallThinker's softmax-attention mixer, Granite 4.0-H's and Laguna's:
    ``num_heads`` query heads over ``kv_heads`` key and value heads, all
    ``head_dim`` wide (which need not be ``hidden / num_heads``: 28 heads of
    128 on a stream of 2,560), no norm on queries or keys, no biases. The
    kinds of layer that are this class differ by fields: with ``rotary`` a
    rotary turn over the whole head, or over its first ``rotary_dim``
    elements, at ``rope_theta``'s frequencies or, under ``yarn`` (its five
    numbers: factor, original positions, beta fast and slow, the factor on
    cos and sin), YaRN's table of them; with ``window`` > 0 a causal band (a
    query sees itself and the ``window - 1`` keys before it); without either,
    the whole causal row and no position term at all (``position_ids`` is
    not read). With ``head_gate`` (Laguna's, the headwise form of
    arXiv:2505.06708) head ``n``'s output times ``sigmoid(x w_n)``, one
    scalar a head and token from a product of the mixer's own input, ahead of
    the output projection.
    Scores times ``score_scale``, or over ``sqrt(head_dim)`` where that is 0
    (Granite's ``attention_multiplier`` is 1/64 on heads of 64, not 1/8): the
    attention functions divide by the root themselves, so the queries are
    multiplied by ``score_scale sqrt(head_dim)`` ahead of them, a power of
    two there and exact in bf16."""

    num_heads: int
    kv_heads: int
    head_dim: int
    window: int = 0
    rotary: bool = True
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    kernel_init: Callable = nn.linear.default_kernel_init
    score_scale: float = 0.0  # 0: 1 / sqrt(head_dim)
    head_gate: bool = False  # a sigmoid gate a head, from the mixer's input
    rotary_dim: int = 0  # 0: the whole head turns
    yarn: tuple = ()  # (factor, original positions, beta fast, beta slow,
    # the factor on cos and sin); empty: rope_theta's own frequencies

    def kernels(self, seq_len: int, width: int) -> dict:
        return _attention_kernel(self.attention_fn, seq_len, self.head_dim,
                                 self.head_dim)

    @nn.compact
    def __call__(self, x, mask=None, segment_ids=None, position_ids=None):
        b, s, h = x.shape
        n, g, d = self.num_heads, self.kv_heads, self.head_dim
        dense = partial(nn.DenseGeneral, dtype=self.dtype,
                        param_dtype=jnp.float32, use_bias=False,
                        kernel_init=self.kernel_init)
        with jax.named_scope("attn.project"):
            q = dense(features=(n, d), name="query")(x)
            k = dense(features=(g, d), name="key")(x)
            v = dense(features=(g, d), name="value")(x)
            if self.score_scale:
                q = q * (self.score_scale * math.sqrt(d))
            if self.rotary:
                pos = jnp.arange(s) if position_ids is None else position_ids
                turn = dict(positions=pos, theta=self.rope_theta,
                            width=self.rotary_dim)
                if self.yarn:
                    turn.update(inv_freq=yarn_frequencies(
                        self.rotary_dim or d, self.rope_theta,
                        *self.yarn[:4]), factor=self.yarn[4])
                q, k = (rotary_embedding(t, **turn) for t in (q, k))
            # [B, S, H, D] -> [B, H, S, D]
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        attn = self.attention_fn
        if attn is None:  # dense off a TPU; knows windows and grouped heads
            from ..ops.flash import make_flash_attention

            attn = make_flash_attention(causal=True, forced=False)
        kwargs = {"window": self.window} if self.window else {}
        if segment_ids is not None:
            kwargs["segment_ids"] = segment_ids
        if self.window:
            self.sow("mixer_stats", "attn_window", jnp.float32(self.window))
        with jax.named_scope("attn.window" if self.window else "attn.full"):
            out = attn(q, k, v, mask=mask, **kwargs)
        out = out.transpose(0, 2, 1, 3)
        if self.head_gate:
            with jax.named_scope("attn.gate"):
                gate = nn.sigmoid(dense(features=n, name="gate")(x).astype(
                    jnp.float32))  # [B, S, N]
                self.sow("mixer_stats", "attn_gate", gate.mean())
                out = (out.astype(jnp.float32) * gate[..., None]).astype(
                    self.dtype)
        with jax.named_scope("attn.out"):
            return dense(features=h, axis=(-2, -1), name="out")(out)


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    num_experts: int = 0  # >0: switch-MoE MLP instead of dense (expert parallel)
    capacity_factor: float = 1.25
    causal: bool = False

    @nn.compact
    def __call__(self, x, mask=None, segment_ids=None):
        norm = partial(nn.LayerNorm, dtype=self.dtype, param_dtype=jnp.float32)
        y = norm(name="ln_attn")(x)
        with jax.named_scope("attention"):
            y = SelfAttention(self.num_heads, self.dtype,
                              attention_fn=self.attention_fn,
                              causal=self.causal, name="attn")(y, mask,
                                                               segment_ids)
        x = x + y
        y = norm(name="ln_mlp")(x)
        if self.num_experts > 0:
            y = MoEMLP(self.num_experts, self.mlp_dim,
                       self.capacity_factor, self.dtype, name="moe")(y)
        else:
            y = nn.Dense(self.mlp_dim, dtype=self.dtype,
                         param_dtype=jnp.float32, name="mlp_in")(y)
            y = nn.gelu(y)
            y = nn.Dense(x.shape[-1], dtype=self.dtype,
                         param_dtype=jnp.float32, name="mlp_out")(y)
        return x + y


class TransformerEncoder(nn.Module):
    """Pre-LN BERT-style encoder with an MLM head.

    ``__call__(input_ids, attention_mask, train)`` → logits ``[B, S, vocab]``
    (tied to the input embedding — standard weight tying keeps the head off
    the parameter budget). The two halves can be called apart, on the same
    parameters: ``return_hidden=True`` stops after the final norm and gives
    the hidden states ``[B, S, H]``, and ``hidden=<[.., H] array>`` applies
    the tied head to that array alone (``input_ids`` is not read), so a
    task can run the head on the positions it has targets for.
    """

    vocab_size: int
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 512
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention_fn: Optional[Callable] = None
    head: str = "mlm"  # "mlm" → tied vocab logits; "none" → hidden states
    num_experts: int = 0  # >0: MoE MLP on every `moe_every`-th block
    moe_every: int = 2
    capacity_factor: float = 1.25
    causal: bool = False  # decoder-only (GPT) variant: autoregressive mask

    def kernels(self, seq_len: int) -> dict:
        """Which kernels the layers run at ``seq_len``, by name: what their
        one kind of attention says (:meth:`TransformerDecoder.kernels`)."""
        return SelfAttention(
            self.num_heads, attention_fn=self.attention_fn,
            parent=None).kernels(seq_len, self.hidden_size)

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, train: bool = True,
                 segment_ids=None, position_ids=None,
                 return_hidden: bool = False, hidden=None):
        embed = nn.Embed(self.vocab_size, self.hidden_size,
                         param_dtype=jnp.float32, name="tok_embed")

        def tied_head(x):
            # Tied MLM head: project back onto the embedding table.
            return embed.attend(x.astype(jnp.float32))

        if hidden is not None:
            return tied_head(hidden)
        b, s = input_ids.shape
        pos_embed = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (self.max_len, self.hidden_size), jnp.float32,
        )
        x = embed(input_ids).astype(self.dtype)
        if position_ids is not None:
            # Packed sequences (the ragged token plane): positions restart
            # per segment, so the embedding gathers at the kernel-emitted
            # intra-sequence offsets instead of the row arange.
            x = x + jnp.take(
                pos_embed, position_ids, axis=0
            ).astype(self.dtype)
        else:
            x = x + pos_embed[:s].astype(self.dtype)

        mask, seg_kwarg = _attention_masks(attention_mask, segment_ids,
                                           self.attention_fn)

        block = EncoderBlock
        if self.remat:
            block = nn.remat(EncoderBlock, static_argnums=())
        for i in range(self.num_layers):
            # MoE on every moe_every-th block (Switch/GShard convention:
            # alternate dense and expert layers).
            moe_here = (
                self.num_experts > 0 and i % self.moe_every == self.moe_every - 1
            )
            x = block(self.num_heads, self.mlp_dim, self.dtype,
                      attention_fn=self.attention_fn,
                      num_experts=self.num_experts if moe_here else 0,
                      capacity_factor=self.capacity_factor,
                      causal=self.causal,
                      name=f"layer_{i}")(x, mask, seg_kwarg)
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                         name="ln_final")(x)
        if self.head == "none" or return_hidden:
            return x  # final hidden states [B, S, H] (e.g. the CLIP text tower)
        return tied_head(x)


class LayerKind(NamedTuple):
    """How a :class:`DecoderBlock` of one kind runs its mixer: the class (a
    preset's ``parts`` size it), its name among the layer's parameters, the
    named scope around its call, the fields the kind itself sets, how many
    of ``(mask, segment_ids, position_ids)`` the call takes after the normed
    stream, and which entry of ``handed`` it takes last (``takes``) or its
    second output becomes (``hands``)."""

    mixer: type
    name: str
    scope: str = ""  # the mixer's own scopes only
    fixed: tuple = ()  # (name, value) pairs
    sequence: int = 0
    takes: Optional[int] = None
    hands: Optional[int] = None


# what rides from layer to layer beside the stream (``handed``): an M*
# layer's scan output, an F* layer's keys and values, a router's state
_MEMORY, _KEYS_VALUES, _ROUTER_STATE = range(3)
# A layer kind is an entry here, its mixer's class above and, where the class
# has sizes, a ``partial`` in the preset's ``parts``. "": rotary attention
# with a norm on queries and keys (OLMoE's); "L": latent attention
# (Moonlight's); SambaY's six (arXiv:2507.06607): "M" a Mamba-1 mixer, "M*" one
# that hands on its scan output, "S" window and "F*" full differential
# attention, the latter handing on its keys and values, "G" a gated memory
# unit over M*'s output, "X" differential attention over F*'s keys and
# values; "C": ZAYA1's attention in a latent (arXiv:2511.17127; the block
# gives such a layer its router and residual scales); Qwen3-Next's two: "D" a
# gated DeltaNet, "A" gated attention; SmallThinker's two: "W" grouped rotary
# attention in a window, "N" the same heads over the whole causal row with no
# position term (Granite 4.0-H's attention layers too, under a score scale of
# their own); "M2": a Mamba-2 mixer, Granite 4.0-H's other nine layers in ten;
# Laguna's two, one class under two sets of sizes (a preset's ``parts`` has
# an entry for each kind): "GW" grouped attention in a window, "GF" the same
# class over the whole causal row, each with a gate a head.
LAYER_KINDS: dict = {
    "": LayerKind(SelfAttention, "attn", "attention",
                  (("causal", True), ("use_bias", False)), 3),
    "L": LayerKind(LatentAttention, "attn", "attention", sequence=3),
    "M": LayerKind(MambaMixer, "ssm"),
    "M*": LayerKind(MambaMixer, "ssm", hands=_MEMORY),
    "S": LayerKind(DifferentialAttention, "attn", "attention", sequence=2),
    "F*": LayerKind(DifferentialAttention, "attn", "attention",
                    (("window", 0),), 2, hands=_KEYS_VALUES),
    "G": LayerKind(GatedMemoryUnit, "gmu", "gmu", takes=_MEMORY),
    "X": LayerKind(DifferentialAttention, "attn", "attention",
                   (("window", 0),), 2, takes=_KEYS_VALUES),
    "C": LayerKind(ConvolutionalAttention, "attn", "attention", sequence=3),
    "D": LayerKind(GatedDeltaNet, "gdn", "linear_attention"),
    "A": LayerKind(GatedAttention, "attn", "attention", sequence=3),
    "W": LayerKind(GroupedAttention, "attn", "attention", sequence=3),
    "N": LayerKind(GroupedAttention, "attn", "attention",
                   (("window", 0), ("rotary", False)), 3),
    "M2": LayerKind(Mamba2Mixer, "ssm", "state_space"),
    "GW": LayerKind(GroupedAttention, "attn", "attention", sequence=3),
    "GF": LayerKind(GroupedAttention, "attn", "attention",
                    (("window", 0),), 3),
}


class DecoderBlock(nn.Module):
    """Pre-norm decoder layer ``x + mixer(norm(x))``, ``x + ffn(norm(x))``,
    bias-free. The choices a layer makes are fields. ``kind`` is its mixer,
    by :data:`LAYER_KINDS`; ``parts`` holds, for each class among the
    layer's parts that has sizes of its own, a ``partial`` of the class over
    them, under the class's own field names, or a ``(kind, partial)`` pair
    where layers of one class differ in size by their kind (Laguna's window
    layers have 72 query heads, its full ones 48, another theta and rotary
    width): a layer takes the pair made for its kind first. A ``"C"`` layer
    (ZAYA1's) also has an expert layer whose router is a
    :class:`..moe.StateRouter` with a state handed from layer to layer. ``residual_scales`` (ZAYA1's) puts
    learned scales and shifts on both sides of both residual sums;
    ``branch_scale`` other than 1 (Granite's ``residual_multiplier``)
    multiplies both branches ahead of their sums. With ``router_early``
    (SmallThinker's) the
    expert layer's logits are one f32 product of the block's input, made
    before ``ln_attn`` and attention, whatever the mixer.
    ``dense_dim`` is the feed-forward (0: the dropless expert layer that
    ``moe`` describes; > 0: a dense SwiGLU of that width), ``layer_norm`` the
    norm (LayerNorm, else RMSNorm, with ``norm_offset`` in its ``1 + w``
    form). The call takes and returns, beside ``x``, what is handed on:
    ``(m, (k, v), r)``, each None until its layer has run."""

    num_heads: int
    expert_dim: int
    num_experts: int
    experts_per_token: int
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    dense_dim: int = 0
    moe: tuple = ()  # further fields of DroplessMoE, as (name, value) pairs
    kind: str = ""
    depth: int = 0  # the layer's published index
    layer_norm: bool = False
    parts: tuple = ()  # partial(Class, **its own sizes), one a sized class,
    # or (kind, partial): that class's sizes in the layers of that kind
    norm_offset: bool = False  # RMSNorm's scale is 1 + w
    router_early: bool = False  # the router reads x, ahead of attention
    residual_scales: bool = False  # learned a and b on both residual sums
    branch_scale: float = 1.0  # x + branch_scale * branch

    def part(self, cls, **fields):
        """``cls`` as this layer holds it: with the sizes ``parts`` states
        for it (an entry made for the layer's kind first, else one for the
        class), those of the layer's own fields that it declares and the
        entry does not state, the layer's initialiser, and ``fields``."""
        by_kind = [p[1] for p in self.parts
                   if isinstance(p, tuple) and p[0] == self.kind]
        by_class = [p for p in self.parts if not isinstance(p, tuple)]
        sized = next((p for p in by_kind + by_class if p.func is cls), cls)
        stated = getattr(sized, "keywords", {})
        own = {name: getattr(self, name) for name in (
            "num_heads", "num_experts", "expert_dim", "experts_per_token",
            "norm_eps", "rope_theta", "dtype", "attention_fn", "depth")
            if name in cls.__dataclass_fields__ and name not in stated}
        return sized(**{
            **own, "kernel_init": nn.initializers.truncated_normal(
                self.init_std), **fields})

    def mixer(self, **fields):
        """The layer's mixer, by its kind: what ``__call__`` runs, and what
        the stack asks which kernels it runs."""
        kind = LAYER_KINDS[self.kind]
        return self.part(kind.mixer, **dict(kind.fixed), **fields)

    def _merge(self, x, y, name):
        """The residual sum ``x + y``, or ``x + branch_scale y`` in f32
        rounded once; under ``residual_scales`` ``(a_r x + b_r) + (a_o y +
        b_o)`` in f32, with learned vectors, a from 1 and b from 0."""
        if not self.residual_scales:
            if self.branch_scale == 1.0:
                return x + y
            return (x.astype(jnp.float32) + self.branch_scale * y.astype(
                jnp.float32)).astype(self.dtype)
        ones, zeros = nn.initializers.ones_init(), nn.initializers.zeros_init()
        a_r, b_r, a_o, b_o = (
            self.param(f"{name}_{part}", init, x.shape[-1:], jnp.float32)
            for part, init in (
                ("stream_scale", ones), ("stream_shift", zeros),
                ("branch_scale", ones), ("branch_shift", zeros)))
        self.sow("mixer_stats", "residual_scale", jnp.stack([a_r, a_o]))
        return ((a_r * x + b_r) + (a_o * y + b_o)).astype(self.dtype)

    @nn.compact
    def __call__(self, x, mask=None, segment_ids=None, position_ids=None,
                 live=None, handed=(None, None, None)):
        norm = partial(RMSNorm, self.norm_eps, self.dtype, self.norm_offset)
        if self.layer_norm:
            norm = partial(nn.LayerNorm, epsilon=self.norm_eps,
                           dtype=self.dtype, param_dtype=jnp.float32)
        kind, handed = LAYER_KINDS[self.kind], list(handed)
        logits = None  # the expert layer's own router
        if self.router_early and not self.dense_dim:
            with jax.named_scope("moe.router"):
                logits = router_product(
                    self.num_experts, nn.initializers.truncated_normal(
                        self.init_std), "router")(x.astype(jnp.float32))
            self.sow("moe_stats", "router_early", jnp.float32(1))
        y = norm(name="ln_attn")(x)
        taken = () if kind.takes is None else (handed[kind.takes],)
        with jax.named_scope(kind.scope) if kind.scope else nullcontext():
            y = self.mixer(name=kind.name)(
                y, *(mask, segment_ids, position_ids)[:kind.sequence], *taken)
        if isinstance(y, tuple):  # beside the output, what could be handed on
            y, own = y
            if kind.hands is not None:
                handed[kind.hands] = own
        x = self._merge(x, y, "attn")
        y = norm(name="ln_mlp")(x)
        if self.dense_dim:
            with jax.named_scope("mlp.dense"):
                y = self.part(SwiGLU, mlp_dim=self.dense_dim, name="mlp")(y)
        else:
            if self.kind == "C":
                with jax.named_scope("moe.router"):
                    logits, handed[_ROUTER_STATE] = self.part(
                        StateRouter, name="router")(y, handed[_ROUTER_STATE])
            y = self.part(DroplessMoE, name="moe", **dict(self.moe))(
                y, live, logits)
        return self._merge(x, y, "mlp"), tuple(handed)


class TransformerDecoder(nn.Module):
    """Decoder-only stack of today's open models' kind: pre-norm, no
    position table, no biases, a final norm and a head. Each layer is a
    :class:`DecoderBlock`; OLMoE's are all alike (RMSNorm, rotary attention,
    an expert layer, an untied ``lm_head``), Moonlight's take latent
    attention (``kind`` ``"L"``), and a dense SwiGLU in the first
    ``dense_layers`` of them.
    A stack with ``layer_kinds`` names every published layer's kind
    (:data:`LAYER_KINDS`): SambaY's (Phi-4-mini-flash) differ by layer, under
    LayerNorm (``layer_norm``), with no position term at all; ZAYA1's are all
    ``"C"``, under RMSNorm; Qwen3-Next's are three ``"D"`` to one ``"A"``,
    under RMSNorm's ``1 + w`` form, with a head of its own; SmallThinker's
    one ``"N"`` to three ``"W"``, every layer's router ahead of its
    attention (``router_early``), a head of its own; Granite 4.0-H's nine
    ``"M2"`` to one ``"N"``, a dense SwiGLU in every layer, and the model's
    four multipliers: the embedding's (``embed_scale``), both branches'
    ahead of their residual sums (``branch_scale``), the attention scores'
    (``GroupedAttention.score_scale``) and the logits' (``logit_scale``), each
    absent from a stack's arithmetic at its default; Laguna's one ``"GF"`` to
    three ``"GW"``, one class whose sizes differ by the layer's kind (48
    query heads and a YaRN half-rotary turn, 72 and a whole one in a window
    of 512), a leading dense layer and a head of its own. SambaY's, ZAYA1's
    and Granite's tie the head to the embedding (``tied_head``).
    ``first_layer`` says which published layers are held (``num_layers`` of
    them from there: one pipeline stage's), and what a layer hands on (M*'s
    and F*'s tensors, a router's state) rides from layer to layer beside
    ``x``.
    Same call signature as :class:`TransformerEncoder`, so the ``causal_lm``
    task drives either; logits ``[B, S, vocab]`` in f32.
    """

    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    expert_dim: int
    num_experts: int
    experts_per_token: int
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    init_std: float = 0.02
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention_fn: Optional[Callable] = None
    dense_layers: int = 0  # the published layers before this one are dense,
    dense_dim: int = 0  # dense_dim wide, held here or not
    moe: tuple = ()  # DecoderBlock's, for every expert layer
    kind: str = ""  # every layer's DecoderBlock.kind, where they are alike
    layer_kinds: tuple = ()  # else every published layer's
    first_layer: int = 0  # published index of the first layer held here
    parts: tuple = ()  # DecoderBlock's, for every layer
    norm_offset: bool = False  # RMSNorm's scale is 1 + w, everywhere
    router_early: bool = False  # DecoderBlock's, for every expert layer
    layer_norm: bool = False  # LayerNorm in place of RMSNorm, everywhere
    tied_head: bool = False  # the head is the embedding, no matrix of its own
    residual_scales: bool = False  # DecoderBlock's, for every layer
    branch_scale: float = 1.0  # DecoderBlock's, for every layer
    embed_scale: float = 1.0  # the embedding's rows times this
    logit_scale: float = 1.0  # the logits times this

    @property
    def held_kinds(self) -> tuple:
        """The kinds of the layers held here, in order (``kind`` without
        ``layer_kinds``); a G or X with no M* or F* before it is refused."""
        if not self.layer_kinds:
            return (self.kind,) * self.num_layers
        span = f"{self.first_layer}:{self.first_layer + self.num_layers}"
        kinds = self.layer_kinds[self.first_layer:
                                 self.first_layer + self.num_layers]
        if len(kinds) != self.num_layers or self.first_layer < 0:
            raise ValueError(f"layer span {span} is not inside the model's "
                             f"{len(self.layer_kinds)} layers")
        for needs, source in (("G", "M*"), ("X", "F*")):
            if needs in kinds and source not in kinds[:kinds.index(needs)]:
                at = self.first_layer + kinds.index(needs)
                raise ValueError(
                    f"layer span {span}: layer {at} is a {needs} layer and "
                    f"reads what the {source} layer (layer "
                    f"{self.layer_kinds.index(source)}) hands on, which the "
                    "span does not hold before it")
        return kinds

    def _layer(self, i: int, kind: str, block=DecoderBlock, **fields):
        """The ``i``-th held layer's block."""
        return block(
            self.num_heads, self.expert_dim, self.num_experts,
            self.experts_per_token, self.norm_eps, self.rope_theta,
            self.init_std, self.dtype, attention_fn=self.attention_fn,
            dense_dim=(self.dense_dim
                       if self.first_layer + i < self.dense_layers else 0),
            moe=self.moe, kind=kind, depth=self.first_layer + i,
            layer_norm=self.layer_norm, parts=self.parts,
            norm_offset=self.norm_offset, router_early=self.router_early,
            residual_scales=self.residual_scales,
            branch_scale=self.branch_scale, **fields)

    def kernels(self, seq_len: int) -> dict:
        """Which kernels the held layers run at ``seq_len``, by name
        (``attention``, ``scan``, ``delta``, ``ssd``, ``conv``, ``norm``):
        every layer's mixer is asked, and a name is True where each mixer
        that has it says so. A span that holds no mixer with some kernel has
        no entry for it."""
        answer: dict = {}
        for i, kind in enumerate(self.held_kinds):
            mixer = self._layer(i, kind, parent=None).mixer(parent=None)
            for name, fused in mixer.kernels(seq_len,
                                             self.hidden_size).items():
                answer[name] = answer.get(name, True) and fused
        return answer

    @property
    def yarn(self) -> tuple:
        """The YaRN rule of the first held mixer that turns under one (its
        ``yarn`` field), or ``()``: the first log line says it."""
        for i, kind in enumerate(self.held_kinds):
            rule = getattr(self._layer(i, kind, parent=None).mixer(
                parent=None), "yarn", ())
            if rule:
                return rule
        return ()

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, train: bool = True,
                 segment_ids=None, position_ids=None):
        init = nn.initializers.truncated_normal(self.init_std)
        embed = nn.Embed(self.vocab_size, self.hidden_size,
                         param_dtype=jnp.float32, embedding_init=init,
                         name="tok_embed")
        x = embed(input_ids)
        if self.embed_scale != 1.0:
            x = x * self.embed_scale  # in the table's f32
        x = x.astype(self.dtype)
        live = None if attention_mask is None else attention_mask > 0
        mask, seg_kwarg = _attention_masks(attention_mask, segment_ids,
                                           self.attention_fn)
        block = DecoderBlock
        if self.remat:
            block = nn.remat(DecoderBlock, static_argnums=())
        handed = (None, None, None)
        for i, kind in enumerate(self.held_kinds):
            x, handed = self._layer(i, kind, block, name=f"layer_{i}")(
                x, mask, seg_kwarg, position_ids, live, handed)
        if self.layer_norm:
            x = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype,
                             param_dtype=jnp.float32, name="ln_final")(x)
        else:
            x = RMSNorm(self.norm_eps, self.dtype, self.norm_offset,
                        name="ln_final")(x)
        with jax.named_scope("lm_head"):
            # bf16 operands on the matrix unit, f32 sums and f32 logits
            if self.tied_head:  # the held rows of the embedding
                logits = jnp.einsum(
                    "bsh,vh->bsv", x, embed.embedding.astype(self.dtype),
                    preferred_element_type=jnp.float32)
            else:
                logits = nn.Dense(
                    self.vocab_size, use_bias=False, dtype=self.dtype,
                    param_dtype=jnp.float32, kernel_init=init,
                    dot_general=partial(jax.lax.dot_general,
                                        preferred_element_type=jnp.float32),
                    name="lm_head")(x)
            return (logits if self.logit_scale == 1.0
                    else logits * self.logit_scale)


bert_base = partial(TransformerEncoder, hidden_size=768, num_layers=12,
                    num_heads=12, mlp_dim=3072)
bert_small = partial(TransformerEncoder, hidden_size=256, num_layers=4,
                     num_heads=4, mlp_dim=1024)
# Decoder-only (GPT-style) presets: same trunk, causal attention, tied LM
# head. gpt_base matches GPT-2 124M's shape (768/12/12).
gpt_base = partial(TransformerEncoder, hidden_size=768, num_layers=12,
                   num_heads=12, mlp_dim=3072, causal=True)
gpt_small = partial(TransformerEncoder, hidden_size=256, num_layers=4,
                    num_heads=4, mlp_dim=1024, causal=True)
# OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct config.json; Muennighoff et
# al., arXiv:2409.02060): 16 layers of 64 experts, 8 a token, none shared.
olmoe_1b_7b = partial(TransformerDecoder, hidden_size=2048, num_layers=16,
                      num_heads=16, expert_dim=1024, num_experts=64,
                      experts_per_token=8)
olmoe_tiny = partial(TransformerDecoder, hidden_size=64, num_layers=2,
                     num_heads=4, expert_dim=32, num_experts=8,
                     experts_per_token=2)
# Moonlight-16B-A3B (moonshotai/Moonlight-16B-A3B config.json, model_type
# deepseek_v3): 27 layers of latent attention (16 heads, keys 128 + 64 rotary,
# values 128, from a 512-wide latent); the first layer a dense SwiGLU of
# 11,264, the others 64 experts of 1,408 with 6 a token by sigmoid scores and
# a selection bias (gamma 0.001: DeepSeek-V3, arXiv:2412.19437), renormalised,
# times 2.446, beside one shared SwiGLU of 2 x 1,408.
_MOONLIGHT_ROUTER = (("scoring", "sigmoid"), ("norm_topk", True),
                     ("routed_scale", 2.446), ("bias_update_rate", 0.001))
moonlight_16b_a3b = partial(
    TransformerDecoder, hidden_size=2048, num_layers=27, num_heads=16,
    expert_dim=1408, num_experts=64, experts_per_token=6, rope_theta=50000.0,
    kind="L", parts=(partial(LatentAttention, kv_rank=512, nope_dim=128,
                             rope_dim=64, v_dim=128),),
    dense_layers=1, dense_dim=11264,
    moe=_MOONLIGHT_ROUTER + (("shared_dim", 2816),))
moonlight_tiny = partial(
    TransformerDecoder, hidden_size=64, num_layers=3, num_heads=4,
    expert_dim=32, num_experts=8, experts_per_token=2, rope_theta=50000.0,
    kind="L", parts=(partial(LatentAttention, kv_rank=32, nope_dim=16,
                             rope_dim=8, v_dim=16),),
    dense_layers=1, dense_dim=128,
    moe=_MOONLIGHT_ROUTER + (("shared_dim", 32),))


def sambay_layers(layers: int) -> tuple:
    """SambaY's layout over ``layers`` layers (arXiv:2507.06607; ``mb_per_
    layer`` 2: every second mixer is a state-space one). The first half, the
    self-decoder: even layers Mamba (M), odd ones window attention (S). Then
    a Mamba layer that also hands on its scan output (M*) and full attention
    whose keys and values are kept (F*). The rest, the cross-decoder: even
    layers gated memory units over M*'s output (G), odd ones attention with
    their own queries over F*'s keys and values (X)."""
    half = layers // 2
    return tuple(
        ("M", "S")[i % 2] if i < half else "M*" if i == half
        else "F*" if i == half + 1 else ("G", "X")[i % 2]
        for i in range(layers))


# Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning
# config.json, model_type phi4flash; Ren et al., arXiv:2507.06607): 32 SambaY
# layers (9 Mamba of which layer 16 is M*, 8 window-512, layer 17 F*, 7 gated
# memory units, 7 cross-attention), 40 query heads over 20 key heads of 64 in
# differential attention, a SwiGLU of 10,240 in every layer, LayerNorm 1e-5,
# no position term, the head tied. The config has no Mamba sizes: inner 2 x
# hidden, 16 states, a convolution over 4, dt_rank hidden / 16 (Mamba's own
# convention; the benchmark's configuration lists them as assumed).
phi4_mini_flash = partial(
    TransformerDecoder, hidden_size=2560, num_layers=32, num_heads=40,
    expert_dim=0, num_experts=0, experts_per_token=0, rope_theta=0.0,
    dense_layers=32, dense_dim=10240, layer_kinds=sambay_layers(32),
    parts=(partial(MambaMixer, inner=5120, states=16, conv=4, dt_rank=160),
           partial(DifferentialAttention, kv_heads=20, window=512)),
    layer_norm=True, tied_head=True)
phi4_mini_flash_tiny = partial(
    TransformerDecoder, hidden_size=64, num_layers=8, num_heads=8,
    expert_dim=0, num_experts=0, experts_per_token=0, rope_theta=0.0,
    dense_layers=8, dense_dim=128, layer_kinds=sambay_layers(8),
    parts=(partial(MambaMixer, inner=128, states=8, conv=4, dt_rank=4),
           partial(DifferentialAttention, kv_heads=4, window=16)),
    layer_norm=True, tied_head=True)
# ZAYA1-8B (Zyphra/ZAYA1-8B config.json, model_type zaya; arXiv:2510.04476 for
# the attention, arXiv:2511.17127 for the rest): 40 layers alike, each
# compressed convolutional attention (8 query heads over 2 key/value heads of
# 128 in a latent, two causal 2-tap convolutions, rotary on half of a head,
# theta 5e6) and 16 SwiGLU experts of 2,048, one a token by a softmax over an
# MLP router of width 256 whose state rides from layer to layer, chosen under a
# selection bias (the program's rule; the config names no rate: DeepSeek-V3's
# 0.001 for sigmoid scores of mean 0.5, scaled to a softmax over 16 of mean
# 1/16); learned scales on both residual sums; RMSNorm 1e-5; the head tied.
_ZAYA_ROUTER = (("bias_update_rate", 0.0001),)
zaya1_8b = partial(
    TransformerDecoder, hidden_size=2048, num_layers=40, num_heads=8,
    expert_dim=2048, num_experts=16, experts_per_token=1,
    rope_theta=5000000.0, moe=_ZAYA_ROUTER, layer_kinds=("C",) * 40,
    parts=(partial(ConvolutionalAttention, kv_heads=2, head_dim=128,
                   rotary_dim=64), partial(StateRouter, width=256)),
    tied_head=True, residual_scales=True)
zaya_tiny = partial(
    TransformerDecoder, hidden_size=64, num_layers=3, num_heads=4,
    expert_dim=32, num_experts=8, experts_per_token=1, rope_theta=5000000.0,
    moe=_ZAYA_ROUTER, layer_kinds=("C",) * 3,
    parts=(partial(ConvolutionalAttention, kv_heads=2, head_dim=16,
                   rotary_dim=8), partial(StateRouter, width=32)),
    tied_head=True, residual_scales=True)


def qwen3_next_layers(layers: int, interval: int = 4) -> tuple:
    """Qwen3-Next's layout (``full_attention_interval``): layer ``i`` is
    gated softmax attention (A) where ``(i + 1) % interval == 0`` and a Gated
    DeltaNet (D) otherwise."""
    return tuple("A" if (i + 1) % interval == 0 else "D"
                 for i in range(layers))


# Qwen3-Next-80B-A3B (Qwen/Qwen3-Next-80B-A3B-Instruct config.json, model_type
# qwen3_next; Gated Delta Networks, arXiv:2412.06464, for the recurrence): 48
# layers, three Gated DeltaNets (16 key heads serving 32 value heads of 128,
# a causal convolution over 4) to one gated attention layer (16 query heads
# over 2 key/value heads of 256, rotary on a quarter of a head, theta 1e7);
# every layer 512 SwiGLU experts of 512 with 10 a token by softmax scores
# renormalised over the chosen, beside one shared expert of 512 under a
# sigmoid gate; RMSNorm 1e-6 with a scale of 1 + w; the head untied.
_QWEN3_NEXT_EXPERTS = (("norm_topk", True), ("shared_gate", True))
qwen3_next_80b_a3b = partial(
    TransformerDecoder, hidden_size=2048, num_layers=48, num_heads=16,
    expert_dim=512, num_experts=512, experts_per_token=10, norm_eps=1e-6,
    rope_theta=10000000.0,
    moe=_QWEN3_NEXT_EXPERTS + (("shared_dim", 512),),
    layer_kinds=qwen3_next_layers(48),
    parts=(partial(GatedDeltaNet, key_heads=16, value_heads=32, key_dim=128,
                   value_dim=128, conv=4),
           partial(GatedAttention, kv_heads=2, head_dim=256, rotary_dim=64)),
    norm_offset=True)
qwen3_next_tiny = partial(
    TransformerDecoder, hidden_size=64, num_layers=4, num_heads=4,
    expert_dim=32, num_experts=64, experts_per_token=4, norm_eps=1e-6,
    rope_theta=10000000.0, moe=_QWEN3_NEXT_EXPERTS + (("shared_dim", 32),),
    layer_kinds=qwen3_next_layers(4),
    parts=(partial(GatedDeltaNet, key_heads=2, value_heads=4, key_dim=16,
                   value_dim=16, conv=4),
           partial(GatedAttention, kv_heads=2, head_dim=32, rotary_dim=8)),
    norm_offset=True)


def smallthinker_layers(layers: int, period: int = 4) -> tuple:
    """SmallThinker's layout (``rope_layout`` and ``sliding_window_layout``,
    one list): layer ``i`` is full attention without a position term (N)
    where ``i % period == 0`` and rotary attention in a window (W)
    otherwise."""
    return tuple("N" if i % period == 0 else "W" for i in range(layers))


# SmallThinker-21BA3B-Instruct (PowerInfer/SmallThinker-21BA3B-Instruct
# config.json; the published class is remote code, so what the config cannot
# confirm is listed as assumed in the benchmark's configuration): 52 layers,
# one full position-free attention layer to three rotary ones in a window of
# 4,096 (theta 1.5e6), 28 query heads over 4 key/value heads of 128 on a
# stream of 2,560; every layer 64 ReGLU experts of 768 with 6 a token, chosen
# by a router that reads the layer's input ahead of attention, weighted by a
# softmax over the chosen six (DroplessMoE's softmax over all, renormalised
# over the chosen); no shared expert; RMSNorm 1e-6; the head untied.
_SMALLTHINKER_EXPERTS = (("norm_topk", True), ("activation", "relu"))
smallthinker_21b_a3b = partial(
    TransformerDecoder, hidden_size=2560, num_layers=52, num_heads=28,
    expert_dim=768, num_experts=64, experts_per_token=6, norm_eps=1e-6,
    rope_theta=1500000.0, moe=_SMALLTHINKER_EXPERTS,
    layer_kinds=smallthinker_layers(52),
    parts=(partial(GroupedAttention, kv_heads=4, head_dim=128, window=4096),),
    router_early=True)
smallthinker_tiny = partial(
    TransformerDecoder, hidden_size=64, num_layers=4, num_heads=4,
    expert_dim=32, num_experts=16, experts_per_token=2, norm_eps=1e-6,
    rope_theta=1500000.0, moe=_SMALLTHINKER_EXPERTS,
    layer_kinds=smallthinker_layers(4),
    parts=(partial(GroupedAttention, kv_heads=2, head_dim=32, window=16),),
    router_early=True)


def granite4_layers(layers: int, period: int = 10, at: int = 5) -> tuple:
    """Granite 4.0-H's layout (``layer_types``): layer ``i`` is grouped
    attention without a position term (N) where ``i % period == at`` and a
    Mamba-2 mixer (M2) otherwise: attention at 5, 15, 25, 35 of 40."""
    return tuple("N" if i % period == at else "M2" for i in range(layers))


# granite-4.0-h-micro (ibm-granite/granite-4.0-h-micro config.json,
# model_type granitemoehybrid with num_local_experts 0; the state-space dual,
# arXiv:2405.21060, for the recurrence): 40 layers, nine Mamba-2 mixers (64
# heads of 64 over one group of 128 states, a causal convolution over 4 with a
# bias, one gated norm over all 4,096 columns) to one attention layer (32
# query heads over 8 key/value heads of 64, no position term, scores times
# 1/64); every layer a dense SwiGLU of 8,192 (the "shared" MLP alone);
# RMSNorm 1e-5; the embedding times 12, both branches times 0.22, the tied
# head's logits over 8.
_GRANITE4_SCALES = dict(embed_scale=12.0, branch_scale=0.22,
                        logit_scale=0.125)
granite4_h_micro = partial(
    TransformerDecoder, hidden_size=2048, num_layers=40, num_heads=32,
    expert_dim=0, num_experts=0, experts_per_token=0, rope_theta=0.0,
    dense_layers=40, dense_dim=8192, layer_kinds=granite4_layers(40),
    parts=(partial(Mamba2Mixer, inner=4096, heads=64, head_dim=64,
                   states=128, conv=4),
           partial(GroupedAttention, kv_heads=8, head_dim=64,
                   score_scale=0.015625)),
    tied_head=True, **_GRANITE4_SCALES)
granite4_h_tiny = partial(
    TransformerDecoder, hidden_size=64, num_layers=4, num_heads=4,
    expert_dim=0, num_experts=0, experts_per_token=0, rope_theta=0.0,
    dense_layers=4, dense_dim=128, layer_kinds=granite4_layers(4, 4, 1),
    parts=(partial(Mamba2Mixer, inner=64, heads=4, head_dim=16, states=16,
                   conv=4),
           partial(GroupedAttention, kv_heads=2, head_dim=16,
                   score_scale=0.015625)),
    tied_head=True, **_GRANITE4_SCALES)


def laguna_layers(layers: int, period: int = 4) -> tuple:
    """Laguna's layout (``layer_types``): layer ``i`` is full attention (GF)
    where ``i % period == 0`` and window attention (GW) otherwise."""
    return tuple("GF" if i % period == 0 else "GW" for i in range(layers))


# Laguna-S-2.1 (poolside/Laguna-S-2.1 config.json, model_type laguna; no class
# of it in the container, so what the config cannot confirm is listed as
# assumed in the benchmark's configuration): 48 layers on a stream of 3,072,
# one full attention layer of 48 query heads (rotary on half of a head, theta
# 5e5 under YaRN: factor 128 over 8,192 positions, cos and sin times
# 1.4852 = 0.1 ln 128 + 1) to three window-512 layers of 72 (plain rotary,
# theta 1e4), all over 8 key/value heads of 128, a sigmoid gate a head; layer
# 0 a dense SwiGLU of 12,288, the others 256 experts of 1,024 with 10 a token
# by sigmoid scores renormalised and times 2.5, no selection bias and no
# balance term, beside one shared SwiGLU of 1,024; RMSNorm 1e-6; the head
# untied.
_LAGUNA_EXPERTS = (("scoring", "sigmoid"), ("norm_topk", True),
                   ("routed_scale", 2.5))
laguna_s_2_1 = partial(
    TransformerDecoder, hidden_size=3072, num_layers=48, num_heads=48,
    expert_dim=1024, num_experts=256, experts_per_token=10, norm_eps=1e-6,
    dense_layers=1, dense_dim=12288,
    moe=_LAGUNA_EXPERTS + (("shared_dim", 1024),),
    layer_kinds=laguna_layers(48),
    parts=(("GW", partial(GroupedAttention, num_heads=72, kv_heads=8,
                          head_dim=128, window=512, rope_theta=10000.0,
                          head_gate=True)),
           ("GF", partial(GroupedAttention, kv_heads=8, head_dim=128,
                          rope_theta=500000.0, rotary_dim=64,
                          yarn=(128.0, 8192, 32.0, 1.0, 1.4852030263919618),
                          head_gate=True))))
# at test size: 6 query heads in the window layers and 4 in the full ones over
# 2 key/value heads of 16, a window of 16, YaRN over 64 positions of 8 of a
# head's 16 (its ramp over all four pairs), 16 experts with 4 a token
laguna_tiny = partial(
    TransformerDecoder, hidden_size=64, num_layers=5, num_heads=4,
    expert_dim=32, num_experts=16, experts_per_token=4, norm_eps=1e-6,
    dense_layers=1, dense_dim=128,
    moe=_LAGUNA_EXPERTS + (("shared_dim", 32),),
    layer_kinds=laguna_layers(5),
    parts=(("GW", partial(GroupedAttention, num_heads=6, kv_heads=2,
                          head_dim=16, window=16, rope_theta=10000.0,
                          head_gate=True)),
           ("GF", partial(GroupedAttention, kv_heads=2, head_dim=16,
                          rope_theta=64.0, rotary_dim=8,
                          yarn=(8.0, 64, 4.0, 1.0, 1.2079441541679836),
                          head_gate=True))))


class Preset(NamedTuple):
    """A ``causal_lm`` preset: the constructor (called with ``vocab_size``
    and whatever a task changes), the vocabulary that is the model's own, and
    the weight of each auxiliary term its expert layers sow into
    ``aux_loss`` (a term without a weight is left out of the loss)."""

    ctor: Callable
    vocab_size: int
    aux_weights: dict


# The GPT presets have expert layers only under --num_experts (MoEMLP: the
# switch balance term); every OLMoE layer is one, with the two weights of the
# paper (arXiv:2409.02060, section 4.1); Moonlight's (all but its first) sow
# the sequence-wise balance term, with DeepSeek-V3's weight (arXiv:2412.19437,
# section 4.2: alpha 0.0001). ZAYA1's are balanced by the selection bias
# alone. Qwen3-Next's take the balance term at its published class's default
# weight (router_aux_loss_coef 0.001) and no z term, and SmallThinker's the
# same (its config names none: the benchmark's configuration lists it as
# assumed). Granite 4.0-H micro has no experts and no auxiliary term;
# Laguna's experts sow the sequence-wise term and the loss takes none (its
# config names no weight: the benchmark's configuration lists it as assumed).
_SWITCH_AUX = {"load_balance": 0.01}
_OLMOE_AUX = {"load_balance": 0.01, "router_z": 0.001}
_MOONLIGHT_AUX = {"seq_balance": 0.0001}
_QWEN3_NEXT_AUX = {"load_balance": 0.001}
_SMALLTHINKER_AUX = {"load_balance": 0.001}
CAUSAL_LMS: dict = {
    "gpt_base": Preset(gpt_base, 50257, _SWITCH_AUX),
    "gpt_small": Preset(gpt_small, 50257, _SWITCH_AUX),
    "olmoe_1b_7b": Preset(olmoe_1b_7b, 50304, _OLMOE_AUX),
    "olmoe_tiny": Preset(olmoe_tiny, 512, _OLMOE_AUX),
    "moonlight_16b_a3b": Preset(moonlight_16b_a3b, 163840, _MOONLIGHT_AUX),
    "moonlight_tiny": Preset(moonlight_tiny, 512, _MOONLIGHT_AUX),
    "phi4_mini_flash": Preset(phi4_mini_flash, 200064, {}),
    "phi4_mini_flash_tiny": Preset(phi4_mini_flash_tiny, 512, {}),
    "zaya1_8b": Preset(zaya1_8b, 262272, {}),
    "zaya_tiny": Preset(zaya_tiny, 512, {}),
    "qwen3_next_80b_a3b": Preset(qwen3_next_80b_a3b, 151936, _QWEN3_NEXT_AUX),
    "qwen3_next_tiny": Preset(qwen3_next_tiny, 512, _QWEN3_NEXT_AUX),
    "smallthinker_21b_a3b": Preset(smallthinker_21b_a3b, 151936,
                                   _SMALLTHINKER_AUX),
    "smallthinker_tiny": Preset(smallthinker_tiny, 512, _SMALLTHINKER_AUX),
    "granite4_h_micro": Preset(granite4_h_micro, 100352, {}),
    "granite4_h_tiny": Preset(granite4_h_tiny, 512, {}),
    "laguna_s_2_1": Preset(laguna_s_2_1, 100352, {}),
    "laguna_tiny": Preset(laguna_tiny, 512, {}),
}
