"""Plain reference of granite-4.0-h-micro's layers as the program runs them:
float32 ``jax.numpy`` at the highest matmul precision, fed the program's own
parameter tree. Sources: ``ibm-granite/granite-4.0-h-micro`` ``config.json``
(``model_type`` ``granitemoehybrid``, ``num_local_experts`` 0) for every
size and all four multipliers; the published class for the equations
(``transformers`` 4.57.6 ``models/granitemoehybrid/modeling_granitemoehybrid.py``:
``GraniteMoeHybridMambaLayer.torch_forward``, ``GraniteMoeHybridRMSNormGated``,
``GraniteMoeHybridAttention``, ``GraniteMoeHybridMLP``,
``GraniteMoeHybridDecoderLayer``, ``GraniteMoeHybridForCausalLM``); Dao and
Gu, arXiv:2405.21060, for the recurrence. With ``u = rms(x)`` (eps 1e-5, a
plain learned scale), no biases but the convolution's and ``dt``'s:

    tokens      x = 12 E[id]                                embedding_multiplier
    M2          [xBC; z] = u W_in   (4,352; 4,096)   dt = softplus(u W_dt + b_dt)
                xBC <- silu(conv4(xBC) + b_c)   depthwise, causal
                [x; B; C] = xBC                 (64 heads x 64; 128; 128)
                A = -exp(A_log)  a head, a scalar
                h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,  h_0 = 0, [64, 128]
                y_t = h_t C_t + D x_t
                g = y silu(z);  out = (w_n g / sqrt(mean_4096(g^2) + 1e-5)) W_out
    N           q = u W_q (32 x 64), k = u W_k, v = u W_v (8 x 64), no position
                o = softmax(q k' / 64 + causal) v;  out = concat(o) W_o
                                                            attention_multiplier
    every layer x <- x + 0.22 out;  x <- x + 0.22 W_d (silu(W_g v) * W_u v),
                v = rms(x)                                  residual_multiplier
    logits      rms(x) E' / 8                               logits_scaling

The parameters say which layer is which (an ``ssm`` or an ``attn`` entry).
The recurrence is a sequential ``lax.scan`` over tokens (in chunks only so
that ``jax.grad`` keeps one state a chunk); attention is a dense masked
softmax a block of ``Q_BLOCK`` queries at a time, a key/value head with its
group of query heads in one product, so that 8,192 tokens fit. No chunked
form, no kernel, nothing from ``lance_distributed_training_tpu``. ``loss`` is
the training loss (shifted cross-entropy), for ``jax.grad``.

``forward(variables, batch, dtype=jnp.bfloat16)`` is the same mathematics with
every tensor and product in bf16 and what the configuration states as float32
rounded to bf16 where it stands (``dt``, the decay, the state and its
read-out, the gated norm, softmax statistics, the logits): the nearest
precision below the configuration's, which the comparison has to refuse
(``TOLERANCE``).
"""

from __future__ import annotations

import math

import numpy as np

EPS = 1e-5
EMBED_SCALE = 12.0  # embedding_multiplier
BRANCH_SCALE = 0.22  # residual_multiplier
SCORE_SCALE = 0.015625  # attention_multiplier
LOGIT_SCALE = 1.0 / 8.0  # 1 / logits_scaling
STATES = 128  # mamba_d_state
HEAD_DIM = 64  # mamba_d_head
EVAL_ROWS = 1
Q_BLOCK = 1024
SCAN_CHUNK = 128

# How ``perturb`` moves the Mamba-2 layers toward long memory. At the
# published start (A = -1..-64, dt = softplus(1 + small) = 1.3) a head's decay
# a token is exp(-1.3) to exp(-84): every state forgets within a few tokens
# and a state kept in bf16 differs from one kept in float32 by no more than
# bf16 activations do anyway, so the stated precision would be unguarded.
# ``perturb`` sets ``A_log`` so that A is log-uniform in [-LONG_A[1],
# -LONG_A[0]] a head and ``dt_bias`` so that softplus(dt_bias) = LONG_DT: a
# token's decay exp(dt A) then lies within 1e-5 to 1e-3 of 1, a state at the
# end of a row of 8,192 tokens still holds its first tokens, and it is a sum
# of thousands of terms of either sign that float32 carries and bf16 does not.
LONG_A = (1e-3, 1e-1)
LONG_DT = 1e-2

# Worst logit difference over the logits' spread, every token live. The
# program computes in bf16 with float32 dt, decay, cumulative sums, state,
# gated norm, softmax statistics and logits; the reference in float32. Two
# readings on the v5e at the published widths and 8,192 tokens, under
# ``perturb`` (PERF.md section 6, PR 49, has every seed's): the program, with
# the dual's, the convolution's and attention's kernels bound, reads 0.081 to
# 0.110 over 14 seeds (evenly along the row: 0.073 to 0.091 by quarter); this
# reference computed in bf16 (``forward(..., dtype=jnp.bfloat16)``: the state,
# dt and the decay rounded where they stand) reads 2.73 to 4.78 and has to
# fail. The limit lies between them, 4.6 times above the one and 5.5 times
# below the other. The long memory is what opens the gap: a bf16 state loses
# 2^-9 of itself every token and the row's end holds thousands of tokens'
# sum; at the published start (a decay of exp(-1.3) to exp(-84) a token)
# nothing would tell a bf16 state from a float32 one.
TOLERANCE = 0.5


def eval_batch(rows, config: dict) -> dict:
    """The first ``EVAL_ROWS`` rows as stored, and the configuration's
    constants."""
    global EPS, EMBED_SCALE, BRANCH_SCALE, SCORE_SCALE, LOGIT_SCALE, STATES, \
        HEAD_DIM
    model = config["model"]
    EPS = float(model["rms_norm_eps"])
    EMBED_SCALE = float(model["embedding_multiplier"])
    BRANCH_SCALE = float(model["residual_multiplier"])
    SCORE_SCALE = float(model["attention_multiplier"])
    LOGIT_SCALE = 1.0 / float(model["logits_scaling"])
    STATES = int(model["mamba_d_state"])
    HEAD_DIM = int(model["mamba_d_head"])
    out = {}
    for name in ("input_ids", "attention_mask"):
        col = rows.column(name).combine_chunks()
        out[name] = np.asarray(col.flatten()).reshape(len(col), -1)[:EVAL_ROWS]
    return out


def perturb(variables, rng):
    """Everything that starts at a value which would hide a fault leaves
    it: the norms' scales (the gated norm's too) and the skip ``D`` leave 1
    (uniform in [0.75, 1.25]), the convolution's bias leaves 0 (normal,
    0.05), and every Mamba-2 layer's ``A_log`` and ``dt_bias`` move to long
    memory (``LONG_A``, ``LONG_DT``, see there)."""
    import jax
    import jax.numpy as jnp

    leaves, tree = jax.tree_util.tree_flatten_with_path(variables)
    keys = jax.random.split(rng, len(leaves))

    def one(path, leaf, key):
        name = getattr(path[-1], "key", "")
        if name in ("scale", "norm_scale", "D"):
            return jax.random.uniform(key, leaf.shape, leaf.dtype, 0.75, 1.25)
        if name == "conv_bias":
            return 0.05 * jax.random.normal(key, leaf.shape, leaf.dtype)
        if name == "A_log":  # log of -A, -A log-uniform over LONG_A
            return jax.random.uniform(key, leaf.shape, leaf.dtype,
                                      math.log(LONG_A[0]), math.log(LONG_A[1]))
        if name == "dt_bias":  # the inverse softplus of LONG_DT
            return jnp.full(leaf.shape, math.log(math.expm1(LONG_DT)),
                            leaf.dtype)
        return leaf

    return jax.tree_util.tree_unflatten(
        tree, [one(path, leaf, k) for (path, leaf), k in zip(leaves, keys)])


def live(batch, want):
    """Every real token: nothing here is routed, so nothing is left out."""
    import jax.numpy as jnp

    return jnp.asarray(batch["attention_mask"]) > 0


def _rms(x, scale):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale.astype(x.dtype)


def _swiglu(y, p):
    import jax

    return (jax.nn.silu(y @ p["gate"]["kernel"]) * (y @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]


def _recurrence(x, dt, a, b, c, held_to):
    """Token by token: x [B, S, H, P], dt [B, S, H], a [H], b, c [B, S, N]
    -> h_t c_t [B, S, H, P]. ``held_to`` rounds what the lower precision
    holds."""
    import jax
    import jax.numpy as jnp

    rows, seq, heads, width = x.shape
    step = math.gcd(SCAN_CHUNK, seq)

    def token(h, parts):
        x_t, dt_t, b_t, c_t = parts  # [B, H, P], [B, H], [B, N], [B, N]
        decay = held_to(jnp.exp(held_to(dt_t * a)))
        h = held_to(decay[..., None, None] * h
                    + held_to(dt_t[..., None] * x_t)[..., None]
                    * b_t[:, None, None, :])
        return h, held_to(jnp.sum(h * c_t[:, None, None, :], -1))

    @jax.checkpoint
    def chunk(h, parts):
        return jax.lax.scan(token, h, parts)

    def chunks(t):  # [B, S, ...] -> [S / step, step, B, ...]
        return jnp.moveaxis(t, 1, 0).reshape(seq // step, step, rows,
                                             *t.shape[2:])

    _, y = jax.lax.scan(
        chunk, jnp.zeros((rows, heads, width, b.shape[-1]), x.dtype),
        (chunks(x), chunks(dt), chunks(b), chunks(c)))
    return jnp.moveaxis(y.reshape(seq, rows, heads, width), 0, 1)


def _mamba2(u, p, held_to):
    import jax
    import jax.numpy as jnp

    heads = p["A_log"].shape[0]
    inner = heads * HEAD_DIM
    rows, seq, _ = u.shape
    xbcz = u @ p["in_proj_xbcz"]["kernel"]
    xbc, z = xbcz[..., :inner + 2 * STATES], xbcz[..., inner + 2 * STATES:]
    dt = held_to(jax.nn.softplus(
        held_to(u @ p["in_proj_dt"]["kernel"] + p["dt_bias"])))
    taps = p["conv_kernel"]
    back = jnp.pad(xbc, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(back[:, k:k + seq] * taps[k]
                          for k in range(taps.shape[0])) + p["conv_bias"])
    x = xbc[..., :inner].reshape(rows, seq, heads, HEAD_DIM)
    a = -jnp.exp(p["A_log"])
    y = _recurrence(x, dt, a, xbc[..., inner:inner + STATES],
                    xbc[..., inner + STATES:], held_to)
    y = (y + p["D"][:, None] * x).reshape(rows, seq, inner)
    gated = held_to(y * jax.nn.silu(z))
    normed = held_to(gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + EPS)) * p["norm_scale"]
    return normed @ p["out_proj"]["kernel"]


def _attention(u, p, real, held_to):
    """Causal grouped softmax attention without a position term, a block of
    query rows at a time."""
    import jax
    import jax.numpy as jnp

    q = jnp.einsum("bsh,hnd->bsnd", u, p["query"]["kernel"])
    k = jnp.einsum("bsh,hgd->bsgd", u, p["key"]["kernel"])
    v = jnp.einsum("bsh,hgd->bsgd", u, p["value"]["kernel"])
    rows, seq, heads, d = q.shape
    groups = k.shape[2]
    q = q.reshape(rows, seq, groups, heads // groups, d)
    block = math.gcd(Q_BLOCK, seq)

    @jax.checkpoint
    def some(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 1)
        scores = held_to(jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) * SCORE_SCALE)
        at = start + jnp.arange(block)
        allow = real[:, None, :] & (at[:, None] >= jnp.arange(seq)[None, :])
        scores = jnp.where(allow[:, None, None], scores,
                           jnp.finfo(scores.dtype).min)
        return jnp.einsum("bgrqk,bkgd->bqgrd",
                          held_to(jax.nn.softmax(scores, -1)), v)

    out = jax.lax.map(some, jnp.arange(0, seq, block))  # [blocks, B, rows, ..]
    out = jnp.moveaxis(out, 0, 1).reshape(rows, seq, heads * d)
    return out @ p["out"]["kernel"].reshape(heads * d, -1)


def layer(p, x, real, held_to=lambda t: t):
    """One layer, of the kind its parameters say."""
    u = _rms(x, p["ln_attn"]["scale"])
    out = (_mamba2(u, p["ssm"], held_to) if "ssm" in p
           else _attention(u, p["attn"], real, held_to))
    x = x + BRANCH_SCALE * out
    return x + BRANCH_SCALE * _swiglu(_rms(x, p["ln_mlp"]["scale"]), p["mlp"])


def forward(variables, batch, dtype=None):
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    params = jax.tree.map(lambda p: p.astype(dtype), variables["params"])
    ids = jnp.asarray(batch["input_ids"], jnp.int32)
    real = jnp.asarray(batch["attention_mask"]) > 0

    def held_to(x):
        """In the lower precision, round where the program is stated to be
        float32: the compiler keeps the intermediates of a bf16 chain in
        float32 (``xla_allow_excess_precision``)."""
        if dtype == jnp.float32:
            return x
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        embedding = params["tok_embed"]["embedding"]
        x = held_to(EMBED_SCALE * embedding[ids])
        layers = sorted((k for k in params if k.startswith("layer_")),
                        key=lambda k: int(k.split("_")[1]))
        for name in layers:
            x = layer(params[name], x, real, held_to)
        x = _rms(x, params["ln_final"]["scale"])
        return held_to(x @ embedding.T * LOGIT_SCALE).astype(jnp.float32)


def loss(variables, batch):
    """The training loss: next-token cross-entropy over real targets."""
    import jax
    import jax.numpy as jnp

    logits = forward(variables, batch)
    ids = jnp.asarray(batch["input_ids"], jnp.int32)
    w = (jnp.asarray(batch["attention_mask"])[:, 1:] > 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
    return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)
