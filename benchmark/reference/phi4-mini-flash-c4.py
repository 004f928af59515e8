"""Plain reference of Phi-4-mini-flash-reasoning's layers (SambaY) as the
program runs them: float32 ``jax.numpy`` at the highest matmul precision, fed
the program's own parameter tree. Sources: ``microsoft/Phi-4-mini-flash-
reasoning`` ``config.json`` (``model_type`` ``phi4flash``) for the sizes it
gives; Ren et al., arXiv:2507.06607 (SambaY and the gated memory unit); Gu and
Dao, arXiv:2312.00752 (the selective scan); Ye et al., arXiv:2410.05258
(differential attention); what the config does not give is listed under
``assumed`` in ``benchmark/configs/phi4-mini-flash-c4.json``.

Every layer, with ``ln`` LayerNorm (eps 1e-5, scale and bias), no other bias:

    x <- x + mix(ln_a(x));  x <- x + W_d (silu(W_g u) * W_u u),  u = ln_m(x)

and by the layer's kind (``KINDS``, from the configuration):

    M, M*   [x; z] = u W_in;  x <- silu(conv4(x) + b_c)   depthwise, causal
            [dl; B; C] = x W_x;  dt = softplus(dl W_dt + b_dt);  A = -exp(A_log)
            h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) outer B_t,   h_0 = 0
            m_t = h_t C_t + D x_t;  out = (m silu(z)) W_out;  M* hands on m
    G       out = (m* silu(u W_1)) W_2
    S, F*   q = u W_q (40 x 64), k = u W_k (20 x 64), v = u W_v (10 x 128)
            head i of 20, j = i // 2:  A1 = softmax(q_2i k_2j' / 8 + mask),
            A2 = softmax(q_2i+1 k_2j+1' / 8 + mask),  o_i = (A1 - lam A2) v_j
            o_i <- (1 - lam_init) rms128(o_i);  out = concat(o) W_o
            lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init,
            lam_init = 0.8 - 0.6 exp(-0.3 l),  l the published layer index
            mask: causal, and for S also t - s < 512;  F* hands on k, v
    X       as F* with its own W_q, lam vectors, norm scale and W_o over F*'s
            k and v

then a LayerNorm, and logits over the held rows of the embedding (tied). The
scan is a sequential ``lax.scan`` over tokens (in chunks only so that
``jax.grad`` keeps one state a chunk); attention is dense masked softmaxes a
block of ``Q_BLOCK`` queries at a time, so that 8,192 tokens fit. No kernel,
nothing from ``lance_distributed_training_tpu``. ``loss`` is the training loss
(shifted cross-entropy), for ``jax.grad``.

``forward(variables, batch, dtype=jnp.bfloat16)`` is the same mathematics with
every tensor and product in bf16 and what the configuration states as float32
rounded to bf16 where it stands (``dt``, the exponent, the scan's state and
its sum, softmax statistics, lambda, the logits): the nearest precision below
the configuration's, which the comparison has to refuse (``TOLERANCE``).
"""

from __future__ import annotations

import math

import numpy as np

EPS = 1e-5
WINDOW = 512
FIRST_LAYER = 14
KINDS = ("M", "S", "M*", "F*", "G", "X")
EVAL_ROWS = 1
Q_BLOCK = 1024
SCAN_CHUNK = 128

# How far ``perturb`` moves the state-space layers toward long memory: A_log
# falls by this much, so that a token's decay exp(dt A) is within 1e-5 to
# 1e-3 of 1 and a state at the end of a row of 8,192 tokens still holds its
# first token. The scan's state then is a sum of thousands of terms of either
# sign: float32 carries it, and a state rounded to bf16 after every token
# loses 2^-9 of itself each time, a random walk that by the row's end is a
# tenth of the state. (At Mamba's own initialisation, A = -1..-16 and dt up
# to 0.1, a state forgets within a few hundred tokens and a bf16 state drifts
# by no more than bf16 activations do anyway: the stated precision would be
# unguarded, as a bf16 router's was before PRs 26 and 30 arranged theirs.)
LONG_MEMORY = 8.0

# Worst logit difference over the logits' spread, every token live. The
# program computes in bf16 with float32 dt, exponent, state, softmax
# statistics, lambda, norm statistics and logits; the reference in float32.
# Two readings on the v5e at published widths and 8,192 tokens, under
# ``perturb`` (PERF.md section 6, PR 33, has every seed's): the program reads
# 0.31 to 0.85 over 15 seeds; this reference computed in bf16 (``forward(...,
# dtype=jnp.bfloat16)``: the state, dt and the exponent rounded where they
# stand) reads 6.8 to 8.1 and has to fail. The limit lies between them, 2.4
# times above the one and 3.4 times below the other. The long memory is what opens the gap,
# and it costs the program too: with ``LONG_MEMORY`` 0 the program reads 0.14
# and the bf16 reference 0.51, under four times apart; with it the program's
# worst error grows along the row (0.20, 0.31, 0.65, 0.52 by quarter), because
# the rounding of its bf16 inputs adds up in a state that is a sum of
# thousands of terms of either sign, while its relative error over all logits
# stays at 0.027 (0.021 without) against the bf16 reference's 0.68 (0.032).
TOLERANCE = 2.0


def eval_batch(rows, config: dict) -> dict:
    """The first ``EVAL_ROWS`` rows as stored, and the configuration's
    layout of layers."""
    global WINDOW, FIRST_LAYER, KINDS, EPS
    model = config["model"]
    WINDOW = int(model["sliding_window"])
    EPS = float(model["layer_norm_eps"])
    FIRST_LAYER = int(model["first_layer"])
    KINDS = tuple(model["layer_kinds"])
    out = {}
    for name in ("input_ids", "attention_mask"):
        col = rows.column(name).combine_chunks()
        out[name] = np.asarray(col.flatten()).reshape(len(col), -1)[:EVAL_ROWS]
    return out


def perturb(variables, rng):
    """Everything that starts at a value which would hide a fault leaves
    it: norm scales and the skip ``D`` leave 1 (uniform in [0.75, 1.25]),
    norm and convolution biases leave 0 (normal, 0.05), and every
    state-space layer's ``A_log`` falls by ``LONG_MEMORY`` (see there). The
    lambda vectors start random (normal, 0.1) and stay."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten_with_path(variables)
    keys = jax.random.split(rng, len(leaves))

    def one(path, leaf, key):
        name = getattr(path[-1], "key", "")
        if name in ("scale", "D"):
            return jax.random.uniform(key, leaf.shape, leaf.dtype, 0.75, 1.25)
        if name in ("bias", "conv_bias"):
            return 0.05 * jax.random.normal(key, leaf.shape, leaf.dtype)
        if name == "A_log":
            return leaf - LONG_MEMORY
        return leaf

    return jax.tree_util.tree_unflatten(
        tree, [one(path, leaf, k) for (path, leaf), k in zip(leaves, keys)])


def live(batch, want):
    """Every real token: nothing here is routed, so nothing is left out."""
    import jax.numpy as jnp

    return jnp.asarray(batch["attention_mask"]) > 0


def _ln(x, p):
    import jax
    import jax.numpy as jnp

    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * p["scale"].astype(
        x.dtype) + p["bias"].astype(x.dtype)


def _rms(x, scale):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale.astype(x.dtype)


def _swiglu(y, p):
    import jax

    return (jax.nn.silu(y @ p["gate"]["kernel"]) * (y @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]


def _scan(x, dt, a, b, c, held_to):
    """Token by token: x, dt [B, L, D], a [D, N], b, c [B, L, N] -> the sums
    h_t . c_t [B, L, D]. ``held_to`` rounds what the lower precision holds."""
    import jax
    import jax.numpy as jnp

    rows, seq, width = x.shape
    step = math.gcd(SCAN_CHUNK, seq)

    def token(h, parts):
        x_t, dt_t, b_t, c_t = parts  # [B, D], [B, D], [B, N], [B, N]
        decay = held_to(jnp.exp(held_to(dt_t[..., None] * a)))
        h = held_to(decay * h + held_to(dt_t * x_t)[..., None]
                    * b_t[:, None, :])
        return h, held_to(jnp.sum(h * c_t[:, None, :], -1))

    @jax.checkpoint
    def chunk(h, parts):
        return jax.lax.scan(token, h, parts)

    def chunks(t):  # [B, L, .] -> [L / step, step, B, .]
        return jnp.moveaxis(t, 1, 0).reshape(seq // step, step, rows, -1)

    _, y = jax.lax.scan(chunk, jnp.zeros((rows, width, a.shape[1]), x.dtype),
                        (chunks(x), chunks(dt), chunks(b), chunks(c)))
    return jnp.moveaxis(y.reshape(seq, rows, width), 0, 1)


def _mamba(u, p, held_to):
    """``(out, m)`` of a Mamba-1 mixer."""
    import jax
    import jax.numpy as jnp

    inner, states = p["A_log"].shape
    seq = u.shape[1]
    xz = u @ p["in_proj"]["kernel"]
    x, z = xz[..., :inner], xz[..., inner:]
    taps = p["conv_kernel"]
    back = jnp.pad(x, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(back[:, k:k + seq] * taps[k]
                        for k in range(taps.shape[0])) + p["conv_bias"])
    dbc = x @ p["x_proj"]["kernel"]
    rank = dbc.shape[-1] - 2 * states
    dt = held_to(jax.nn.softplus(
        held_to(dbc[..., :rank] @ p["dt_proj"]["kernel"] + p["dt_bias"])))
    a = -jnp.exp(p["A_log"])
    m = _scan(x, dt, a, dbc[..., rank:rank + states],
              dbc[..., rank + states:], held_to) + p["D"] * x
    return (m * jax.nn.silu(z)) @ p["out_proj"]["kernel"], m


def _softmax_rows(q, k, v, allow_rows, held_to):
    """Masked softmax attention, a block of query rows at a time: q [B, S,
    H, d], k [B, S, H, d], v [B, S, H, dv]; ``allow_rows(start, rows)`` is
    the boolean [B, rows, S] of keys each of those queries may see."""
    import jax
    import jax.numpy as jnp

    seq = q.shape[1]
    block = math.gcd(Q_BLOCK, seq)
    scale = 1.0 / np.sqrt(q.shape[-1])

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 1)
        scores = held_to(jnp.einsum("bqnd,bknd->bnqk", qb, k) * scale)
        scores = jnp.where(allow_rows(start, block)[:, None], scores,
                           jnp.finfo(scores.dtype).min)
        return jnp.einsum("bnqk,bknd->bqnd",
                          held_to(jax.nn.softmax(scores, -1)), v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block))  # [blocks, B, rows, ..]
    return jnp.moveaxis(out, 0, 1).reshape(q.shape[:3] + v.shape[-1:])


def _differential(u, p, depth, window, shared, allow, held_to):
    """``(out, (k, v))`` of a differential attention layer; ``shared`` the
    keys and values of an earlier layer, or None for its own."""
    import jax.numpy as jnp

    q = jnp.einsum("bsh,hnd->bsnd", u, p["query"]["kernel"])
    if shared is None:
        k = jnp.einsum("bsh,hnd->bsnd", u, p["key"]["kernel"])
        v = jnp.einsum("bsh,hnd->bsnd", u, p["value"]["kernel"])
    else:
        k, v = shared
    heads, groups = q.shape[2], k.shape[2]
    per = heads // groups  # differential heads that share a pair of key heads
    of = np.arange(heads // 2) // per  # head i's pair j
    a1 = _softmax_rows(q[:, :, 0::2], k[:, :, 2 * of], v[:, :, of],
                       lambda s, n: allow(s, n, window), held_to)
    a2 = _softmax_rows(q[:, :, 1::2], k[:, :, 2 * of + 1], v[:, :, of],
                       lambda s, n: allow(s, n, window), held_to)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = held_to(
        jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init)
    o = (1.0 - lam_init) * _rms(a1 - lam * a2, p["sub_norm"]["scale"])
    out = o.reshape(o.shape[:2] + (-1,)) @ p["out"]["kernel"].reshape(
        -1, p["out"]["kernel"].shape[-1])
    return out, (k, v)


def causal_mask(real):
    """``allow(start, rows, window)`` -> boolean [B, rows, S]: the real keys
    a block of queries may see, causal and, with ``window`` > 0, no further
    back than ``window - 1`` tokens."""
    import jax.numpy as jnp

    seq = real.shape[1]

    def allow(start, n, window):
        at = start + jnp.arange(n)
        back = at[:, None] - jnp.arange(seq)[None, :]
        ok = (back >= 0) & (back < window) if window else back >= 0
        return real[:, None, :] & ok

    return allow


def forward(variables, batch, dtype=None):
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    params = jax.tree.map(lambda p: p.astype(dtype), variables["params"])
    ids = jnp.asarray(batch["input_ids"], jnp.int32)
    real = jnp.asarray(batch["attention_mask"]) > 0
    allow = causal_mask(real)

    def held_to(x):
        """In the lower precision, round where the program is stated to be
        float32: the compiler keeps the intermediates of a bf16 chain in
        float32 (``xla_allow_excess_precision``)."""
        if dtype == jnp.float32:
            return x
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    precision = "highest" if dtype == jnp.float32 else "default"
    handed = (None, None)
    with jax.default_matmul_precision(precision):
        embedding = params["tok_embed"]["embedding"]
        x = embedding[ids]
        for i, kind in enumerate(KINDS):
            x, handed = layer(kind, params[f"layer_{i}"], x, handed,
                              FIRST_LAYER + i, allow, held_to)
        x = _ln(x, params["ln_final"])
        return held_to(x @ embedding.T).astype(jnp.float32)


def layer(kind, p, x, handed, depth, allow, held_to=lambda t: t):
    """One layer of ``kind`` at published index ``depth``: ``(x, handed)``,
    ``handed`` the ``(m, (k, v))`` that M* and F* hand on (either None until
    its layer has run)."""
    import jax

    memory, shared = handed
    u = _ln(x, p["ln_attn"])
    if kind in ("M", "M*"):
        out, m = _mamba(u, p["ssm"], held_to)
        memory = m if kind == "M*" else memory
    elif kind == "G":
        g = p["gmu"]
        out = (memory * jax.nn.silu(u @ g["in_proj"]["kernel"])
               ) @ g["out_proj"]["kernel"]
    else:
        out, own = _differential(
            u, p["attn"], depth, WINDOW if kind == "S" else 0,
            shared if kind == "X" else None, allow, held_to)
        shared = own if kind == "F*" else shared
    x = x + out
    return x + _swiglu(_ln(x, p["ln_mlp"]), p["mlp"]), (memory, shared)


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
