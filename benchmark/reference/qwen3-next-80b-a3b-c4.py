"""Plain reference of Qwen3-Next-80B-A3B's decoder layers as the program runs
them: float32 ``jax.numpy`` at the highest matmul precision, fed the
program's own parameter tree and the same share of the experts. Source:
``Qwen/Qwen3-Next-80B-A3B-Instruct`` ``config.json`` (``model_type``
``qwen3_next``) for every size; every equation is that of the published
implementation (``transformers`` 4.57 ``models/qwen3_next/
modeling_qwen3_next.py``, named by its class), and
``tests/test_qwen3_next.py`` holds this file to those modules on copied
weights. ``rms`` is RMSNorm with a scale of ``1 + w`` (eps 1e-6), no biases
anywhere. Layer ``i`` is attention where ``(i + 1) % 4 == 0``, else linear
attention; the parameters say which (``gdn`` or ``attn``).

    linear attention (Qwen3NextGatedDeltaNet), Hk key heads serving Hv value
    heads (key head j the value heads 2j and 2j + 1), d = 128:
      [q~; k~; v~; z] = u W_qkvz          [b; a] = u W_ba
      [q; k; v] = silu(conv4([q~; k~; v~]))   depthwise, causal, no bias
      beta = sigmoid(b)      g = -exp(A_log) * softplus(a + dt_bias)
      q <- q / sqrt(|q|^2 + 1e-6) / sqrt(d)   k <- k / sqrt(|k|^2 + 1e-6)
      a value head, token by token, S in R^{d x d} from 0:
         S <- exp(g_t) S ;  S <- S + k_t (beta_t (v_t - S' k_t))' ;  o_t = S' q_t
      out = (o / sqrt(mean(o^2) + eps) * w_n * silu(z)) W_out

    gated attention (Qwen3NextAttention), H query heads over G key/value
    heads of d = 256:
      [q; gate] = u W_q  a head      k = u W_k    v = u W_v
      q <- rms_d(q)(1 + w_q)    k <- rms_d(k)(1 + w_k)
      rope on the first 64 of a head's 256, theta 1e7, q and k
      o = softmax(causal(q k' / sqrt(d))) v,  query head h on key head h // (H/G)
      out = (concat(o) * sigmoid(gate)) W_o

    experts (Qwen3NextSparseMoeBlock), E = 512, k = 10:
      p = softmax(u W_r) ;  the ten largest ;  w = p_top / sum(p_top)
      y = sum_{chosen e held here} w_e down_e(silu(gate_e u) * up_e u)
          + sigmoid(u w_g) * shared(u)

    x <- x + mixer(rms(x)) ;  x <- x + experts(rms(x)) ;  logits = rms(x) W_head

The delta rule is the token-by-token recurrence (a ``lax.scan`` over tokens),
not a chunked form: it shares no algebra with the program's. ``rope`` turns
element i with element i + 32 of the rotary 64, at ``position_ids`` where the
batch has them. No sort of assignments, no grouped product, no kernel: every
held expert is applied to every token, one at a time, under the top-k mask
(the scores at or above a token's k-th largest); experts this rank does not
hold add nothing, here as in the program (``FIRST``, and the number of
experts in the parameters), and the shared expert is whole. Attention is
computed a block of ``Q_BLOCK`` query rows at a time, so that 8,192 tokens
fit. ``loss`` is the training loss (shifted cross-entropy plus 0.001 x the
load-balance term over live tokens, summed over the layers), for
``jax.grad``.

``forward(variables, batch, dtype=jnp.bfloat16)`` is the same mathematics with
every tensor and product in bf16, and what the program states to be float32
(``g``, ``beta``, the state of the rule, the router's logits and scores, the
logits) rounded to bf16 explicitly: the nearest precision below the
configuration's, which the comparison has to refuse (``TOLERANCE``).
"""

from __future__ import annotations

import numpy as np

EPS = 1e-6
THETA = 10000000.0
ROTARY = 64  # partial_rotary_factor x head_dim
KEY_DIM = 128  # linear_key_head_dim
TOP_K = 10  # the configuration's num_experts_per_tok
FIRST = 0  # the first expert held here: rank x (512 / ranks)
BALANCE_WEIGHT = 0.001  # router_aux_loss_coef
EVAL_ROWS = 1
Q_BLOCK = 1024

# The program computes in bf16 (f32 router, f32 softmax statistics, f32 norm
# statistics, f32 decays and state, f32 logits); the reference in f32. A
# token for which an expert held here is close to changing sides (chosen,
# and little above the best logit not chosen; or not chosen, and little below
# the least logit chosen) may have it on the other side in the program, and
# its output then moves by a whole expert's contribution. (Where two absent
# experts change places both add nothing here, and the renormalised weights
# move by the difference of two nearly equal scores.) The comparison is a
# maximum, so such tokens are left out, as the Moonlight reference leaves
# them out: those with a held expert within MARGIN of the boundary, in units
# of the spread (standard deviation) of a token's 512 router logits, in any
# layer. With 32 of 512 held that is a sixteenth of the experts near the
# boundary: 31% of a row's tokens are left out at 0.03, 51-52% at 0.06, 70% at
# 0.1 (the share is printed). On the v5e at published widths (my chip run,
# PR 41, twelve seeds of 8,192 tokens by margin): worst token that stays
# 0.52-0.89 at margin 0, 0.39-0.50 at 0.03 (two seeds), 0.29-0.58 at 0.06 and
# 0.28-0.55 at 0.1: from 0.06 on what is left is hardly routing.
MARGIN = 0.06

# Worst logit difference over the logits' spread on the tokens that stay,
# under ``perturb``. On the v5e at published widths (my chip run, PR 41, 23
# seeds of 8,192 tokens; PERF.md section 6 has each): the program reads
# 0.29-0.64, its median token 0.14-0.21: under ``perturb``'s long memory a
# head's state sums thousands of tokens' products, each with operands
# rounded to bf16, and the logits' spread is 0.8. (With no token left out at
# all the worst reads 0.52-0.89: an expert of ten changing sides is worth
# that much, so the readings over 0.5 are tokens whose room was wider than
# MARGIN or that attend to one.) The reference in bf16 (state, decays and
# router rounded where they stand) reads 2.36-3.21 on the same tokens over
# 12 seeds (1.7-2.0 at the median). TOLERANCE is twice the program's largest
# reading and 1.9 times under the bf16 reference's least.
TOLERANCE = 1.25

# How far ``perturb`` moves the linear-attention layers toward long memory:
# at the published start A = exp(A_log) is uniform over (0, 16) and dt_bias
# 1, so g = -A softplus(a + 1) is about -1.3 A: all but the few heads with A
# under 0.01 forget within a few tokens, and an error in the state carried
# from chunk to chunk would not show at the row's end. Each head's A_log
# falls by LONG_MEMORY times a uniform draw, so that a layer's 32 heads have
# decays from the published ones down to e^-10 of them: a third of the heads
# then keep a state over a thousand tokens and more.
LONG_MEMORY = 10.0

_NOTES: dict = {}  # forward() leaves near ties and load here for live()


def eval_batch(rows, config: dict) -> dict:
    """The first ``EVAL_ROWS`` rows as stored, and the configuration's
    constants and share."""
    global TOP_K, ROTARY, FIRST, THETA, KEY_DIM
    model = config["model"]
    TOP_K = int(model["num_experts_per_tok"])
    ROTARY = int(float(model["partial_rotary_factor"]) * int(
        model["head_dim"]))
    THETA = float(model["rope_theta"])
    KEY_DIM = int(model["linear_key_head_dim"])
    rank = int(config["task"].get("expert_share", "0/1").split("/")[0])
    FIRST = rank * int(model["num_experts"])  # held here: a rank's
    out = {}
    for name in ("input_ids", "attention_mask"):
        col = rows.column(name).combine_chunks()
        out[name] = np.asarray(col.flatten()).reshape(len(col), -1)[:EVAL_ROWS]
    return out


def perturb(variables, rng):
    """Every ``1 + w`` norm's ``w`` leaves 0 (uniform in [-0.25, 0.25]) and
    every gated norm's scale leaves 1 (uniform in [0.75, 1.25]): at zeros
    and ones a missing or misplaced scale would not show. Every
    linear-attention head's ``A_log`` falls by ``LONG_MEMORY`` times a
    uniform draw and its ``dt_bias`` leaves 1 (normal, 0.5): see
    ``LONG_MEMORY``."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten_with_path(variables)
    keys = jax.random.split(rng, len(leaves))

    def one(path, leaf, key):
        last = getattr(path[-1], "key", "")
        if last == "scale":
            return jax.random.uniform(key, leaf.shape, leaf.dtype, -0.25, 0.25)
        if last == "norm_scale":
            return jax.random.uniform(key, leaf.shape, leaf.dtype, 0.75, 1.25)
        if last == "A_log":
            return leaf - LONG_MEMORY * jax.random.uniform(
                key, leaf.shape, leaf.dtype)
        if last == "dt_bias":
            return leaf + 0.5 * jax.random.normal(key, leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_unflatten(
        tree, [one(path, leaf, k) for (path, leaf), k in zip(leaves, keys)])


def live(batch, want):
    """Real tokens with no expert held here within ``MARGIN`` of the routing
    boundary, in any layer of the reference."""
    import jax
    import jax.numpy as jnp

    real = jnp.asarray(batch["attention_mask"]) > 0
    near_tie = _NOTES["near_tie"].reshape(real.shape)
    jax.debug.print(
        "reference: {n} of {m} real tokens have a held expert within the "
        "routing margin of the boundary between the chosen and the others, "
        "left out of the comparison ({p:.2f} %); in the last layer {a} "
        "assignments went to held experts (busiest {b}), of {c} in all",
        n=(real & near_tie).sum(), m=real.sum(),
        p=100.0 * (real & near_tie).sum() / real.sum(), a=_NOTES["load"][0],
        b=_NOTES["load"][1], c=_NOTES["load"][2])
    return real & ~near_tie


def _rms(x, w):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * (
        1.0 + w.astype(x.dtype))


def _rope(x, positions):
    """``x`` [B, S, N, D]: of its first ``ROTARY`` elements, element i turns
    with element i + ROTARY / 2; the others stay."""
    import jax.numpy as jnp

    half = ROTARY // 2
    freq = THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * freq
    cos = jnp.cos(angle)[..., None, :].astype(x.dtype)
    sin = jnp.sin(angle)[..., None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:ROTARY]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., ROTARY:]], -1)


def _conv(x, taps):
    """Depthwise and causal: ``out_t = sum_j taps[j] x_{t - (K - 1) + j}``,
    ``x`` [B, S, D], ``taps`` [K, D], zeros before the row."""
    import jax.numpy as jnp

    k = taps.shape[0]
    out = jnp.zeros_like(x)
    for j in range(k):
        back = k - 1 - j
        shifted = x if back == 0 else jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :-back]], 1)
        out = out + taps[j] * shifted
    return out


def _delta_rule(q, k, v, g, beta, held_to):
    """The recurrence, a token at a time: q, k, v [B, S, H, d], g, beta [B,
    S, H] -> (o [B, S, H, d], the last state [B, H, d, d])."""
    import jax
    import jax.numpy as jnp

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = held_to(s * jnp.exp(g_t)[..., None, None])
        kept = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        delta = (v_t - kept) * b_t[..., None]
        s = held_to(s + k_t[..., :, None] * delta[..., None, :])
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    rows, _, heads, d_k = q.shape
    last, o = jax.lax.scan(
        token, jnp.zeros((rows, heads, d_k, v.shape[-1]), q.dtype),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


def _linear_attention(u, p, held_to):
    import jax
    import jax.numpy as jnp

    rows, seq, _ = u.shape
    hv, dv = p["A_log"].shape[0], p["norm_scale"].shape[0]
    values = hv * dv
    qkvz = u @ p["in_proj_qkvz"]
    ba = u @ p["in_proj_ba"]
    keys = (qkvz.shape[-1] - 2 * values) // 2
    hk = keys // KEY_DIM
    mixed = jax.nn.silu(_conv(qkvz[..., :2 * keys + values],
                              p["conv_kernel"]))
    q = mixed[..., :keys].reshape(rows, seq, hk, KEY_DIM)
    k = mixed[..., keys:2 * keys].reshape(rows, seq, hk, KEY_DIM)
    v = mixed[..., 2 * keys:].reshape(rows, seq, hv, dv)
    z = qkvz[..., 2 * keys + values:].reshape(rows, seq, hv, dv)
    beta = held_to(jax.nn.sigmoid(ba[..., :hv]))
    g = held_to(-jnp.exp(p["A_log"]) * jax.nn.softplus(
        ba[..., hv:] + p["dt_bias"]))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        / np.sqrt(KEY_DIM).astype(np.float32)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
    o, last = _delta_rule(q, k, v, g, beta, held_to)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + EPS) \
        * p["norm_scale"]
    gated = (o * jax.nn.silu(z)).reshape(rows, seq, values)
    return gated @ p["out_proj"]["kernel"], last


def _attention(q, k, v, allow_rows):
    """Causal softmax attention, a block of query rows at a time: q [B, S, N,
    D], k, v [B, S, G, D] with query head h on key head h // (N / G);
    ``allow_rows(start, rows)`` gives the boolean [B, rows, S] of keys each
    of those queries may see."""
    import jax
    import jax.numpy as jnp

    seq, heads = q.shape[1:3]
    k, v = (jnp.repeat(t, heads // t.shape[2], axis=2) for t in (k, v))
    block = min(Q_BLOCK, seq)
    scale = 1.0 / np.sqrt(q.shape[-1])

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 1)
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k) * scale
        scores = jnp.where(allow_rows(start, block)[:, None], scores,
                           jnp.finfo(scores.dtype).min)
        return jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block))  # [blocks, B, rows, ..]
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


def _gated_attention(u, p, positions, allow_rows):
    import jax
    import jax.numpy as jnp

    q_gate = jnp.einsum("bsh,hnd->bsnd", u, p["query"]["kernel"])
    d = q_gate.shape[-1] // 2
    k = jnp.einsum("bsh,hnd->bsnd", u, p["key"]["kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", u, p["value"]["kernel"])
    q = _rope(_rms(q_gate[..., :d], p["q_norm"]["scale"]), positions)
    k = _rope(_rms(k, p["k_norm"]["scale"]), positions)
    ctx = _attention(q, k, v, allow_rows) * jax.nn.sigmoid(q_gate[..., d:])
    return jnp.einsum("bsnd,ndh->bsh", ctx, p["out"]["kernel"])


def _experts(y, moe, weights):
    """Σ_e weights[:, e] · down_e(silu(gate_e(y)) · up_e(y)) over the held
    experts: each on every token, one at a time (recomputed in the backward
    pass, so that ``jax.grad`` keeps one expert's activations)."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def expert(ws):
        gate, up, down, w = ws
        return w[:, None] * ((jax.nn.silu(y @ gate) * (y @ up)) @ down)

    return jax.lax.scan(lambda acc, ws: (acc + expert(ws), None),
                        jnp.zeros_like(y), (moe["w_gate"], moe["w_up"],
                                            moe["w_down"], weights.T))[0]


def _sparse_block(u, moe, held_to=lambda x: x):
    """The expert layer on tokens ``u`` [T, H]: ``(y, router logits, scores,
    the top-k mask)``, with the held experts' part of the routed sum and the
    shared expert whole."""
    import jax
    import jax.numpy as jnp

    logits = held_to(u @ moe["router"]["kernel"])
    probs = held_to(jax.nn.softmax(logits, -1))
    e, held = probs.shape[-1], moe["w_gate"].shape[0]
    # the k largest: at or above a token's k-th largest score
    kth = jnp.sort(probs, -1)[:, e - TOP_K][:, None]
    chosen = probs >= kth
    top = probs * chosen
    weights = top / top.sum(-1, keepdims=True)
    y = _experts(u, moe, weights[:, FIRST:FIRST + held])
    shared = moe["shared"]
    y = y + jax.nn.sigmoid(u @ moe["shared_gate"]["kernel"]) * (
        (jax.nn.silu(u @ shared["gate"]["kernel"])
         * (u @ shared["up"]["kernel"])) @ shared["down"]["kernel"])
    return y, logits, probs, chosen


def _near_boundary(logits, chosen, here, margin):
    """[T] bool: is some expert held here within ``margin`` (in units of the
    spread of a token's logits) of changing sides: a chosen one that close
    above the best logit not chosen, or one not chosen that close below the
    least logit chosen."""
    import jax.numpy as jnp

    least = jnp.where(chosen, logits, jnp.inf).min(-1, keepdims=True)
    best = jnp.where(chosen, -jnp.inf, logits).max(-1, keepdims=True)
    room = jnp.where(chosen, logits - best, least - logits)
    return ((room < margin * jnp.sqrt(jnp.var(logits, -1).mean()))
            & here).any(-1)


def forward(variables, batch, dtype=None, with_aux: bool = False):
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    params = jax.tree.map(lambda p: p.astype(dtype), variables["params"])
    ids = jnp.asarray(batch["input_ids"], jnp.int32)
    real = jnp.asarray(batch["attention_mask"]) > 0
    rows, seq = ids.shape
    if "position_ids" in batch:
        positions = jnp.asarray(batch["position_ids"])
        seg = jnp.asarray(batch["segment_ids"])
    else:
        positions, seg = jnp.arange(seq), None

    def allow_rows(start, n):
        at = start + jnp.arange(n)
        allow = real[:, None, :] & (jnp.arange(seq)[None, :] <= at[:, None])
        if seg is not None:
            mine = jax.lax.dynamic_slice_in_dim(seg, start, n, 1)
            allow &= mine[:, :, None] == seg[:, None, :]
        return allow

    w = real.astype(jnp.float32).reshape(-1, 1)
    n_live = jnp.maximum(w.sum(), 1.0)
    near_tie = jnp.zeros((rows * seq,), bool)
    aux = jnp.zeros((), jnp.float32)
    precision = "highest" if dtype == jnp.float32 else "default"

    def held_to(x):
        """In the lower precision, round where the program is stated to be
        float32: the compiler keeps the intermediates of a bf16 chain in
        float32 (``xla_allow_excess_precision``), which made Moonlight's
        first bf16 reading look like float32's."""
        if dtype == jnp.float32:
            return x
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    with jax.default_matmul_precision(precision):
        x = params["tok_embed"]["embedding"][ids]
        layers = sum(1 for k in params if k.startswith("layer_"))
        _NOTES["states"] = []
        for i in range(layers):
            p = params[f"layer_{i}"]
            u = _rms(x, p["ln_attn"]["scale"])
            if "gdn" in p:
                y, last = _linear_attention(u, p["gdn"], held_to)
                _NOTES["states"].append(last)
            else:
                y = _gated_attention(u, p["attn"], positions, allow_rows)
            x = x + y

            u = _rms(x, p["ln_mlp"]["scale"]).reshape(rows * seq, -1)
            moe = p["moe"]
            y, logits, probs, chosen = _sparse_block(u, moe, held_to)
            x = x + y.reshape(x.shape)
            e, held = probs.shape[-1], moe["w_gate"].shape[0]

            here = (jnp.arange(e) >= FIRST) & (jnp.arange(e) < FIRST + held)
            near_tie |= _near_boundary(logits.astype(jnp.float32), chosen,
                                       here, MARGIN)
            load = (chosen * w).sum(0)
            _NOTES["load"] = jnp.stack([(load * here).sum(),
                                        (load * here).max(), load.sum()])
            # E x sum_e (share of the live assignments) x (mean score)
            aux += e * jnp.sum(load / (n_live * TOP_K) * (
                probs.astype(jnp.float32) * w).sum(0) / n_live)
        _NOTES["near_tie"] = near_tie
        x = _rms(x, params["ln_final"]["scale"])
        logits = held_to(x @ params["lm_head"]["kernel"]).astype(jnp.float32)
        return (logits, aux) if with_aux else logits


def loss(variables, batch):
    """The training loss: next-token cross-entropy over real targets that
    stay inside their document, plus ``BALANCE_WEIGHT`` x the load-balance
    term of every layer."""
    import jax
    import jax.numpy as jnp

    logits, aux = forward(variables, batch, with_aux=True)
    ids = jnp.asarray(batch["input_ids"], jnp.int32)
    w = (jnp.asarray(batch["attention_mask"])[:, 1:] > 0).astype(jnp.float32)
    if "segment_ids" in batch:
        seg = jnp.asarray(batch["segment_ids"])
        w = w * (seg[:, 1:] == seg[:, :-1])
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
    return (nll * w).sum() / jnp.maximum(w.sum(), 1.0) + BALANCE_WEIGHT * aux
