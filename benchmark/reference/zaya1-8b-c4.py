"""Plain reference of ZAYA1-8B's decoder layer as the program runs it: float32
``jax.numpy`` at the highest matmul precision, fed the program's own
parameter tree and the same share of the experts. Source: ``Zyphra/ZAYA1-8B``
``config.json`` (``model_type`` ``zaya``) for every size; Zyphra's CCA paper,
arXiv:2510.04476, for the attention and the ZAYA1 report, arXiv:2511.17127,
for the router and the residual sums, as ISSUE 38 recalls them (the
configuration's file lists under ``assumed`` what the config cannot confirm).
``rms`` is RMSNorm (eps 1e-5, learned scale), no biases anywhere; H = 8 query
heads over G = 2 key/value heads, d = 128.

    u = rms(x)
    q~ = u Wq [S, 8, 128]    k~ = u Wk [S, 2, 128]
    v  = [ u_t Wv0 ; u_{t-1} Wv1 ]            head 1 from the token before
    conv(z) = C1(C0(z)):  C0(z)_t = a0 * z_{t-1} + a1 * z_t      a channel
                          C1(z)_t = z_{t-1} A0_h + z_t A1_h       a head
    q = conv(q~) + (q~ + rep(k~)) / 2
    k = conv(k~) + (k~ + groupmean(q~)) / 2
    q <- sqrt(128) q / |q|    k <- tau_g sqrt(128) k / |k|
    rope on the first 64 of each head's 128, theta 5e6, q and k
    o = softmax(causal(q k' / sqrt(128))) v,  query head h on key head h // 4
    x <- (a_r x + b_r) + (a_o (concat(o) Wo) + b_o)

    u = rms(x)
    r_l = u Wr + g_l * r_{l-1}                r_{-1} = 0, handed on
    s = softmax(W3 gelu(W2 gelu(W1 rms(r_l))))           16 experts, gelu exact
    e* = argmax(s + b)                        b takes no gradient
    y = s_{e*} down_{e*}(silu(gate_{e*} u) * up_{e*} u)   if e* is held here
    x <- (a_r x + b_r) + (a_o y + b_o)

    logits = rms(x_last) E'                   the held rows of the embedding

``rope`` turns element i with element i + 32 (half-rotation pairing) of the
rotary 64, at ``position_ids`` where the batch has them. No sort, no grouped
product, no kernel: every held expert is applied to every token, one at a
time, under the top-1 mask, which is built from pairwise comparisons of
``s + b``; a token whose expert this rank does not hold gets nothing from the
expert sub-layer, here as in the program (``FIRST``, and the number of
experts in the parameters). Attention is computed a block of ``Q_BLOCK``
query rows at a time, so that 8,192 tokens fit. ``loss`` is the training loss
(shifted cross-entropy over real targets; the router adds no term), for
``jax.grad``.

``forward(variables, batch, dtype=jnp.bfloat16)`` is the same mathematics with
every tensor and product in bf16, and the router's state, logits, scores,
bias and their sum and the logits rounded to bf16 explicitly: the nearest
precision below the configuration's, which the comparison has to refuse
(``TOLERANCE``).
"""

from __future__ import annotations

import numpy as np

EPS = 1e-5
THETA = 5000000.0
ROTARY = 64  # partial_rotary_factor x head_dim
TOP_K = 1  # the configuration's num_experts_per_tok
FIRST = 0  # the first expert held here: rank x (16 / ranks)
EVAL_ROWS = 1
Q_BLOCK = 1024

# The program computes in bf16 (f32 router from its down-projection on, f32
# softmax statistics, f32 norm statistics, f32 residual sums, f32 logits);
# the reference in f32. With one expert a token, a token whose two largest
# s + b change places takes another expert altogether, and its output moves
# by a whole expert's contribution; where one of the two is held here and the
# other is not, it moves between that and nothing. (Where both are absent
# both add nothing here.) The comparison is a maximum, so such tokens are
# left out: those whose chosen and best not chosen s + b lie within MARGIN,
# in any layer, where one of the two is held here. The room is measured where
# the program's rounding lives: the gap as a share of the chosen score, which
# for a small gap is the gap of the two logits, in units of the spread
# (standard deviation) of a token's 16 logits. bf16 activations reach the
# router's state through every layer before it: over forty seeds of 1,024
# tokens at published widths on the CPU the widest room a token's choice
# crossed was 0.051, over 12 seeds of 8,192 on the chip 0.043 (PERF.md
# section 6, PR 38, has the readings by margin). MARGIN is 1.6 times the
# wider. A token whose choice flips reads 1 to 2.5.
MARGIN = 0.08

# A token left out for its routing hands its changed expert output to its
# neighbours in the next layer, which no other model here does: the value
# shift gives the token after it a whole value head of it, and the two 2-tap
# convolutions a share of its normed stream in queries and keys. So the
# token after a token left out is left out with it, layer after layer
# (``left_out``): without that such neighbours read 0.25 to 1.0 at any
# margin. The second token after it sees it through the convolutions' far
# tap alone, and later tokens through attention, one key among many: they
# stay, and read up to 0.11 (same section).
REACH = 1

# Worst logit difference over the logits' spread on the tokens that stay. On
# the v5e at published widths (my chip run, PR 38, calls 3 to 5: 55 seeds of
# 8,192 tokens, 450,560 tokens): the median token reads 0.03, the worst token
# that stays 0.053 to 0.074 in 50 seeds and 0.143, 0.245, 0.302, 0.373 and
# 0.402 in five. Those are single tokens far from any tie (rooms of 0.2 and
# more, clean neighbours) that attend hard to a token which did change
# experts, 15 to 120 positions back: one key among many for most queries,
# most of the context for a few. Fourteen such tokens read over 0.2 among
# all 450,560, the largest 0.748; nothing in ``live`` knows the attention
# weights, so the limit has to take them. The reference in bf16 reads 1.73
# and more on the tokens that stay (0.36 to 0.50 at the median of all
# tokens), and a token whose own choice flipped 1 to 2.5. TOLERANCE is twice
# the largest sound reading among tokens that stay and under half of the
# least unsound one.
TOLERANCE = 0.8

# What holds the router's scores and its choice to float32 on the chip: the
# one expert is the largest of s + b, and a constant added to every b changes
# nothing if s and b are added and compared in float32 (one ulp at 64 is
# 7.6e-6, 1e-4 of the scores' spread under ROUTER_GAIN); the weight
# never sees b. bf16 cannot carry a score beside 64 (its ulp there is 0.5),
# so a router whose scores, bias or their sum pass through bf16 chooses by
# rounding. ``perturb`` gives every router's bias this shared offset on top
# of its random part, as the Moonlight reference does.
OFFSET = 64.0

# At initialisation (truncated normal 0.02 through three layers 256 wide) the
# router's logits have a spread of 0.01 and every score is 1/16 to three
# digits: a router that decides nothing, whose one weight is 0.0625 for every
# token. ``perturb`` rescales the MLP's three matrices to a standard deviation
# of this over the square root of their inputs, so that each layer keeps its
# input's scale and the logits' spread is about 1.5 at any width, as a router
# that has learned to choose: the weight s_e* then differs by token, and a
# choice by s alone, by b alone or by s + b are three different routings.
ROUTER_GAIN = 1.5

_NOTES: dict = {}  # forward() leaves near ties and load here for live(),
# and each layer's s + b for a builder who asks where a reading is from


def eval_batch(rows, config: dict) -> dict:
    """The first ``EVAL_ROWS`` rows as stored, and the configuration's
    constants and share."""
    global TOP_K, ROTARY, FIRST, THETA
    model = config["model"]
    TOP_K = int(model["num_experts_per_tok"])
    ROTARY = int(float(model["partial_rotary_factor"]) * int(
        model["head_dim"]))
    THETA = float(model["rope_theta"])
    rank = int(config["task"].get("expert_share", "0/1").split("/")[0])
    FIRST = rank * int(model["num_experts"])  # held here: a rank's
    out = {}
    for name in ("input_ids", "attention_mask"):
        col = rows.column(name).combine_chunks()
        out[name] = np.asarray(col.flatten()).reshape(len(col), -1)[:EVAL_ROWS]
    return out


def perturb(variables, rng):
    """Every learned scale leaves 1 (uniform in [0.75, 1.25]: the norms', the
    residual sums' on both sides, the key temperatures, the routers' depth
    mix) and every shift of a residual sum leaves 0 (normal, 0.02): at ones
    and zeros a missing or misplaced one would not show. Every router's MLP
    is rescaled (``ROUTER_GAIN``), and its selection bias leaves 0 (normal,
    0.01: a tenth of the scores' spread then) and shares ``OFFSET``, which
    only a float32 choice does not see."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten_with_path(variables)
    keys = jax.random.split(rng, len(leaves))

    def one(path, leaf, key):
        names = [getattr(k, "key", "") for k in path]
        last = names[-1]
        if last.endswith("scale") or last in ("key_temperature", "depth_mix"):
            return jax.random.uniform(key, leaf.shape, leaf.dtype, 0.75, 1.25)
        if last.endswith("shift"):
            return 0.02 * jax.random.normal(key, leaf.shape, leaf.dtype)
        if last == "bias":
            return OFFSET + 0.01 * jax.random.normal(key, leaf.shape,
                                                     leaf.dtype)
        if names[-2] in ("mlp_1", "mlp_2", "mlp_3"):
            return leaf * ROUTER_GAIN / (leaf.std() * np.sqrt(leaf.shape[0]))
        return leaf

    return jax.tree_util.tree_unflatten(
        tree, [one(path, leaf, k) for (path, leaf), k in zip(leaves, keys)])


def live(batch, want):
    """Real tokens whose one expert is not within ``MARGIN`` of changing
    places with the runner-up where one of the two is held here, in any layer
    of the reference."""
    import jax
    import jax.numpy as jnp

    real = jnp.asarray(batch["attention_mask"]) > 0
    near_tie = _NOTES["near_tie"].reshape(real.shape)
    jax.debug.print(
        "reference: {n} of {m} real tokens within the routing margin of a "
        "tie that a held expert is part of, left out of the comparison "
        "({p:.2f} %); in the last layer {a} tokens chose a held expert "
        "(busiest {b}), of {c} in all",
        n=(real & near_tie).sum(), m=real.sum(),
        p=100.0 * (real & near_tie).sum() / real.sum(), a=_NOTES["load"][0],
        b=_NOTES["load"][1], c=_NOTES["load"][2])
    return real & ~near_tie


def left_out(rooms, either, rows: int, margin=None, reach=None):
    """[T] bool from each layer's ``rooms`` and ``either`` [L, T]: the tokens
    whose routing may differ in the program. A token is marked in the layer
    where its room is under ``margin`` and one of the two experts is held
    here; a marked token's expert output may be another's altogether, and
    the next layer's value shift and convolutions read it from the ``reach``
    tokens after it in its row, so those are marked with it, layer after
    layer."""
    import jax.numpy as jnp

    margin = MARGIN if margin is None else margin
    reach = REACH if reach is None else reach
    marked = jnp.zeros(rooms.shape[1:], bool).reshape(rows, -1)
    for room, held in zip(rooms, either):
        spread = marked
        for k in range(1, reach + 1):
            spread = spread | jnp.pad(marked, ((0, 0), (k, 0)))[:, :-k]
        marked = spread | ((room < margin) & held).reshape(rows, -1)
    return marked.reshape(-1)


def _rms(x, scale):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale.astype(x.dtype)


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


def _before(z):
    """``z_{t-1}`` along the sequence (axis 1), zeros before the first."""
    import jax.numpy as jnp

    return jnp.concatenate([jnp.zeros_like(z[:, :1]), z[:, :-1]], 1)


def _conv(z, taps, mats):
    """``C1(C0(z))``: ``z`` [B, S, N, D], ``taps`` [2, N, D] (the token
    before, this token), ``mats`` [2, N, D, D]."""
    import jax.numpy as jnp

    z = taps[0] * _before(z) + taps[1] * z
    return (jnp.einsum("bsnd,nde->bsne", _before(z), mats[0])
            + jnp.einsum("bsnd,nde->bsne", z, mats[1]))


def _unit(z):
    """``sqrt(d) z / |z|`` over the last axis."""
    import jax
    import jax.numpy as jnp

    return z * jax.lax.rsqrt(jnp.mean(z * z, -1, keepdims=True) + 1e-12)


def _rank(scores):
    """[T, E] int: how many of a token's scores come before each one, larger
    first and lower index first on a tie; from pairwise comparisons, so the
    top k are ``rank < k`` without a sort."""
    import jax.numpy as jnp

    e = scores.shape[-1]
    a, b = scores[:, :, None], scores[:, None, :]
    earlier = jnp.arange(e)[None, :] < jnp.arange(e)[:, None]  # [e, e']
    return ((b > a) | ((b == a) & earlier[None])).sum(-1)


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


def _merge(x, y, p, name):
    """``(a_r x + b_r) + (a_o y + b_o)`` with the layer's four vectors."""
    a_r, b_r, a_o, b_o = (p[f"{name}_{part}"].astype(x.dtype) for part in (
        "stream_scale", "stream_shift", "branch_scale", "branch_shift"))
    return (a_r * x + b_r) + (a_o * y + b_o)


def forward(variables, batch, dtype=None):
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    params = jax.tree.map(lambda p: p.astype(dtype), variables["params"])
    biases = variables.get("batch_stats", {})
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
    rooms, eithers = [], []
    _NOTES["scores"] = []
    precision = "highest" if dtype == jnp.float32 else "default"

    def held_to(x):
        """In the lower precision, round where the program is stated to be
        float32 (the router's state, logits, scores, bias and their sum, the
        logits): the compiler keeps the intermediates of a bf16 chain in
        float32 (``xla_allow_excess_precision``), which made Moonlight's
        first bf16 reading look like float32's."""
        if dtype == jnp.float32:
            return x
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    with jax.default_matmul_precision(precision):
        embedding = params["tok_embed"]["embedding"]
        x = embedding[ids]
        state = None  # r_{-1} = 0: the first stage's
        layers = sum(1 for k in params if k.startswith("layer_"))
        for i in range(layers):
            p = params[f"layer_{i}"]
            a = p["attn"]
            u = _rms(x, p["ln_attn"]["scale"])
            q0 = jnp.einsum("bsh,hnd->bsnd", u, a["query"]["kernel"])
            k0 = jnp.einsum("bsh,hnd->bsnd", u, a["key"]["kernel"])
            v = jnp.einsum("bsh,hnd->bsnd", u, a["value"]["kernel"])
            n, g = q0.shape[2], k0.shape[2]
            v = jnp.concatenate([v[:, :, :g // 2],
                                 _before(v[:, :, g // 2:])], 2)
            q = _conv(q0, a["q_conv0"], a["q_conv1"]) + 0.5 * (
                q0 + jnp.repeat(k0, n // g, axis=2))
            k = _conv(k0, a["k_conv0"], a["k_conv1"]) + 0.5 * (
                k0 + q0.reshape(rows, seq, g, n // g, -1).mean(3))
            q = _rope(_unit(q), positions)
            k = _rope(_unit(k) * a["key_temperature"][:, None], positions)
            ctx = _attention(q, k, v, allow_rows)
            x = _merge(x, jnp.einsum("bsnd,ndh->bsh", ctx,
                                     a["out"]["kernel"]), p, "attn")

            u = _rms(x, p["ln_mlp"]["scale"]).reshape(rows * seq, -1)
            router, moe = p["router"], p["moe"]
            r = u @ router["down"]["kernel"]
            if state is not None:
                r = r + router["depth_mix"] * state
            state = r = held_to(r)
            mlp = router["mlp"]
            y = _rms(r, mlp["norm"]["scale"])
            for name in ("mlp_1", "mlp_2"):
                y = jax.nn.gelu(y @ mlp[name]["kernel"], approximate=False)
            s = held_to(jax.nn.softmax(
                held_to(y @ mlp["mlp_3"]["kernel"]), -1))
            e, held = s.shape[-1], moe["w_gate"].shape[0]
            bias = held_to(biases.get(f"layer_{i}", {}).get("moe", {}).get(
                "bias", jnp.zeros((e,))).astype(dtype))
            sel = held_to(s + bias)
            rank = _rank(sel)
            chosen = rank < TOP_K
            weights = s * chosen
            y = _experts(u, moe, weights[:, FIRST:FIRST + held])
            x = _merge(x, y.reshape(x.shape), p, "mlp")

            here = (jnp.arange(e) >= FIRST) & (jnp.arange(e) < FIRST + held)
            sel = sel.astype(jnp.float32)
            _NOTES["scores"].append(sel)
            # the last chosen and the first not chosen: how far apart as a
            # share of the chosen one's score (for a small gap that is the
            # gap of their logits, which is where the program's rounding
            # lives), in units of the spread of a token's logits; and
            # whether either is held here
            inside = jnp.where(rank == TOP_K - 1, sel, 0).sum(-1)
            outside = jnp.where(rank == TOP_K, sel, 0).sum(-1)
            score = jnp.where(rank == TOP_K - 1, s, 0).sum(-1)
            spread = jnp.sqrt(jnp.var(jnp.log(jnp.maximum(
                s.astype(jnp.float32), 1e-30)), -1).mean())
            rooms.append((inside - outside) / score.astype(jnp.float32)
                         / spread)
            eithers.append((((rank == TOP_K - 1) | (rank == TOP_K))
                            & here).any(-1))
            load = (chosen * w).sum(0)
            _NOTES["load"] = jnp.stack([(load * here).sum(),
                                        (load * here).max(), load.sum()])
        _NOTES["rooms"] = jnp.stack(rooms)
        _NOTES["either"] = jnp.stack(eithers)
        near_tie = left_out(_NOTES["rooms"], _NOTES["either"], rows)
        _NOTES["near_tie"] = near_tie
        x = _rms(x, params["ln_final"]["scale"])
        return held_to(jnp.einsum("bsh,vh->bsv", x, embedding)).astype(
            jnp.float32)


def loss(variables, batch):
    """The training loss: next-token cross-entropy over real targets that
    stay inside their document. The router adds no term: its balance is the
    selection bias's."""
    import jax
    import jax.numpy as jnp

    logits = forward(variables, batch)
    ids = jnp.asarray(batch["input_ids"], jnp.int32)
    w = (jnp.asarray(batch["attention_mask"])[:, 1:] > 0).astype(jnp.float32)
    if "segment_ids" in batch:
        seg = jnp.asarray(batch["segment_ids"])
        w = w * (seg[:, 1:] == seg[:, :-1])
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
    return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)
