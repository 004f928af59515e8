"""Plain reference of Laguna-S-2.1's decoder layers as the program runs them:
float32 ``jax.numpy`` at the highest matmul precision, fed the program's own
parameter tree and the same share of the experts. Source:
``poolside/Laguna-S-2.1`` ``config.json`` (``model_type`` ``laguna``) for every
size. The container has no class of that type and no network, so the
equations are the catalog row's keys as the issue of PR 53 wrote them down;
what the row's ``config`` cannot confirm is under ``assumed`` in
``benchmark/configs/laguna-s-2.1-c4.json``. ``rms`` is RMSNorm with a plain
learned scale (eps 1e-6), no biases anywhere. With ``x`` the stream entering
published layer ``i``:

    h = rms(x) ;  q = h W_q  (N_i heads of d) ;  k = h W_k, v = h W_v  (G heads)
    i % 4 == 0 (full, N_i = 48): rotary on the first 64 of d = 128, theta 5e5,
        YaRN's table of 32 inverse frequencies, cos and sin times 1.4852...;
        the whole causal row
    else (window, N_i = 72): rotary over the whole d, theta 1e4; a query sees
        itself and the 511 keys before it
    o = softmax(mask(q k' / sqrt(d))) v,   query head n on key head n // (N/G)
    g = sigmoid(h W_g)                     one scalar a head and token
    x' = x + concat(g_n o_n) W_o
    u = rms(x')
    layer 0:  x'' = x' + down(silu(gate u) * up u)              width 12,288
    others:   s = sigmoid(u W_r)                                256 scores
              chosen: the ten largest ;  w_e = 2.5 s_e / sum_chosen s
              x'' = x' + sum_{chosen e held here} w_e expert_e(u) + shared(u)
    logits = rms(x_last) W_head

YaRN (Peng et al., arXiv:2309.00071), written out here and not taken from the
program: with D = 64 the rotary width, ``f_j = theta^(-2j/D)``,
``c(r) = D ln(L / (2 pi r)) / (2 ln theta)`` over L = 8,192 original
positions, ``low = max(floor(c(32)), 0)``, ``high = min(ceil(c(1)), D - 1)``,
``ramp_j = clip((j - low) / (high - low), 0, 1)``, ``inv_j = f_j / 128 ramp_j
+ f_j (1 - ramp_j)``; the angle is ``position inv_j``.

The parameters say which layer is which: a layer with ``mlp`` is the dense
one, the number of query heads is ``W_q``'s, and this file reads the
published index from ``FIRST_LAYER`` and the period (``PERIOD``). ``rope``
turns element i with element i + width/2, at ``position_ids`` where the batch
has them. No sort of assignments, no grouped product, no kernel, no repeated
keys: every held expert is applied to every token, one at a time, under the
top-k mask (the scores at or above a token's k-th largest); experts this rank
does not hold add nothing, here as in the program (``FIRST``, and the number
of experts in the parameters). Attention is computed a block of ``Q_BLOCK``
query rows at a time against every key under a dense mask (the band or the
triangle), a key/value head with its group of query heads in one product, so
that a row of 8,192 tokens fits. ``loss`` is the training loss (shifted
cross-entropy; the model's loss has no balance term), for ``jax.grad``.

``forward(variables, batch, dtype=jnp.bfloat16)`` is the same mathematics with
every tensor and product in bf16, and what the program states to be float32
(the router's logits and scores, the attention scores, the gate, the logits)
rounded to bf16 explicitly: the nearest precision below the configuration's,
which the comparison has to refuse (``TOLERANCE``).
"""

from __future__ import annotations

import math

import numpy as np

EPS = 1e-6
WINDOW = 512  # sliding_window
PERIOD = 4  # layer i is full attention where i % PERIOD == 0
FIRST_LAYER = 0  # the published index of the first layer held here
THETA_WINDOW = 10000.0  # rope_parameters.sliding_attention
THETA_FULL = 500000.0  # rope_parameters.full_attention ...
ROTARY_FULL = 64  # ... partial_rotary_factor 0.5 of a head of 128
YARN = (128.0, 8192, 32.0, 1.0, 1.4852030263919618)  # factor, original
# positions, beta_fast, beta_slow, attention_factor
TOP_K = 10  # num_experts_per_tok
ROUTED_SCALE = 2.5  # moe_routed_scaling_factor
FIRST = 0  # the first expert held here: rank x (256 / ranks)
EVAL_ROWS = 1
Q_BLOCK = 512

# The program computes in bf16 (f32 router at the highest precision, f32
# sigmoid scores, f32 softmax statistics, f32 norm statistics, f32 gate, f32
# logits); the reference in f32. A token for which an expert held here is
# close to changing sides (chosen, and little above the best logit not
# chosen; or not chosen, and little below the least logit chosen) may have it
# on the other side in the program, whose router reads a stream that bf16
# products made, and its output then moves by a whole expert's contribution.
# The comparison is a maximum, so such tokens are left out, as the four sparse
# references before this one leave them out: those with a held expert within
# MARGIN of the boundary, in units of the spread (standard deviation) of a
# token's 256 router logits about their mean, in any expert layer. The
# sigmoid is monotonic and the choice has no bias, so the boundary is read on
# the logits. What MARGIN has to cover is the widest room a held expert
# crosses in a token that no earlier layer has moved (a token that changed
# sides once is out already, and its later layers read another stream): at
# the published widths on rows of 2,048 tokens, four seeds (this sandbox's
# CPU, PR 53: ``scripts/laguna_variants.py --seq``; correctness, not a
# device's time) that was 0.016-0.030 of the spread under ``perturb`` as it
# stands, and 0.037-0.057 and 0.069-0.076 with W_q and W_k times 1.25 and
# 1.5: a sharper softmax makes the program's stream, and so its router's
# logits, less exact, which is why ``perturb`` leaves the scores' gain alone.
# 0.1 is three times the widest seen. With 8 of 256 held and 10 a token, 256 x
# 0.085 x 2 x 0.1 / 32 = 0.14 held experts a token and layer lie that close:
# 42% of a row's tokens over four expert layers (``live`` prints the share),
# and some 4,700 tokens by 12,544 logits are compared.
MARGIN = 0.1

# Worst logit difference over the logits' spread on the tokens that stay,
# under ``perturb``, on the v5e at the published widths and one row of 8,192
# tokens (my chip run, PR 53; PERF.md section 6 has every reading): the
# program reads 0.059-0.122 over thirteen seeds (nine of them the cell's own
# model check). Over four of those seeds the wrong programs read: the reference
# computed in bf16 (router logits and scores, attention scores, gate and
# logits rounded where they stand) 1.30-1.55, a program without the gate
# 3.27-3.55, with plain rotary in the full layers (no YaRN table, no factor)
# 1.64-1.77, with softmax scores 1.40-1.63, without the window 1.25-1.55.
# TOLERANCE is 2.5 times the program's largest reading and 4.3 times under
# the least reading of a wrong program. (On this sandbox's CPU at a row of
# 1,024 tokens, three seeds, the same order: 0.055-0.104 against 0.955-1.36
# for the reference in bf16.)
TOLERANCE = 0.3

# ``perturb``: with every matrix at its initial 0.02 the mechanisms this model
# has would not show. The embedding is 0.02 a value, so the first branch would
# swamp the stream; a held expert under a weight of a quarter adds a twentieth
# of what attention adds. So: the embedding times EMBED_GAIN (a stream of
# about 1 a value from the start), every expert's last matrix times DOWN_GAIN
# (a held expert adds about what the shared one adds, so an expert on the
# wrong side shows), and one random column, OFFSET times as large as a router
# column's own, added to all 256 of every router's: a token's 256 logits
# share an offset of a few of their spreads, up or down. A float32 sigmoid
# keeps the order and the ratios of the scores under it (at a logit of 11,
# 1 - s is 1.7e-5 and float32 resolves a logit there to 0.004); a bf16 score
# beside 1 has steps of 0.004, so above an offset of 3 a bf16 router chooses
# its ten by rounding, and those tokens are what the reference in bf16 misses
# by. OFFSET is kept where the largest offset in a row (about 4.2 spreads of
# it: 8 to 9 logits) stays well inside what float32 itself resolves. W_q and
# W_k keep their scale (the note on MARGIN says why): the scores'
# spread is about 1.2 at the published widths, 2.6 on the full layers' rotary
# half under YaRN's factor, and a missing gate, window, table or factor still
# reads three to thirty times the program (the note on TOLERANCE).
EMBED_GAIN = 50.0
DOWN_GAIN = 4.0
OFFSET = 2.0

_NOTES: dict = {}  # forward() leaves near ties and load here for live()


def configure(config: dict) -> None:
    """This file's constants from the configuration: the model group's
    published keys, and the rank's first expert."""
    global TOP_K, ROUTED_SCALE, WINDOW, FIRST, FIRST_LAYER, THETA_WINDOW, \
        THETA_FULL, ROTARY_FULL, YARN
    model = config["model"]
    TOP_K = int(model["num_experts_per_tok"])
    ROUTED_SCALE = float(model["moe_routed_scaling_factor"])
    WINDOW = int(model["sliding_window"])
    FIRST_LAYER = int(model.get("first_layer", 0))
    full = model["rope_parameters"]["full_attention"]
    THETA_WINDOW = float(
        model["rope_parameters"]["sliding_attention"]["rope_theta"])
    THETA_FULL = float(full["rope_theta"])
    ROTARY_FULL = int(full["partial_rotary_factor"] * int(model["head_dim"]))
    YARN = (float(full["factor"]),
            int(full["original_max_position_embeddings"]),
            float(full["beta_fast"]), float(full["beta_slow"]),
            float(full["attention_factor"]))
    rank = int(config["task"].get("expert_share", "0/1").split("/")[0])
    FIRST = rank * int(model["num_experts"])  # held here: a rank's


def eval_batch(rows, config: dict) -> dict:
    """The first ``EVAL_ROWS`` rows as stored, under the configuration's
    constants and share."""
    configure(config)
    out = {}
    for name in ("input_ids", "attention_mask"):
        col = rows.column(name).combine_chunks()
        out[name] = np.asarray(col.flatten()).reshape(len(col), -1)[:EVAL_ROWS]
    return out


def perturb(variables, rng):
    """Every norm's scale leaves 1 (uniform in [0.75, 1.25]): at all ones a
    missing or misplaced scale would not show. The gains and the routers'
    shared column: see the note on ``EMBED_GAIN``."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten_with_path(variables)
    keys = jax.random.split(rng, len(leaves))

    def one(path, leaf, key):
        names = [getattr(k, "key", "") for k in path]
        if names[-1] == "scale":
            return jax.random.uniform(key, leaf.shape, leaf.dtype, 0.75, 1.25)
        if names[-1] == "embedding":
            return EMBED_GAIN * leaf
        if names[-1] == "w_down":
            return DOWN_GAIN * leaf
        if names[-2:] == ["router", "kernel"]:
            return leaf + OFFSET * leaf.std() * jax.random.normal(
                key, leaf.shape[:1] + (1,), leaf.dtype)
        return leaf

    return jax.tree_util.tree_unflatten(
        tree, [one(path, leaf, k) for (path, leaf), k in zip(leaves, keys)])


def live(batch, want):
    """Real tokens with no expert held here within ``MARGIN`` of the routing
    boundary, in any expert layer of the reference."""
    import jax
    import jax.numpy as jnp

    real = jnp.asarray(batch["attention_mask"]) > 0
    near_tie = _NOTES["near_tie"].reshape(real.shape)
    jax.debug.print(
        "reference: {n} of {m} real tokens have a held expert within the "
        "routing margin of the boundary between the chosen and the others, "
        "left out of the comparison ({p:.2f} %); in the last layer {a} "
        "assignments went to held experts (busiest {b}), of {c} in all; a "
        "token's shared router offset reached {o:.2f} logits",
        n=(real & near_tie).sum(), m=real.sum(),
        p=100.0 * (real & near_tie).sum() / real.sum(), a=_NOTES["load"][0],
        b=_NOTES["load"][1], c=_NOTES["load"][2], o=_NOTES["offset"])
    return real & ~near_tie


def _rms(x, w):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * w.astype(x.dtype)


def yarn_inverse_frequencies(width: int, theta: float, factor: float,
                             original: int, beta_fast: float,
                             beta_slow: float) -> list:
    """The module text's equations, in Python floats."""
    def c(r):
        return width * math.log(original / (2 * math.pi * r)) / (
            2 * math.log(theta))

    low, high = max(math.floor(c(beta_fast)), 0), min(math.ceil(c(beta_slow)),
                                                      width - 1)
    if low == high:
        high += 0.001
    out = []
    for j in range(width // 2):
        f = theta ** (-2.0 * j / width)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return out


def _rope(x, positions, inv_freq, factor=1.0):
    """``x`` [B, S, N, D]: its first ``2 len(inv_freq)`` elements turn,
    element i with element i + len(inv_freq); the rest stay."""
    import jax.numpy as jnp

    half = len(inv_freq)
    angle = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv_freq, jnp.float32)
    cos = (factor * jnp.cos(angle))[..., None, :].astype(x.dtype)
    sin = (factor * jnp.sin(angle))[..., None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., 2 * half:]], -1)


def _attention(q, k, v, allow_rows, held_to=lambda x: x):
    """Softmax attention, a block of query rows at a time: q [B, S, N, D], k,
    v [B, S, G, D] with query head n on key head n // (N / G), a key head
    with its N / G query heads in one product; ``allow_rows(start, rows)``
    gives the boolean [B, rows, S] of keys each of those queries may see."""
    import jax
    import jax.numpy as jnp

    rows_n, seq, heads, d = q.shape
    groups = k.shape[2]
    block = min(Q_BLOCK, seq)
    scale = 1.0 / np.sqrt(d)
    q = q.reshape(rows_n, seq, groups, heads // groups, d)

    @jax.checkpoint
    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 1)
        scores = held_to(jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) * scale)
        scores = jnp.where(allow_rows(start, block)[:, None, None], scores,
                           jnp.finfo(scores.dtype).min)
        return jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(rows, jnp.arange(0, seq, block))  # [blocks, B, rows, ..]
    return jnp.moveaxis(out, 0, 1).reshape(rows_n, seq, heads, d)


def _gated_attention(u, p, positions, allow_rows, full: bool,
                     held_to=lambda x: x):
    """The mixer on the normed stream ``u``: the layer's kind picks the
    rotary scheme; the number of heads is the parameters'."""
    import jax
    import jax.numpy as jnp

    q = jnp.einsum("bsh,hnd->bsnd", u, p["query"]["kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", u, p["key"]["kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", u, p["value"]["kernel"])
    d = q.shape[-1]
    if full:
        table = yarn_inverse_frequencies(ROTARY_FULL, THETA_FULL, *YARN[:4])
        factor = YARN[4]
    else:
        table = [THETA_WINDOW ** (-2.0 * j / d) for j in range(d // 2)]
        factor = 1.0
    q, k = (_rope(t, positions, table, factor) for t in (q, k))
    o = _attention(q, k, v, allow_rows, held_to)
    gate = jax.nn.sigmoid(held_to(u @ p["gate"]["kernel"]))  # [B, S, N]
    return jnp.einsum("bsnd,ndh->bsh", o * held_to(gate)[..., None],
                      p["out"]["kernel"])


def _swiglu(y, p):
    import jax

    return (jax.nn.silu(y @ p["gate"]["kernel"]) * (y @ p["up"]["kernel"])
            ) @ p["down"]["kernel"]


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


def _route(logits, held_to=lambda x: x):
    """``(weights [T, E], the top-k mask)`` of router logits [T, E]: sigmoid
    scores, the k largest, their scores over their sum times
    ``ROUTED_SCALE``."""
    import jax
    import jax.numpy as jnp

    e = logits.shape[-1]
    s = held_to(jax.nn.sigmoid(logits))
    # the k largest: at or above a token's k-th largest score
    kth = jnp.sort(s, -1)[:, e - TOP_K][:, None]
    chosen = s >= kth
    return ROUTED_SCALE * s * chosen / (
        (s * chosen).sum(-1, keepdims=True) + 1e-20), chosen


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


def forward(variables, batch, dtype=None):
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

    def allow_rows(window):
        def allow(start, n):
            at = (start + jnp.arange(n))[:, None]
            key = jnp.arange(seq)[None, :]
            band = key <= at  # the triangle
            if window:  # the band: itself and the window - 1 keys before it
                band &= at - key < window
            allow = real[:, None, :] & band
            if seg is not None:
                mine = jax.lax.dynamic_slice_in_dim(seg, start, n, 1)
                allow &= mine[:, :, None] == seg[:, None, :]
            return allow
        return allow

    w = real.astype(jnp.float32).reshape(-1, 1)
    near_tie = jnp.zeros((rows * seq,), bool)
    _NOTES["load"], _NOTES["offset"] = jnp.zeros((3,)), jnp.zeros(())
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
        for i in range(layers):
            p = params[f"layer_{i}"]
            full = (FIRST_LAYER + i) % PERIOD == 0
            u = _rms(x, p["ln_attn"]["scale"])
            x = x + _gated_attention(
                u, p["attn"], positions, allow_rows(0 if full else WINDOW),
                full, held_to)

            u = _rms(x, p["ln_mlp"]["scale"]).reshape(rows * seq, -1)
            if "mlp" in p:  # the leading dense layer
                x = x + _swiglu(u, p["mlp"]).reshape(x.shape)
                continue
            moe = p["moe"]
            e, held = moe["router"]["kernel"].shape[1], moe["w_gate"].shape[0]
            logits = held_to(u @ moe["router"]["kernel"])
            weights, chosen = _route(logits, held_to)
            x = x + (_experts(u, moe, weights[:, FIRST:FIRST + held].astype(
                u.dtype)) + _swiglu(u, moe["shared"])).reshape(x.shape)

            here = (jnp.arange(e) >= FIRST) & (jnp.arange(e) < FIRST + held)
            logits32 = logits.astype(jnp.float32)
            near_tie |= _near_boundary(logits32, chosen, here, MARGIN)
            load = (chosen * w).sum(0)
            _NOTES["load"] = jnp.stack([(load * here).sum(),
                                        (load * here).max(), load.sum()])
            _NOTES["offset"] = jnp.maximum(
                _NOTES["offset"], jnp.abs(logits32.mean(-1)).max())
        _NOTES["near_tie"] = near_tie
        x = _rms(x, params["ln_final"]["scale"])
        return held_to(x @ params["lm_head"]["kernel"]).astype(jnp.float32)


def loss(variables, batch):
    """The training loss: next-token cross-entropy over real targets that
    stay inside their document. The model's loss has no balance term."""
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
