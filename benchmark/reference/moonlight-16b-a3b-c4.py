"""Plain reference of Moonlight-16B-A3B's decoder as the program runs it:
float32 ``jax.numpy`` at the highest matmul precision, fed the program's own
parameter tree and the same share of the experts. Source:
``moonshotai/Moonlight-16B-A3B`` ``config.json`` (``model_type``
``deepseek_v3``) for every size; the DeepSeek-V3 report, arXiv:2412.19437,
for what the config does not say (the bias update, the sequence-wise balance
term). ``rms`` is RMSNorm (eps 1e-5, learned scale), no biases anywhere.

    q = Wq · rms(x)                      16 heads x (128 + 64 rotary)
    [c, k_pe] = Wkva · rms(x)            512 + 64; k_pe one head for all
    [k_nope, v] = Wkvb · rms_kv(c)       16 heads x (128 + 128)
    h = x + Wo · softmax(causal([q_nope, rope(q_pe)] · [k_nope, rope(k_pe)]
                                / sqrt(192))) · v
    layer 0:       y = h + down(silu(gate(rms(h))) * up(rms(h)))   width 11,264
    other layers:  s = sigmoid(Wr · rms(h))                        64 experts
                   chosen: the six largest of s + b  (b takes no gradient)
                   w_e = 2.446 · s_e / Σ_chosen s     (without b)
                   y = h + Σ_{chosen e held here} w_e · expert_e(rms(h))
                         + shared(rms(h))             width 1,408; 2 x 1,408
    logits = Whead · rms(y_last)

``rope`` turns element i with element i + 32 (half-rotation pairing), theta
50,000, at ``position_ids`` where the batch has them. No sort, no grouped
product, no kernel: every held expert is applied to every token, one at a
time, under the top-6 mask, which is built from pairwise comparisons of
``s + b``; experts this rank does not hold add nothing, here as in the
program (``FIRST``, and the number of experts in the parameters). Attention
is computed a block of ``Q_BLOCK`` query rows at a time, so that 8,192
tokens fit (a full float32 score tensor is 4.3 GB a row). ``loss`` is the
training loss (shifted cross-entropy plus 0.0001 x the sequence-wise balance
term over live tokens), for ``jax.grad``.

``forward(variables, batch, dtype=jnp.bfloat16)`` is the same mathematics
with every tensor and product in bf16, and the router's logits, scores, bias
and their sum and the logits rounded to bf16 explicitly: the nearest
precision below the configuration's, which the comparison has to refuse
(``TOLERANCE``).
"""

from __future__ import annotations

import numpy as np

EPS = 1e-5
THETA = 50000.0
TOP_K = 6  # the configuration's num_experts_per_tok
ROUTED_SCALE = 2.446  # routed_scaling_factor
NOPE, ROPE = 128, 64  # qk_nope_head_dim, qk_rope_head_dim
FIRST = 0  # the first expert held here: rank x (64 / ranks)
SEQ_BALANCE_WEIGHT = 0.0001
EVAL_ROWS = 1
Q_BLOCK = 1024

# The program computes in bf16 (f32 router, f32 softmax statistics, f32 norm
# statistics, f32 logits); the reference in f32. A token for which an expert
# held here is close to changing sides (chosen, and little above the best
# score not chosen; or not chosen, and little below the least score chosen)
# may have it on the other side in the program, and its output then moves
# by a whole expert's contribution. (Where two absent experts change places
# both add nothing here, and the renormalised weights move by the
# difference of two nearly equal scores.) The comparison is a maximum, so
# such tokens are left out: those with a held expert within MARGIN of the
# spread of a token's s + b of the boundary, in any expert layer. With 8 of
# 64 held that is a quarter of the tokens near a tie, not all of them. On the v5e at published widths (my chip
# run, PR 30, call 4, eight seeds) the program's s + b differs from the
# reference's by 0.0074 of the spread (standard deviation) in the first
# expert layer and 0.0123 in the fifth, over the tokens no earlier layer has
# moved, and the widest room a held expert crossed in such a token was 0.045.
# MARGIN is 2.7 times that, 6.9 standard deviations of the difference of two
# such errors in the fifth layer; at 0.08 no token slipped through in 19 seeds
# either, with 69% left out where 0.12 leaves out 83% (the share is printed):
# some 1,400 tokens by 20,480 logits are compared. A token that does slip
# through reads 0.6 or more. (The first form of this test looked only at the
# sixth and seventh scores: a held expert in fifth place that changes places
# with an absent seventh slipped through, 19 to 21 tokens a seed.)
MARGIN = 0.12

# Worst logit difference over the logits' spread, tokens near a held routing
# boundary left out. On the v5e at published widths (my chip run, PR 30,
# calls 3 and 4): 0.075-0.108 over nineteen seeds of 8,192 tokens at MARGIN
# 0.08 and 0.077-0.091 over eight at 0.12. The larger readings are tokens
# early in a row, which attend to few keys: a neighbour that was left out
# because its routing differs feeds them a large share of their context.
# TOLERANCE is 1.9 times the largest reading. The reference computed in bf16
# throughout (``forward(..., dtype=jnp.bfloat16)``) read 0.068-0.080 at
# first, like the program (calls 4 and 5): bf16 activations already move the
# scores by more than a bf16 router adds, the tokens that moves are left out,
# and the compiler kept the bf16 chain's intermediates in float32. With the
# bias under OFFSET and the router's values rounded where they stand, it
# picks other experts for nearly every token (PERF.md section 6, PR 30, call 6).
TOLERANCE = 0.2

# What holds the router's scores and its choice to float32 on the chip. The
# six are the largest of s + b, and a constant added to every b changes
# nothing if s and b are added and compared in float32 (one ulp at 64 is
# 7.6e-6, a few 1e-5 of the scores' spread); the weights never see b. bf16
# cannot carry a score beside 64 (its ulp there is 0.5), so a router whose
# scores, bias or their sum pass through bf16 chooses by rounding. ``perturb``
# therefore gives every router's bias this shared offset on top of its
# random part, as the OLMoE reference gives the router's logits theirs.
OFFSET = 64.0

_NOTES: dict = {}  # forward() leaves near ties and load here for live(),
# and each expert layer's s + b for a builder who asks where a reading is from


def eval_batch(rows, config: dict) -> dict:
    """The first ``EVAL_ROWS`` rows as stored, and the configuration's
    routing constants and share."""
    global TOP_K, ROUTED_SCALE, NOPE, ROPE, FIRST, THETA
    model = config["model"]
    TOP_K = int(model["num_experts_per_tok"])
    ROUTED_SCALE = float(model["routed_scaling_factor"])
    NOPE, ROPE = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    THETA = float(model["rope_theta"])
    rank = int(config["task"].get("expert_share", "0/1").split("/")[0])
    FIRST = rank * int(model["n_routed_experts"])  # held here: a rank's
    out = {}
    for name in ("input_ids", "attention_mask"):
        col = rows.column(name).combine_chunks()
        out[name] = np.asarray(col.flatten()).reshape(len(col), -1)[:EVAL_ROWS]
    return out


def perturb(variables, rng):
    """Every norm's scale leaves 1 (uniform in [0.75, 1.25]): at all ones a
    missing or misplaced scale would not show. Every router's selection bias
    leaves 0 (normal, 0.02: a tenth of the scores' spread at
    initialisation, twenty steps of the update at its rate): at zero a bias
    that entered the weights, or never the choice, would not show; and all
    64 share ``OFFSET``, which only a float32 choice does not see."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten_with_path(variables)
    keys = jax.random.split(rng, len(leaves))

    def one(path, leaf, key):
        names = [getattr(k, "key", "") for k in path]
        if names[-1] == "scale":
            return jax.random.uniform(key, leaf.shape, leaf.dtype, 0.75, 1.25)
        if names[-1] == "bias":
            return OFFSET + 0.02 * jax.random.normal(key, leaf.shape,
                                                     leaf.dtype)
        return leaf

    return jax.tree_util.tree_unflatten(
        tree, [one(path, leaf, k) for (path, leaf), k in zip(leaves, keys)])


def live(batch, want):
    """Real tokens whose routing is not within ``MARGIN`` of a tie that a
    held expert is part of, in any layer of the reference."""
    import jax
    import jax.numpy as jnp

    real = jnp.asarray(batch["attention_mask"]) > 0
    near_tie = _NOTES["near_tie"].reshape(real.shape)
    jax.debug.print(
        "reference: {n} of {m} real tokens within the routing margin of a "
        "tie that a held expert is part of, left out of the comparison "
        "({p:.2f} %); in the last layer {a} assignments landed on held "
        "experts (busiest {b}), of {c} in all",
        n=(real & near_tie).sum(), m=real.sum(),
        p=100.0 * (real & near_tie).sum() / real.sum(), a=_NOTES["load"][0],
        b=_NOTES["load"][1], c=_NOTES["load"][2])
    return real & ~near_tie


def _rms(x, scale):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * scale.astype(x.dtype)


def _rope(x, positions):
    """``x`` [B, S, N, D]: element i turns with element i + D/2."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * freq
    cos = jnp.cos(angle)[..., None, :].astype(x.dtype)
    sin = jnp.sin(angle)[..., None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _rank(scores):
    """[T, E] int: how many of a token's scores come before each one, larger
    first and lower index first on a tie; from pairwise comparisons, so the
    top k are ``rank < k`` without a sort."""
    import jax.numpy as jnp

    e = scores.shape[-1]
    a, b = scores[:, :, None], scores[:, None, :]
    earlier = jnp.arange(e)[None, :] < jnp.arange(e)[:, None]  # [e, e']
    return ((b > a) | ((b == a) & earlier[None])).sum(-1)


def _near_boundary(sel, rank, here, margin):
    """[T] bool: is some expert held here within ``margin`` (in units of the
    spread of a token's scores) of changing sides: a chosen one that close
    above the best score not chosen, or one not chosen that close below the
    least score chosen."""
    import jax.numpy as jnp

    sixth = jnp.where(rank == TOP_K - 1, sel, 0).sum(-1, keepdims=True)
    seventh = jnp.where(rank == TOP_K, sel, 0).sum(-1, keepdims=True)
    room = jnp.where(rank < TOP_K, sel - seventh, sixth - sel)
    return ((room < margin * jnp.sqrt(jnp.var(sel, -1).mean()))
            & here).any(-1)


def _attention(q, k, v, allow_rows):
    """Causal softmax attention, a block of query rows at a time: q, k
    [B, S, N, Dqk], v [B, S, N, Dv]; ``allow_rows(start, rows)`` gives the
    boolean [B, rows, S] of keys each of those queries may see."""
    import jax
    import jax.numpy as jnp

    seq = q.shape[1]
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
    return jnp.moveaxis(out, 0, 1).reshape(v.shape)


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


def _forward(variables, batch, train: bool, dtype=None):
    """``(logits, aux)``: aux is the weighted sequence-wise balance term
    summed over the layers (zero in eval mode, as in the program)."""
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

    w = real.astype(jnp.float32)  # [B, S]
    aux = jnp.zeros((), jnp.float32)
    near_tie = jnp.zeros((rows * seq,), bool)
    _NOTES["scores"] = []
    precision = "highest" if dtype == jnp.float32 else "default"

    def held_to(x):
        """In the lower precision, round where the program is stated to be
        float32 (the router's logits, scores, bias and their sum, the
        logits): the compiler keeps the intermediates of a bf16 chain in
        float32 (``xla_allow_excess_precision``), which made the first bf16
        reading look like float32's."""
        if dtype == jnp.float32:
            return x
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    with jax.default_matmul_precision(precision):
        x = params["tok_embed"]["embedding"][ids]
        layers = sum(1 for k in params if k.startswith("layer_"))
        for i in range(layers):
            p = params[f"layer_{i}"]
            a = p["attn"]
            y = _rms(x, p["ln_attn"]["scale"])
            q = jnp.einsum("bsh,hnd->bsnd", y, a["query"]["kernel"])
            latent = y @ a["kv_a"]["kernel"]
            rank_kv = latent.shape[-1] - ROPE
            kv = jnp.einsum("bsc,cnd->bsnd", _rms(
                latent[..., :rank_kv], a["kv_norm"]["scale"]),
                a["kv_b"]["kernel"])
            k_pe = _rope(latent[:, :, None, rank_kv:], positions)
            q = jnp.concatenate(
                [q[..., :NOPE], _rope(q[..., NOPE:], positions)], -1)
            k = jnp.concatenate([kv[..., :NOPE], jnp.broadcast_to(
                k_pe, k_pe.shape[:2] + (q.shape[2], ROPE))], -1)
            ctx = _attention(q, k, kv[..., NOPE:], allow_rows)
            x = x + jnp.einsum("bsnd,ndh->bsh", ctx, a["out"]["kernel"])

            y = _rms(x, p["ln_mlp"]["scale"]).reshape(rows * seq, -1)
            if "mlp" in p:  # the leading dense layer
                x = x + _swiglu(y, p["mlp"]).reshape(x.shape)
                continue
            moe = p["moe"]
            e, held = moe["router"]["kernel"].shape[1], moe["w_gate"].shape[0]
            s = held_to(jax.nn.sigmoid(held_to(y @ moe["router"]["kernel"])))
            bias = held_to(biases.get(f"layer_{i}", {}).get("moe", {}).get(
                "bias", jnp.zeros((e,))).astype(dtype))
            rank = _rank(held_to(s + bias))
            chosen = rank < TOP_K
            weights = ROUTED_SCALE * s * chosen / (
                (s * chosen).sum(-1, keepdims=True) + 1e-20)
            x = x + (_experts(y, moe, weights[:, FIRST:FIRST + held])
                     + _swiglu(y, moe["shared"])).reshape(x.shape)

            here = (jnp.arange(e) >= FIRST) & (jnp.arange(e) < FIRST + held)
            sel = held_to(s + bias).astype(jnp.float32)
            _NOTES["scores"].append(sel)
            near_tie |= _near_boundary(sel, rank, here, MARGIN)
            load = (chosen * w.reshape(-1, 1)).sum(0)
            _NOTES["load"] = jnp.stack([(load * here).sum(),
                                        (load * here).max(), load.sum()])
            if train:
                # per row: E/k x the experts' share of the row's live
                # assignments, times their mean normalised score
                n = jnp.maximum(w.sum(1), 1.0)[:, None]
                live_rows = w[..., None]
                frac = (chosen.reshape(rows, seq, e) * live_rows).sum(1) / (
                    n * TOP_K)
                share = ((s / s.sum(-1, keepdims=True)).reshape(rows, seq, e)
                         * live_rows).sum(1) / n
                aux += SEQ_BALANCE_WEIGHT * e * jnp.mean(
                    jnp.sum(frac * share, -1))
        _NOTES["near_tie"] = near_tie
        x = _rms(x, params["ln_final"]["scale"])
        return held_to(x @ params["lm_head"]["kernel"]).astype(
            jnp.float32), aux


def forward(variables, batch, dtype=None):
    return _forward(variables, batch, False, dtype)[0]


def loss(variables, batch):
    """The training loss: next-token cross-entropy over real targets that
    stay inside their document, plus the weighted balance term."""
    import jax
    import jax.numpy as jnp

    logits, aux = _forward(variables, batch, True)
    ids = jnp.asarray(batch["input_ids"], jnp.int32)
    w = (jnp.asarray(batch["attention_mask"])[:, 1:] > 0).astype(jnp.float32)
    if "segment_ids" in batch:
        seg = jnp.asarray(batch["segment_ids"])
        w = w * (seg[:, 1:] == seg[:, :-1])
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    nll = -jnp.take_along_axis(logp, ids[:, 1:, None], -1)[..., 0]
    return (nll * w).sum() / jnp.maximum(w.sum(), 1.0) + aux
