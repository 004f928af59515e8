"""Plain reference of SmallThinker-21BA3B's decoder layers as the program
runs them: float32 ``jax.numpy`` at the highest matmul precision, fed the
program's own parameter tree and the same share of the experts. Source:
``PowerInfer/SmallThinker-21BA3B-Instruct`` ``config.json`` for every size.
The published class is remote code that this container does not have, so the
equations are the catalog row's and its ``described_as``, as the issue of
PR 46 wrote them down; what the row's ``config`` cannot confirm is under
``assumed`` in ``benchmark/configs/smallthinker-21b-a3b-c4.json``. ``rms`` is
RMSNorm with a plain learned scale (eps 1e-6), no biases anywhere. With ``x``
the stream entering layer ``i``:

    r = x W_r                                  the router reads the layer's
                                               input, ahead of the norm and
                                               of attention; E = 64 logits
    h = rms(x) ;  q = h W_q  (N heads of d) ;  k = h W_k, v = h W_v  (G heads)
    where rope_layout[i] = 1 (i % 4 != 0): rope over the whole d, theta 1.5e6,
        q and k, and a query sees itself and the window - 1 keys before it
    where it is 0 (i % 4 == 0): no position term, the whole causal row
    o = softmax(mask(q k' / sqrt(d))) v,   query head n on key head n // (N/G)
    x' = x + concat(o) W_o
    u = rms(x') ;  the six largest of r ;  w = softmax over those six
    x'' = x' + sum_{chosen e held here} w_e down_e(relu(gate_e u) * up_e u)
    logits = rms(x_last) W_head

The parameters say which layer is which: this file reads the published
index from ``FIRST_LAYER`` and the period (``PERIOD``), and the window from
``WINDOW``. ``rope`` turns element i with element i + d/2, at ``position_ids``
where the batch has them. No sort of assignments, no grouped product, no
kernel, no repeated keys: every held expert is applied to every token, one at
a time, under the top-k mask (the logits at or above a token's k-th largest);
experts this rank does not hold add nothing, here as in the program
(``FIRST``, and the number of experts in the parameters). Attention is
computed a block of ``Q_BLOCK`` query rows at a time against every key under
the mask, a key/value head with its group of query heads in one product, so
that a row of 16,384 tokens fits. ``loss`` is the training loss (shifted
cross-entropy plus 0.001 x the load-balance term over live tokens, summed
over the layers), for ``jax.grad``.

``forward(variables, batch, dtype=jnp.bfloat16)`` is the same mathematics with
every tensor and product in bf16, and what the program states to be float32
(the router's logits and scores, the attention scores, the logits) rounded to
bf16 explicitly: the nearest precision below the configuration's, which the
comparison has to refuse (``TOLERANCE``).
"""

from __future__ import annotations

import numpy as np

EPS = 1e-6
THETA = 1500000.0
WINDOW = 4096  # sliding_window_size
PERIOD = 4  # layer i is full and position-free where i % PERIOD == 0
FIRST_LAYER = 0  # the published index of the first layer held here
TOP_K = 6  # moe_num_active_primary_experts
FIRST = 0  # the first expert held here: rank x (64 / ranks)
BALANCE_WEIGHT = 0.001
EVAL_ROWS = 1
Q_BLOCK = 512

# The program computes in bf16 (f32 router at the highest precision, f32
# softmax statistics, f32 norm statistics, f32 logits); the reference in f32.
# A token for which an expert held here is close to changing sides (chosen,
# and little above the best logit not chosen; or not chosen, and little below
# the least logit chosen) may have it on the other side in the program, whose
# router reads a bf16 stream, and its output then moves by a whole expert's
# contribution. The comparison is a maximum, so such tokens are left out, as
# the three sparse references before this one leave them out: those with a
# held expert within MARGIN of the boundary, in units of the spread (standard
# deviation) of a token's 64 router logits about their mean, in any layer.
# With 16 of 64 held, 6 a token and four layers that is 43-44% of a row's
# tokens at 0.03 and 69% at 0.06 (``live`` prints it). On the v5e at the
# published widths and 16,384 tokens (my chip run, PR 46): the program's
# worst token reads 0.56-0.82 at 0.03 over seven seeds and 0.39-0.69 at 0.06
# over ten (0.58 where the same seed reads 0.79 at 0.03); the wrong programs'
# readings hardly move with it (PERF.md section 6 has both). 0.06 leaves
# 5,100 tokens of 16,384 and the room a fresh seed needs under TOLERANCE.
MARGIN = 0.06

# Worst logit difference over the logits' spread on the tokens that stay,
# under ``perturb``, on the v5e at the published widths and one row of 16,384
# tokens (my chip run, PR 46; PERF.md section 6 has every reading). The
# program reads 0.39-0.69 at this margin over ten seeds (0.56-0.82 over seven
# at 0.03); its error is bf16's on sharp softmaxes (``QK_GAIN``) and on the
# held experts' sums (``DOWN_GAIN``), the logits' spread 0.90. The wrong
# programs at this margin over six seeds (at 0.03 over three, in brackets):
# the reference in bf16 (router, scores, logits rounded where they stand)
# 1.93-2.52 (2.29-2.60), SiLU for ReLU 2.29-2.63 (2.51-2.66), the W layers
# without their window 3.36-3.76 (3.48-3.91), a router fed ``ln_mlp``'s
# output 3.66-4.05 (3.85-4.24), a rotary turn in the full layer 5.07-5.94
# (5.13-5.75). TOLERANCE is 1.7 times the program's largest reading and 1.6
# times under the least reading of a wrong program.
TOLERANCE = 1.2

# ``perturb``: with every matrix at its initial 0.02 the mechanisms this model
# has would not show. The embedding is 0.02 a value, so layer 0's router (which
# reads the stream as it is, not normed) would see logits of 0.02 and every
# later layer's some 50 times that; the queries' and keys' scores have a
# spread of 1 over up to 16,384 keys, where a softmax is nearly a mean, its
# output nearly nothing, and a window, a rotary turn or a missing mask move
# the stream by less than bf16 does; an expert adds a tenth of what attention
# adds. So: the embedding times EMBED_GAIN (a stream of about 1 a value from
# the start), W_q and W_k each times QK_GAIN (scores of some QK_GAIN^2 units of
# spread: a query's weight lies on a few dozen keys, and which keys they are
# depends on the mask and on the positions), the router times ROUTER_GAIN (the
# six chosen logits a few units apart: the six-way softmax then weighs them
# unevenly), every expert's last matrix times DOWN_GAIN (the held experts add
# about what attention adds), and one random column added to all 64 of every
# router's (OFFSET times as large as its own: a token's 64 logits share an
# offset of tens of their spreads, which a float32 softmax and top-k do not
# see and a bf16 logit cannot carry: OLMoE's reference has the argument).
EMBED_GAIN = 50.0
QK_GAIN = 2.0
ROUTER_GAIN = 2.0
DOWN_GAIN = 4.0
OFFSET = 40.0

_NOTES: dict = {}  # forward() leaves near ties and load here for live()


def eval_batch(rows, config: dict) -> dict:
    """The first ``EVAL_ROWS`` rows as stored, and the configuration's
    constants and share."""
    global TOP_K, THETA, WINDOW, FIRST, FIRST_LAYER
    model = config["model"]
    TOP_K = int(model["moe_num_active_primary_experts"])
    THETA = float(model["rope_theta"])
    WINDOW = int(model["sliding_window_size"])
    FIRST_LAYER = int(model.get("first_layer", 0))
    rank = int(config["task"].get("expert_share", "0/1").split("/")[0])
    FIRST = rank * int(model["moe_num_primary_experts"])  # held here: a rank's
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
        if names[-2:] in (["query", "kernel"], ["key", "kernel"]):
            return QK_GAIN * leaf
        if names[-1] == "w_down":
            return DOWN_GAIN * leaf
        if names[-2:] == ["router", "kernel"]:
            return ROUTER_GAIN * (leaf + OFFSET * leaf.std() * jax.random.normal(
                key, leaf.shape[:1] + (1,), leaf.dtype))
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

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * w.astype(x.dtype)


def _rope(x, positions):
    """``x`` [B, S, N, D]: element i turns with element i + D / 2."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * freq
    cos = jnp.cos(angle)[..., None, :].astype(x.dtype)
    sin = jnp.sin(angle)[..., None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


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


def _grouped_attention(u, p, positions, allow_rows, rotary: bool,
                       held_to=lambda x: x):
    import jax.numpy as jnp

    q = jnp.einsum("bsh,hnd->bsnd", u, p["query"]["kernel"])
    k = jnp.einsum("bsh,hnd->bsnd", u, p["key"]["kernel"])
    v = jnp.einsum("bsh,hnd->bsnd", u, p["value"]["kernel"])
    if rotary:
        q, k = _rope(q, positions), _rope(k, positions)
    return jnp.einsum("bsnd,ndh->bsh", _attention(q, k, v, allow_rows,
                                                  held_to),
                      p["out"]["kernel"])


def _experts(y, moe, weights):
    """Σ_e weights[:, e] · down_e(relu(gate_e(y)) · up_e(y)) over the held
    experts: each on every token, one at a time (recomputed in the backward
    pass, so that ``jax.grad`` keeps one expert's activations)."""
    import jax
    import jax.numpy as jnp

    @jax.checkpoint
    def expert(ws):
        gate, up, down, w = ws
        return w[:, None] * ((jax.nn.relu(y @ gate) * (y @ up)) @ down)

    return jax.lax.scan(lambda acc, ws: (acc + expert(ws), None),
                        jnp.zeros_like(y), (moe["w_gate"], moe["w_up"],
                                            moe["w_down"], weights.T))[0]


def _route(logits, held_to=lambda x: x):
    """``(weights [T, E], the top-k mask)`` of router logits [T, E]: the k
    largest, and a softmax over those k alone."""
    import jax.numpy as jnp

    e = logits.shape[-1]
    # the k largest: at or above a token's k-th largest logit
    kth = jnp.sort(logits, -1)[:, e - TOP_K][:, None]
    chosen = logits >= kth
    top = jnp.where(chosen, logits, -jnp.inf)
    top = jnp.exp(top - top.max(-1, keepdims=True))
    return held_to(top / top.sum(-1, keepdims=True)), chosen


def _sparse_block(u, logits, moe, held_to=lambda x: x):
    """The expert layer on normed tokens ``u`` [T, H] under router logits
    [T, E]: ``(y, the top-k mask)``, the held experts' part of the sum."""
    held = moe["w_gate"].shape[0]
    weights, chosen = _route(logits, held_to)
    return _experts(u, moe, weights[:, FIRST:FIRST + held].astype(u.dtype)), \
        chosen


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

    def allow_rows(window):
        def allow(start, n):
            at = (start + jnp.arange(n))[:, None]
            key = jnp.arange(seq)[None, :]
            band = key <= at
            if window:
                band &= at - key < window
            allow = real[:, None, :] & band
            if seg is not None:
                mine = jax.lax.dynamic_slice_in_dim(seg, start, n, 1)
                allow &= mine[:, :, None] == seg[:, None, :]
            return allow
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
        for i in range(layers):
            p = params[f"layer_{i}"]
            moe = p["moe"]
            full = (FIRST_LAYER + i) % PERIOD == 0
            # the router reads the layer's input, as it is
            logits = held_to(x.reshape(rows * seq, -1) @ p["router"]["kernel"])
            u = _rms(x, p["ln_attn"]["scale"])
            x = x + _grouped_attention(
                u, p["attn"], positions, allow_rows(0 if full else WINDOW),
                rotary=not full, held_to=held_to)

            u = _rms(x, p["ln_mlp"]["scale"]).reshape(rows * seq, -1)
            y, chosen = _sparse_block(u, logits, moe, held_to)
            x = x + y.reshape(x.shape)
            e, held = logits.shape[-1], moe["w_gate"].shape[0]

            here = (jnp.arange(e) >= FIRST) & (jnp.arange(e) < FIRST + held)
            logits32 = logits.astype(jnp.float32)
            near_tie |= _near_boundary(logits32, chosen, here, MARGIN)
            load = (chosen * w).sum(0)
            _NOTES["load"] = jnp.stack([(load * here).sum(),
                                        (load * here).max(), load.sum()])
            # E x sum_e (share of the live assignments) x (mean score)
            probs = jax.nn.softmax(logits32, -1)
            aux += e * jnp.sum(load / (n_live * TOP_K)
                               * (probs * w).sum(0) / n_live)
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
