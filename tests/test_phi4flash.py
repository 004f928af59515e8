"""Phi-4-mini-flash-reasoning's layers (SambaY) at test size: a Mamba-1 mixer
and its selective scan, a gated memory unit, window and full differential
attention over grouped heads, and cross-decoder layers that read what two
earlier layers hand on (``models/transformer.py``, ``ops/scan.py``,
``ops/flash.py``), against the plain float32 reference the benchmark ships
(``benchmark/reference/phi4-mini-flash-c4.py``).

Everything runs the ``phi4_mini_flash_tiny`` preset (8 layers: M S M S M* F*
G X; hidden 64, 8 query heads over 4 key heads of 8, window 16, inner 128
with 8 states) on the CPU, float32 against float32 unless said. The Pallas
kernels run in TPU interpret mode, every such call inside one jitted program
that is waited for (``tests/test_olmoe.py`` tells why).
"""

from __future__ import annotations

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import register_preset

from lance_distributed_training_tpu.models import get_task
from lance_distributed_training_tpu.models import transformer
from lance_distributed_training_tpu.ops import conv, flash, scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_TOL = 2e-4  # summation order only (measured 1e-7 to 3e-6)
VOCAB, SEQ, KERNEL_SEQ = 512, 48, 128
KINDS = ("M", "S", "M", "S", "M*", "F*", "G", "X")
GROUPS = ("in_proj", "x_proj", "dt_proj", "dt_bias", "A_log", "D",
          "conv_kernel", "conv_bias", "out_proj", "query", "key", "value",
          "out", "lambda", "sub_norm", "mlp", "norms", "tok_embed")


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "phi4_reference", os.path.join(
            ROOT, "benchmark", "reference", "phi4-mini-flash-c4.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.WINDOW, module.FIRST_LAYER, module.KINDS = 16, 0, KINDS
    return module


def _register(name, **kwargs):
    return register_preset(name, "phi4_mini_flash_tiny", **kwargs)


@pytest.fixture(scope="module")
def f32_task():
    _register("phi4_tiny_f32", dtype=jnp.float32)
    try:
        yield get_task("causal_lm", model_name="phi4_tiny_f32", seq_len=SEQ)
    finally:
        del transformer.CAUSAL_LMS["phi4_tiny_f32"]


@pytest.fixture(scope="module")
def variables(ref, f32_task):
    return ref.perturb(f32_task.init_variables(jax.random.key(33)),
                       jax.random.key(34))


def _batch(seq, rows=2, seed=5):
    ids = np.random.default_rng(seed).integers(2, VOCAB, (rows, seq))
    mask = np.ones((rows, seq), np.int8)
    mask[-1, seq - 5:] = 0
    return {"input_ids": ids.astype(np.int32), "attention_mask": mask}


@pytest.fixture(scope="module")
def batch():
    return _batch(SEQ)


def _groups(tree) -> dict:
    out: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        keys = [k.key for k in path if hasattr(k, "key")]
        if keys[-1].startswith("lambda_"):
            name = "lambda"
        elif keys[-2] in ("ln_attn", "ln_mlp", "ln_final"):
            name = "norms"
        else:
            name = next(k for k in GROUPS if k in keys)
        out.setdefault(name, []).append(jnp.ravel(leaf))
    return {k: jnp.concatenate(v) for k, v in out.items()}


def _relative(got, want) -> float:
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _one_program(fn, *args):
    return jax.block_until_ready(jax.jit(fn)(*args))


def _program_loss(task, batch):
    def loss(v):
        outputs, _ = task.forward(v, batch, True, None)
        return task.loss(outputs, batch)

    return loss


# -- (a) the stack whole, float32 against float32 -----------------------------


def _everything(forward_logits, loss_fn):
    """Eval logits, loss (with what else it returns) and gradients as one
    program: one compile a side."""
    def program(v):
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(v)
        return forward_logits(v), loss, aux, _groups(g["params"])

    return program


@pytest.fixture(scope="module")
def whole(ref, f32_task, variables, batch):
    def program_loss(v):
        outputs, state = f32_task.forward(v, batch, True, None)
        assert state is None
        return f32_task.loss(outputs, batch), f32_task.stats(outputs)

    got = _one_program(_everything(
        lambda v: f32_task.forward(v, batch, False, None)[0][0],
        program_loss), variables)
    want = _one_program(_everything(
        lambda v: ref.forward(v, batch),
        lambda v: (ref.loss(v, batch), None)), variables)
    return got, want


def test_logits_match_reference(ref, whole, batch):
    (got, *_), (want, *_) = whole
    live = ref.live(batch, want)[..., None]
    n = live.sum() * want.shape[-1]
    mean = jnp.where(live, want, 0).sum() / n
    spread = jnp.sqrt(jnp.where(live, (want - mean) ** 2, 0).sum() / n)
    # the benchmark's statistic (``benchmark/run.py`` ``check_model``)
    assert float(jnp.where(live, jnp.abs(got - want), 0).max()
                 / spread) < F32_TOL


def test_loss_matches_reference_and_the_step_reports_its_mixers(whole):
    (_, got, stats, _), (_, want, _, _) = whole
    assert abs(float(got) - float(want)) < F32_TOL * float(want)
    assert float(stats["ssm_scan_fused"]) == 0.0
    assert float(stats["conv_fused"]) == 0.0  # the CPU: the plain form
    assert float(stats["ssm_state_abs_max"]) > 0
    # four attention layers, depths 1, 3, 5, 7: lambda near lambda_init
    assert 0.2 < float(stats["diff_lambda_min"]) < float(
        stats["diff_lambda_max"]) < 0.9


@pytest.mark.parametrize("group", GROUPS)
def test_gradient_matches_reference(group, whole):
    got, want = whole[0][3], whole[1][3]
    assert float(jnp.linalg.norm(want[group])) > 0
    assert _relative(got[group], want[group]) < F32_TOL


# -- (a) each kind of layer alone ----------------------------------------------


@pytest.mark.parametrize("kind", ["M", "S", "M*", "F*", "G", "X"])
def test_each_kind_of_layer_alone_matches_reference(ref, kind):
    """One ``DecoderBlock`` of each kind at a published depth of its own,
    fed a random stream and random handed-on tensors."""
    depth, b, s, h = 11, 2, 40, 64
    keys = jax.random.split(jax.random.key(7), 5)
    x = jax.random.normal(keys[0], (b, s, h))
    memory = jax.random.normal(keys[1], (b, s, 128))
    k = jax.random.normal(keys[2], (b, 4, s, 8))
    v = jax.random.normal(keys[3], (b, 2, s, 16))
    block = transformer.DecoderBlock(
        8, 0, 0, 0, dtype=jnp.float32, dense_dim=128, kind=kind, depth=depth,
        parts=transformer.phi4_mini_flash_tiny.keywords["parts"],
        layer_norm=True)
    handed = (memory, (k, v), None)  # no router state: no layer here has one
    params = ref.perturb(block.init(keys[4], x, handed=handed),
                         jax.random.key(8))["params"]
    (got, (got_m, got_kv, _)), _ = _one_program(lambda p: block.apply(
        {"params": p}, x, handed=handed, mutable=["mixer_stats"]), params)

    def reference(p):  # it keeps keys and values [B, S, heads, d]
        with jax.default_matmul_precision("highest"):
            return ref.layer(
                kind, p, x, (memory, tuple(t.transpose(0, 2, 1, 3)
                                           for t in (k, v))),
                depth, ref.causal_mask(jnp.ones((b, s), bool)))

    want, (want_m, want_kv) = _one_program(reference, params)
    assert _relative(got, want) < F32_TOL
    assert _relative(got_m, want_m) < F32_TOL
    for g, w in zip(got_kv, want_kv):
        assert _relative(g, w.transpose(0, 2, 1, 3)) < F32_TOL
    if kind == "M*":
        assert _relative(got_m, memory) > 0.1  # its own scan output
    if kind == "F*":
        assert got_kv[0].shape == k.shape and _relative(got_kv[0], k) > 0.1


# -- (b) the scan: kernel, plain chunked form, token by token ------------------


def _loop(x, dt, a, b, c):
    def row(x, dt, b, c):
        def token(h, parts):
            x_t, dt_t, b_t, c_t = parts
            h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t
            return h, h @ c_t

        h, y = jax.lax.scan(token, jnp.zeros(a.shape), (x, dt, b, c))
        return y, h

    return jax.vmap(row)(x, dt, b, c)


SCANS = {  # rows, tokens, channels, states, chunk, long memory
    "one chunk": (2, 16, 128, 8, 16, False),
    "several chunks": (2, 64, 256, 16, 16, False),
    "a carried state that is not small": (1, 96, 128, 8, 32, True),
}


def _scan_inputs(rows, seq, width, states, long_memory):
    k = jax.random.split(jax.random.key(seq), 6)
    x = jax.random.normal(k[0], (rows, seq, width))
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, seq, width))
                         - (3.0 if long_memory else 1.0))
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (width, states))
                 - (4.0 if long_memory else 0.0))
    b = jax.random.normal(k[3], (rows, seq, states))
    c = jax.random.normal(k[4], (rows, seq, states))
    weight = jax.random.normal(k[5], (rows, seq, width))
    return (x, dt, a, b, c), weight


@pytest.fixture(scope="module", params=list(SCANS))
def scans(request):
    """``(forward, gradients)`` of the three forms on one case."""
    from jax.experimental.pallas import tpu as pltpu

    rows, seq, width, states, chunk, long_memory = SCANS[request.param]
    args, weight = _scan_inputs(rows, seq, width, states, long_memory)

    def both(fn):
        def loss(*args):
            y, last = fn(*args)
            return (y * weight).sum(), (y, last)

        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)

    out = {"loop": _one_program(both(_loop), *args),
           "chunked": _one_program(both(
               lambda *a: scan.scan_chunked(*a, chunk=chunk)), *args)}
    with pltpu.force_tpu_interpret_mode():
        out["kernel"] = _one_program(both(lambda *a: scan.scan_kernel(
            *a, chunk=chunk, block_d=128)), *args)
    return request.param, out


@pytest.mark.parametrize("form", ["chunked", "kernel"])
def test_scan_forward_equals_the_token_loop(scans, form):
    case, out = scans
    (_, (y, last)), _ = out[form]
    (_, (want_y, want_last)), _ = out["loop"]
    assert _relative(y, want_y) < F32_TOL
    assert _relative(last, want_last) < F32_TOL
    if "not small" in case:  # the state at the row's end holds many tokens
        assert float(jnp.abs(want_last).max()) > 3.0


@pytest.mark.parametrize("form", ["chunked", "kernel"])
def test_scan_gradients_equal_the_token_loop(scans, form):
    _, out = scans
    for got, want in zip(out[form][1], out["loop"][1]):
        assert _relative(got, want) < F32_TOL


def test_scan_refuses_what_it_cannot_tile():
    args, _ = _scan_inputs(1, 24, 128, 8, False)
    with pytest.raises(ValueError, match="whole chunks"):
        scan.scan_chunked(*args, chunk=16)
    args, _ = _scan_inputs(1, 16, 96, 8, False)
    with pytest.raises(ValueError, match="whole groups of 128"):
        scan.scan_kernel(*args, chunk=16)


def test_the_scan_rule(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    assert scan.scan_fused_applies(8192, 5120, 16, platform="tpu")
    assert not scan.scan_fused_applies(8192, 5120, 16, platform="cpu")
    assert not scan.scan_fused_applies(8192 + 64, 5120, 16, platform="tpu")
    assert not scan.scan_fused_applies(8192, 5120 + 64, 16, platform="tpu")
    assert not scan.scan_fused_applies(8192, 5120, 4, platform="tpu")
    assert not scan.scan_fused_applies(8192, 5120, 16)  # here: the CPU
    monkeypatch.setattr(jax, "device_count", lambda *a: 4)  # no mesh met yet
    assert not scan.scan_fused_applies(8192, 5120, 16, platform="tpu")


# -- the whole stack with both kernels, as the chip runs it --------------------


@pytest.fixture(scope="module")
def kernel_run(ref, variables):
    """Logits, loss and gradients of published layers 2 to 7 of the tiny
    stack (M, S, M*, F*, G, X) with the scan's and the convolution's kernels
    and ``unequal_attention``
    bound as the rules bind them on a TPU (queries and keys of 8, values of
    16, a window of 16 in a row of 128), in interpret mode, one program; and
    the reference's on the same batch."""
    from jax.experimental.pallas import tpu as pltpu

    batch = _batch(KERNEL_SEQ, rows=1, seed=9)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        attention = flash.make_flash_attention(causal=True)
    _register("phi4_tiny_f32_kernel", dtype=jnp.float32)
    try:
        task = get_task("causal_lm", model_name="phi4_tiny_f32_kernel",
                        seq_len=KERNEL_SEQ, attention_fn=attention,
                        layer_span="2:8")
    finally:
        del transformer.CAUSAL_LMS["phi4_tiny_f32_kernel"]
    held = variables["params"]  # published layer i + 2 is held as layer_i
    v = {"params": {
        **{k: p for k, p in held.items() if not k.startswith("layer_")},
        **{f"layer_{i}": held[f"layer_{i + 2}"] for i in range(6)}}}
    program = _everything(
        lambda v: task.forward(v, batch, False, None)[0][0],
        lambda v: (_program_loss(task, batch)(v), None))
    with pytest.MonkeyPatch.context() as patch, \
            pltpu.force_tpu_interpret_mode():
        patch.setattr(scan, "scan_fused_applies", lambda *a, **k: True)
        patch.setattr(conv, "conv_fused_applies", lambda *a, **k: True)
        traced = jax.jit(program).trace(v)
        got = jax.block_until_ready(traced.lower().compile()(v))
    old = ref.FIRST_LAYER, ref.KINDS
    ref.FIRST_LAYER, ref.KINDS = 2, KINDS[2:]
    try:
        want = _one_program(_everything(
            lambda v: ref.forward(v, batch),
            lambda v: (ref.loss(v, batch), None)), v)
    finally:
        ref.FIRST_LAYER, ref.KINDS = old
    return str(traced.jaxpr), got, want, batch["attention_mask"][..., None]


def test_with_both_kernels_logits_and_loss_match_reference(kernel_run):
    text, (logits, loss, *_), (want_logits, want_loss, *_), live = kernel_run
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text
    assert "causal_conv_silu_fwd" in text and "causal_conv_silu_bwd" in text
    # a dead slot means nothing: the kernel lets it see the dead keys only
    assert _relative(logits * live, want_logits * live) < F32_TOL
    assert abs(float(loss) - float(want_loss)) < F32_TOL * float(want_loss)


@pytest.mark.parametrize("group", GROUPS)
def test_with_both_kernels_gradient_matches_reference(group, kernel_run):
    _, got, want, _ = kernel_run
    assert _relative(got[3][group], want[3][group]) < 5 * F32_TOL


# -- (c) what the layers mean ----------------------------------------------------


def _logits(task, variables, batch):
    return _one_program(
        lambda v, b: task.forward(v, b, False, None)[0][0], variables, batch)


def _span_task(span, **kwargs):
    return get_task("causal_lm", model_name="phi4_tiny_f32_span",
                    seq_len=SEQ, layer_span=span, **kwargs)


@pytest.fixture()
def f32_preset():
    _register("phi4_tiny_f32_span", dtype=jnp.float32)
    yield
    del transformer.CAUSAL_LMS["phi4_tiny_f32_span"]


def test_window_layer_sees_the_window_and_no_further(f32_preset, ref):
    """Published layer 1 alone (an S layer, window 16): the output at t
    moves with the token 15 back and not with one 16 or more back."""
    task = _span_task("1:2")
    v = task.init_variables(jax.random.key(1))
    batch = _batch(SEQ, rows=1)
    batch["attention_mask"][:] = 1
    t = 40
    base = _logits(task, v, batch)[0, t]
    for back, moves in ((15, True), (16, False), (30, False)):
        other = {**batch, "input_ids": batch["input_ids"].copy()}
        other["input_ids"][0, t - back] = (
            other["input_ids"][0, t - back] + 7) % VOCAB
        delta = float(jnp.abs(_logits(task, v, other)[0, t] - base).max())
        assert (delta > 1e-6) == moves, (back, delta)


def test_window_of_512_is_the_band_and_grouped_heads_repeat_in_order():
    """The dense path of the bound attention function at the published
    window: query 600 sees keys 89 to 600 and no other; key head g serves
    the query heads 2g and 2g + 1."""
    attention = flash.make_flash_attention(causal=True, forced=False)
    seq = 640
    q = jnp.zeros((1, 4, seq, 8))
    k = jnp.zeros((1, 2, seq, 8))
    v = jnp.broadcast_to(jnp.eye(seq)[None, None], (1, 2, seq, seq)) \
        * jnp.asarray([1.0, 2.0])[None, :, None, None]
    out = attention(q, k, v, window=512)  # uniform weights: who is seen
    seen = np.asarray(out[0, 0, 600] > 0)
    assert seen[89:601].all() and not seen[:89].any() and not seen[601:].any()
    np.testing.assert_allclose(out[0, 0, 600, 89:601], 1 / 512, rtol=1e-5)
    # heads 0, 1 read value head 0; heads 2, 3 value head 1 (twice as large)
    np.testing.assert_allclose(out[0, 1], out[0, 0])
    np.testing.assert_allclose(out[0, 2], 2 * out[0, 0])
    np.testing.assert_allclose(out[0, 3], 2 * out[0, 0])


def test_full_attentions_keys_take_gradient_from_the_cross_layer(f32_preset):
    """Layers 4 to 7 (M*, F*, G, X) with F*'s own output projection at zero:
    what F*'s keys and values still receive comes back from X alone."""
    task = _span_task("4:8")
    v = task.init_variables(jax.random.key(2))
    attn = v["params"]["layer_1"]["attn"]
    attn["out"]["kernel"] = jnp.zeros_like(attn["out"]["kernel"])
    assert "key" not in v["params"]["layer_3"]["attn"]  # X has none
    g = _one_program(jax.grad(_program_loss(task, _batch(SEQ))), v)["params"]
    for name in ("key", "value"):
        assert float(jnp.abs(g["layer_1"]["attn"][name]["kernel"]).max()) > 0
    assert float(jnp.abs(g["layer_1"]["attn"]["query"]["kernel"]).max()) == 0


def test_memory_unit_follows_the_scan_output_handed_on(f32_preset):
    """Layers 4 to 6 (M*, F*, G) with M*'s own output projection at zero:
    M*'s skip ``D`` reaches the logits through G's reading of ``m`` alone."""
    task = _span_task("4:7")
    v = task.init_variables(jax.random.key(3))
    ssm = v["params"]["layer_0"]["ssm"]
    ssm["out_proj"]["kernel"] = jnp.zeros_like(ssm["out_proj"]["kernel"])
    batch = _batch(SEQ)
    base = _logits(task, v, batch)
    ssm["D"] = ssm["D"] + 1.0
    assert float(jnp.abs(_logits(task, v, batch) - base).max()) > 1e-4
    assert "ssm" not in v["params"]["layer_2"]  # G scans nothing


def test_lambda_init_reads_the_published_index(f32_preset):
    """Layers 0:2 and 2:4 are both (M, S) and take the same parameters; the
    S layer's lambda_init is 0.8 - 0.6 exp(-0.3 l) at l = 1 and l = 3."""
    early, late = _span_task("0:2"), _span_task("2:4")
    v = early.init_variables(jax.random.key(4))
    batch = _batch(SEQ)
    assert float(jnp.abs(_logits(early, v, batch)
                         - _logits(late, v, batch)).max()) > 1e-4
    p = v["params"]["layer_1"]["attn"]
    for task, depth in ((early, 1), (late, 3)):
        stats = task.stats(jax.jit(
            lambda v: task.forward(v, batch, True, None))(v)[0])
        want = (math.exp(float(p["lambda_q1"] @ p["lambda_k1"]))
                - math.exp(float(p["lambda_q2"] @ p["lambda_k2"]))
                + 0.8 - 0.6 * math.exp(-0.3 * depth))
        assert float(stats["diff_lambda_min"]) == pytest.approx(want, 1e-5)


# -- (d) differential attention is two dense softmaxes -------------------------


def test_differential_attention_equals_two_dense_softmaxes_at_40_over_20():
    """40 query heads over 20 key heads and 10 double-width values, written
    out head by head in numpy: head i of 20 subtracts the softmax of query
    2i+1 over key 2j+1 from that of query 2i over key 2j, j = i // 2."""
    b, s, d, depth = 1, 24, 4, 17
    h = 40 * d
    module = transformer.DifferentialAttention(40, 20, depth,
                                               dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (b, s, h))
    params = module.init(jax.random.key(1), x)["params"]
    (got, (k, v)), _ = module.apply({"params": params}, x,
                                    mutable=["mixer_stats"])
    p = jax.tree.map(lambda t: np.asarray(t, np.float64), params)
    xs = np.asarray(x, np.float64)[0]
    q = np.einsum("sh,hnd->nsd", xs, p["query"]["kernel"])
    keys = np.einsum("sh,hnd->nsd", xs, p["key"]["kernel"])
    values = np.einsum("sh,hnd->nsd", xs, p["value"]["kernel"])
    np.testing.assert_allclose(k[0], keys, atol=1e-4)
    assert v.shape == (1, 10, s, 2 * d)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = (math.exp(p["lambda_q1"] @ p["lambda_k1"])
           - math.exp(p["lambda_q2"] @ p["lambda_k2"]) + lam_init)
    causal = np.tril(np.ones((s, s), bool))

    def softmax(scores):
        scores = np.where(causal, scores / math.sqrt(d), -np.inf)
        e = np.exp(scores - scores.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    heads = []
    for i in range(20):
        j = i // 2
        o = (softmax(q[2 * i] @ keys[2 * j].T)
             - lam * softmax(q[2 * i + 1] @ keys[2 * j + 1].T)) @ values[j]
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5)
        heads.append((1 - lam_init) * o * p["sub_norm"]["scale"])
    want = np.concatenate(heads, -1) @ p["out"]["kernel"]
    np.testing.assert_allclose(got[0], want, atol=2e-4)


# -- (e) the span ----------------------------------------------------------------


def test_the_published_layout_and_the_cells_span():
    kinds = transformer.sambay_layers(32)
    assert [kinds.count(k) for k in ("M", "M*", "S", "F*", "G", "X")] == [
        8, 1, 8, 1, 7, 7]
    assert kinds[16] == "M*" and kinds[17] == "F*"
    model = transformer.phi4_mini_flash(vocab_size=25008, first_layer=14,
                                        num_layers=6)
    assert model.held_kinds == ("M", "S", "M*", "F*", "G", "X")
    asked = []
    attention = flash.make_flash_attention(causal=True, forced=False)
    attention.fused = lambda *shape: asked.append(shape) or False
    assert set(model.clone(attention_fn=attention).kernels(8192)) == {
        "attention", "scan", "conv"}
    assert set(asked) == {(8192, 64, 128)}  # S, F* and X alike
    # a span of state-space layers and memory units has no attention to ask
    assert set(transformer.phi4_mini_flash(
        vocab_size=8, first_layer=16, num_layers=1).kernels(8192)) == {
        "scan", "conv"}


def test_the_cells_share_counts_697_million_parameters():
    task = get_task("causal_lm", model_name="phi4_mini_flash", seq_len=128,
                    vocab_size=25008, layer_span="14:20")
    shapes = jax.eval_shape(task.init_variables, jax.random.key(0))["params"]
    assert sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(shapes)) == 697_073_792
    assert shapes["layer_0"]["ssm"]["A_log"].shape == (5120, 16)
    assert shapes["layer_0"]["ssm"]["dt_proj"]["kernel"].shape == (160, 5120)
    assert shapes["layer_1"]["attn"]["key"]["kernel"].shape == (2560, 20, 64)
    assert shapes["layer_5"]["attn"]["query"]["kernel"].shape == (
        2560, 40, 64)
    assert "lm_head" not in shapes  # tied


@pytest.mark.parametrize("span,message", [
    ("18:20", "layer 18 is a G layer and reads what the M\\* layer"),
    ("17:20", "layer 18 is a G layer"),
    ("19:20", "layer 19 is a X layer and reads what the F\\* layer"),
    ("14:40", "not inside the preset's 32 layers"),
    ("14", "layer_span is 'first:end'"),
])
def test_a_span_that_cannot_run_is_refused_by_name(span, message):
    with pytest.raises(ValueError, match=message):
        get_task("causal_lm", model_name="phi4_mini_flash", seq_len=128,
                 layer_span=span)


def test_a_span_is_for_presets_whose_layers_differ():
    with pytest.raises(ValueError, match="layers of several kinds"):
        get_task("causal_lm", model_name="olmoe_tiny", seq_len=64,
                 layer_span="0:1")
    with pytest.raises(ValueError, match="both state the depth"):
        get_task("causal_lm", model_name="phi4_mini_flash_tiny", seq_len=64,
                 layer_span="0:2", num_layers=2)
    with pytest.raises(ValueError, match="layer_span applies to"):
        get_task("masked_lm", model_name="bert_small", layer_span="0:2")
    with pytest.raises(ValueError, match="expert_share states which"):
        get_task("causal_lm", model_name="phi4_mini_flash_tiny", seq_len=64,
                 expert_share="0/2")


# -- the benchmark's guard of the stated precision -----------------------------


def test_a_bf16_state_drifts_under_the_long_memory_perturb_sets(
        ref, f32_task, variables):
    """The reference with its scan state, dt and exponent rounded to bf16
    after every token reads further from itself in float32 with ``perturb``'s
    long memory than without (at this size by a little; on the chip at
    8,192 tokens by what PERF.md section 6 reports)."""
    batch = _batch(KERNEL_SEQ, rows=1, seed=3)
    batch["attention_mask"][:] = 1

    @jax.jit
    def error(v):
        want = ref.forward(v, batch)
        got = ref.forward(v, batch, dtype=jnp.bfloat16)
        return jnp.abs(got - want).max() / jnp.std(want)

    plain = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + ref.LONG_MEMORY
        if path[-1].key == "A_log" else leaf, variables)
    assert error(variables) > 0.01
    assert np.isfinite(error(plain))
