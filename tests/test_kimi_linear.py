"""The linear-attention expert decoder (``models/kimi_linear.py``: Kimi Delta
Attention in chunks, NoPE latent attention with values narrower than keys
over ``packed_rows``, the routed layer and the routing state of
``parallel/moe.py``) against the plain reference of the
``kimi_linear_48b_a3b`` configuration, at ``Config.tiny()`` in float32 on the
CPU.

Tolerances: both sides compute in float32 with products at the highest
precision, so they differ only by the order of their sums and by what an
``exp`` of a difference rounds to against a product of ``exp``s (the
program's chunked recurrence, triangular solve, sorted grouped products,
running softmax and padded shifts against the reference's token-by-token
state, masked dense experts, whole softmax and rolled shifts): 2e-5 relative
to the largest entry covers a few hundred float32 additions in another order
and the solve's 64 rows, is 1,000 times tighter than a forgotten boundary,
decay, gate or norm would need, and bfloat16 activations miss it by two
orders of magnitude (a test below shows they do).
"""

import dataclasses
import gc

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from benchmark.configs.kimi_linear_48b_a3b import program, reference
from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.models import (kda_pallas, kernels, kimi_linear,
                                          mla_moe, packed_decoder,
                                          packed_rows)
from tensorflowonspark_tpu.parallel import moe

BIG_SEED = 2 ** 31 + 4321           # the driver's seeds pass 32 signed bits
TOL = 2e-5


def _tiny_dict(config: kimi_linear.Config, learning_rate=1e-3) -> dict:
    """``Config.tiny()`` under the keys the configuration's file has."""
    return {
        "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "moe_intermediate_size": config.moe_intermediate_size,
        "num_hidden_layers": config.num_hidden_layers,
        "first_k_dense_replace": config.first_k_dense_replace,
        "linear_attn_config": {
            "kda_layers": list(config.kda_layers),
            "full_attn_layers": list(config.full_attn_layers),
            "num_heads": config.kda_num_heads,
            "head_dim": config.kda_head_dim,
            "short_conv_kernel_size": config.short_conv_kernel_size},
        "num_experts": len(config.experts_held),
        "experts_held": list(config.experts_held),
        "published": {"num_experts": config.num_experts,
                      "num_hidden_layers": config.num_hidden_layers},
        "num_shared_experts": config.num_shared_experts,
        "num_experts_per_token": config.num_experts_per_token,
        "routed_scaling_factor": config.routed_scaling_factor,
        "moe_renormalize": config.moe_renormalize,
        "moe_router_activation_func": "sigmoid", "moe_layer_freq": 1,
        "num_expert_group": 1, "topk_group": 1,
        "num_attention_heads": config.num_attention_heads,
        "q_lora_rank": None, "mla_use_nope": True,
        "kv_lora_rank": config.kv_lora_rank,
        "qk_nope_head_dim": config.qk_nope_head_dim,
        "qk_rope_head_dim": config.qk_rope_head_dim,
        "v_head_dim": config.v_head_dim,
        "rms_norm_eps": config.rms_norm_eps, "vocab_size": config.vocab_size,
        "num_nextn_predict_layers": 0, "tie_word_embeddings": False,
        "bias_update_speed": config.bias_update_speed,
        "init_std": config.init_std, "dtype": config.dtype,
        "seq_len": config.seq_len, "kda_chunk": config.kda_chunk,
        "parameters": kimi_linear.parameter_count(config),
        "program_model": "kimi_linear",
        "optimizer": dict(kimi_linear.ADAMW, name="adamw",
                          learning_rate=learning_rate),
    }


def _rows(config: kimi_linear.Config, n: int, seed: int) -> dict:
    """Packed rows of three documents: the first ends inside a chunk, the
    second on a chunk's edge, the third at the row's end."""
    rng = np.random.default_rng(seed)
    t, chunk = config.seq_len, config.kda_chunk
    seg = np.stack([np.searchsorted(
        [int(rng.integers(1, chunk)), chunk * int(rng.integers(2, t // chunk))],
        np.arange(t), side="right") for _ in range(n)]).astype(np.int32)
    return {"tokens": rng.integers(0, config.vocab_size, (n, t), np.int32),
            "segment_ids": seg}


def _close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


@pytest.fixture(scope="module")
def tiny():
    config = kimi_linear.Config.tiny()
    ref_config = _tiny_dict(config)
    weights = reference.make_weights(ref_config, BIG_SEED)
    params = {program.program_name(k): v for k, v in weights.items()}
    return config, ref_config, weights, params


@pytest.fixture(scope="module", autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _bias(config, seed=0, spread=0.05):
    """Correction biases that move some choices."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(spread * rng.standard_normal(
        (config.expert_layers, config.num_experts)), jnp.float32)


# ---------------------------------------------------------------------------
# the chunked recurrence against the recurrence
# ---------------------------------------------------------------------------


def _value_and_gradients(fn, w, n):
    """``((sum(fn * w), fn's value), gradients of the sum by the first n
    arguments)`` in one compiled call."""
    def weighed(*a):
        out = fn(*a)
        return jnp.sum(out * w), out

    return jax.jit(jax.value_and_grad(weighed, argnums=tuple(range(n)),
                                      has_aux=True))


def _scan_inputs(t, heads, dk, dv, decay, seed):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    arrays = (unit(rng.standard_normal((t, heads, dk))) / np.sqrt(dk),
              unit(rng.standard_normal((t, heads, dk))),
              rng.standard_normal((t, heads, dv)),
              -decay * rng.uniform(0.5, 1.0, (t, heads, dk)),
              rng.uniform(0.05, 0.95, (t, heads)))
    return [jnp.asarray(a, jnp.float32) for a in arrays]


def _segments(t, cuts):
    return jnp.asarray(np.searchsorted(np.asarray(cuts, int), np.arange(t),
                                       side="right"), jnp.int32)


@pytest.mark.parametrize("t,chunk,cuts,decay", [
    (128, 64, (), 0.1),                 # one document over two chunks
    (128, 64, (37, 64, 100), 0.1),      # ends inside a chunk, on an edge
    (128, 64, (37, 64, 100), 2.5),      # the same at g of -1.25 to -2.5
    (192, 64, (5, 191), 2.0),           # a chunk whole inside a document
    (40, 8, (5, 16, 33), 0.5),          # the tiny chunk, sub-blocks of 2
    (30, 8, (5, 16), 0.5),              # a row that is no whole chunks
    (160, 8, (5, 80, 121), 0.5),        # five groups of four chunks
    (24, 6, (7,), 0.5),                 # a chunk with no quarter
])
def test_kda_chunked_scan_is_the_recurrence_values_and_gradients(
        t, chunk, cuts, decay):
    """``kimi_linear.kda_scan`` against the reference's token-by-token
    ``delta_rule``: the outputs and the gradients of all five operands.  At
    a decay of 2.5 ``g`` is -1.25 to -2.5 a token and its running sum passes
    -100 inside a 64-token chunk: ``exp(-G)`` is no float32 there (the last
    is ``exp(88.7)``), the differences the program takes are."""
    q, k, v, g, beta = args = _scan_inputs(t, 2, 8, 6, decay, seed=t + chunk)
    seg = _segments(t, cuts)
    if decay > 2:
        assert float(np.cumsum(np.asarray(g)[:64], 0).min()) < -100
    w = jnp.asarray(np.random.default_rng(1).standard_normal((t, 2, 6)),
                    jnp.float32)

    def mine(*a):
        return kimi_linear.kda_scan(*a, seg, chunk, jnp.float32)

    def theirs(*a):
        return reference.delta_rule(*a, seg)

    (_, out), got = _value_and_gradients(mine, w, 5)(*args)
    (_, want_out), want = _value_and_gradients(theirs, w, 5)(*args)
    _close(out, want_out)
    for a, b in zip(got, want):
        assert float(jnp.abs(b).max()) > 0
        _close(a, b)


@pytest.mark.parametrize("fused", [False, True])
def test_kda_scan_stays_finite_at_any_decay(fused, monkeypatch):
    """Past ``EXPONENT_CAP`` inside one sub-block (15 tokens at -6 and
    more) the program forgets what the recurrence would still remember by
    ``exp(-80)``: the outputs stay finite and a token's own write is
    exact, as ``jnp`` code and on the kernels (the interpreter here)."""
    heads, dk, dv = (4, 128, 128) if fused else (2, 8, 6)
    q, k, v, g, beta = _scan_inputs(64, heads, dk, dv, 12.0, seed=3)
    seg = jnp.zeros((64,), jnp.int32)
    if fused:
        monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    assert kimi_linear.kda_scan_runs_fused(64, heads, dk, dv) == fused
    with pltpu.force_tpu_interpret_mode():
        out = kimi_linear.kda_scan(q, k, v, g, beta, seg, 64, jnp.float32)
    assert bool(jnp.isfinite(out).all())
    first = beta[0, :, None] * v[0] * jnp.sum(k[0] * q[0], -1)[:, None]
    _close(out[0], first, tol=1e-5)
    assert kimi_linear.sub_block(64) == 16 and kimi_linear.sub_block(8) == 2
    assert kimi_linear.sub_block(6) == 6
    assert kda_pallas.SUB == 16


# ---------------------------------------------------------------------------
# the recurrence on the Pallas kernels (interpret mode), the rule that picks
# them
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,heads,cuts,decay", [
    (128, 4, (37, 64, 100), 0.1),   # starts inside a chunk, on an edge
    (128, 4, (37, 64, 100), 2.5),   # the same at g of -1.25 to -2.5
    (192, 4, (5, 190, 191), 2.0),   # a chunk whole inside a document, a
                                    # one-token document, an odd chunk
    (100, 8, (64, 65), 0.5),        # a row that is no whole chunks, a
                                    # one-token document on a chunk's first
                                    # token, two blocks of heads
])
def test_kda_kernels_are_the_recurrence_values_and_gradients(
        t, heads, cuts, decay, monkeypatch):
    """``kimi_linear.kda_scan`` on the kernels of ``kda_pallas``, run in
    Pallas's interpreter, at keys and values of 128 in float32 against the
    reference's token-by-token ``delta_rule``: the outputs and the gradients
    of all five operands, to the tolerance the ``jnp`` form is held to."""
    args = _scan_inputs(t, heads, 128, 128, decay, seed=t + heads)
    seg = _segments(t, cuts)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((t, heads, 128)),
                    jnp.float32)

    def mine(*a):
        return kimi_linear.kda_scan(*a, seg, 64, jnp.float32, ("a", "b"))

    def theirs(*a):
        return reference.delta_rule(*a, seg)

    (_, want_out), want = _value_and_gradients(theirs, w, 5)(*args)
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        fn = _value_and_gradients(mine, w, 5)
        assert "kda_backward" in str(jax.make_jaxpr(fn)(*args))
        (_, out), got = fn(*args)
    _close(out, want_out)
    for a, b in zip(got, want):
        assert float(jnp.abs(b).max()) > 0
        _close(a, b)


def test_kda_kernels_follow_the_jnp_form_in_bfloat16(monkeypatch):
    """With operands in bfloat16 the two executions round at the same
    places but for the state between chunks (the kernels hold the entering
    state and the inverse in bfloat16 between their passes, the ``jnp`` form
    makes them again): values and gradients agree to bfloat16's rounding,
    far inside what separates either from a forgotten term."""
    t, heads = 256, 4
    q, k, v, g, beta = _scan_inputs(t, heads, 128, 128, 0.3, seed=11)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    seg = _segments(t, (50, 128, 131))
    w = jnp.asarray(np.random.default_rng(2).standard_normal((t, heads, 128)),
                    jnp.float32)

    def mine(*a):
        return kimi_linear.kda_scan(*a, seg, 64, jnp.bfloat16).astype(
            jnp.float32)

    (_, want_out), want = _value_and_gradients(mine, w, 5)(q, k, v, g, beta)
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        (_, out), got = _value_and_gradients(mine, w, 5)(q, k, v, g, beta)
    _close(out, want_out, tol=2e-2)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        _close(a.astype(jnp.float32), b.astype(jnp.float32), tol=3e-2)


@pytest.mark.parametrize("backend,published,fused", [
    ("cpu", False, 0), ("cpu", True, 0), ("tpu", False, 0), ("tpu", True, 1)])
def test_one_rule_says_which_recurrence_runs_and_which_is_counted(
        backend, published, fused, monkeypatch):
    """``kda_scan_runs_fused`` reads the backend and the shapes: the
    published 32 heads of 128 x 128 at chunks of 64 run the kernels on a
    TPU, ``Config.tiny()``'s run ``jnp`` code everywhere; and
    ``batch_counters`` names ``kda_scan_fused_steps_total`` and
    ``kda_scan_plain_steps_total``, one of them 1 and the other 0, by that
    rule."""
    import json
    import os

    config = kimi_linear.Config.tiny()
    if published:
        with open(os.path.join(os.path.dirname(program.__file__),
                               "config.json")) as f:
            config = program.model_config(json.load(f))
        assert (config.kda_num_heads, config.kda_head_dim,
                config.kda_chunk) == (32, 128, 64)
    monkeypatch.setattr(kernels, "backend", lambda: backend)
    assert kimi_linear.kda_scan_runs_fused(
        config.kda_chunk, config.kda_num_heads, config.kda_head_dim,
        config.kda_head_dim) == bool(fused)
    counts = kimi_linear.batch_counters(
        {"segment_ids": np.zeros((1, config.seq_len), np.int32)}, config)
    assert (counts["kda_scan_fused_steps_total"],
            counts["kda_scan_plain_steps_total"]) == (fused, 1 - fused)
    assert counts["kda_chunks_total"] == (
        config.seq_len // config.kda_chunk * config.kda_num_heads
        * len([i for i in config.kda_layers
               if i <= config.num_hidden_layers]))
    assert not kda_pallas.fits(64, 30, 128, 128)        # no whole blocks
    assert not kda_pallas.fits(32, 32, 128, 128)        # no pair a cell
    assert not kda_pallas.fits(64, 32, 128, 64)         # values of half a row
    for text in (obs.__doc__, kimi_linear.__doc__):
        assert "kda_scan_fused_steps_total" in text
        assert "kda_scan_plain_steps_total" in text
        assert "kda_scan_runs_fused" in text


def test_a_step_counts_the_execution_of_its_recurrence(tiny):
    """One ``Trainer.step``: exactly one of ``kda_scan_fused_steps_total``
    and ``kda_scan_plain_steps_total`` goes up by one, by the rule
    ``kda_scan`` applied when the step was traced (here the CPU's: the
    ``jnp`` form); the other is on the record with what it had."""
    from tensorflowonspark_tpu.trainer import Trainer

    config = tiny[0]
    trainer = Trainer("kimi_linear", config=config,
                      devices=jax.devices()[:1])
    names = ("kda_scan_fused_steps_total", "kda_scan_plain_steps_total")

    def totals():
        counters = obs.get_registry().snapshot()["counters"]
        return np.array([counters.get(k, 0) for k in names])

    for seed in (13, 14):
        before = totals()
        trainer.step(_rows(config, 2, seed))
        assert set(names) <= set(obs.get_registry().snapshot()["counters"])
        np.testing.assert_array_equal(totals() - before, [0, 1])


@pytest.mark.parametrize("c,sub,scale", [(64, 16, 0.3), (64, 16, 1.0),
                                         (8, 2, 0.5), (6, 6, 0.5)])
def test_unit_lower_inverse_is_the_inverse(c, sub, scale):
    """``kimi_linear.unit_lower_inverse`` against ``numpy.linalg.inv`` in
    float64: random strictly lower triangles, and — at ``scale`` 1.0 — the
    one a document that repeats one token gives (every entry the same),
    whose powers a Neumann series could not cancel in float32."""
    rng = np.random.default_rng(c + sub)
    a = np.tril(rng.standard_normal((3, 2, c, c)) * scale / np.sqrt(c), -1)
    if scale == 1.0:
        a[0] = np.tril(np.full((c, c), 0.9), -1)
    got = kimi_linear.unit_lower_inverse(jnp.asarray(a, jnp.float32), sub)
    want = np.linalg.inv(np.eye(c) + a)
    _close(got, want, tol=1e-5)
    assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0.0


@pytest.mark.parametrize("fused", [False, True])
def test_a_layers_recomputation_keeps_what_the_scan_names(fused, monkeypatch):
    """``hidden_states`` recomputes a layer in the backward pass but for
    ``kimi_linear.SAVED`` — the scan's outputs and what it holds between its
    passes: the state entering each group of chunks, and of the kernels the
    state entering each pair and the chunks' inverses —, so the recurrence
    runs forward once and not twice: with the names taken away the gradient
    holds more loops (the ``jnp`` form, lowered) or a second forward kernel
    (the kernels, traced with a TPU in the backend's place; the gradients
    themselves are the whole model's test's)."""
    config = dataclasses.replace(
        kimi_linear.Config.tiny(), num_hidden_layers=1, kda_layers=(1,),
        full_attn_layers=(), seq_len=64)
    if fused:
        config = dataclasses.replace(config, kda_num_heads=4,
                                     kda_head_dim=128, kda_chunk=64,
                                     seq_len=128)
        monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    assert kimi_linear.batch_counters(
        {"segment_ids": np.zeros((1, config.seq_len), np.int32)}, config)[
            "kda_scan_fused_steps_total"] == int(fused)
    params = {k: jnp.zeros(s, jnp.float32)
              for k, s in kimi_linear.leaf_shapes(config).items()}
    batch = kimi_linear.example_batch(config, 1, seq_len=config.seq_len)
    bias = jnp.zeros((config.expert_layers, config.num_experts))

    def loops():
        grad = jax.jit(jax.grad(lambda p: kimi_linear.loss_terms(
            p, bias, batch["tokens"], batch["segment_ids"], config)[0]))
        if fused:
            text = str(jax.make_jaxpr(grad)(params))
            assert text.count("kda_backward") == 1
            return text.count("kda_forward")
        return grad.lower(params).as_text().count("stablehlo.while")

    kept = loops()
    # (``run_layer`` makes its policy once a tuple of names: patch the maker)
    monkeypatch.setattr(
        packed_decoder, "_keeping",
        lambda names: jax.checkpoint_policies.save_only_these_names())
    assert loops() > kept > 0
    assert not fused or kept == 1


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_state_and_the_convolutions_stop_at_a_documents_first_token(
        tiny, side):
    """Two documents packed give each what it gives alone: the KDA mixer on
    the row's halves apart is the mixer on the row — the cut inside a chunk
    —, and the second document does change when the boundary is taken
    away."""
    config, ref_config, weights, params = tiny
    rng = np.random.default_rng(11)
    t, cut = 24, 11
    h = jnp.asarray(rng.standard_normal((t, config.hidden_size)), jnp.float32)
    seg = (np.arange(t) >= cut).astype(np.int32)
    if side == "program":
        mix = jax.jit(lambda x, s: kimi_linear.kda_mixer(
            params, "l00_", x, jnp.asarray(s), config))
    else:
        w = {k[4:]: v for k, v in weights.items() if k.startswith("l00/")}
        mix = jax.jit(lambda x, s: reference.kda_mixer(
            w, x, jnp.asarray(s), ref_config, lambda a: a))
    packed = mix(h, seg)
    _close(packed[:cut], mix(h[:cut], seg[:cut]), tol=1e-5)
    _close(packed[cut:], mix(h[cut:], seg[cut:]), tol=1e-5)
    one = mix(h, np.zeros(t, np.int32))
    assert float(jnp.abs(one[cut:] - packed[cut:]).max()) > (
        0.1 * float(jnp.abs(packed).max()))
    _close(one[:cut], packed[:cut], tol=1e-5)


# ---------------------------------------------------------------------------
# attention with values narrower than keys
# ---------------------------------------------------------------------------


def _one_softmax(q, k, v, seg, scale):
    """(T, kv, rep, hd) x (T, kv, hd) x (T, kv, vd): one masked softmax."""
    t = q.shape[0]
    s = jnp.einsum("ikrd,jkd->krij", q, k) * scale
    at = jnp.arange(t)
    mask = (at[:, None] >= at[None, :]) & (seg[:, None] == seg[None, :])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("krij,jkd->ikrd", p, v)


@pytest.mark.parametrize("hd,vd,kv,rep", [(12, 8, 2, 1), (12, 8, 1, 2),
                                          (8, 8, 2, 1), (8, 12, 2, 1)])
def test_document_attention_takes_values_at_their_own_width(hd, vd, kv, rep):
    """``packed_rows.document_attention`` with keys of ``hd`` and values of
    ``vd`` (narrower, the same, wider) against one masked softmax: the
    output is ``vd`` wide, and the three gradients match."""
    rng = np.random.default_rng(hd + vd)
    t = 32
    q = jnp.asarray(rng.standard_normal((t, kv, rep, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((t, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((t, kv, vd)), jnp.float32)
    seg = jnp.asarray([0] * 7 + [1] * 9 + [2] * 16, jnp.int32)
    w = jnp.asarray(rng.standard_normal((t, kv, rep, vd)), jnp.float32)

    def mine(q, k, v):
        return packed_rows.document_attention(q, k, v, seg, hd ** -0.5, 8,
                                              jnp.float32)

    (_, out), got = _value_and_gradients(mine, w, 3)(q, k, v)
    (_, want_out), want = _value_and_gradients(
        lambda *a: _one_softmax(*a, seg, hd ** -0.5), w, 3)(q, k, v)
    assert out.shape == (t, kv, rep, vd)
    _close(out, want_out, tol=1e-5)
    for a, b in zip(got, want):
        _close(a, b, tol=1e-5)


@pytest.mark.parametrize("hd,vd,fused", [(256, 256, 1), (256, None, 1),
                                         (256, 128, 0), (192, 128, 0)])
def test_one_rule_says_which_attention_runs_and_which_is_counted(
        hd, vd, fused, monkeypatch):
    """``attention_runs_fused`` takes the values' width: on a TPU, keys that
    fill whole rows of lanes over narrower values run the ``jnp`` blocks
    (the kernels take one width), ``row_counters`` counts the step by the
    same rule, and ``document_attention`` does what was counted (on this
    host the kernels could not run: the narrower values still compute)."""
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    t = 384
    assert packed_rows.attention_runs_fused(t, hd, vd) == bool(fused)
    counts = packed_rows.row_counters(np.zeros((1, t), np.int32), hd,
                                      v_head_dim=vd)
    assert (counts["attention_fused_steps_total"],
            counts["attention_plain_steps_total"]) == (fused, 1 - fused)
    if fused:
        return
    rng = np.random.default_rng(hd)
    q = jnp.asarray(rng.standard_normal((t, 1, 1, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((t, 1, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((t, 1, vd)), jnp.float32)
    seg = jnp.asarray((np.arange(t) >= 100).astype(np.int32))
    out = packed_rows.document_attention(q, k, v, seg, hd ** -0.5, 128,
                                         jnp.float32)
    _close(out, _one_softmax(q, k, v, seg, hd ** -0.5), tol=1e-5)


def test_latent_attention_is_one_function_with_the_layouts_as_arguments(tiny):
    """``packed_rows.latent_attention`` is what both latent-attention
    models call: GLM's with a query latent and RoPE, this one's with
    neither; this one's against the reference's, and unrotated — the same
    document moved along the row gives the same output."""
    config, ref_config, weights, params = tiny
    assert kimi_linear.latent_attention is packed_rows.latent_attention
    glm = mla_moe.Config.tiny()
    assert glm.qk_head_dim == glm.v_head_dim       # GLM's are one width
    assert mla_moe.Config(qk_nope_head_dim=128, v_head_dim=128).qk_head_dim \
        == 192                                     # and need not be
    rng = np.random.default_rng(13)
    t, cut = 24, 10
    h = jnp.asarray(rng.standard_normal((t, config.hidden_size)), jnp.float32)
    seg = jnp.asarray((np.arange(t) >= cut).astype(np.int32))
    short = dataclasses.replace(config, attention_block=8)
    mine = kimi_linear.attention(params, "l01_", h, seg, short)
    w = {k[4:]: v for k, v in weights.items() if k.startswith("l01/")}
    _close(mine, reference.attention(w, h, seg, ref_config, lambda a: a),
           tol=1e-5)
    alone = kimi_linear.attention(params, "l01_", h[cut:], seg[cut:],
                                  dataclasses.replace(config,
                                                      attention_block=7))
    _close(mine[cut:], alone, tol=1e-5)


# ---------------------------------------------------------------------------
# program against reference
# ---------------------------------------------------------------------------


def test_kimi_tiny_is_the_issues_size_and_names_the_references_leaves(tiny):
    config, ref_config, weights, params = tiny
    assert kimi_linear.layer_kinds(config) == [
        ("l00_", "kda", "dense"), ("l01_", "full_attention", "experts")]
    assert (config.num_experts, config.experts_held,
            config.num_experts_per_token, config.kda_num_heads,
            config.kda_head_dim, config.qk_head_dim, config.v_head_dim,
            config.kda_chunk) == (16, (2, 3), 3, 2, 8, 12, 8, 8)
    shapes = kimi_linear.leaf_shapes(config)
    assert {k: tuple(v.shape) for k, v in params.items()} == shapes
    assert list(shapes) == [program.program_name(n)
                            for n in reference.leaf_shapes(ref_config)]
    assert kimi_linear.parameter_count(config) == sum(
        int(np.prod(v.shape)) for v in weights.values())
    assert kimi_linear.collection_shapes(config) == moe.routing_state_shapes(
        16, 1)
    assert dataclasses.replace(
        program.model_config(ref_config), attention_block=16,
        loss_block=16) == config
    published = kimi_linear.Config()
    kinds = [m for _, m, _ in kimi_linear.layer_kinds(published)]
    assert kinds.count("kda") == 20 and kinds.count("full_attention") == 7
    assert kinds[:8] == ["kda"] * 3 + ["full_attention"] + ["kda"] * 3 + [
        "full_attention"] and kinds[-3:] == ["kda", "kda", "full_attention"]
    with pytest.raises(ValueError):
        kimi_linear.Config(num_hidden_layers=4, kda_layers=(1, 2, 3),
                           full_attn_layers=(3, 4))
    for broken in (dict(ref_config, experts_held=[0]),
                   dict(ref_config, q_lora_rank=16),
                   dict(ref_config, mla_use_nope=False),
                   dict(ref_config, num_nextn_predict_layers=1)):
        with pytest.raises(ValueError):
            program.model_config(broken)


def test_kimi_seeded_decays_are_drawn_as_the_public_code_draws_them(tiny):
    """``A_log = log U(1, 16)`` a head and ``dt_bias`` the inverse softplus
    of a step log-uniform in 0.001-0.1, in the reference's seeded weights
    and in the zoo's own initialisation."""
    config, _, weights, _ = tiny
    module = kimi_linear.make_model(config)
    batch = kimi_linear.example_batch(config, 1)
    own = jax.jit(module.init)(jax.random.PRNGKey(0), batch["tokens"],
                               batch["segment_ids"])["params"]
    for a_log, dt_bias, taps in (
            (weights["l00/kda_A_log"], weights["l00/kda_dt_bias"],
             weights["l00/kda_q_conv"]),
            (own["l00_kda_A_log"], own["l00_kda_dt_bias"],
             own["l00_kda_q_conv"])):
        assert a_log.shape == (2,) and dt_bias.shape == (16,)
        assert 0 <= float(a_log.min()) and float(a_log.max()) <= np.log(16)
        dt = np.asarray(jax.nn.softplus(dt_bias))
        assert 0.000999 <= dt.min() and dt.max() <= 0.1001
        assert taps.shape == (4, 16) and float(jnp.abs(taps).max()) <= 0.5
    assert float(own["l00_kda_o_norm"].min()) == 1.0


def test_kimi_logits_loss_and_every_leafs_gradient_match(tiny):
    """With correction biases that move some choices (zero biases are the
    Trainer test's)."""
    config, ref_config, weights, params = tiny
    batch = _rows(config, 2, 1)
    bias = _bias(config)
    tokens, seg = batch["tokens"], batch["segment_ids"]

    def mine(p):
        total, n, counts = kimi_linear.loss_terms(p, bias, tokens, seg,
                                                  config)
        return total / n, counts

    def theirs(w):
        logits, loss, counts = reference.forward(w, tokens, seg, ref_config,
                                                 bias)
        return loss, (logits, counts)

    (want_loss, (want_logits, want_counts)), want = jax.jit(
        jax.value_and_grad(theirs, has_aux=True))(weights)
    (loss, counts), grads = jax.jit(
        jax.value_and_grad(mine, has_aux=True))(params)
    _close(jax.jit(lambda p: kimi_linear.apply_tokens(p, bias, tokens, seg,
                                                      config))(params),
           want_logits)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts.sum()) == (config.num_experts_per_token * tokens.size
                                 * config.expert_layers)
    # the bias moved some choices: the counts are not the zero bias's
    assert not np.array_equal(counts, jax.jit(lambda w: reference.forward(
        w, tokens, seg, ref_config)[2])(weights))
    assert set(grads) == {program.program_name(k) for k in want}
    for name, g in want.items():
        assert float(jnp.abs(g).max()) > 0, name    # every leaf is trained
        _close(grads[program.program_name(name)], g)


def test_kimi_bfloat16_where_float32_is_stated_fails_the_tolerance(tiny):
    """The same comparison with the program's activations in bfloat16 (the
    cell's own precision, not this test's): the logits miss 2e-5 by two
    orders of magnitude, and stay within what 8 bits keep — a loss near
    log(64) moves by well under a hundredth."""
    config, ref_config, weights, params = tiny
    batch = _rows(config, 2, 3)
    bias = jnp.zeros((config.expert_layers, config.num_experts))
    low = dataclasses.replace(config, dtype="bfloat16")
    want_logits, want_loss, _ = jax.jit(lambda w: reference.forward(
        w, batch["tokens"], batch["segment_ids"], ref_config))(weights)
    logits = jax.jit(lambda p: kimi_linear.apply_tokens(
        p, bias, batch["tokens"], batch["segment_ids"], low))(params)
    gap = float(jnp.abs(logits - want_logits).max()
                / jnp.abs(want_logits).max())
    assert 100 * TOL < gap < 0.05, gap
    total, n, _ = jax.jit(lambda p: kimi_linear.loss_terms(
        p, bias, batch["tokens"], batch["segment_ids"], low))(params)
    assert float(total / n) == pytest.approx(float(want_loss), rel=1e-2)


def test_kimi_the_float8_control_moves_the_reference(tiny):
    """``lower="float8"`` rounds the products' operands — the recurrence's
    queries, keys and values among them — and leaves the router, the decay
    and the state alone: the loss moves, the choices do not have to."""
    config, ref_config, weights, _ = tiny
    batch = _rows(config, 2, 4)
    sound, low = jax.jit(lambda w: [reference.forward(
        w, batch["tokens"], batch["segment_ids"], ref_config, lower=lower)[1]
        for lower in (None, "float8")])(weights)
    assert abs(float(low) - float(sound)) > 1e-5 * float(sound)
    with pytest.raises(ValueError):
        reference.forward(weights, batch["tokens"], batch["segment_ids"],
                          ref_config, lower="float4")


def test_kimi_trainer_follows_the_reference_and_checkpoints_its_routing(
        tiny, tmp_path):
    """Through ``Trainer`` — nothing in it is this model's: the seeded
    weights loaded a leaf at a time, three steps, then the losses, the first
    gradient's norms as AdamW's first moment shows them, every parameter and
    the routing biases.  After three steps of AdamW a difference of 1e-6 in
    a gradient whose second moment is still tiny can move an update by its
    whole size, so the parameters are held to 1e-3 of their largest entry;
    the change's norm, which the benchmark compares, to 1e-3.  The biases
    move by whole steps of 0.001 and have to agree to rounding; so do the
    counts behind them.  Then a third model with a non-gradient collection
    goes through ``Trainer.save`` / ``restore`` as the others did: the whole
    ``moe`` collection comes back, and the counters go on from it."""
    from tensorflowonspark_tpu.trainer import Trainer

    config, ref_config, seeded, _ = tiny
    before = obs.get_registry().snapshot()["counters"]
    trainer = Trainer("kimi_linear", config=config, learning_rate=1e-3,
                      devices=jax.devices()[:1])      # the cell's one chip
    names = program.load_weights(trainer, ref_config, reference, BIG_SEED)
    batches = [_rows(config, 2, 10 + i) for i in range(3)]
    losses = []
    for i, batch in enumerate(batches):
        losses.append(float(trainer.step(program.host_batch(dict(batch)))))
        if i == 0:
            grad_norms = program.first_gradient_norms(trainer, ref_config,
                                                      names)
    theirs = reference.follow(ref_config, BIG_SEED, batches)
    np.testing.assert_allclose(losses, theirs["losses"], rtol=1e-5)
    for name in names:
        assert grad_norms[name] == pytest.approx(
            theirs["grad_norms"][name], rel=1e-4), name
    routing = {k: np.asarray(v) for k, v in
               trainer.state.collections[kimi_linear.COLLECTION].items()}
    np.testing.assert_allclose(routing["bias"], theirs["bias"], atol=1e-7)
    assert np.abs(routing["bias"]).max() == pytest.approx(0.003, rel=1e-5)
    np.testing.assert_array_equal(routing["counts"],
                                  np.sum(theirs["counts"], axis=0))
    weights = {k: jnp.array(v) for k, v in seeded.items()}  # steps donate
    state = {"mu": {k: jnp.zeros_like(v) for k, v in weights.items()},
             "nu": {k: jnp.zeros_like(v) for k, v in weights.items()},
             "count": 0, "bias": reference.zero_bias(ref_config)}
    first = {k: np.asarray(v) for k, v in weights.items()}
    for batch in batches:
        reference.train_step(weights, state, batch, ref_config)
    mine = program.parameters(trainer, ref_config, names)
    for name in names:
        _close(mine[name], weights[name], tol=1e-3)
        change = float(np.linalg.norm(np.asarray(mine[name]) - first[name]))
        assert change == pytest.approx(theirs["change_norms"][name],
                                       rel=1e-3), name

    # a checkpoint carries the routing state: a fourth step, back, and on
    trainer.save(str(tmp_path / "ckpt"))
    extra = program.host_batch(dict(_rows(config, 2, 20)))
    trainer.step(extra)
    fourth = np.asarray(trainer.state.collections[kimi_linear.COLLECTION][
        "counts"]) - routing["counts"]
    assert fourth.sum() == routing["counts"].sum() // 3
    trainer.restore(str(tmp_path / "ckpt"))
    got = trainer.state.collections[kimi_linear.COLLECTION]
    assert set(got) == {"bias", "counts", "busiest", "overflow", "tight"}
    for name in got:
        np.testing.assert_array_equal(got[name], routing[name])
    trainer.step(extra)
    np.testing.assert_array_equal(
        trainer.state.collections[kimi_linear.COLLECTION]["counts"],
        routing["counts"] + fourth)
    batches = batches + [extra, extra]      # every step run, no step twice

    # the program's counters: the host batch's, and — a step late, the last
    # when the trainer goes — what the device decided, under the names the
    # other expert models' counters have
    del trainer, mine
    gc.collect()
    after = obs.get_registry().snapshot()["counters"]
    grew = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    tokens = sum(b["tokens"].size for b in batches)
    counts = np.concatenate([np.asarray(theirs["counts"]), fourth[None],
                             fourth[None]])
    held = list(config.experts_held)
    assert grew["lm_tokens_total"] == tokens
    assert grew["lm_loss_tokens_total"] == sum(
        (b["segment_ids"][:, 1:] == b["segment_ids"][:, :-1]).sum()
        for b in batches)
    assert grew["lm_documents_total"] == 5 * 2 * 3
    assert grew["attention_plain_steps_total"] == 5
    assert grew["attention_fused_steps_total"] == 0
    assert grew["moe_grouped_plain_steps_total"] == 5
    # chunks a row x rows x heads x KDA layers, a step
    assert grew["kda_chunks_total"] == 5 * (32 // 8) * 2 * 2 * 1
    assert grew["moe_slots_total"] == (config.num_experts_per_token * tokens
                                       * config.expert_layers)
    assert grew["moe_local_slots_total"] == counts[..., held].sum()
    assert grew["moe_busiest_expert_slots_total"] == counts.max(-1).sum()
    assert grew["moe_overflow_layers_total"] == int((
        counts[..., held].sum(-1) > moe.prefix_rows(
            config.num_experts_per_token * batches[0]["tokens"].size,
            len(held), config.num_experts)).sum())


# ---------------------------------------------------------------------------
# the expert layer at the shares of two of sixteen, a shared expert beside
# ---------------------------------------------------------------------------


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(tiny):
    """Eight chips hold two of sixteen experts each (the cell: 32 of 8 of
    256).  The routed parts that the eight shares give (``routed_experts``
    told which two), with the shared expert — which every chip computes
    alike — counted once, add up to what the uncut reference gives for the
    whole layer, to float32 rounding; every share reports the same counts,
    and every slot lands on exactly one share."""
    config, ref_config, weights, params = tiny
    rng = np.random.default_rng(0)
    d, f, n = config.hidden_size, config.moe_intermediate_size, 16
    g = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]),  # noqa: E731
                               jnp.float32)
    w = {"router": g(d, n), "experts_gate": g(n, d, f),
         "experts_up": g(n, d, f), "experts_down": g(n, f, d),
         "shared_gate": g(d, f), "shared_up": g(d, f),
         "shared_down": g(f, d)}
    h = jnp.asarray(rng.standard_normal((48, d)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(n) * 0.05, jnp.float32)
    uncut = dict(ref_config, experts_held=list(range(n)))
    whole, want_counts = reference.experts(w, h, bias, uncut, lambda a: a)

    @jax.jit
    def shares(h):
        total = packed_rows.swiglu(h, w["shared_gate"], w["shared_up"],
                                   w["shared_down"])        # counted once
        every = []
        for share in range(8):
            held = (2 * share, 2 * share + 1)
            take = np.asarray(held)
            part, counts = moe.routed_experts(
                h, w["router"], bias, w["experts_gate"][take],
                w["experts_up"][take], w["experts_down"][take], held,
                top_k=config.num_experts_per_token,
                scale=config.routed_scaling_factor)
            total = total + part
            every.append(counts)
        return total, jnp.stack(every)

    total, every = shares(h)
    for counts in np.asarray(every):
        np.testing.assert_array_equal(counts, want_counts)
    # every slot lands on exactly one share
    assert int(np.asarray(want_counts).sum()) == (
        config.num_experts_per_token * h.shape[0])
    _close(total, whole, tol=1e-6)
    # one share with its shared expert is the program's layer
    own = {f"x_{k}": (v[np.asarray(config.experts_held)]
                      if k.startswith("experts") else v) for k, v in w.items()}
    y, _ = moe.expert_ffn(own, "x_", h, bias, kimi_linear.routing(config),
                          shared=True)
    theirs, _ = reference.experts(
        {k: (v[np.asarray(config.experts_held)] if k.startswith("experts")
             else v) for k, v in w.items()}, h, bias, ref_config,
        lambda a: a)
    _close(y, theirs, tol=1e-6)


# ---------------------------------------------------------------------------
# documents, scopes, checkpoints
# ---------------------------------------------------------------------------


def test_a_documents_logits_do_not_change_when_another_document_does(tiny):
    """The whole model: replace the second document's tokens, and move the
    row's first document behind another — its logits stay, at the places it
    now has (no part of the model reads a position)."""
    config, _, _, params = tiny
    bias = _bias(config, seed=5)
    rng = np.random.default_rng(6)
    t = config.seq_len
    seg = (np.arange(t) >= 11).astype(np.int32) + (np.arange(t) >= 23)
    tokens = rng.integers(0, config.vocab_size, t, np.int32)
    changed = tokens.copy()
    changed[11:23] = rng.integers(0, config.vocab_size, 12)
    moved = np.concatenate([tokens[11:23], tokens[:11], tokens[23:]])
    moved_seg = np.concatenate([np.zeros(12), np.ones(11),
                                np.full(t - 23, 2)]).astype(np.int32)
    logits = jax.jit(lambda p: kimi_linear.apply_tokens(
        p, bias, np.stack([tokens, changed, moved]),
        np.stack([seg, seg, moved_seg]), config))(params)
    _close(logits[1, :11], logits[0, :11], tol=1e-6)
    _close(logits[1, 23:], logits[0, 23:], tol=1e-6)
    assert float(jnp.abs(logits[1, 11:23] - logits[0, 11:23]).max()) > 1e-3
    _close(logits[2, 12:23], logits[0, :11], tol=1e-5)
    _close(logits[2, :12], logits[0, 11:23], tol=1e-5)


def test_kimi_step_names_its_scopes_forward_and_backward(tiny):
    """Every scope the cell's per-layer metrics read is on an operation of
    the lowered gradient, in the forward pass and in the backward pass:
    ``benchmark/kda_scopes.py`` and ``moe_scopes.py`` find them by word."""
    import re

    config, _, _, params = tiny
    batch = _rows(config, 1, 2)
    bias = jnp.zeros((config.expert_layers, config.num_experts))
    text = jax.jit(jax.grad(lambda p: kimi_linear.loss_terms(
        p, bias, batch["tokens"], batch["segment_ids"], config)[0])).lower(
            params).as_text(debug_info=True)
    # an operation's name is its path of scopes and transformations (a bare
    # word is a frame of the call stack, not an operation)
    names = {n for n in re.findall(r'loc\("([^"]*)"', text) if "/" in n}
    for scope in ("kda_mixer", "kda_project", "kda_conv", "kda_scan",
                  "kda_out", "attention", "mla_project", "mlp",
                  "shared_expert", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "lm_head"):
        word = re.compile(rf"\b{scope}\b")
        found = [n for n in names if word.search(n)]
        assert any("transpose" in n for n in found), scope
        assert any("transpose" not in n for n in found), scope
    for inner, outer in (("kda_project", "kda_mixer"),
                         ("kda_conv", "kda_mixer"), ("kda_scan", "kda_mixer"),
                         ("kda_out", "kda_mixer"),
                         ("mla_project", "attention")):
        alone = [n for n in names if re.search(rf"\b{inner}\b", n)
                 and not re.search(rf"\b{outer}\b", n)]
        assert not alone, alone[:5]
