"""The gated sliding-window / position-free attention expert decoder
(``models/afmoe.py``: ``packed_rows.grouped_query_attention`` with its output
gate, behind a window and rotated or over the whole document and not, four
norms a layer, a scaled embedding, the sigmoid-routed layer of
``parallel/moe.py`` beside a shared expert) against the plain reference of
the ``trinity_mini`` configuration, at ``Config.tiny()`` in float32 on the
CPU; each mechanism the layout brought alone against a few lines written by
hand; and the shared code's default arguments, which leave the four sibling
decoders what they were.

Tolerances: both sides compute in float32 with products at the highest
precision, so they differ only by the order of their sums (the program's
sorted grouped products and running softmax over the blocks a window reaches
against the reference's masked dense experts and one masked softmax over
every key): 2e-5 relative to the largest entry covers what a few hundred
float32 additions in another order move, and is 1,000 times tighter than a
missing gate, a missing post-norm, a rotation in the wrong layer, a window
off by one or a missing multiplier would need (tests below show they miss
it); bfloat16 activations miss it by two orders of magnitude.  After three
AdamW steps at 1e-3 a parameter is held to 1e-3 of the largest entry, as
``test_lfm2_moe.py`` argues it.
"""

import dataclasses
import gc
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.configs.trinity_mini import program, reference
from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.models import (afmoe, kimi_linear, lfm2_moe,
                                          mellum_moe, mla_moe,
                                          packed_decoder, packed_rows)
from tensorflowonspark_tpu.parallel import moe

BIG_SEED = 2 ** 31 + 5101           # the driver's seeds pass 32 signed bits
TOL = 2e-5
#: documents a row of ``Config.tiny()``'s 48 tokens: one shorter than the
#: window of 12, one longer, one a token over it
LENGTHS = ([5, 30, 13], [20, 8, 20])


def _tiny_dict(config: afmoe.Config, learning_rate=1e-3) -> dict:
    """``Config.tiny()`` under the keys the configuration's file has."""
    dense = sum(at < config.num_dense_layers for at in config.layers_run)
    return {
        "hidden_size": config.hidden_size, "head_dim": config.head_dim,
        "intermediate_size": config.intermediate_size,
        "moe_intermediate_size": config.moe_intermediate_size,
        "layer_types": list(config.layer_types),
        "layers_run": list(config.layers_run),
        "num_hidden_layers": len(config.layers_run),
        "num_dense_layers": dense,
        "sliding_window": config.sliding_window,
        "rope_theta": config.rope_theta, "rope_scaling": None,
        "num_experts": len(config.experts_held),
        "experts_held": list(config.experts_held),
        "published": {"num_experts": config.num_experts,
                      "num_hidden_layers": len(config.layer_types),
                      "num_dense_layers": config.num_dense_layers},
        "num_experts_per_tok": config.num_experts_per_tok,
        "num_shared_experts": config.num_shared_experts,
        "score_func": config.score_func, "route_norm": config.route_norm,
        "route_scale": config.route_scale,
        "load_balance_coeff": config.load_balance_coeff,
        "gate_sum_eps": afmoe.GATE_SUM_EPS,
        "mup_enabled": config.mup_enabled,
        "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
        "num_limited_groups": 1,
        "num_attention_heads": config.num_attention_heads,
        "num_key_value_heads": config.num_key_value_heads,
        "rms_norm_eps": config.rms_norm_eps, "vocab_size": config.vocab_size,
        "init_std": config.init_std,
        "post_norm_init": config.post_norm_init, "dtype": config.dtype,
        "seq_len": config.seq_len, "hidden_act": "silu",
        "tie_word_embeddings": False,
        "parameters": afmoe.parameter_count(config),
        "program_model": "afmoe",
        "optimizer": dict(afmoe.ADAMW, name="adamw",
                          learning_rate=learning_rate),
    }


def _rows(config: afmoe.Config, seed: int) -> dict:
    """Two packed rows of three documents each (``LENGTHS``)."""
    rng = np.random.default_rng(seed)
    seg = np.stack([np.repeat(rng.permutation(9)[:3], n)
                    for n in LENGTHS]).astype(np.int32)
    assert seg.shape == (2, config.seq_len)
    return {"tokens": rng.integers(0, config.vocab_size, seg.shape, np.int32),
            "segment_ids": seg}


def _close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


def _gap(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def tiny():
    config = afmoe.Config.tiny()
    ref_config = _tiny_dict(config)
    weights = reference.make_weights(ref_config, BIG_SEED)
    # norms' scales off one, so that a norm left out or applied twice shows
    rng = np.random.default_rng(7)
    weights = {k: v * jnp.asarray(rng.uniform(0.5, 1.5, v.shape), jnp.float32)
               if v.ndim == 1 else v for k, v in weights.items()}
    params = {program.program_name(k): v for k, v in weights.items()}
    return config, ref_config, weights, params


@pytest.fixture(scope="module", autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _zero_bias(config):
    return jnp.zeros((config.expert_layers, config.num_experts), jnp.float32)


# ---------------------------------------------------------------------------
# program against reference
# ---------------------------------------------------------------------------


def test_afmoe_tiny_is_the_issues_shape_and_names_the_references_leaves(tiny):
    config, ref_config, weights, params = tiny
    assert afmoe.layer_kinds(config) == [
        ("l00_", "sliding_attention", "dense"),
        ("l01_", "sliding_attention", "experts"),
        ("l02_", "full_attention", "experts"),
        ("l03_", "sliding_attention", "experts")]
    shapes = afmoe.leaf_shapes(config)
    assert {k: tuple(v.shape) for k, v in params.items()} == shapes
    assert list(shapes) == [program.program_name(n)
                            for n in reference.leaf_shapes(ref_config)]
    assert [k[4:] for k in shapes if k.startswith("l01_")] == [
        "norm1", "wq", "wk", "wv", "wg", "q_norm", "k_norm", "wo", "norm2",
        "norm3", "router", "shared_gate", "shared_up", "shared_down",
        "experts_gate", "experts_up", "experts_down", "norm4"]
    assert dataclasses.replace(program.model_config(ref_config),
                               attention_block=16, loss_block=16) == config
    assert afmoe.collection_shapes(config) == {
        **moe.routing_state_shapes(8, 3), "gate_open": ((3,), "int32")}
    routing = afmoe.routing(config)
    assert (routing.score, routing.speed, routing.normalize, routing.scale,
            routing.sum_eps, routing.layers) == (
                "sigmoid", 0.001, True, 2.826, 1e-20, 3)
    published = afmoe.Config()
    assert published.layer_types == (("sliding_attention",) * 3
                                     + ("full_attention",)) * 8
    kinds = afmoe.layer_kinds(published)
    assert [f for *_, f in kinds] == ["dense"] * 2 + ["experts"] * 30
    assert [m for _, m, _ in kinds][3::4] == ["full_attention"] * 8
    # the cell's cut: the published layers 1-5, the dense one counted once
    cut = dataclasses.replace(published, layers_run=(1, 2, 3, 4, 5))
    assert [(m[:4], f) for _, m, f in afmoe.layer_kinds(cut)] == [
        ("slid", "dense"), ("slid", "experts"), ("full", "experts"),
        ("slid", "experts"), ("slid", "experts")]
    assert cut.expert_layers == 4
    with pytest.raises(ValueError):
        afmoe.Config(layer_types=("sliding_attention", "chunked"))
    with pytest.raises(ValueError):
        afmoe.Config(num_shared_experts=0)


def test_afmoe_logits_loss_and_every_leafs_gradient_match(tiny):
    config, ref_config, weights, params = tiny
    batch = _rows(config, 1)
    rng = np.random.default_rng(3)
    bias = jnp.asarray(rng.normal(0, 0.05, (3, 8)), jnp.float32)
    tokens, seg = batch["tokens"], batch["segment_ids"]

    def mine(p):
        total, n, (counts, opened) = afmoe.loss_terms(p, bias, tokens, seg,
                                                      config)
        return total / n, (counts, opened)

    def theirs(w):
        logits, loss, counts = reference.forward(w, tokens, seg, ref_config,
                                                 bias=bias)
        return loss, (logits, counts)

    (want_loss, (want_logits, want_counts)), want = jax.jit(
        jax.value_and_grad(theirs, has_aux=True))(weights)
    (loss, (counts, opened)), grads = jax.jit(
        jax.value_and_grad(mine, has_aux=True))(params)
    _close(jax.jit(lambda p: afmoe.apply_tokens(p, bias, tokens, seg,
                                                config))(params),
           want_logits)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts.sum()) == (config.num_experts_per_tok * tokens.size
                                 * config.expert_layers)
    # a seeded gate sits near a half of its cells, a layer
    cells = tokens.size * config.num_attention_heads * config.head_dim
    assert opened.shape == (3,) and opened.dtype == jnp.int32
    assert np.all(np.abs(np.asarray(opened) / cells - 0.5) < 0.05)
    assert set(grads) == {program.program_name(k) for k in want}
    for name, g in want.items():
        assert float(jnp.abs(g).max()) > 0, name    # every leaf is trained
        _close(grads[program.program_name(name)], g)


def _program_logits(config, params, batch, bias=None):
    return jax.jit(lambda p: afmoe.apply_tokens(
        p, _zero_bias(config) if bias is None else bias, batch["tokens"],
        batch["segment_ids"], config))(params)


def _reference_logits(ref_config, weights, batch):
    return jax.jit(lambda w: reference.forward(
        w, batch["tokens"], batch["segment_ids"], ref_config)[0])(weights)


@pytest.fixture(scope="module")
def sound(tiny):
    """The two sides as they should be, on one batch: ``(batch, the
    program's logits, the reference's)``, which agree."""
    config, ref_config, weights, params = tiny
    batch = _rows(config, 3)
    mine = _program_logits(config, params, batch)
    theirs = _reference_logits(ref_config, weights, batch)
    _close(mine, theirs)
    return batch, mine, theirs


@pytest.mark.parametrize("mistake", [
    "gate_missing", "post_norms_missing", "full_layer_rotated",
    "sliding_layer_not_rotated", "window_missing", "shared_expert_scaled"])
def test_afmoe_a_wrong_reference_fails_the_tolerance(tiny, sound, mistake,
                                                     monkeypatch):
    """The reference with one thing wrong against the program as it is: the
    logits miss 2e-5 by more than ten times.  The first five are the wrong
    references the chip's readings are taken against (``limits.json``)."""
    config, ref_config, weights, params = tiny
    batch, mine, _ = sound
    if mistake == "shared_expert_scaled":
        # scale 1 on the routed part and 2.826 on the shared one is the
        # layer's result with the scale on the wrong part, over 2.826
        ref_config = dict(ref_config, route_scale=1.0)
        weights = {k: v * config.route_scale if "shared_down" in k else v
                   for k, v in weights.items()}
    else:
        name, wrong = {
            "gate_missing": ("output_gate", lambda o, g: o),
            "post_norms_missing": ("post_norm", lambda y, w, eps: y),
            "full_layer_rotated": ("rotated", lambda mixer: True),
            "sliding_layer_not_rotated": ("rotated", lambda mixer: False),
            "window_missing": ("windowed", lambda mixer: False)}[mistake]
        monkeypatch.setattr(reference, name, wrong)
    want = _reference_logits(ref_config, weights, batch)
    assert _gap(mine, want) > 10 * TOL, _gap(mine, want)


@pytest.mark.parametrize("mistake", [
    "window_off_by_one", "embedding_not_scaled", "bfloat16_activations"])
def test_afmoe_a_wrong_program_fails_the_tolerance(tiny, sound, mistake):
    """The program with one thing wrong against the reference as it is."""
    config, _, _, params = tiny
    batch, _, want = sound
    wrong = dataclasses.replace(config, **{
        "window_off_by_one": dict(sliding_window=config.sliding_window + 1),
        "embedding_not_scaled": dict(mup_enabled=False),
        "bfloat16_activations": dict(dtype="bfloat16")}[mistake])
    got = _program_logits(wrong, params, batch)
    assert _gap(got, want) > 10 * TOL, _gap(got, want)
    if mistake == "bfloat16_activations":
        assert 100 * TOL < _gap(got, want) < 0.05


def test_afmoe_the_float8_control_moves_the_reference(tiny):
    """``lower="float8"`` rounds the products' operands and leaves the
    router, the softmax, the sigmoids and the norms alone: the loss moves."""
    config, ref_config, weights, _ = tiny
    batch = _rows(config, 4)
    sound, low = jax.jit(lambda w: [reference.forward(
        w, batch["tokens"], batch["segment_ids"], ref_config, lower=lower)[1]
        for lower in (None, "float8")])(weights)
    assert abs(float(low) - float(sound)) > 1e-5 * float(sound)
    with pytest.raises(ValueError):
        reference.forward(weights, batch["tokens"], batch["segment_ids"],
                          ref_config, lower="float4")


def test_afmoe_trainer_follows_the_reference_for_three_adamw_steps(tiny,
                                                                   tmp_path):
    """Through ``Trainer`` — nothing in it is this model's: the seeded
    weights loaded a leaf at a time, three steps, then the losses, the first
    gradient's norms as AdamW's first moment shows them and every parameter
    (1e-3 of the largest entry after three AdamW steps), the routing state
    — the biases a step of ``load_balance_coeff`` towards the mean load
    three times — and the program's counters, the gate's among them; and a
    checkpoint carries the routing state."""
    from tensorflowonspark_tpu.trainer import Trainer

    config, ref_config, _, _ = tiny
    before = obs.get_registry().snapshot()["counters"]
    trainer = Trainer("afmoe", config=config, learning_rate=1e-3,
                      devices=jax.devices()[:1])      # the cell's one chip
    names = program.load_weights(trainer, ref_config, reference, BIG_SEED)
    batches = [_rows(config, 10 + i) for i in range(3)]
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
               trainer.state.collections[afmoe.COLLECTION].items()}
    np.testing.assert_allclose(routing["bias"], theirs["bias"], atol=1e-7)
    assert {round(abs(float(b)), 6) for b in routing["bias"].ravel()} <= {
        0.0, 0.001, 0.002, 0.003}
    assert routing["bias"].any()
    np.testing.assert_array_equal(routing["counts"],
                                  np.sum(theirs["counts"], axis=0))
    weights = reference.make_weights(ref_config, BIG_SEED)
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

    # a checkpoint, a step, a restore: the whole ``moe`` collection comes
    # back (biases, counts, fullest experts, overflows, the gate's row), and
    # the counters go on from it: every step run, no step twice
    trainer.save(str(tmp_path / "ckpt"))
    trainer.step(program.host_batch(dict(batches[0])))
    extra = {k: np.asarray(v) - routing[k] for k, v in
             trainer.state.collections[afmoe.COLLECTION].items()}
    trainer.restore(str(tmp_path / "ckpt"))
    got = trainer.state.collections[afmoe.COLLECTION]
    assert set(got) == {"bias", "counts", "busiest", "overflow", "tight",
                        "gate_open"}
    for name in got:
        np.testing.assert_array_equal(got[name], routing[name])
    assert afmoe.counter_rows(config) == {
        "moe": (*moe.COUNTER_ROWS, "gate_open")}

    del trainer, mine, got
    gc.collect()
    after = obs.get_registry().snapshot()["counters"]
    grew = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    tokens = sum(b["tokens"].size for b in batches)
    counts = np.asarray(theirs["counts"])
    held = list(config.experts_held)
    # (the step between the checkpoint and the restore ran: the host's
    # counters have it, the device's go on from the restored state)
    assert grew["lm_tokens_total"] == tokens + batches[0]["tokens"].size
    assert grew["lm_loss_tokens_total"] == 4 * (2 * 48 - 6)
    assert grew["lm_documents_total"] == 4 * 2 * 3
    assert grew["attention_plain_steps_total"] == 4
    assert grew["attention_fused_steps_total"] == 0
    assert grew["moe_grouped_plain_steps_total"] == 4
    assert grew["moe_slots_total"] == (config.num_experts_per_tok * tokens
                                       * config.expert_layers * 4 // 3)
    assert grew["moe_local_slots_total"] == (
        counts[..., held].sum() + extra["counts"][:, held].sum())
    assert grew["moe_busiest_expert_slots_total"] == (
        counts.max(-1).sum() + extra["busiest"].sum())
    # by hand: a document of n tokens holds n (n + 1) / 2 pairs, under the
    # window of 12 no more than 78 + 12 (n - 12); one full layer, three
    # sliding (the dense layer's among them)
    full = sum(n * (n + 1) // 2 for row in LENGTHS for n in row)
    band = sum(n * (n + 1) // 2 if n <= 12 else 78 + 12 * (n - 12)
               for row in LENGTHS for n in row)
    assert grew["attention_full_pairs_total"] == 4 * full
    assert grew["attention_window_pairs_total"] == 4 * 3 * band
    # the gates of the three expert layers: four heads of eight numbers a
    # token; seeded, a gate is open by a half
    cells = tokens * 4 * 8 * 3
    assert grew["attention_gate_cells_total"] == cells * 4 // 3
    assert grew["attention_gate_open_total"] == (
        routing["gate_open"].sum() + extra["gate_open"].sum())
    assert abs(routing["gate_open"].sum() / cells - 0.5) < 0.02


def test_afmoe_step_names_its_scopes_forward_and_backward(tiny):
    """Every scope the cell's per-layer metrics read is on an operation of
    the lowered gradient, in the forward pass and under ``transpose``:
    ``benchmark/afmoe_scopes.py`` finds them by word, and what nests in
    ``attention`` is not found as it."""
    config, _, _, params = tiny
    batch = _rows(config, 2)
    text = jax.jit(jax.grad(lambda p: afmoe.loss_terms(
        p, _zero_bias(config), batch["tokens"], batch["segment_ids"],
        config)[0])).lower(params).as_text(debug_info=True)
    names = {n for n in re.findall(r'loc\("([^"]*)"', text) if "/" in n}
    for scope in ("embed_scale", "attention", "qk_norm_rope",
                  "attention_gate", "window_attention", "full_attention",
                  "post_norm", "mlp", "shared_expert", "moe_router",
                  "moe_dispatch", "moe_experts", "moe_combine", "lm_head"):
        word = re.compile(rf"\b{scope}\b")
        found = [n for n in names if word.search(n)]
        assert any("transpose" in n for n in found), scope
        assert any("transpose" not in n for n in found), scope
    for inner in ("qk_norm_rope", "attention_gate", "window_attention",
                  "full_attention"):
        alone = [n for n in names if re.search(rf"\b{inner}\b", n)
                 and not re.search(r"\battention\b", n)]
        assert not alone, alone[:5]
    outside = [n for n in names if re.search(r"\bpost_norm\b", n)
               and re.search(r"\b(attention|mlp|moe_\w+)\b", n)]
    assert not outside, outside[:5]


# ---------------------------------------------------------------------------
# each mechanism alone, against a few lines written by hand
# ---------------------------------------------------------------------------


def _attention_leaves(rng, d=32, heads=4, kv=2, hd=8):
    def normal(*shape):
        return jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)

    return {"a_wq": normal(d, heads * hd), "a_wk": normal(d, kv * hd),
            "a_wv": normal(d, kv * hd), "a_wg": normal(d, heads * hd),
            "a_wo": normal(heads * hd, d),
            "a_q_norm": jnp.asarray(rng.uniform(0.5, 1.5, hd), jnp.float32),
            "a_k_norm": jnp.asarray(rng.uniform(0.5, 1.5, hd), jnp.float32)}


def _gqa(leaves, h, seg, pos, **kw):
    return packed_rows.grouped_query_attention(
        leaves, "a_", h, seg, pos, heads=4, kv=2, hd=8, eps=1e-5, size=16,
        **kw)


def _by_hand(leaves, h, seg, pos, theta=None, window=None, gate=False):
    """Grouped-query attention in a few lines: every pair scored, the mask
    written out, one softmax."""
    t = h.shape[0]

    def normed(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * w

    def turned(x):
        if theta is None:
            return x
        angle = pos[:, None, None] * theta ** (-jnp.arange(4) / 4.0)
        a, b = x[..., :4], x[..., 4:]
        return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                b * jnp.cos(angle) + a * jnp.sin(angle)], -1)

    q = turned(normed((h @ leaves["a_wq"]).reshape(t, 4, 8),
                      leaves["a_q_norm"]))
    k = turned(normed((h @ leaves["a_wk"]).reshape(t, 2, 8),
                      leaves["a_k_norm"]))
    v = (h @ leaves["a_wv"]).reshape(t, 2, 8)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = (j <= i) & (seg[:, None] == seg[None, :])
    if window is not None:
        mask = mask & (i - j < window)
    out = []
    for head in range(4):
        s = q[:, head] @ k[:, head // 2].T / math.sqrt(8)
        out.append(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
                   @ v[:, head // 2])
    o = jnp.concatenate(out, -1)
    if gate:
        o = o * jax.nn.sigmoid(h @ leaves["a_wg"])
    return o @ leaves["a_wo"]


@pytest.fixture(scope="module")
def row():
    rng = np.random.default_rng(11)
    seg = jnp.asarray(np.repeat([4, 2, 7], [5, 30, 13]).astype(np.int32))
    return (_attention_leaves(rng),
            jnp.asarray(rng.normal(size=(48, 32)), jnp.float32), seg,
            packed_rows.document_positions(seg))


def test_the_gate_multiplies_attentions_output_before_wo(row):
    leaves, h, seg, pos = row
    freq = packed_rows.rope_frequencies(100.0, 4)

    def run(p):
        got, opened = _gqa(p, h, seg, pos, freq=freq, gate=True)
        return jnp.sum(got ** 2) + opened, (got, opened)

    (_, (got, opened)), grads = jax.jit(jax.value_and_grad(
        run, has_aux=True))(leaves)
    want, plain = jax.jit(lambda p: (
        _by_hand(p, h, seg, pos, theta=100.0, gate=True),
        _by_hand(p, h, seg, pos, theta=100.0)))(leaves)
    _close(got, want)
    assert _gap(got, plain) > 0.1
    assert float(opened) == pytest.approx(
        float(jnp.sum(jax.nn.sigmoid(h @ leaves["a_wg"]))), rel=1e-6)
    # the gate's weights are trained, and through the output alone: the
    # count takes no gradient
    by_hand = jax.jit(jax.grad(lambda p: jnp.sum(_by_hand(
        p, h, seg, pos, theta=100.0, gate=True) ** 2)))(leaves)
    _close(grads["a_wg"], by_hand["a_wg"])
    assert float(jnp.abs(grads["a_wg"]).max()) > 0


def test_no_frequencies_is_no_rotation_and_positions_are_not_read(row):
    """``freq=None``: queries and keys go to the scores as the norm left
    them — a full layer of this layout — and what ``pos`` holds changes
    nothing; with frequencies, positions that restart inside the row are
    not positions in the row, and the plain layer (no gate) gives an array
    as it did."""
    leaves, h, seg, pos = row
    freq = packed_rows.rope_frequencies(100.0, 4)
    got, moved, turned, in_row = jax.jit(lambda p: (
        _gqa(p, h, seg, pos, freq=None), _gqa(p, h, seg, pos * 0 + 5,
                                              freq=None),
        _gqa(p, h, seg, pos, freq=freq),
        _gqa(p, h, seg, jnp.arange(48), freq=freq)))(leaves)
    want, want_turned = jax.jit(lambda p: (
        _by_hand(p, h, seg, pos), _by_hand(p, h, seg, pos, theta=100.0)))(
            leaves)
    _close(got, want)
    np.testing.assert_array_equal(got, moved)
    _close(turned, want_turned)
    assert _gap(turned, got) > 1e-2
    _close(in_row, turned)      # RoPE is relative inside a document


@pytest.mark.parametrize("window", [1, 4, 12, 13, 30, 48])
def test_the_windows_edge_is_i_minus_j_under_w(row, window):
    """A query sees itself and ``w - 1`` keys before it: against the mask
    written out, at 2e-5, where a window one wider or one narrower misses
    it by far."""
    leaves, h, seg, pos = row
    near = [w for w in (window - 1, window + 1) if w >= 1]
    got, want, *wrong = jax.jit(lambda p: (
        _gqa(p, h, seg, pos, freq=None, window=window, gate=True)[0],
        *(_by_hand(p, h, seg, pos, window=w, gate=True)
          for w in [window] + near)))(leaves)
    _close(got, want)
    if window < 30:             # the longest document: 30 tokens
        for other in wrong:
            assert _gap(got, other) > 50 * TOL, window


def test_both_halves_of_a_layer_add_what_they_made_normed(tiny):
    """``x + rms(f(rms(x; pre)); post)``, both halves: a layer by hand from
    the pieces, and ``add_normed`` without a scale is a plain sum."""
    config, _, _, params = tiny
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(1, 48, 32)), jnp.float32)
    seg = jnp.asarray(np.repeat([4, 2, 7], [5, 30, 13])[None].astype(
        np.int32))
    pos = jax.vmap(packed_rows.document_positions)(seg)
    eps = config.rms_norm_eps

    def rms(v, w):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * w

    def both(lp):
        got, _ = afmoe._layer("sliding_attention", "dense", "l00_", config,
                              (), lp, x, seg, pos, None)
        a, _ = afmoe.attention(lp, "l00_", rms(x[0], lp["l00_norm1"]),
                               seg[0], pos[0], config, "sliding_attention")
        mid = x[0] + rms(a, lp["l00_norm2"])
        f = packed_rows.swiglu(rms(mid, lp["l00_norm3"]), lp["l00_mlp_gate"],
                               lp["l00_mlp_up"], lp["l00_mlp_down"])
        return got[0], mid + rms(f, lp["l00_norm4"]), x[0] + a + f

    got, want, siblings = jax.jit(both)(
        {k: v for k, v in params.items() if k.startswith("l00_")})
    _close(got, want)
    assert _gap(got, siblings) > 0.1    # the siblings' layer
    y = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    np.testing.assert_array_equal(packed_decoder.add_normed(x, y, None, eps),
                                  x + y)


def test_the_embedding_enters_times_the_root_of_the_width(tiny):
    config, _, _, params = tiny
    tokens = jnp.asarray([[3, 1, 4, 1, 5]])
    want = params["embed"][tokens[0]] * math.sqrt(32)
    _close(afmoe.embed(params, tokens, config)[0], want)
    off = dataclasses.replace(config, mup_enabled=False)
    np.testing.assert_array_equal(afmoe.embed(params, tokens, off)[0],
                                  params["embed"][tokens[0]])
    np.testing.assert_array_equal(
        packed_decoder.embed(params, tokens, config)[0],
        params["embed"][tokens[0]])


def test_the_scale_is_on_the_routed_part_and_the_sum_has_its_epsilon(tiny):
    """``g_e = 2.826 s_e / (sum of the chosen s + 1e-20)`` on the routed
    experts, the shared expert unscaled; where every chosen score underflows
    to zero the weights are zero, not 0 / 0."""
    config, _, _, params = tiny
    rng = np.random.default_rng(9)
    h = jnp.asarray(rng.normal(size=(24, 32)), jnp.float32)
    routing = afmoe.routing(config)

    def both(lp):
        got, counts = moe.expert_ffn(lp, "l01_", h, jnp.zeros(8), routing,
                                     shared=True)
        s = jax.nn.sigmoid(h @ lp["l01_router"])
        third = jnp.sort(s, axis=-1)[:, -3][:, None]
        chosen = s >= third                     # the three largest
        weight = 2.826 * s / jnp.sum(jnp.where(chosen, s, 0), -1,
                                     keepdims=True)
        want = packed_rows.swiglu(h, lp["l01_shared_gate"],
                                  lp["l01_shared_up"], lp["l01_shared_down"])
        for at, e in enumerate(config.experts_held):
            want = want + jnp.where(chosen[:, e], weight[:, e], 0)[:, None] \
                * packed_rows.swiglu(h, lp["l01_experts_gate"][at],
                                     lp["l01_experts_up"][at],
                                     lp["l01_experts_down"][at])
        return got, counts, want

    got, counts, want = jax.jit(both)(
        {k: v for k, v in params.items() if k.startswith("l01_")})
    _close(got, want)
    assert int(counts.sum()) == 24 * 3
    dead = jnp.full((32, 8), -1e4, jnp.float32)
    _, gates = moe.topk_route(jnp.ones((4, 32)), dead, jnp.zeros(8), top_k=3,
                              scale=2.826, sum_eps=afmoe.GATE_SUM_EPS)
    np.testing.assert_array_equal(gates, np.zeros((4, 3)))
    _, nan = moe.topk_route(jnp.ones((4, 32)), dead, jnp.zeros(8), top_k=3,
                            scale=2.826)
    assert np.isnan(np.asarray(nan)).all()


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """The share test: the routed sums of the shares that hold experts
    {0, 1}, {2, 3}, {4, 5}, {6, 7} of the router's eight — the program's
    ``expert_ffn`` told which it holds — with the shared expert, which every
    chip computes alike, counted once, add up to what the reference gives
    for the whole layer, before ``norm4`` (a norm of a sum is not the sum of
    the norms); and the cell's own share (experts 2 and 5) is what the
    reference computes when it is given that share."""
    config, ref_config, _, _ = tiny
    whole = dict(ref_config, num_experts=8, experts_held=list(range(8)))
    weights = reference.make_weights(whole, BIG_SEED)
    rng = np.random.default_rng(13)
    h = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.05, 8), jnp.float32)
    rnd = reference._rounder(None)

    def share(w, held):
        """The program's layer told it holds ``held``."""
        lp = {"s_" + k: (v[jnp.asarray(held)] if k.startswith("experts_")
                         else v) for k, v in w.items()}
        routing = afmoe.routing(dataclasses.replace(config,
                                                    experts_held=held))
        return moe.expert_ffn(lp, "s_", h, bias, routing, shared=True)

    def both(w):
        want, want_counts = reference.experts(w, h, bias, whole, rnd)
        shared = packed_rows.swiglu(h, w["shared_gate"], w["shared_up"],
                                    w["shared_down"])
        total, counts = shared, []
        for held in ((0, 1), (2, 3), (4, 5), (6, 7)):
            y, c = share(w, held)
            total = total + (y - shared)
            counts.append(c)
        mine = {k: (v[jnp.asarray([2, 5])] if k.startswith("experts_")
                    else v) for k, v in w.items()}
        part, _ = reference.experts(mine, h, bias, ref_config, rnd)
        return (want, want_counts, total, jnp.stack(counts),
                share(w, (2, 5))[0], part)

    want, want_counts, total, counts, cell, part = jax.jit(both)(
        {k[len("l02/"):]: v for k, v in weights.items()
         if k.startswith("l02/")})
    _close(total, want)
    for c in counts:
        np.testing.assert_array_equal(c, want_counts)
    _close(cell, part)
    assert _gap(cell, want) > 0.01      # a share is not the layer


def test_packed_documents_restart_their_positions(tiny):
    """A document's logits are the same wherever it lies in a row and
    whatever lies before it: positions, both masks and the loss restart at
    every document's first token."""
    config, _, _, params = tiny
    rng = np.random.default_rng(17)
    doc = rng.integers(0, 64, 20)
    other = rng.integers(0, 64, 28)
    rows = {"tokens": np.stack([np.concatenate([doc, other]),
                                np.concatenate([other, doc])]).astype(
                                    np.int32),
            "segment_ids": np.stack([np.repeat([1, 2], [20, 28]),
                                     np.repeat([5, 3], [28, 20])]).astype(
                                         np.int32)}
    logits = _program_logits(config, params, rows)
    _close(logits[0, :20], logits[1, 28:])
    _close(logits[0, 20:], logits[1, :28])


# ---------------------------------------------------------------------------
# the shared code's defaults leave the siblings what they were
# ---------------------------------------------------------------------------


def _primitives(jaxpr) -> list:
    """Every primitive's name in ``jaxpr`` and in what it calls."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out.extend(_primitives(inner))
    return out


@pytest.mark.parametrize("lib", [lfm2_moe, mellum_moe])
def test_the_grouped_query_layers_defaults_add_no_gate(lib):
    """No ``gate`` and frequencies given, as ``lfm2_moe`` and ``mellum_moe``
    call it: the layer traces no sigmoid, two head norms, and its rotation;
    the result is an array and no tuple."""
    config = lib.Config.tiny()
    params = {k: jnp.zeros(s, jnp.float32)
              for k, s in lib.leaf_shapes(config).items()}
    prefix, kinds = next((p, k) for p, *k in lib.layer_kinds(config)
                         if k[0].endswith("attention"))
    t = config.seq_len
    h = jnp.zeros((t, config.hidden_size), jnp.float32)
    seg = jnp.zeros((t,), jnp.int32)
    args = (params, prefix, h, seg, seg, config) + (
        (kinds[0],) if lib is mellum_moe else ())
    jaxpr = jax.make_jaxpr(lambda p, x: lib.attention(
        p, *args[1:2], x, *args[3:]))(params, h)
    found = _primitives(jaxpr.jaxpr)
    assert "logistic" not in found
    assert found.count("rsqrt") == 2            # q_norm and k_norm
    assert found.count("cos") == 2 and found.count("sin") == 2
    assert len(jaxpr.out_avals) == 1


def test_the_gated_position_free_layer_traces_a_sigmoid_and_no_rotation():
    config = afmoe.Config.tiny()
    params = {k: jnp.zeros(s, jnp.float32)
              for k, s in afmoe.leaf_shapes(config).items()}
    seg = jnp.zeros((48,), jnp.int32)
    found = _primitives(jax.make_jaxpr(lambda p, x: afmoe.attention(
        p, "l02_", x, seg, seg, config, "full_attention"))(
            params, jnp.zeros((48, 32))).jaxpr)
    assert found.count("logistic") == 1 and "cos" not in found
    assert found.count("rsqrt") == 2


@pytest.mark.parametrize("lib", [mla_moe, lfm2_moe, kimi_linear, mellum_moe])
@pytest.mark.parametrize("ffn", ["dense", "experts"])
def test_the_feed_forwards_defaults_add_no_norm(lib, ffn):
    """No ``post_norm``, as the four sibling expert models call
    ``feed_forward``: one norm (``norm2``, before the feed-forward), and
    ``embed`` without a multiplier multiplies nothing."""
    config = lib.Config.tiny()
    kinds = [k for _, *k in (lib.layer_prefixes(config) if lib is mla_moe
                             else lib.layer_kinds(config))]
    if ffn not in {k[-1] for k in kinds}:
        pytest.skip(f"{lib.__name__} has no {ffn} layer")
    prefix = "l00_" if ffn == "dense" else next(
        p for p, *k in (lib.layer_prefixes(config) if lib is mla_moe
                        else lib.layer_kinds(config)) if k[-1] == "experts")
    params = {k: jnp.zeros(s, jnp.float32)
              for k, s in lib.leaf_shapes(config).items()}
    routing = lib.routing(config)
    x = jnp.zeros((1, config.seq_len, config.hidden_size), jnp.float32)
    shared = (prefix + "shared_gate") in params
    found = _primitives(jax.make_jaxpr(
        lambda p, v: packed_decoder.feed_forward(
            p, prefix, ffn, v, jnp.zeros(routing.n_experts), 1e-5, routing,
            shared=shared))(params, x).jaxpr)
    assert found.count("rsqrt") == 1
    with_post = _primitives(jax.make_jaxpr(
        lambda p, v: packed_decoder.feed_forward(
            p, prefix, ffn, v, jnp.zeros(routing.n_experts), 1e-5, routing,
            shared=shared, post_norm="norm1"))(params, x).jaxpr)
    assert with_post.count("rsqrt") == 2
    tokens = jnp.zeros((1, 4), jnp.int32)
    plain = _primitives(jax.make_jaxpr(lambda p: packed_decoder.embed(
        p, tokens, config))(params).jaxpr)
    assert "mul" not in plain


def test_a_recomputed_layer_keeps_what_its_post_norms_read(tiny):
    """``x + rms(f)`` needs ``f`` in the backward pass where a sibling's
    ``x + f`` does not: a layer that keeps nothing but attention's output
    makes the routed part a third time (the compiled gradient holds its
    ``switch`` over the row counts three times an expert layer: forward,
    the recomputation, the part's own backward rule), a layer that keeps
    ``afmoe.SAVED`` twice, as a sibling does; the gradient is the same
    either way."""
    config, _, _, params = tiny
    batch = _rows(config, 6)

    def gradient(decoder):
        return jax.jit(jax.grad(lambda p: (lambda total, n, _: total / n)(
            *decoder.next_token_terms(p, _zero_bias(config),
                                      batch["tokens"], batch["segment_ids"],
                                      config)))).lower(params).compile()

    def switches(compiled):
        return len(re.findall(r" conditional\(", compiled.as_text()))

    assert afmoe._DECODER.saved == afmoe.SAVED == (
        "attention_gate", "mixer_added", "ffn_added")
    kept = gradient(afmoe._DECODER)
    bare = gradient(dataclasses.replace(afmoe._DECODER, saved=()))
    assert switches(kept) == 2 * config.expert_layers
    assert switches(bare) == 3 * config.expert_layers
    want, got = bare(params), kept(params)
    for name in want:
        _close(got[name], want[name])
