"""The short-convolution expert decoder (``models/lfm2_moe.py``: the gated
short convolution and QK-normed RoPE GQA over ``packed_rows``, the routed
layer and the routing state of ``parallel/moe.py``) against the plain
reference of the ``lfm2_8b_a1b`` configuration, at ``Config.tiny()`` in
float32 on the CPU.

Tolerances: both sides compute in float32 with products at the highest
precision, so they differ only by the order of their sums (the program's
sorted grouped products, running softmax and padded shifts against the
reference's masked dense experts, whole softmax and rolled shifts): 2e-5
relative to the largest entry covers what a few hundred float32 additions in
another order move, is 1,000 times tighter than a forgotten boundary,
position, gate or norm would need, and bfloat16 activations miss it by two
orders of magnitude (a test below shows they do).
"""

import dataclasses
import gc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.configs.lfm2_8b_a1b import program, reference
from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.models import (granite_hybrid, lfm2_moe, mla_moe,
                                          packed_decoder, packed_rows)
from tensorflowonspark_tpu.parallel import moe

BIG_SEED = 2 ** 31 + 4021           # the driver's seeds pass 32 signed bits
TOL = 2e-5


def _tiny_dict(config: lfm2_moe.Config, learning_rate=1e-3) -> dict:
    """``Config.tiny()`` under the keys the configuration's file has."""
    n = len(config.layer_types)
    return {
        "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "moe_intermediate_size": config.moe_intermediate_size,
        "layer_types": list(config.layer_types),
        "layers_run": list(range(n)), "num_hidden_layers": n,
        "num_dense_layers": config.num_dense_layers,
        "num_experts": len(config.experts_held),
        "experts_held": list(config.experts_held),
        "published": {"num_experts": config.num_experts,
                      "num_hidden_layers": n},
        "num_experts_per_tok": config.num_experts_per_tok,
        "routed_scaling_factor": config.routed_scaling_factor,
        "norm_topk_prob": config.norm_topk_prob,
        "use_expert_bias": config.use_expert_bias,
        "num_attention_heads": config.num_attention_heads,
        "num_key_value_heads": config.num_key_value_heads,
        "conv_L_cache": config.conv_L_cache, "conv_bias": False,
        "rope_theta": config.rope_theta, "norm_eps": config.norm_eps,
        "vocab_size": config.vocab_size,
        "bias_update_speed": config.bias_update_speed,
        "init_std": config.init_std, "dtype": config.dtype,
        "seq_len": config.seq_len,
        "parameters": lfm2_moe.parameter_count(config),
        "program_model": "lfm2_moe",
        "optimizer": dict(lfm2_moe.ADAMW, name="adamw",
                          learning_rate=learning_rate),
    }


def _rows(config: lfm2_moe.Config, n: int, seed: int) -> dict:
    """Packed rows of three or four documents of uneven length."""
    rng = np.random.default_rng(seed)
    t = config.seq_len
    seg = np.stack([np.searchsorted(
        np.sort(rng.choice(np.arange(1, t), size=3, replace=False)),
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
    config = lfm2_moe.Config.tiny()
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
# program against reference
# ---------------------------------------------------------------------------


def test_lfm2_tiny_is_the_issues_size_and_names_the_references_leaves(tiny):
    config, ref_config, weights, params = tiny
    assert lfm2_moe.layer_kinds(config) == [
        ("l00_", "conv", "dense"), ("l01_", "full_attention", "experts"),
        ("l02_", "conv", "experts")]
    assert (config.num_experts, config.experts_held,
            config.num_experts_per_tok, config.head_dim) == (8, (2, 5), 2, 16)
    shapes = lfm2_moe.leaf_shapes(config)
    assert {k: tuple(v.shape) for k, v in params.items()} == shapes
    assert list(shapes) == [program.program_name(n)
                            for n in reference.leaf_shapes(ref_config)]
    assert lfm2_moe.parameter_count(config) == sum(
        int(np.prod(v.shape)) for v in weights.values())
    assert lfm2_moe.collection_shapes(config) == moe.routing_state_shapes(
        8, 2)
    assert lfm2_moe.Config().layer_types == tuple(
        ["conv"] * 2 + ["full_attention", "conv", "conv", "conv"] * 4
        + ["full_attention", "conv", "conv"] * 2)
    with pytest.raises(ValueError):
        lfm2_moe.Config(layer_types=("conv", "mamba"))


def test_lfm2_logits_loss_and_every_leafs_gradient_match(tiny):
    """With correction biases that move some choices (zero biases are the
    Trainer test's)."""
    config, ref_config, weights, params = tiny
    batch = _rows(config, 2, 1)
    bias = _bias(config)
    tokens, seg = batch["tokens"], batch["segment_ids"]

    def mine(p):
        total, n, counts = lfm2_moe.loss_terms(p, bias, tokens, seg, config)
        return total / n, counts

    def theirs(w):
        logits, loss, counts = reference.forward(w, tokens, seg, ref_config,
                                                 bias)
        return loss, (logits, counts)

    (want_loss, (want_logits, want_counts)), want = jax.jit(
        jax.value_and_grad(theirs, has_aux=True))(weights)
    (loss, counts), grads = jax.jit(
        jax.value_and_grad(mine, has_aux=True))(params)
    _close(jax.jit(lambda p: lfm2_moe.apply_tokens(p, bias, tokens, seg,
                                                   config))(params),
           want_logits)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts.sum()) == (config.num_experts_per_tok * tokens.size
                                 * config.expert_layers)
    # the bias moved some choices: the counts are not the zero bias's
    assert not np.array_equal(counts, reference.forward(
        weights, tokens, seg, ref_config)[2])
    assert set(grads) == {program.program_name(k) for k in want}
    for name, g in want.items():
        assert float(jnp.abs(g).max()) > 0, name    # every leaf is trained
        _close(grads[program.program_name(name)], g)


def test_lfm2_bfloat16_where_float32_is_stated_fails_the_tolerance(tiny):
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
    logits = jax.jit(lambda p: lfm2_moe.apply_tokens(
        p, bias, batch["tokens"], batch["segment_ids"], low))(params)
    gap = float(jnp.abs(logits - want_logits).max()
                / jnp.abs(want_logits).max())
    assert 100 * TOL < gap < 0.05, gap
    total, n, _ = jax.jit(lambda p: lfm2_moe.loss_terms(
        p, bias, batch["tokens"], batch["segment_ids"], low))(params)
    assert float(total / n) == pytest.approx(float(want_loss), rel=1e-2)


def test_lfm2_the_float8_control_moves_the_reference(tiny):
    """``lower="float8"`` rounds the products' operands and leaves the
    router alone: the loss moves, the choices do not have to."""
    config, ref_config, weights, _ = tiny
    batch = _rows(config, 2, 4)
    sound, low = jax.jit(lambda w: [reference.forward(
        w, batch["tokens"], batch["segment_ids"], ref_config, lower=lower)[1]
        for lower in (None, "float8")])(weights)
    assert abs(float(low) - float(sound)) > 1e-5 * float(sound)
    with pytest.raises(ValueError):
        reference.forward(weights, batch["tokens"], batch["segment_ids"],
                          ref_config, lower="float4")


def test_lfm2_trainer_follows_the_reference_for_three_adamw_steps(tiny):
    """Through ``Trainer`` — nothing in it is this model's: the seeded
    weights loaded a leaf at a time, three steps, then the losses, the first
    gradient's norms as AdamW's first moment shows them, every parameter and
    the routing biases.  After three steps of AdamW a difference of 1e-6 in
    a gradient whose second moment is still tiny can move an update by its
    whole size, so the parameters are held to 1e-3 of their largest entry;
    the change's norm, which the benchmark compares, to 1e-3.  The biases
    move by whole steps of 0.001 and have to agree to rounding; so do the
    counts behind them."""
    from tensorflowonspark_tpu.trainer import Trainer

    config, ref_config, _, _ = tiny
    before = obs.get_registry().snapshot()["counters"]
    trainer = Trainer("lfm2_moe", config=config, learning_rate=1e-3,
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
               trainer.state.collections[lfm2_moe.COLLECTION].items()}
    np.testing.assert_allclose(routing["bias"], theirs["bias"], atol=1e-7)
    assert np.abs(routing["bias"]).max() == pytest.approx(0.003, rel=1e-5)
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

    # the program's counters: the host batch's, and — a step late, the last
    # when the trainer goes — what the device decided, under the names the
    # other expert model's counters have
    del trainer, mine
    gc.collect()
    after = obs.get_registry().snapshot()["counters"]
    grew = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    tokens = sum(b["tokens"].size for b in batches)
    counts = np.asarray(theirs["counts"])
    held = list(config.experts_held)
    assert grew["lm_tokens_total"] == tokens
    assert grew["lm_loss_tokens_total"] == sum(
        (b["segment_ids"][:, 1:] == b["segment_ids"][:, :-1]).sum()
        for b in batches)
    assert grew["lm_documents_total"] == 3 * 2 * 4
    assert grew["attention_plain_steps_total"] == 3
    assert grew["attention_fused_steps_total"] == 0
    assert grew["moe_slots_total"] == (config.num_experts_per_tok * tokens
                                       * config.expert_layers)
    assert grew["moe_local_slots_total"] == counts[..., held].sum()
    assert grew["moe_busiest_expert_slots_total"] == counts.max(-1).sum()
    assert grew["moe_overflow_layers_total"] == int((
        counts[..., held].sum(-1) > moe.prefix_rows(
            config.num_experts_per_tok * batches[0]["tokens"].size,
            len(held), config.num_experts)).sum())


def test_lfm2_without_the_expert_bias_the_bias_neither_chooses_nor_moves(tiny):
    config, _, _, params = tiny
    off = dataclasses.replace(config, use_expert_bias=False)
    batch = _rows(config, 2, 7)
    state = {name: jnp.zeros(shape, dtype) for name, (shape, dtype) in
             lfm2_moe.collection_shapes(config).items()}
    pushed = dict(state, bias=state["bias"].at[:, 0].set(10.0))
    _, new = jax.jit(lfm2_moe.make_loss_fn(None, off))(
        params, {lfm2_moe.COLLECTION: pushed}, batch)
    _, plain = jax.jit(lfm2_moe.make_loss_fn(None, config))(
        params, {lfm2_moe.COLLECTION: state}, batch)
    new, plain = new[lfm2_moe.COLLECTION], plain[lfm2_moe.COLLECTION]
    np.testing.assert_array_equal(new["bias"], pushed["bias"])
    np.testing.assert_array_equal(new["counts"], plain["counts"])
    assert float(jnp.abs(plain["bias"]).max()) == pytest.approx(0.001)


# ---------------------------------------------------------------------------
# what moved: one function, two callers
# ---------------------------------------------------------------------------


def test_the_shared_pieces_exist_once():
    """``causal_conv``, the grouped-query layer (since PR 47, with the
    rotation it calls), ``document_positions`` and the routing state are
    ``packed_rows``'s and ``parallel/moe.py``'s; the models that call them
    hold the same objects, not copies."""
    assert granite_hybrid.causal_conv is packed_rows.causal_conv
    assert lfm2_moe.causal_conv is packed_rows.causal_conv
    assert lfm2_moe.grouped_query_attention \
        is packed_rows.grouped_query_attention
    assert not hasattr(lfm2_moe, "rope")
    # GLM's rotation went with its latent attention into ``packed_rows``,
    # and the positions both models' layers read are the skeleton's
    assert mla_moe.packed_rows is packed_rows and not hasattr(mla_moe, "rope")
    for lib in (lfm2_moe, mla_moe):
        assert lib._DECODER.positions
        assert not hasattr(lib, "document_positions")
    assert packed_decoder.document_positions is packed_rows.document_positions
    assert mla_moe.COLLECTION == lfm2_moe.COLLECTION == "moe"
    glm = mla_moe.Config.tiny()
    assert mla_moe.collection_shapes(glm) == moe.routing_state_shapes(
        glm.n_routed_experts, glm.expert_layers)


def test_routing_state_is_a_function_of_experts_layers_choices_and_speed():
    """``moe.step_routing_state`` with no model's ``Config``: 4 experts, one
    held, 16 tokens choosing 2 (8 of 32 slots if the router is even, 24 fit
    ``moe.prefix_rows`` and, a whole row tile being more, ``tight_rows``);
    a speed of 0 leaves the bias."""
    counts = jnp.asarray([[16, 0, 8, 8], [2, 2, 26, 2]], jnp.int32)
    state = {name: jnp.ones(shape, dtype) for name, (shape, dtype) in
             moe.routing_state_shapes(4, 2).items()}
    new = moe.step_routing_state(state, counts, (2,), top_k=2, speed=0.01,
                                 tokens=16)
    np.testing.assert_allclose(new["bias"], [[0.99, 1.01, 1.0, 1.0],
                                             [1.01, 1.01, 0.99, 1.01]],
                               atol=1e-7)
    assert new["counts"].tolist() == [[17, 1, 9, 9], [3, 3, 27, 3]]
    assert new["busiest"].tolist() == [17, 27]
    assert new["overflow"].tolist() == [1, 2]
    assert new["tight"].tolist() == [2, 1]
    still = moe.step_routing_state(state, counts, (2,), top_k=2, speed=0.0,
                                   tokens=16)
    np.testing.assert_array_equal(still["bias"], state["bias"])
    shown = moe.routing_counters(new, (2,))
    assert shown["moe_local_slots_total"].tolist() == [[9], [27]]
    assert set(shown) == {"moe_slots_total", "moe_local_slots_total",
                          "moe_busiest_expert_slots_total",
                          "moe_overflow_layers_total",
                          "moe_tight_layers_total"}


def test_the_gates_sum_epsilon_is_an_argument_of_the_call():
    """``topk_route(sum_eps=1e-6)``: the chosen scores over (their sum +
    1e-6), as the public ``lfm2_moe`` implementation writes it; without it
    (GLM's call) the gates add up to the scale."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((12, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((8, 6)), jnp.float32)
    chosen, plain = moe.topk_route(h, w, jnp.zeros(6), top_k=2, scale=1.0)
    same, gates = moe.topk_route(h, w, jnp.zeros(6), top_k=2, scale=1.0,
                                 sum_eps=0.5)
    np.testing.assert_array_equal(chosen, same)
    np.testing.assert_allclose(plain.sum(-1), 1.0, rtol=1e-6)
    scores = np.take_along_axis(np.asarray(jax.nn.sigmoid(h @ w)),
                                np.asarray(chosen), 1)
    np.testing.assert_allclose(gates, scores / (scores.sum(-1, keepdims=True)
                                                + 0.5), rtol=1e-6)


# ---------------------------------------------------------------------------
# the expert layer at a quarter share, no shared expert
# ---------------------------------------------------------------------------


def _expert_layer(seed=0, tokens=48, d=32, f=16, n_experts=8):
    rng = np.random.default_rng(seed)
    g = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]),  # noqa: E731
                               jnp.float32)
    return {"h": jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32),
            "router": g(d, n_experts), "experts_gate": g(n_experts, d, f),
            "experts_up": g(n_experts, d, f),
            "experts_down": g(n_experts, f, d)}


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips hold two of eight experts each (the cell: four of 8 of
    32).  The parts of the result that the four shares give
    (``routed_experts`` told which two; nothing is computed alike on every
    chip: the layout has no shared expert) add up to what the uncut
    reference gives for the whole layer, to float32 rounding; every share
    reports the same counts, and every slot lands on exactly one share.  At
    a quarter share three times the even share is 36 of the 48 x 2 slots'
    rows (``moe.prefix_rows``)."""
    w = _expert_layer()
    bias = jnp.asarray(np.random.default_rng(1).standard_normal(8) * 0.05,
                       jnp.float32)
    ref_config = {"num_experts_per_tok": 2, "norm_topk_prob": True,
                  "routed_scaling_factor": 1.0, "use_expert_bias": True,
                  "experts_held": list(range(8)),
                  "published": {"num_experts": 8}}
    whole, want_counts = reference.experts(w, w["h"], bias, ref_config,
                                           lambda a: a)
    assert moe.prefix_rows(2 * 48, 2, 8) == 72
    total, landed = jnp.zeros_like(w["h"]), 0
    for share in range(4):
        held = (2 * share, 2 * share + 1)
        take = np.asarray(held)
        part, counts = jax.jit(lambda h, held=held, take=take: (
            moe.routed_experts(
                h, w["router"], bias, w["experts_gate"][take],
                w["experts_up"][take], w["experts_down"][take], held,
                top_k=2, scale=1.0, sum_eps=lfm2_moe.GATE_SUM_EPS)))(w["h"])
        np.testing.assert_array_equal(counts, want_counts)
        total = total + part
        landed += int(np.asarray(counts)[take].sum())
    assert landed == 2 * w["h"].shape[0]        # every slot on one share
    _close(total, whole, tol=1e-6)


# ---------------------------------------------------------------------------
# documents: the convolution's look-back, positions, the mask
# ---------------------------------------------------------------------------


def _two_documents(rng, t, cut, d):
    seg = (np.arange(t) >= cut).astype(np.int32)
    return jnp.asarray(rng.standard_normal((t, d)), jnp.float32), seg


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_short_convolution_stops_at_a_documents_first_token(tiny, side):
    """Two documents packed give each what it gives alone: the mixer on the
    row's halves apart is the mixer on the row, and the second document's
    first two tokens (three taps) do change when the boundary is taken
    away."""
    config, _, weights, params = tiny
    rng = np.random.default_rng(11)
    t, cut = 24, 9
    h, seg = _two_documents(rng, t, cut, config.hidden_size)
    if side == "program":
        mix = lambda x, s: lfm2_moe.conv_mixer(  # noqa: E731
            params, "l00_", x, jnp.asarray(s))
    else:
        w = {k[4:]: v for k, v in weights.items() if k.startswith("l00/")}
        mix = lambda x, s: reference.conv_mixer(  # noqa: E731
            w, x, jnp.asarray(s), lambda a: a)
    packed = mix(h, seg)
    _close(packed[:cut], mix(h[:cut], seg[:cut]), tol=1e-6)
    _close(packed[cut:], mix(h[cut:], seg[cut:]), tol=1e-6)
    one = mix(h, np.zeros(t, np.int32))
    assert float(jnp.abs(one[cut:cut + 2] - packed[cut:cut + 2]).max()) > (
        0.1 * float(jnp.abs(packed).max()))
    _close(one[cut + 2:], packed[cut + 2:], tol=1e-6)
    _close(one[:cut], packed[:cut], tol=1e-6)


def test_causal_conv_is_the_three_shifted_sums(tiny):
    """``packed_rows.causal_conv`` with three taps and no bias against the
    reference's rolled sums, document boundaries inside the look-back."""
    rng = np.random.default_rng(12)
    v = jnp.asarray(rng.standard_normal((20, 6)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((3, 6)), jnp.float32)
    seg = jnp.asarray([0] * 1 + [1] * 2 + [2] * 9 + [3] * 8, jnp.int32)
    got = packed_rows.causal_conv(v, taps, 0.0, seg)
    _close(got, reference.short_conv(v, taps, seg), tol=1e-6)
    by_hand = taps[2] * v[3] + taps[1] * 0 + taps[0] * 0    # a first token
    _close(got[3], by_hand, tol=1e-6)
    by_hand = taps[2] * v[5] + taps[1] * v[4] + taps[0] * v[3]
    _close(got[5], by_hand, tol=1e-6)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_attention_and_rope_stop_at_a_documents_first_token(tiny, side):
    """The QK-normed RoPE attention on two packed documents gives each what
    it gives alone — mask and positions both: with the positions running on
    across the boundary the second document's output is still its own
    (RoPE is relative), so the positions are checked on their own too."""
    config, ref_config, weights, params = tiny
    rng = np.random.default_rng(13)
    t, cut = 24, 10
    h, seg = _two_documents(rng, t, cut, config.hidden_size)

    def mix(x, s):
        s = np.asarray(s)
        if side == "program":
            pos = packed_rows.document_positions(jnp.asarray(s))
            return lfm2_moe.attention(params, "l01_", x, jnp.asarray(s), pos,
                                      dataclasses.replace(
                                          config, attention_block=8))
        w = {k[4:]: v for k, v in weights.items() if k.startswith("l01/")}
        return reference.attention(w, x, jnp.asarray(s), jnp.asarray(
            reference.positions(s[None])[0]), ref_config, lambda a: a)

    packed = mix(h, seg)
    _close(packed[:cut], mix(h[:cut], seg[:cut]), tol=1e-5)
    _close(packed[cut:], mix(h[cut:], seg[cut:]), tol=1e-5)
    one = mix(h, np.zeros(t, np.int32))
    assert float(jnp.abs(one[cut:] - packed[cut:]).max()) > (
        0.1 * float(jnp.abs(packed).max()))
    want = [0, 1, 2, 0, 1, 0, 1, 2, 3, 0]
    s = np.array([3, 3, 3, 5, 5, 9, 9, 9, 9, 2], np.int32)
    assert packed_rows.document_positions(jnp.asarray(s)).tolist() == want
    assert reference.positions(s[None])[0].tolist() == want


def test_rope_turns_the_halves_by_the_position(tiny):
    """``packed_rows.rope`` against the reference's ``rotate`` on heads of
    16 at theta 1e6, and by hand: pair ``i`` of a head is (x_i, x_{i+8}),
    turned by ``pos * theta ** (-i / 8)``; position 0 leaves a head as it
    is."""
    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.standard_normal((5, 2, 3, 16)), jnp.float32)
    pos = jnp.asarray([0, 1, 2, 0, 7], jnp.int32)
    got = packed_rows.rope(x, pos, packed_rows.rope_frequencies(1e6, 8))
    _close(got, reference.rotate(x, pos, 1e6), tol=1e-6)
    np.testing.assert_array_equal(got[0], x[0])
    angle = 7 * 1e6 ** (-3 / 8)
    a, b = float(x[4, 1, 2, 3]), float(x[4, 1, 2, 11])
    assert float(got[4, 1, 2, 3]) == pytest.approx(
        a * np.cos(angle) - b * np.sin(angle), rel=1e-5)
    assert float(got[4, 1, 2, 11]) == pytest.approx(
        b * np.cos(angle) + a * np.sin(angle), rel=1e-5)


def test_a_documents_logits_do_not_change_when_another_document_does(tiny):
    """The whole model: replace the second document's tokens, and move the
    row's first document behind another — its logits stay, at the places it
    now has.  (The routing is a token's own affair.)"""
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
    logits = jax.jit(lambda p: lfm2_moe.apply_tokens(
        p, bias, np.stack([tokens, changed, moved]),
        np.stack([seg, seg, moved_seg]), config))(params)
    _close(logits[1, :11], logits[0, :11], tol=1e-6)
    _close(logits[1, 23:], logits[0, 23:], tol=1e-6)
    assert float(jnp.abs(logits[1, 11:23] - logits[0, 11:23]).max()) > 1e-3
    _close(logits[2, 12:23], logits[0, :11], tol=1e-5)
    _close(logits[2, :12], logits[0, 11:23], tol=1e-5)


def test_query_heads_read_the_key_head_of_their_group(tiny):
    """Two query heads on one key head at ``Config.tiny()``: make the
    layer's two query heads the same and their outputs are the same; the
    reference repeats a key head over its group in the same order."""
    config, ref_config, weights, params = tiny
    rng = np.random.default_rng(15)
    four = dataclasses.replace(config, num_attention_heads=4,
                               num_key_value_heads=2, hidden_size=32)
    hd, d = four.head_dim, 32
    assert hd == 8
    p = {"wq": rng.standard_normal((d, 32)), "wk": rng.standard_normal((d, 16)),
         "wv": rng.standard_normal((d, 16)), "wo": rng.standard_normal((32, d)),
         "q_norm": 1 + 0.1 * rng.standard_normal(hd),
         "k_norm": 1 + 0.1 * rng.standard_normal(hd)}
    p = {k: jnp.asarray(v / np.sqrt(len(v)), jnp.float32) if v.ndim == 2
         else jnp.asarray(v, jnp.float32) for k, v in p.items()}
    h = jnp.asarray(rng.standard_normal((16, d)), jnp.float32)
    seg = jnp.asarray([0] * 7 + [1] * 9, jnp.int32)
    pos = packed_rows.document_positions(seg)
    mine = lfm2_moe.attention({"x_" + k: v for k, v in p.items()}, "x_", h,
                              seg, pos, four)
    ref = dict(ref_config, num_attention_heads=4, num_key_value_heads=2)
    _close(mine, reference.attention(p, h, seg, pos, ref, lambda a: a),
           tol=1e-5)


# ---------------------------------------------------------------------------
# scopes, checkpoints
# ---------------------------------------------------------------------------


def test_lfm2_step_names_its_scopes_forward_and_backward(tiny):
    """Every scope the cell's per-layer metrics read is on an operation of
    the lowered gradient, in the forward pass and under ``transpose(jvp(``:
    ``benchmark/conv_scopes.py`` and ``moe_scopes.py`` find them by word."""
    import re

    config, _, _, params = tiny
    batch = _rows(config, 1, 2)
    bias = jnp.zeros((config.expert_layers, config.num_experts))
    text = jax.jit(jax.grad(lambda p: lfm2_moe.loss_terms(
        p, bias, batch["tokens"], batch["segment_ids"], config)[0])).lower(
            params).as_text(debug_info=True)
    # an operation's name is its path of scopes and transformations (a bare
    # word is a frame of the call stack, not an operation)
    names = {n for n in re.findall(r'loc\("([^"]*)"', text) if "/" in n}
    for scope in ("conv_mixer", "conv_in_proj", "short_conv", "conv_out_proj",
                  "attention", "qk_norm_rope", "mlp", "moe_router",
                  "moe_dispatch", "moe_experts", "moe_combine", "lm_head"):
        word = re.compile(rf"\b{scope}\b")
        found = [n for n in names if word.search(n)]
        assert any("transpose(jvp(" in n or "transpose" in n for n in found), \
            scope
        assert any("transpose" not in n for n in found), scope
    for inner, outer in (("short_conv", "conv_mixer"),
                         ("qk_norm_rope", "attention")):
        alone = [n for n in names if re.search(rf"\b{inner}\b", n)
                 and not re.search(rf"\b{outer}\b", n)]
        assert not alone, alone[:5]


def test_lfm2_checkpoints_carry_the_routing_state(tiny, tmp_path):
    """A second model with a non-gradient collection goes through
    ``Trainer.save`` / ``restore`` as the first did: the whole ``moe``
    collection comes back, and the counters go on from it."""
    from tensorflowonspark_tpu.trainer import Trainer

    config = tiny[0]
    before = obs.get_registry().snapshot()["counters"].get(
        "moe_slots_total", 0)
    trainer = Trainer("lfm2_moe", config=config, devices=jax.devices()[:1])
    batch = lfm2_moe.example_batch(config, 2, seq_len=config.seq_len)
    trainer.step(batch)
    trainer.step(batch)
    want = {k: np.asarray(v) for k, v in
            trainer.state.collections[lfm2_moe.COLLECTION].items()}
    per_step = (config.num_experts_per_tok * 2 * config.seq_len
                * config.expert_layers)
    assert want["counts"].sum() == 2 * per_step
    assert np.abs(want["bias"]).max() == pytest.approx(0.002, rel=1e-5)
    trainer.save(str(tmp_path / "ckpt"))
    trainer.step(batch)
    trainer.restore(str(tmp_path / "ckpt"))
    got = trainer.state.collections[lfm2_moe.COLLECTION]
    assert set(got) == {"bias", "counts", "busiest", "overflow", "tight"}
    for name in got:
        np.testing.assert_array_equal(got[name], want[name])
    trainer.step(batch)
    del trainer
    gc.collect()
    assert obs.get_registry().snapshot()["counters"]["moe_slots_total"] \
        - before == 4 * per_step        # every step run, no step twice
