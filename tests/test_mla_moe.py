"""The latent-attention mixture-of-experts decoder (``models/mla_moe.py``,
``parallel/moe.py::routed_experts``) against the plain reference of the
``glm_4_7_flash`` configuration, at ``Config.tiny()`` in float32 on the CPU.

Tolerances: both sides compute in float32 with products at the highest
precision, so they differ only by the order of their sums (the program's
sorted grouped products and running softmax against the reference's masked
dense experts and whole softmax): 2e-5 relative to the largest entry covers
what a few hundred float32 additions in another order move, and is 1,000
times tighter than a forgotten boundary, position or gate would need.
"""

import dataclasses
import gc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.configs.glm_4_7_flash import program, reference
from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.models import mla_moe, packed_rows
from tensorflowonspark_tpu.parallel import moe

BIG_SEED = 2 ** 31 + 54321          # the driver's seeds pass 32 signed bits
TOL = 2e-5


def _tiny_dict(config: mla_moe.Config, learning_rate=1e-3) -> dict:
    """``Config.tiny()`` under the keys the configuration's file has."""
    return {
        "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "moe_intermediate_size": config.moe_intermediate_size,
        "num_hidden_layers": config.num_hidden_layers,
        "first_k_dense_replace": config.first_k_dense_replace,
        "n_routed_experts": len(config.experts_held),
        "experts_held": list(config.experts_held),
        "published": {"n_routed_experts": config.n_routed_experts,
                      "num_hidden_layers": config.num_hidden_layers},
        "init_std": config.init_std,
        "n_shared_experts": config.n_shared_experts,
        "num_experts_per_tok": config.num_experts_per_tok,
        "routed_scaling_factor": config.routed_scaling_factor,
        "norm_topk_prob": config.norm_topk_prob,
        "num_attention_heads": config.num_attention_heads,
        "q_lora_rank": config.q_lora_rank,
        "kv_lora_rank": config.kv_lora_rank,
        "qk_nope_head_dim": config.qk_nope_head_dim,
        "qk_rope_head_dim": config.qk_rope_head_dim,
        "v_head_dim": config.v_head_dim, "rope_theta": config.rope_theta,
        "rms_norm_eps": config.rms_norm_eps,
        "num_nextn_predict_layers": config.num_nextn_predict_layers,
        "vocab_size": config.vocab_size,
        "mtp_loss_weight": config.mtp_loss_weight,
        "bias_update_speed": config.bias_update_speed,
        "dtype": config.dtype, "seq_len": config.seq_len,
        "parameters": mla_moe.parameter_count(config),
        "program_model": "mla_moe",
        "optimizer": dict(mla_moe.ADAMW, name="adamw",
                          learning_rate=learning_rate),
    }


def _rows(config: mla_moe.Config, n: int, seed: int) -> dict:
    """Packed rows of three or four documents of uneven length."""
    rng = np.random.default_rng(seed)
    t = config.seq_len
    seg = np.stack([np.searchsorted(
        np.sort(rng.choice(np.arange(1, t), size=3, replace=False)),
        np.arange(t), side="right") for _ in range(n)]).astype(np.int32)
    return {"tokens": rng.integers(0, config.vocab_size, (n, t), np.int32),
            "segment_ids": seg}


def _routing_state(trainer) -> dict:
    """The program's routing biases and cumulative counts, as NumPy."""
    return {k: np.asarray(v)
            for k, v in trainer.state.collections[mla_moe.COLLECTION].items()}


def _close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


@pytest.fixture(scope="module")
def tiny():
    config = mla_moe.Config.tiny()
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
        (config.expert_layers, config.n_routed_experts)), jnp.float32)


# ---------------------------------------------------------------------------
# program against reference
# ---------------------------------------------------------------------------


def test_mla_moe_counts_the_parameters_the_reference_names(tiny):
    config, ref_config, weights, params = tiny
    shapes = mla_moe.leaf_shapes(config)
    assert {k: tuple(v.shape) for k, v in params.items()} == shapes
    assert list(shapes) == [program.program_name(n)
                            for n in reference.leaf_shapes(ref_config)]
    assert mla_moe.parameter_count(config) == sum(
        int(np.prod(v.shape)) for v in weights.values())
    assert mla_moe.layer_prefixes(config) == [
        ("l00_", "dense"), ("l01_", "experts"), ("mtp_", "experts")]


def test_mla_moe_logits_both_losses_and_every_leafs_gradient_match(tiny):
    """With correction biases that move some choices (zero biases are the
    Trainer test's)."""
    config, ref_config, weights, params = tiny
    batch = _rows(config, 2, 1)
    bias = _bias(config)
    tokens, seg = batch["tokens"], batch["segment_ids"]

    def mine(p):
        main, n_main, mtp, n_mtp, counts = mla_moe.loss_terms(
            p, bias, tokens, seg, config)
        main, mtp = main / n_main, mtp / n_mtp
        return main + config.mtp_loss_weight * mtp, (main, mtp, counts)

    def theirs(w):
        logits, loss, main, mtp, counts = reference.forward(
            w, tokens, seg, ref_config, bias)
        return loss, (logits, main, mtp, counts)

    (want_loss, (want_logits, want_main, want_mtp, want_counts)), want = \
        jax.jit(jax.value_and_grad(theirs, has_aux=True))(weights)
    (loss, (main, mtp, counts)), grads = jax.jit(
        jax.value_and_grad(mine, has_aux=True))(params)
    _close(jax.jit(lambda p: mla_moe.apply_tokens(p, bias, tokens, seg,
                                                  config))(params),
           want_logits)
    assert float(main) == pytest.approx(float(want_main), rel=1e-6)
    assert float(mtp) == pytest.approx(float(want_mtp), rel=1e-6)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts.sum()) == (config.num_experts_per_tok * tokens.size
                                 * config.expert_layers)
    assert set(grads) == {program.program_name(k) for k in want}
    for name, g in want.items():
        assert float(jnp.abs(g).max()) > 0, name    # every leaf is trained
        _close(grads[program.program_name(name)], g)


def test_mla_moe_collection_moves_on_a_step():
    """Counts add up, the fullest expert of every layer is remembered, the
    bias goes a step towards the mean load (and stays where the load is
    the mean), and a layer is counted whose held expert (one of four: 8 of
    32 slots if the router is even, 24 fit ``moe.prefix_rows``) took more
    than fit, and one whose held expert took no more than fit
    ``moe.tight_rows`` (the same 24: a whole row tile is more)."""
    config = dataclasses.replace(
        mla_moe.Config.tiny(), n_routed_experts=4, experts_held=(2,),
        num_experts_per_tok=2)
    counts = jnp.asarray([[16, 0, 8, 8], [8, 8, 8, 8], [2, 2, 26, 2]],
                         jnp.int32)
    state = {"bias": jnp.full((3, 4), 0.5), "busiest": jnp.asarray([7] * 3),
             "counts": jnp.ones((3, 4), jnp.int32),
             "overflow": jnp.asarray([3] * 3), "tight": jnp.asarray([5] * 3)}
    new = mla_moe.step_collection(state, counts, config, tokens=16)
    np.testing.assert_allclose(
        new["bias"], [[0.499, 0.501, 0.5, 0.5], [0.5] * 4,
                      [0.501, 0.501, 0.499, 0.501]], atol=1e-7)
    assert new["counts"].tolist() == [[17, 1, 9, 9], [9] * 4, [3, 3, 27, 3]]
    assert new["busiest"].tolist() == [23, 15, 33]
    assert new["overflow"].tolist() == [3, 3, 4]
    assert new["tight"].tolist() == [6, 6, 5]


@pytest.mark.parametrize("favoured, overflowed", [
    ((), 0), ((2, 3, 5), 1)], ids=["even_bias", "everything_here"])
def test_mla_moe_counts_the_layers_whose_held_slots_overflow(
        tiny, favoured, overflowed):
    """``moe_overflow_layers_total``: a step of the whole model under a
    bias that leaves the choice to the scores (2 of 16 experts held: an
    eighth of the slots lands here, ``moe.prefix_rows`` holds three), and
    under one that sends two of every token's three choices here."""
    config, _, _, params = tiny
    batch = _rows(config, 2, 21)
    bias = jnp.zeros((config.expert_layers, config.n_routed_experts)
                     ).at[:, jnp.asarray(favoured, jnp.int32)].set(10.0)
    state = {"bias": bias, **{
        name: jnp.zeros(shape, dtype) for name, (shape, dtype) in
        mla_moe.collection_shapes(config).items() if name != "bias"}}
    loss_fn = mla_moe.make_loss_fn(None, config)
    _, new = jax.jit(loss_fn)(params, {mla_moe.COLLECTION: state}, batch)
    new = new[mla_moe.COLLECTION]
    slots = batch["tokens"].size * config.num_experts_per_tok
    fits = moe.prefix_rows(slots, 2, config.n_routed_experts)
    assert fits == 3 * slots // 8
    landed = np.asarray(new["counts"])[:, list(config.experts_held)].sum(-1)
    assert ((landed > fits) == bool(overflowed)).all(), (landed, fits)
    assert new["overflow"].tolist() == [overflowed] * config.expert_layers
    assert mla_moe.device_counters({mla_moe.COLLECTION: new}, config)[
        "moe_overflow_layers_total"].tolist() == new["overflow"].tolist()


def test_mla_moe_trainer_follows_the_reference_for_three_adamw_steps(tiny):
    """Through ``Trainer``: the seeded weights loaded a leaf at a time, three
    steps, then the losses, the first gradient's norms as AdamW's first
    moment shows them, every parameter and the routing biases.  After three
    steps of AdamW a difference of 1e-6 in a gradient whose second moment is
    still tiny can move an update by its whole size, so the parameters are
    held to 1e-3 of their largest entry; the change's norm, which the
    benchmark compares, to 1e-3.  The biases move by whole steps of 0.001
    and have to agree to rounding; so do the counts behind them."""
    from tensorflowonspark_tpu.trainer import Trainer

    config, ref_config, _, _ = tiny
    before = obs.get_registry().snapshot()["counters"]
    trainer = Trainer("mla_moe", config=config, learning_rate=1e-3,
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
    routing = _routing_state(trainer)
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
    # when the trainer goes — what the device decided
    del trainer, mine
    gc.collect()
    after = obs.get_registry().snapshot()["counters"]
    grew = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    tokens = sum(b["tokens"].size for b in batches)
    counts = np.sum(theirs["counts"], axis=0)
    assert grew["lm_tokens_total"] == tokens
    assert grew["moe_slots_total"] == (config.num_experts_per_tok * tokens
                                       * config.expert_layers)
    assert grew["moe_local_slots_total"] == counts[
        :, list(config.experts_held)].sum()
    assert grew["moe_busiest_expert_slots_total"] == sum(
        np.max(c, axis=-1).sum() for c in theirs["counts"])
    landed = np.asarray(theirs["counts"])[..., list(config.experts_held)]
    assert grew["moe_overflow_layers_total"] == int((
        landed.sum(-1) > moe.prefix_rows(
            config.num_experts_per_tok * batches[0]["tokens"].size,
            len(config.experts_held), config.n_routed_experts)).sum())
    assert grew["mtp_loss_tokens_total"] == sum(
        mla_moe.batch_counters(b, config)["mtp_loss_tokens_total"]
        for b in batches)


def test_mla_moe_bfloat16_activations_stay_near_the_float32_reference(tiny):
    """The configuration's own precision at the tiny size: bfloat16 keeps 8
    bits, and a loss near 1.3 log(64) moves by well under a hundredth."""
    config, ref_config, weights, params = tiny
    batch = _rows(config, 2, 3)
    bias = jnp.zeros((config.expert_layers, config.n_routed_experts))
    main, n_main, mtp, n_mtp, _ = jax.jit(lambda p: mla_moe.loss_terms(
        p, bias, batch["tokens"], batch["segment_ids"],
        dataclasses.replace(config, dtype="bfloat16")))(params)
    want = jax.jit(lambda w: reference.forward(
        w, batch["tokens"], batch["segment_ids"], ref_config)[1])(weights)
    assert float(main / n_main + config.mtp_loss_weight * mtp / n_mtp) == \
        pytest.approx(float(want), rel=1e-2)


def test_mla_moe_the_float8_control_moves_the_reference(tiny):
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


# ---------------------------------------------------------------------------
# the expert layer: shares, extremes
# ---------------------------------------------------------------------------


def _expert_layer(seed=0, tokens=48, d=32, f=16, n_experts=16):
    rng = np.random.default_rng(seed)
    g = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]),  # noqa: E731
                               jnp.float32)
    return {"h": jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32),
            "router": g(d, n_experts), "shared_gate": g(d, f),
            "shared_up": g(d, f), "shared_down": g(f, d),
            "experts_gate": g(n_experts, d, f),
            "experts_up": g(n_experts, d, f),
            "experts_down": g(n_experts, f, d)}


def _ref_config(n_experts, top_k, held):
    return {"num_experts_per_tok": top_k, "norm_topk_prob": True,
            "routed_scaling_factor": 1.8, "experts_held": list(held),
            "published": {"n_routed_experts": n_experts}}


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Eight chips hold two of sixteen experts each.  The parts of the
    result that the eight shares give (``routed_experts`` told which two),
    with what every chip computes alike — the shared expert — counted
    once, add up to what the uncut reference gives for the whole layer, to
    float32 rounding; every share reports the same counts."""
    w = _expert_layer()
    bias = jnp.asarray(np.random.default_rng(1).standard_normal(16) * 0.05,
                       jnp.float32)
    whole, want_counts = reference.experts(
        w, w["h"], bias, _ref_config(16, 3, range(16)), lambda a: a)
    total = packed_rows.swiglu(w["h"], w["shared_gate"], w["shared_up"],
                               w["shared_down"])
    landed = 0
    for share in range(8):
        held = (2 * share, 2 * share + 1)
        part, counts = jax.jit(
            lambda h, held=held: moe.routed_experts(
                h, w["router"], bias, w["experts_gate"][np.asarray(held)],
                w["experts_up"][np.asarray(held)], w["experts_down"][np.asarray(held)],
                held, top_k=3, scale=1.8))(w["h"])
        np.testing.assert_array_equal(counts, want_counts)
        total = total + part
        landed += int(np.asarray(counts)[np.asarray(held)].sum())
    assert landed == 3 * w["h"].shape[0]        # every slot on one share
    _close(total, whole, tol=1e-6)


def _rows_multiplied(fn, *args) -> set:
    """The row counts of the grouped products that ``fn`` *runs*: with
    ``jax.disable_jit`` a ``cond`` calls the one branch the count chose, so
    this names the form of the routed part that was taken."""
    seen, real = [], jax.lax.ragged_dot

    def spy(lhs, rhs, group_sizes, **kw):
        seen.append(lhs.shape[0])
        return real(lhs, rhs, group_sizes, **kw)

    with pytest.MonkeyPatch.context() as patch, jax.disable_jit():
        patch.setattr(jax.lax, "ragged_dot", spy)
        fn(*args)
    return set(seen)


@pytest.mark.parametrize("case", ["all_here", "none_here", "one_expert"])
def test_routed_experts_drop_no_token_at_the_extremes(case):
    """A bias of 10 decides every choice.  All three choices of every token
    on held experts: the worst case, every slot live, the whole form (144
    rows).  None on a held expert: the result is zero, the prefix form (88
    rows) has no live row, the gradient is zero and finite.  Every token
    choosing one held expert beside two held elsewhere: an expert with
    every token, no capacity to overflow, and the 48 live rows fit the
    prefix form."""
    w = _expert_layer(seed=2)
    held = (1, 4, 6)
    favoured = {"all_here": [1, 4, 6], "none_here": [0, 2, 3],
                "one_expert": [4, 0, 2]}[case]
    bias = jnp.zeros(16).at[jnp.asarray(favoured)].set(10.0)
    take = np.asarray(held)

    def part(h, gate):
        return moe.routed_experts(
            h, w["router"], bias, gate, w["experts_up"][take],
            w["experts_down"][take], held, top_k=3, scale=1.8)

    (y, counts), grad = jax.jit(lambda h, g: (
        part(h, g), jax.grad(lambda h_, g_: jnp.sum(part(h_, g_)[0] ** 2),
                             (0, 1))(h, g)))(w["h"], w["experts_gate"][take])
    tokens = w["h"].shape[0]
    assert moe.prefix_rows(3 * tokens, 3, 16) == 88
    assert _rows_multiplied(part, w["h"], w["experts_gate"][take]) == {
        3 * tokens if case == "all_here" else 88}
    assert [int(counts[e]) for e in favoured] == [tokens] * 3
    assert int(counts.sum()) == 3 * tokens
    held_weights = {k: w[k][take] for k in
                    ("experts_gate", "experts_up", "experts_down")}
    want, _ = reference.experts(
        dict(w, **held_weights), w["h"], bias, _ref_config(16, 3, held),
        lambda a: a)
    want = want - packed_rows.swiglu(w["h"], w["shared_gate"], w["shared_up"],
                                     w["shared_down"])
    _close(y, want, tol=1e-5) if case != "none_here" else \
        np.testing.assert_array_equal(y, 0.0)
    assert all(bool(jnp.isfinite(g).all()) for g in grad)
    if case == "none_here":
        assert all(float(jnp.abs(g).max()) == 0.0 for g in grad)
    else:
        assert float(jnp.abs(y).max()) > 0


@pytest.mark.parametrize("landed, rows", [(87, 88), (88, 88), (89, 144)],
                         ids=["under", "equal", "over"])
def test_routed_experts_take_the_form_their_count_fits(landed, rows,
                                                       monkeypatch):
    """48 tokens, top-3 of 16, three held: 144 slots, of which
    ``moe.prefix_rows`` takes 88.  A bias of 10 gives every token one held
    and one other expert, a bias of -10 leaves two experts, one of them
    held, for the third choice, and the router's columns for those two are
    each other's negative: the sign of a token's product with it decides,
    so flipping tokens sets the count to the slot.  The value and every
    gradient against ``reference.experts``, under the count, at it and one
    over, where the whole form runs; and against the whole form alone
    (``prefix_rows`` answering "all of them") on the same input."""
    w = _expert_layer(seed=4)
    held, first, other, last, rival = (1, 4, 6), 4, 0, 6, 9
    take, tokens = np.asarray(held), w["h"].shape[0]
    router = w["router"].at[:, rival].set(-w["router"][:, last])
    bias = jnp.full(16, -10.0).at[jnp.asarray([first, other])].set(10.0)
    bias = bias.at[jnp.asarray([last, rival])].set(0.0)
    here = np.arange(tokens) < landed - tokens      # third choice held
    sign = np.where((np.asarray(w["h"] @ router[:, last]) > 0) == here, 1, -1)
    h = w["h"] * sign[:, None]
    leaves = {"router": router, **{k: w[k][take] for k in (
        "experts_gate", "experts_up", "experts_down")}}

    def mine(h, p):
        y, counts = moe.routed_experts(
            h, p["router"], bias, p["experts_gate"], p["experts_up"],
            p["experts_down"], held, top_k=3, scale=1.8)
        return jnp.sum(y ** 2), (y, counts)

    def theirs(h, p):
        y, counts = reference.experts(dict(w, **p), h, bias,
                                      _ref_config(16, 3, held), lambda a: a)
        y = y - packed_rows.swiglu(h, w["shared_gate"], w["shared_up"],
                                   w["shared_down"])
        return jnp.sum(y ** 2), (y, counts)

    def run(f):
        return jax.jit(jax.value_and_grad(f, (0, 1), has_aux=True))(h, leaves)

    (_, (y, counts)), grads = run(mine)
    (_, (want, want_counts)), want_grads = run(theirs)
    assert int(counts[take].sum()) == landed
    np.testing.assert_array_equal(counts, want_counts)
    assert _rows_multiplied(jax.grad(lambda *a: mine(*a)[0], (0, 1)),
                            h, leaves) == {rows}
    _close(y, want, tol=1e-5)
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert float(jnp.abs(ref).max()) > 0
        _close(got, ref, tol=1e-5)
    monkeypatch.setattr(moe, "prefix_rows", lambda slots, *_: slots)
    (_, (whole, _)), whole_grads = run(mine)
    _close(y, whole, tol=1e-6)
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(whole_grads)):
        _close(got, ref, tol=1e-6)


def _sub_jaxprs(jaxpr):
    """Every jaxpr inside ``jaxpr``, itself first."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from _sub_jaxprs(inner)


def _layer_jaxpr(held):
    """The value and gradient of ``routed_experts`` on 64 bfloat16 tokens,
    top-3 of 16 (192 slots), D = 32, F = 24, float32 weights."""
    w = _expert_layer(seed=5, tokens=64, f=24)
    take = np.asarray(held)

    def loss(h, gate, up, down):
        y, _ = moe.routed_experts(h, w["router"], jnp.zeros(16), gate, up,
                                  down, held, top_k=3, scale=1.8)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    return jax.make_jaxpr(jax.value_and_grad(loss, (0, 1, 2, 3)))(
        w["h"].astype(jnp.bfloat16), w["experts_gate"][take],
        w["experts_up"][take], w["experts_down"][take]).jaxpr


def test_the_prefix_form_holds_no_row_a_slot():
    """Two of 16 experts held: 72 of the 192 slots' rows.  The forward pass
    and the backward pass choose each for itself (two ``cond``s — what a
    ``switch`` binds —, and no residual crosses one), and in the branch a
    fitting count takes no array of any type has a row a slot (192 rows,
    or 64 x 3, of ``F`` or ``D`` numbers): the products' outputs, the
    ``silu`` pass, the masks, the gathers and the weighted sum all work on
    72 rows or on a row a token.  The other branch has such arrays in
    float32, so the check can see one."""
    assert moe.prefix_rows(192, 2, 16) == 72
    conds = [eqn for jaxpr in _sub_jaxprs(_layer_jaxpr((3, 7)))
             for eqn in jaxpr.eqns if eqn.primitive.name == "cond"]
    assert len(conds) == 2

    def a_row_a_slot(jaxpr, dtypes):
        return [v.aval for inner in _sub_jaxprs(jaxpr) for eqn in inner.eqns
                for v in eqn.outvars
                if v.aval.dtype in dtypes and v.aval.ndim >= 2
                and v.aval.shape[-1] in (32, 24)
                and int(np.prod(v.aval.shape[:-1])) == 192]

    for eqn in conds:
        prefix, whole = eqn.params["branches"]      # the sizes, rising
        found = a_row_a_slot(prefix.jaxpr, (jnp.float32, jnp.bfloat16))
        assert not found, found
        assert a_row_a_slot(whole.jaxpr, (jnp.float32,))
        assert all(v.aval.shape[:1] != (192,) for v in eqn.outvars)


def test_half_the_experts_held_trace_one_form():
    """Eight of 16 held: three times an even router's share is more than
    every slot, so the routed part is traced once, at all the slots, under
    no ``cond``."""
    assert moe.prefix_rows(192, 8, 16) == 192
    assert not [eqn for jaxpr in _sub_jaxprs(_layer_jaxpr(tuple(range(8))))
                for eqn in jaxpr.eqns if eqn.primitive.name == "cond"]


def test_routed_experts_refuse_experts_the_router_does_not_have():
    w = _expert_layer(seed=3)
    for held in [(0, 0), (3, 16), (1, 2, 3)]:
        with pytest.raises(ValueError):
            moe.routed_experts(
                w["h"], w["router"], jnp.zeros(16), w["experts_gate"][:2],
                w["experts_up"][:2], w["experts_down"][:2], held, top_k=3,
                scale=1.8)


# ---------------------------------------------------------------------------
# documents: mask, positions, the second loss's boundary
# ---------------------------------------------------------------------------


def test_positions_restart_at_every_document(tiny):
    seg = np.array([[3, 3, 3, 5, 5, 9, 9, 9, 9, 2]], np.int32)
    want = [0, 1, 2, 0, 1, 0, 1, 2, 3, 0]
    assert packed_rows.document_positions(
        jnp.asarray(seg[0])).tolist() == want
    assert reference.positions(seg)[0].tolist() == want


def test_a_documents_logits_do_not_change_when_another_document_does(tiny):
    """Mask and positions: replace the second document's tokens, and move
    the row's first document behind another — its logits stay, at the
    positions it now has, because a token sees only its own document at
    positions counted from the document's start.  (The routing is a
    token's own affair.)"""
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
    logits = jax.jit(lambda p: mla_moe.apply_tokens(
        p, bias, np.stack([tokens, changed, moved]),
        np.stack([seg, seg, moved_seg]), config))(params)
    _close(logits[1, :11], logits[0, :11], tol=1e-6)
    _close(logits[1, 23:], logits[0, 23:], tol=1e-6)
    assert float(jnp.abs(logits[1, 11:23] - logits[0, 11:23]).max()) > 1e-3
    _close(logits[2, 12:23], logits[0, :11], tol=1e-5)
    _close(logits[2, :12], logits[0, 11:23], tol=1e-5)


def test_the_second_loss_scores_nothing_across_a_boundary(tiny):
    """Position ``t`` predicts ``u_{t+2}`` only where ``t``, ``t+1`` and
    ``t+2`` are one document's; and what the prediction module gives at
    those positions does not change when another document's tokens do."""
    config, _, _, params = tiny
    seg = jnp.asarray([0] * 5 + [1] * 2 + [2] * 1 + [3] * 4)
    assert packed_rows.loss_positions(seg, 2).tolist() == [
        True, True, True, False, False,  False, False,  False,
        True, True, False, False]
    assert packed_rows.loss_positions(seg, 1).tolist() == [
        True, True, True, True, False,  True, False,  False,
        True, True, True, False]
    batch = {"segment_ids": np.asarray(seg)[None]}
    assert mla_moe.batch_counters(batch, config)["mtp_loss_tokens_total"] == 5
    assert mla_moe.batch_counters(batch, config)["lm_loss_tokens_total"] == 8
    assert mla_moe.batch_counters(batch, config)["lm_documents_total"] == 4

    rng = np.random.default_rng(8)
    t = config.seq_len
    seg = (np.arange(t) >= 13).astype(np.int32)[None]
    tokens = rng.integers(0, config.vocab_size, (1, t), np.int32)
    changed = tokens.copy()
    changed[0, 13:] = rng.integers(0, config.vocab_size, t - 13)
    bias = _bias(config, seed=9)

    @jax.jit
    def second(u):
        x, pos, counts = mla_moe.hidden_states(params, bias, u, seg, config)
        h, _ = mla_moe.prediction_states(
            params, bias[len(counts)],
            packed_rows.rms(x, params["final_norm"], config.rms_norm_eps),
            u, seg, pos, config)
        return h[0]

    a, b = second(tokens), second(changed)
    scored = np.asarray(packed_rows.loss_positions(jnp.asarray(seg[0]), 2))
    assert scored[:11].all() and not scored[11:13].any()
    _close(b[:11], a[:11], tol=1e-6)
    # the document's last position took the next document's first token in:
    # it is not scored, and it did change
    assert float(jnp.abs(b[12] - a[12]).max()) > 1e-4


# ---------------------------------------------------------------------------
# the Trainer's state
# ---------------------------------------------------------------------------


def test_mla_moe_checkpoints_carry_the_routing_state(tiny, tmp_path):
    """A checkpoint holds the whole ``moe`` collection, the counters' rows
    with it.  One written without a row that enters the step (the biases)
    is not filled in: a checkpoint's tree is that of the code that wrote it
    (no format is published, the Trainer restores into its own template),
    and the restore refuses the other tree by the missing row's name rather
    than resume with a bias that starts at nothing.  (A row that only the
    counters read is another matter: the tests after this one.)"""
    from tensorflowonspark_tpu import ckpt
    from tensorflowonspark_tpu.trainer import Trainer

    config = tiny[0]
    before = obs.get_registry().snapshot()["counters"].get(
        "moe_slots_total", 0)
    trainer = Trainer("mla_moe", config=config, devices=jax.devices()[:1])
    batch = mla_moe.example_batch(config, 2, seq_len=config.seq_len)
    trainer.step(batch)
    trainer.step(batch)
    want = _routing_state(trainer)
    per_step = (config.num_experts_per_tok * 2 * config.seq_len
                * config.expert_layers)
    assert want["counts"].sum() == 2 * per_step
    assert np.abs(want["bias"]).max() == pytest.approx(0.002, rel=1e-5)
    trainer.save(str(tmp_path / "ckpt"))
    trainer.step(batch)
    assert _routing_state(trainer)["counts"].sum() == 3 * per_step
    trainer.restore(str(tmp_path / "ckpt"))
    got = _routing_state(trainer)
    assert set(got) == {"bias", "counts", "busiest", "overflow", "tight"}
    for name in got:
        np.testing.assert_array_equal(got[name], want[name])
    old = trainer._state_tree()
    old["collections"] = {mla_moe.COLLECTION: {
        k: v for k, v in old["collections"][mla_moe.COLLECTION].items()
        if k != "bias"}}
    ckpt.save_pytree(old, str(tmp_path / "before"))
    with pytest.raises(ValueError, match="moe.bias"):
        trainer.restore(str(tmp_path / "before"))
    # restored counts are where the counters go on from, not growth
    trainer.step(batch)
    assert mla_moe.device_counters(trainer.state.collections, config)[
        "moe_local_slots_total"].shape == (config.expert_layers, 2)
    del trainer
    gc.collect()
    assert obs.get_registry().snapshot()["counters"]["moe_slots_total"] \
        - before == 4 * per_step        # every step run, no step twice


@pytest.mark.parametrize("how", ["path", "manager"])
@pytest.mark.parametrize("lacks", [("tight",), ("tight", "overflow")],
                         ids="_".join)
def test_a_checkpoint_written_before_a_counter_row_resumes(tiny, tmp_path,
                                                           how, lacks):
    """The collection of a checkpoint written before ``tight`` was counted
    (PR 50), or ``overflow`` (PR 39): ``moe.COUNTER_ROWS`` are read by the
    counters and by no step, so ``Trainer.restore`` and ``restore_latest``
    take what the file has, start the missing rows at zero, and the
    counters go on from there — the step after adds one step's growth to
    each, not the restored totals."""
    from tensorflowonspark_tpu import ckpt
    from tensorflowonspark_tpu.parallel import moe
    from tensorflowonspark_tpu.trainer import Trainer

    config = tiny[0]
    assert set(lacks) < set(moe.COUNTER_ROWS)
    assert mla_moe.counter_rows(config) == {
        mla_moe.COLLECTION: moe.COUNTER_ROWS}
    trainer = Trainer("mla_moe", config=config, devices=jax.devices()[:1])
    batch = mla_moe.example_batch(config, 2, seq_len=config.seq_len)
    trainer.step(batch)
    trainer.step(batch)
    want = _routing_state(trainer)
    assert want["tight"].sum() + want["overflow"].sum() > 0
    old = trainer._state_tree()
    old["collections"] = {mla_moe.COLLECTION: {
        k: v for k, v in old["collections"][mla_moe.COLLECTION].items()
        if k not in lacks}}
    if how == "path":
        ckpt.save_pytree(old, str(tmp_path / "before"))
    else:
        trainer.checkpoint(str(tmp_path / "steps"), every_steps=0,
                           async_save=False)
        trainer._ckpt_mgr.save(2, old)
        trainer.finish_checkpoints()
    trainer.step(batch)
    if how == "path":
        trainer.restore(str(tmp_path / "before"))
    else:
        assert trainer.restore_latest() == 2
    got = _routing_state(trainer)
    assert set(got) == set(want)
    for name in got:
        np.testing.assert_array_equal(
            got[name], 0 * want[name] if name in lacks else want[name])
    assert int(trainer.state.step) == 2
    seen = obs.get_registry().snapshot()["counters"]
    trainer.step(batch)
    trainer._device_counters.drain()
    now = obs.get_registry().snapshot()["counters"]
    after = _routing_state(trainer)
    for name in lacks:
        assert now.get(f"moe_{name}_layers_total", 0) - seen.get(
            f"moe_{name}_layers_total", 0) == after[name].sum()
    assert now["moe_slots_total"] - seen["moe_slots_total"] == (
        config.num_experts_per_tok * 2 * config.seq_len
        * config.expert_layers)
