"""The sliding-window / full attention expert decoder (``models/mellum_moe.py``:
``packed_rows.grouped_query_attention`` behind a window or over the whole
document, two rotations by layer type, the softmax-routed layer of
``parallel/moe.py``) against the plain reference of the ``mellum2_12b_a2_5b``
configuration, at ``Config.tiny()`` in float32 on the CPU; and what the model
brought to the shared code: ``document_attention``'s ``window`` in the ``jnp``
blocks and in the kernels of ``attention_pallas`` (Pallas's interpreter),
YaRN's frequencies, ``topk_route``'s softmax scores.

Tolerances: both sides compute in float32 with products at the highest
precision, so they differ only by the order of their sums (the program's
sorted grouped products and running softmax over the blocks a window reaches
against the reference's masked dense experts and one masked softmax over
every key): 2e-5 relative to the largest entry covers what a few hundred
float32 additions in another order move, is 1,000 times tighter than a window
off by one, a missing window, a forgotten factor or a wrong frequency would
need (tests below show they miss it), and bfloat16 activations miss it by two
orders of magnitude.  The kernels in bfloat16 are held to 1.5e-2 as
``test_packed_rows_attention.py`` holds them (a few 8-bit steps).
"""

import dataclasses
import gc
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.configs.mellum2_12b_a2_5b import program, reference
from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.models import (attention_pallas, kernels, lfm2_moe,
                                          mellum_moe, packed_rows)
from tensorflowonspark_tpu.parallel import moe

BIG_SEED = 2 ** 31 + 4703           # the driver's seeds pass 32 signed bits
TOL = 2e-5
#: documents a row of ``Config.tiny()``'s 48 tokens: one shorter than the
#: window of 12, one longer, one a token over it
LENGTHS = ([5, 30, 13], [20, 8, 20])


def _tiny_dict(config: mellum_moe.Config, learning_rate=1e-3) -> dict:
    """``Config.tiny()`` under the keys the configuration's file has."""
    return {
        "hidden_size": config.hidden_size, "head_dim": config.head_dim,
        "moe_intermediate_size": config.moe_intermediate_size,
        "layer_types": list(config.layer_types),
        "layers_run": list(config.layers_run),
        "num_hidden_layers": len(config.layers_run),
        "sliding_window": config.sliding_window,
        "rope_parameters": config.rope_parameters,
        "num_experts": len(config.experts_held),
        "experts_held": list(config.experts_held),
        "published": {"num_experts": config.num_experts,
                      "num_hidden_layers": len(config.layer_types)},
        "num_experts_per_tok": config.num_experts_per_tok,
        "norm_topk_prob": config.norm_topk_prob,
        "num_attention_heads": config.num_attention_heads,
        "num_key_value_heads": config.num_key_value_heads,
        "rms_norm_eps": config.rms_norm_eps, "vocab_size": config.vocab_size,
        "init_std": config.init_std,
        "embed_init_std": config.embed_init_std, "dtype": config.dtype,
        "seq_len": config.seq_len, "attention_bias": False,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "use_sliding_window": True,
        "mlp_layer_types": ["sparse"] * len(config.layer_types),
        "parameters": mellum_moe.parameter_count(config),
        "program_model": "mellum_moe",
        "optimizer": dict(mellum_moe.ADAMW, name="adamw",
                          learning_rate=learning_rate),
    }


def _rows(config: mellum_moe.Config, seed: int) -> dict:
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
    config = mellum_moe.Config.tiny()
    ref_config = _tiny_dict(config)
    weights = reference.make_weights(ref_config, BIG_SEED)
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


def test_mellum_tiny_is_the_issues_size_and_names_the_references_leaves(tiny):
    config, ref_config, weights, params = tiny
    assert mellum_moe.layer_kinds(config) == [
        ("l00_", "sliding_attention", "experts"),
        ("l01_", "full_attention", "experts"),
        ("l02_", "sliding_attention", "experts")]
    assert (config.num_experts, config.experts_held,
            config.num_experts_per_tok, config.head_dim,
            config.num_attention_heads, config.num_key_value_heads,
            config.sliding_window, config.seq_len) == (
        8, (2, 5), 3, 8, 4, 2, 12, 48)
    shapes = mellum_moe.leaf_shapes(config)
    assert {k: tuple(v.shape) for k, v in params.items()} == shapes
    assert list(shapes) == [program.program_name(n)
                            for n in reference.leaf_shapes(ref_config)]
    assert dataclasses.replace(program.model_config(ref_config),
                               attention_block=16, loss_block=16) == config
    assert mellum_moe.collection_shapes(config) == moe.routing_state_shapes(
        8, 3)
    routing = mellum_moe.routing(config)
    assert (routing.score, routing.speed, routing.normalize,
            routing.scale) == ("softmax", 0.0, True, 1.0)
    published = mellum_moe.Config()
    assert published.layer_types == (("sliding_attention",) * 3
                                     + ("full_attention",)) * 7
    assert [k for _, k, _ in mellum_moe.layer_kinds(published)][3::4] == [
        "full_attention"] * 7
    with pytest.raises(ValueError):
        mellum_moe.Config(layer_types=("sliding_attention", "chunked"))
    with pytest.raises(ValueError):
        mellum_moe.Config(rope_parameters={
            "full_attention": {"rope_type": "longrope"},
            "sliding_attention": {"rope_type": "default", "rope_theta": 1}})


def test_mellum_logits_loss_and_every_leafs_gradient_match(tiny):
    config, ref_config, weights, params = tiny
    batch = _rows(config, 1)
    bias = _zero_bias(config)
    tokens, seg = batch["tokens"], batch["segment_ids"]

    def mine(p):
        total, n, counts = mellum_moe.loss_terms(p, bias, tokens, seg,
                                                 config)
        return total / n, counts

    def theirs(w):
        logits, loss, counts = reference.forward(w, tokens, seg, ref_config)
        return loss, (logits, counts)

    (want_loss, (want_logits, want_counts)), want = jax.jit(
        jax.value_and_grad(theirs, has_aux=True))(weights)
    (loss, counts), grads = jax.jit(
        jax.value_and_grad(mine, has_aux=True))(params)
    _close(jax.jit(lambda p: mellum_moe.apply_tokens(p, bias, tokens, seg,
                                                     config))(params),
           want_logits)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts.sum()) == (config.num_experts_per_tok * tokens.size
                                 * config.expert_layers)
    assert set(grads) == {program.program_name(k) for k in want}
    for name, g in want.items():
        assert float(jnp.abs(g).max()) > 0, name    # every leaf is trained
        _close(grads[program.program_name(name)], g)


@pytest.mark.parametrize("mistake", [
    "window_off_by_one", "window_missing", "attention_factor_forgotten",
    "plain_frequencies_in_the_full_layer", "sigmoid_scores",
    "bfloat16_activations"])
def test_mellum_a_mistake_this_layout_invites_fails_the_tolerance(tiny,
                                                                  mistake):
    """The comparison above with one thing wrong on the program's side: the
    logits miss 2e-5 by more than ten times (a window that reaches one token
    further moves one probability in thirteen; the others move far more)."""
    config, ref_config, weights, params = tiny
    batch = _rows(config, 3)
    rope = config.rope_parameters
    wrong = {
        "window_off_by_one": dict(sliding_window=config.sliding_window + 1),
        "window_missing": dict(sliding_window=config.seq_len),
        "attention_factor_forgotten": dict(rope_parameters={
            **rope, "full_attention": {**rope["full_attention"],
                                       "attention_factor": 1.0}}),
        "plain_frequencies_in_the_full_layer": dict(rope_parameters={
            **rope, "full_attention": {
                **rope["full_attention"], "rope_type": "default"}}),
        "sigmoid_scores": {}, "bfloat16_activations": dict(dtype="bfloat16"),
    }[mistake]
    broken = dataclasses.replace(config, **wrong)
    want = jax.jit(lambda w: reference.forward(
        w, batch["tokens"], batch["segment_ids"], ref_config)[0])(weights)
    run = jax.jit(lambda p: mellum_moe.apply_tokens(
        p, _zero_bias(config), batch["tokens"], batch["segment_ids"], broken))
    if mistake == "sigmoid_scores":
        real = mellum_moe.routing
        mellum_moe.routing = lambda c: real(c)._replace(score="sigmoid")
        try:
            got = run(params)
        finally:
            mellum_moe.routing = real
    else:
        got = run(params)
    assert _gap(got, want) > 10 * TOL, _gap(got, want)
    if mistake == "bfloat16_activations":
        assert 100 * TOL < _gap(got, want) < 0.05


def test_mellum_the_float8_control_moves_the_reference(tiny):
    """``lower="float8"`` rounds the products' operands and leaves the
    router and the softmaxes alone: the loss moves."""
    config, ref_config, weights, _ = tiny
    batch = _rows(config, 4)
    sound, low = jax.jit(lambda w: [reference.forward(
        w, batch["tokens"], batch["segment_ids"], ref_config, lower=lower)[1]
        for lower in (None, "float8")])(weights)
    assert abs(float(low) - float(sound)) > 1e-5 * float(sound)
    with pytest.raises(ValueError):
        reference.forward(weights, batch["tokens"], batch["segment_ids"],
                          ref_config, lower="float4")


def test_mellum_trainer_follows_the_reference_for_three_adamw_steps(tiny):
    """Through ``Trainer`` — nothing in it is this model's: the seeded
    weights loaded a leaf at a time, three steps, then the losses, the first
    gradient's norms as AdamW's first moment shows them and every parameter
    (1e-3 of the largest entry after three AdamW steps, as
    ``test_lfm2_moe.py`` argues it), the routing state — the bias still
    zero: the layout has no correction bias — and the program's counters."""
    from tensorflowonspark_tpu.trainer import Trainer

    config, ref_config, _, _ = tiny
    before = obs.get_registry().snapshot()["counters"]
    trainer = Trainer("mellum_moe", config=config, learning_rate=1e-3,
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
               trainer.state.collections[mellum_moe.COLLECTION].items()}
    assert not routing["bias"].any()
    np.testing.assert_array_equal(routing["counts"],
                                  np.sum(theirs["counts"], axis=0))
    weights = reference.make_weights(ref_config, BIG_SEED)
    state = {"mu": {k: jnp.zeros_like(v) for k, v in weights.items()},
             "nu": {k: jnp.zeros_like(v) for k, v in weights.items()},
             "count": 0}
    first = {k: np.asarray(v) for k, v in weights.items()}
    for batch in batches:
        reference.train_step(weights, state, batch, ref_config)
    mine = program.parameters(trainer, ref_config, names)
    for name in names:
        _close(mine[name], weights[name], tol=1e-3)
        change = float(np.linalg.norm(np.asarray(mine[name]) - first[name]))
        assert change == pytest.approx(theirs["change_norms"][name],
                                       rel=1e-3), name

    del trainer, mine
    gc.collect()
    after = obs.get_registry().snapshot()["counters"]
    grew = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    tokens = sum(b["tokens"].size for b in batches)
    counts = np.asarray(theirs["counts"])
    held = list(config.experts_held)
    assert grew["lm_tokens_total"] == tokens
    assert grew["lm_loss_tokens_total"] == 3 * (2 * 48 - 6)
    assert grew["lm_documents_total"] == 3 * 2 * 3
    assert grew["attention_plain_steps_total"] == 3
    assert grew["attention_fused_steps_total"] == 0
    assert grew["moe_grouped_plain_steps_total"] == 3
    assert grew["moe_slots_total"] == (config.num_experts_per_tok * tokens
                                       * config.expert_layers)
    assert grew["moe_local_slots_total"] == counts[..., held].sum()
    assert grew["moe_busiest_expert_slots_total"] == counts.max(-1).sum()
    # by hand: a document of n tokens holds n (n + 1) / 2 pairs, under the
    # window of 12 no more than 78 + 12 (n - 12); one full layer, two sliding
    full = sum(n * (n + 1) // 2 for row in LENGTHS for n in row)
    band = sum(n * (n + 1) // 2 if n <= 12 else 78 + 12 * (n - 12)
               for row in LENGTHS for n in row)
    assert (full, band) == (15 + 465 + 91 + 210 + 36 + 210,
                            15 + 294 + 90 + 174 + 36 + 174)
    assert grew["attention_full_pairs_total"] == 3 * full
    assert grew["attention_window_pairs_total"] == 3 * 2 * band


def test_mellum_checkpoints_carry_the_routing_state(tiny, tmp_path):
    """A step, a checkpoint, a step, a restore: the whole ``moe`` collection
    comes back (counts, fullest experts, overflows; a bias of zeros), and
    the counters go on from it."""
    from tensorflowonspark_tpu.trainer import Trainer

    config = tiny[0]
    before = obs.get_registry().snapshot()["counters"].get(
        "moe_slots_total", 0)
    trainer = Trainer("mellum_moe", config=config, devices=jax.devices()[:1])
    batch = mellum_moe.example_batch(config, 2, seq_len=config.seq_len)
    trainer.step(batch)
    trainer.step(batch)
    want = {k: np.asarray(v) for k, v in
            trainer.state.collections[mellum_moe.COLLECTION].items()}
    per_step = (config.num_experts_per_tok * 2 * config.seq_len
                * config.expert_layers)
    assert want["counts"].sum() == 2 * per_step
    assert not want["bias"].any()
    trainer.save(str(tmp_path / "ckpt"))
    trainer.step(batch)
    trainer.restore(str(tmp_path / "ckpt"))
    got = trainer.state.collections[mellum_moe.COLLECTION]
    assert set(got) == {"bias", "counts", "busiest", "overflow", "tight"}
    for name in got:
        np.testing.assert_array_equal(got[name], want[name])
    trainer.step(batch)
    del trainer
    gc.collect()
    assert obs.get_registry().snapshot()["counters"]["moe_slots_total"] \
        - before == 4 * per_step        # every step run, no step twice


def test_mellum_step_names_its_scopes_forward_and_backward(tiny):
    """Every scope the cell's per-layer metrics read is on an operation of
    the lowered gradient, in the forward pass and under ``transpose``:
    ``benchmark/swa_scopes.py`` finds them by word, and the two kinds of
    blocks nest in ``attention`` without being found as it."""
    config, _, _, params = tiny
    batch = _rows(config, 2)
    text = jax.jit(jax.grad(lambda p: mellum_moe.loss_terms(
        p, _zero_bias(config), batch["tokens"], batch["segment_ids"],
        config)[0])).lower(params).as_text(debug_info=True)
    names = {n for n in re.findall(r'loc\("([^"]*)"', text) if "/" in n}
    for scope in ("attention", "qk_norm_rope", "window_attention",
                  "full_attention", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "lm_head"):
        word = re.compile(rf"\b{scope}\b")
        found = [n for n in names if word.search(n)]
        assert any("transpose" in n for n in found), scope
        assert any("transpose" not in n for n in found), scope
    for inner in ("qk_norm_rope", "window_attention", "full_attention"):
        alone = [n for n in names if re.search(rf"\b{inner}\b", n)
                 and not re.search(r"\battention\b", n)]
        assert not alone, alone[:5]
    both = [n for n in names if re.search(r"\bwindow_attention\b", n)
            and re.search(r"\bfull_attention\b", n)]
    assert not both, both[:5]


# ---------------------------------------------------------------------------
# the window in the shared attention
# ---------------------------------------------------------------------------


def _masked_softmax(q, k, v, seg, scale, window):
    """One masked softmax over every key: the oracle of both executions."""
    t = q.shape[0]
    at = jnp.arange(t)
    mask = (at[:, None] >= at[None, :]) & (seg[:, None] == seg[None, :])
    if window is not None:
        mask = mask & (at[:, None] - at[None, :] < window)
    s = jnp.einsum("ikrd,jkd->krij", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST) * scale
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("krij,jkd->ikrd", p, v.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST).astype(q.dtype)


def _inputs(lengths, kv, rep, hd, dtype, seed=0):
    rng = np.random.default_rng(seed + len(lengths) + hd)
    t = sum(lengths)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)

    seg = jnp.asarray(np.repeat(np.arange(len(lengths)) + 3,
                                lengths).astype(np.int32))
    return (normal(t, kv, rep, hd), normal(t, kv, hd), normal(t, kv, hd),
            seg, normal(t, kv, rep, hd))


def _output_and_gradients(attend, inputs):
    q, k, v, seg, weigh = inputs

    def run(q, k, v):
        out = attend(q, k, v, seg)
        return jnp.sum(out.astype(jnp.float32) * weigh.astype(jnp.float32)
                       ), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        run, (0, 1, 2), has_aux=True))(q, k, v)
    return (out,) + grads


@pytest.fixture
def kernels_on_the_cpu(monkeypatch):
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        yield


#: windows against blocks of 16 on a row of 64: shorter than a block, a
#: block, between one and two, a whole number of blocks, longer than the row
@pytest.mark.parametrize("window", [2, 5, 16, 23, 32, 64, 100])
@pytest.mark.parametrize("lengths", [[64], [3, 40, 21]])
def test_window_in_the_jnp_blocks_is_one_masked_softmax(window, lengths):
    """``document_attention(window=w)`` in blocks of 16 — values, ``dq``,
    ``dk``, ``dv`` — against one masked softmax that JAX differentiates, on
    rows whose documents are shorter and longer than the window."""
    inputs = _inputs(lengths, 2, 2, 8, jnp.float32)
    got = _output_and_gradients(
        lambda q, k, v, seg: packed_rows.document_attention(
            q, k, v, seg, 0.3, 16, jnp.float32, window=window), inputs)
    want = _output_and_gradients(
        lambda q, k, v, seg: _masked_softmax(q, k, v, seg, 0.3, window),
        inputs)
    for g, w in zip(got, want):
        _close(g, w)


@pytest.mark.parametrize("blocks,window,dtype", [
    ((128, 128), 40, "float32"), ((128, 128), 128, "float32"),
    ((128, 128), 200, "bfloat16"), ((128, 128), 1000, "float32"),
    ((256, 128), 40, "bfloat16"), ((256, 128), 200, "float32"),
    ((256, 128), 256, "float32"), ((128, 256), 40, "float32"),
    ((128, 256), 128, "bfloat16"), ((128, 256), 300, "float32")])
def test_window_in_the_kernels_is_one_masked_softmax(blocks, window, dtype,
                                                     kernels_on_the_cpu):
    """The kernels of ``attention_pallas`` in Pallas's interpreter on a row
    of 512 tokens, two query heads on one key head, at blocks of queries and
    keys alike and unlike, the forward pass's other than the backward's, at
    windows shorter than a block, a block, between one and two, two, and
    longer than the row, over documents shorter and longer than the window:
    against one masked softmax."""
    dtype = jnp.dtype(dtype)
    inputs = _inputs([17, 300, 1, 194], 1, 2, 128, dtype)
    got = _output_and_gradients(
        lambda q, k, v, seg: attention_pallas.fused_attention(
            q, k, v, seg, 0.125, dtype, ("attention",), blocks, blocks[::-1],
            window=window), inputs)
    want = _output_and_gradients(
        lambda q, k, v, seg: _masked_softmax(q, k, v, seg, 0.125, window),
        tuple(x.astype(jnp.float32) if x.dtype == dtype and i != 3 else x
              for i, x in enumerate(inputs)))
    tol = 2e-5 if dtype == jnp.float32 else 1.5e-2
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), name


def test_the_rule_hands_the_window_to_the_kernels(kernels_on_the_cpu):
    """``document_attention`` at shapes that fit (the kernels' own blocks: a
    row of 512 is one block) passes its window on."""
    inputs = _inputs([100, 412], 1, 2, 128, jnp.float32)
    assert packed_rows.attention_runs_fused(512, 128)
    got = _output_and_gradients(
        lambda q, k, v, seg: packed_rows.document_attention(
            q, k, v, seg, 0.125, 64, jnp.float32, window=77), inputs)
    want = _output_and_gradients(
        lambda q, k, v, seg: _masked_softmax(q, k, v, seg, 0.125, 77), inputs)
    for g, w in zip(got, want):
        _close(g, w)


@pytest.mark.parametrize("form", ["jnp", "kernels"])
def test_no_window_is_todays_result_bit_for_bit(form, request):
    """``window=None`` takes the loops and the mask as they were (the
    lowered steps of the four models that call it so are the parent's:
    ``CHANGES.md``, PR 47), and a window that holds the whole row gives the
    same bits: it visits the same blocks and masks nothing more."""
    if form == "kernels":
        request.getfixturevalue("kernels_on_the_cpu")
        inputs = _inputs([100, 412], 1, 2, 128, jnp.float32)

        def attend(window):
            return lambda q, k, v, seg: attention_pallas.fused_attention(
                q, k, v, seg, 0.1, jnp.float32, (), (128, 128), (128, 128),
                window=window)
    else:
        inputs = _inputs([3, 40, 21], 2, 2, 8, jnp.float32)

        def attend(window):
            return lambda q, k, v, seg: packed_rows.document_attention(
                q, k, v, seg, 0.3, 16, jnp.float32, window=window)
    t = inputs[0].shape[0]
    plain = _output_and_gradients(attend(None), inputs)
    for g, w in zip(_output_and_gradients(attend(t), inputs), plain):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # and by position alone: the default is no window
    old = _output_and_gradients(
        lambda q, k, v, seg: packed_rows._attend()(
            q, k, v, seg, 0.3, 16, jnp.float32, ()), inputs) \
        if form == "jnp" else plain
    for g, w in zip(old, plain):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("form", ["jnp", "kernels"])
def test_blocks_behind_the_window_are_not_visited(form, request):
    """A value that is not a number in the row's first block reaches every
    query that visits the block, through a probability of exactly 0 where
    the mask hides it (0 x NaN): without a window it spoils the whole row
    (``test_packed_rows_attention.py``), under a window of one block it
    spoils the first two blocks of queries — the second visits the first
    block for its window's far edge — and no later one, whatever the
    documents are: those blocks of scores are never made.  (Without a
    window the kernels' loops stop at a document's edge, and the last block
    of queries of the row of three documents, whose own starts in the
    third, is clean: ``test_packed_rows_attention.py``.)"""
    size, t, hd = (128, 512, 128) if form == "kernels" else (16, 64, 8)
    if form == "kernels":
        request.getfixturevalue("kernels_on_the_cpu")
    for lengths in ([t], [t // 8, t // 2, 3 * t // 8]):
        q, k, v, seg, _ = _inputs(lengths, 1, 2, hd, jnp.float32)
        v = v.at[5].set(jnp.nan)
        if form == "kernels":
            run = lambda w: attention_pallas.fused_attention(  # noqa: E731
                q, k, v, seg, 0.1, jnp.float32, (), (size, size),
                (size, size), window=w)
        else:
            run = lambda w: packed_rows.document_attention(  # noqa: E731
                q, k, v, seg, 0.1, size, jnp.float32, (), window=w)
        spoiled = np.isnan(np.asarray(run(None))).any(axis=(1, 2, 3))
        reached = t - size if form == "kernels" and len(lengths) > 1 else t
        assert spoiled[:reached].all() and not spoiled[reached:].any()
        spoiled = np.isnan(np.asarray(run(size))).any(axis=(1, 2, 3))
        assert spoiled[:2 * size].all() and not spoiled[2 * size:].any()
        # two tokens more and the third block's first query reaches the
        # first block's last key: the whole third block visits it
        spoiled = np.isnan(np.asarray(run(size + 2))).any(axis=(1, 2, 3))
        assert spoiled[:3 * size].all() and not spoiled[3 * size:].any()


def test_blocks_visited_are_the_hand_count():
    """From the loops' bounds at the published row of 8,192 tokens and
    window of 1,024.  The kernels: forward at 1,024 x 1,024 a block of
    queries visits its own block of keys and the one before, 2 x 8 - 1 = 15
    of the 36 (4.5 a block) it visits without a window; backward at 512 x
    512 a block of keys is visited by its own block of queries and the two
    after, 3 x 16 - 3 = 45 of 136 (8.5 a block).  The ``jnp`` blocks of 256:
    the first four blocks of queries visit 1, 2, 3, 4, every later one 5
    (the window's 1,023 tokens back reach into a fifth block): 150 of 528."""
    assert attention_pallas.visited(
        8192, attention_pallas.FORWARD_BLOCKS,
        attention_pallas.BACKWARD_BLOCKS) == (36, 136)
    assert attention_pallas.visited(
        8192, attention_pallas.FORWARD_BLOCKS,
        attention_pallas.BACKWARD_BLOCKS, 1024) == (15, 45)
    assert attention_pallas.visited(8192, (512, 512), (512, 512), 1024) \
        == (45, 45)
    # a query 1,025 back is still in the block before; two tokens more reach
    # one block further, and a window of two tokens still crosses an edge
    assert attention_pallas.visited(8192, (1024, 1024), (512, 512), 1025) \
        == (15, 45)
    assert attention_pallas.visited(8192, (1024, 1024), (512, 512), 1026) \
        == (1 + 2 + 6 * 3, 13 * 4 + 3 + 2 + 1)
    assert attention_pallas.visited(8192, (1024, 1024), (512, 512), 2) \
        == (15, 16 + 15)
    assert attention_pallas.visited(8192, (1024, 1024), (512, 512), 1) \
        == (8, 16)

    def jnp_blocks(window):
        return sum(i + 1 - int(packed_rows.first_key_block(i, 256, window))
                   for i in range(32))

    assert jnp_blocks(None) == 32 * 33 // 2 == 528
    assert jnp_blocks(1024) == 1 + 2 + 3 + 4 + 28 * 5 == 150
    assert jnp_blocks(1025) == 150
    assert jnp_blocks(1026) == 1 + 2 + 3 + 4 + 5 + 27 * 6
    assert jnp_blocks(1) == 32
    # the forward kernel's bounds at block 5 of 8: keys from block 4 on, the
    # far edge crosses block 4 alone; the backward kernel's at block 3 of 16
    first, inside = attention_pallas.forward_bounds(5, 1024, 1024, 1024)
    assert (int(first), int(inside)) == (4, 5)
    inside, last = attention_pallas.backward_bounds(3, 512, 512, 1024, 16)
    assert (int(inside), int(last)) == (5, 6)


def test_the_kernels_loops_under_a_window_come_from_the_shapes_alone():
    """The forward kernel's jaxpr with a window: the segment ids are read
    into the mask's comparison only; the two loops' bounds (the blocks the
    far edge crosses, the blocks inside) are ``program_id`` arithmetic,
    clipped by the documents' bound (the call's first operand:
    ``test_packed_rows_attention.py``)."""
    t, hd = 1024, 128
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((t, 2 * hd), jnp.float32), ((t, 2 * hd), jnp.float32),
        ((t, 2 * hd), jnp.float32), ((t,), jnp.int32))]
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, seg: attention_pallas._forward(
            q, k, v, seg, 0.1, jnp.dtype("float32"), hd, 256, 128, 300)
    )(*shapes)
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    kernel = call.params["jaxpr"]
    tainted = set(kernel.invars[4:6])
    for e in kernel.eqns:
        if tainted & {v for v in e.invars if hasattr(v, "count")}:
            tainted |= set(e.outvars)
    loops = 0
    for e in kernel.eqns:
        if e.primitive.name != "while":
            continue
        n, m = e.params["cond_nconsts"], e.params["body_nconsts"]
        decides = list(e.invars[:n]) + list(e.invars[n + m:])
        assert not (tainted & {v for v in decides if hasattr(v, "count")})
        loops += 1
    assert loops == 2


# ---------------------------------------------------------------------------
# the two rotations
# ---------------------------------------------------------------------------


def test_yarn_frequencies_are_the_hand_count():
    """The published ``rope_parameters``: ``c(r) = 128 ln(8192 / (2 pi r)) /
    (2 ln 500000)`` gives ``low = floor(c(32)) = 18`` and ``high =
    ceil(c(1)) = 35``; frequency 0 and 18 are plain RoPE's, 26 is blended
    (ramp 8/17), 35 and 63 are a sixteenth of plain's."""
    full = mellum_moe.published_rope_parameters()["full_attention"]
    c = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(500000))
    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    low, high, ramp = packed_rows.yarn_ramp(64, 500000, 8192, 32, 1)
    assert (low, high) == (18, 35)
    assert ramp[18] == 0 and ramp[35] == 1 and ramp[26] == pytest.approx(
        8 / 17)
    freq, factor = mellum_moe.rotation(mellum_moe.Config(), "full_attention")
    plain = lambda i: 500000.0 ** (-i / 64)     # noqa: E731
    assert factor == full["attention_factor"] == pytest.approx(
        0.1 * math.log(16) + 1)
    assert float(freq[0]) == 1.0
    assert float(freq[18]) == pytest.approx(plain(18), rel=1e-6)
    assert float(freq[26]) == pytest.approx(
        plain(26) * (9 / 17 + 8 / 17 / 16), rel=1e-6)
    assert float(freq[35]) == pytest.approx(plain(35) / 16, rel=1e-6)
    assert float(freq[63]) == pytest.approx(plain(63) / 16, rel=1e-6)
    sliding, one = mellum_moe.rotation(mellum_moe.Config(),
                                       "sliding_attention")
    assert one == 1.0
    np.testing.assert_allclose(sliding, [plain(i) for i in range(64)],
                               rtol=1e-6)
    # the reference writes the same from the formula, on its own
    theirs, theirs_factor = reference.frequencies(
        {"rope_parameters": mellum_moe.published_rope_parameters(),
         "head_dim": 128}, "full_attention")
    np.testing.assert_allclose(freq, theirs, rtol=1e-6)
    assert theirs_factor == factor


def test_rope_takes_frequencies_and_a_factor():
    """``packed_rows.rope`` against the reference's ``rotate`` at YaRN's
    frequencies with cosine and sine times the factor, and by hand: pair
    ``i`` of a head of 8 is (x_i, x_{i+4}), turned by ``pos * f_i`` and
    stretched by the factor; position 0 leaves a head times the factor."""
    config = mellum_moe.Config.tiny()
    freq, factor = mellum_moe.rotation(config, "full_attention")
    assert factor == pytest.approx(0.1 * math.log(4) + 1)
    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.standard_normal((5, 2, 3, 8)), jnp.float32)
    pos = jnp.asarray([0, 1, 2, 0, 7], jnp.int32)
    got = packed_rows.rope(x, pos, freq, factor)
    _close(got, reference.rotate(x, pos, freq, factor), tol=1e-6)
    _close(got[0], factor * x[0], tol=1e-6)
    # tiny's YaRN: low 0, high 2, so frequency 1 is half blended
    assert packed_rows.yarn_ramp(4, 10000.0, 16, 2.0, 0.25)[:2] == (0, 2)
    f1 = 10000.0 ** (-1 / 4) * (0.5 + 0.5 / 4)
    assert float(freq[1]) == pytest.approx(f1, rel=1e-6)
    a, b = float(x[4, 1, 2, 1]), float(x[4, 1, 2, 5])
    assert float(got[4, 1, 2, 1]) == pytest.approx(
        factor * (a * np.cos(7 * f1) - b * np.sin(7 * f1)), rel=1e-5)
    assert float(got[4, 1, 2, 5]) == pytest.approx(
        factor * (b * np.cos(7 * f1) + a * np.sin(7 * f1)), rel=1e-5)
    # a factor of 1 and plain frequencies are the rotation the other
    # models have
    plain = packed_rows.rope(x, pos, packed_rows.rope_frequencies(1e6, 4))
    assert float(jnp.abs(plain[0] - x[0]).max()) == 0.0


# ---------------------------------------------------------------------------
# the router's scores
# ---------------------------------------------------------------------------


def _route_inputs():
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.standard_normal((12, 8)), jnp.float32),
            jnp.asarray(rng.standard_normal((8, 6)), jnp.float32))


@pytest.mark.parametrize("normalize", [True, False])
def test_topk_route_with_softmax_scores_is_a_top_k_of_a_softmax(normalize):
    h, w = _route_inputs()
    chosen, gates = moe.topk_route(h, w, jnp.zeros(6), top_k=3, scale=1.0,
                                   normalize=normalize, score="softmax")
    p = np.asarray(jax.nn.softmax(h @ w, axis=-1))
    want = np.argsort(-p, axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(want, -1))
    picked = np.take_along_axis(p, np.asarray(chosen), 1)
    np.testing.assert_allclose(
        gates, picked / picked.sum(-1, keepdims=True) if normalize
        else picked, rtol=1e-6)
    if normalize:
        np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    with pytest.raises(ValueError):
        moe.topk_route(h, w, jnp.zeros(6), top_k=3, scale=1.0, score="tanh")


def test_topk_route_with_sigmoid_scores_is_unchanged():
    """The default is the score the three earlier layouts have, and their
    ``Routing`` names it without being told."""
    h, w = _route_inputs()
    chosen, gates = moe.topk_route(h, w, jnp.zeros(6), top_k=2, scale=1.8)
    same, named = moe.topk_route(h, w, jnp.zeros(6), top_k=2, scale=1.8,
                                 score="sigmoid")
    np.testing.assert_array_equal(chosen, same)
    np.testing.assert_array_equal(gates, named)
    scores = np.take_along_axis(np.asarray(jax.nn.sigmoid(h @ w)),
                                np.asarray(chosen), 1)
    np.testing.assert_allclose(
        gates, 1.8 * scores / scores.sum(-1, keepdims=True), rtol=1e-6)
    assert lfm2_moe.routing(lfm2_moe.Config.tiny()).score == "sigmoid"
    assert moe.Routing(8, 1, (0,), 2, 1.0, True, 0.0).score == "sigmoid"


# ---------------------------------------------------------------------------
# the expert layer at a quarter share, softmax scores, no shared expert
# ---------------------------------------------------------------------------


def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips hold two of eight experts each (the cell: four of 16 of
    64).  The parts of the result that the four shares give
    (``routed_experts`` told which two, softmax scores; nothing is computed
    alike on every chip: the layout has no shared expert) add up to what the
    uncut reference gives for the whole layer, to float32 rounding; every
    share reports the same counts, and every slot lands on exactly one
    share."""
    rng = np.random.default_rng(0)
    d, f, n, tokens = 32, 16, 8, 48
    g = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]),  # noqa: E731
                               jnp.float32)
    w = {"router": g(d, n), "experts_gate": g(n, d, f),
         "experts_up": g(n, d, f), "experts_down": g(n, f, d)}
    h = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    ref_config = {"num_experts_per_tok": 3, "norm_topk_prob": True,
                  "experts_held": list(range(n)),
                  "published": {"num_experts": n}}
    whole, want_counts = reference.experts(w, h, ref_config, lambda a: a)
    total, landed = jnp.zeros_like(h), 0
    for share in range(4):
        held = (2 * share, 2 * share + 1)
        take = np.asarray(held)
        part, counts = jax.jit(lambda h, held=held, take=take: (
            moe.routed_experts(
                h, w["router"], jnp.zeros(n), w["experts_gate"][take],
                w["experts_up"][take], w["experts_down"][take], held,
                top_k=3, scale=1.0, score="softmax")))(h)
        np.testing.assert_array_equal(counts, want_counts)
        total = total + part
        landed += int(np.asarray(counts)[take].sum())
    assert landed == 3 * tokens         # every slot on one share
    _close(total, whole, tol=1e-6)
    # the cell's own share: 16 of 64 hold a quarter of a row's 65,536 slots
    # if the router is even, and three times that fit the first form
    assert moe.prefix_rows(8 * 8192, 16, 64) == 49152


# ---------------------------------------------------------------------------
# documents and the window: what a token sees
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("side", ["program", "reference"])
@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_a_layer_sees_its_document_and_no_further_than_its_window(
        tiny, side, kind):
    """One attention layer of each kind on a row of two documents (20 and 28
    tokens, the window 12): change the token 12 places before a query and
    the sliding layer's output there stays to the last bit while the full
    layer's moves; change the token 11 places before and both move; nothing
    of the first document reaches the second; and the positions restart (a
    document alone gives what it gives packed)."""
    config, ref_config, weights, params = tiny
    rng = np.random.default_rng(21)
    t, cut, at = 48, 20, 40
    h = jnp.asarray(rng.standard_normal((t, config.hidden_size)), jnp.float32)
    seg = (np.arange(t) >= cut).astype(np.int32)
    layer = {"sliding_attention": 0, "full_attention": 1}[kind]

    @jax.jit
    def mine(x, s):
        return mellum_moe.attention(
            params, f"l{layer:02d}_", x, s,
            packed_rows.document_positions(s), config, kind)

    @jax.jit
    def theirs(x, s, pos):
        w = {k[4:]: v for k, v in weights.items()
             if k.startswith(f"l{layer:02d}/")}
        return reference.attention(w, x, s, pos, ref_config, lambda a: a,
                                   kind)

    def mix(x, s):
        s = np.asarray(s)
        if side == "program":
            return mine(x, jnp.asarray(s))
        return theirs(x, jnp.asarray(s),
                      jnp.asarray(reference.positions(s[None])[0]))

    packed = mix(h, seg)
    _close(packed[:cut], mix(h[:cut], seg[:cut]), tol=1e-5)
    _close(packed[cut:], mix(h[cut:], seg[cut:]), tol=1e-5)
    far = mix(h.at[at - 12].add(1.0), seg)
    near = mix(h.at[at - 11].add(1.0), seg)
    moved = float(jnp.abs(far[at] - packed[at]).max())
    if kind == "sliding_attention":
        assert moved == 0.0
    else:
        assert moved > 1e-4 * float(jnp.abs(packed).max())
    assert float(jnp.abs(near[at] - packed[at]).max()) > (
        1e-4 * float(jnp.abs(packed).max()))
    other = mix(h.at[cut - 1].add(1.0), seg)
    np.testing.assert_array_equal(np.asarray(other[cut:]),
                                  np.asarray(packed[cut:]))


def test_the_grouped_query_layer_has_one_body(tiny):
    """``lfm2_moe.attention`` and ``mellum_moe.attention`` both call
    ``packed_rows.grouped_query_attention``; with no window, plain
    frequencies and a factor of 1 the two models' layers are the same
    function of the same leaves."""
    config, _, _, params = tiny
    assert lfm2_moe.grouped_query_attention \
        is mellum_moe.grouped_query_attention \
        is packed_rows.grouped_query_attention
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((48, config.hidden_size)),
                    jnp.float32)
    seg = jnp.asarray(np.repeat([0, 1], [20, 28]).astype(np.int32))
    pos = packed_rows.document_positions(seg)
    plain = dataclasses.replace(
        config, sliding_window=48, rope_parameters={
            k: {"rope_type": "default", "rope_theta": 1e6}
            for k in config.rope_parameters})
    theirs = lfm2_moe.Config(
        hidden_size=config.hidden_size, num_attention_heads=4,
        num_key_value_heads=2, rope_theta=1e6, norm_eps=config.rms_norm_eps,
        dtype="float32", attention_block=16, layer_types=("full_attention",))
    assert theirs.head_dim == config.head_dim
    for kind in ("sliding_attention", "full_attention"):
        np.testing.assert_array_equal(
            np.asarray(mellum_moe.attention(params, "l00_", h, seg, pos,
                                            plain, kind)),
            np.asarray(lfm2_moe.attention(params, "l00_", h, seg, pos,
                                          theirs)))


@pytest.mark.parametrize("window", [None, 1, 12, 13, 100])
def test_mask_pairs_is_a_count_of_the_mask(window):
    seg = np.stack([np.repeat([7, 2, 5], n) for n in LENGTHS])
    at = np.arange(seg.shape[1])
    brute = 0
    for row in seg:
        mask = (at[:, None] >= at[None, :]) & (row[:, None] == row[None, :])
        if window is not None:
            mask &= at[:, None] - at[None, :] < window
        brute += int(mask.sum())
    assert mellum_moe.mask_pairs(seg, window) == brute
