"""The hybrid state-space / attention decoder (``models/granite_hybrid.py``)
against the plain reference of the ``granite_4_0_h_micro`` configuration, at
``Config.tiny()`` in float32 on the CPU; the packed-row traffic generator and
the configuration's operation counts.

Tolerances: both sides compute in float32 with products at the highest
precision, so they differ only by the order of their sums (the program's
chunked scan and running softmax against the reference's token-by-token
recurrence and whole softmax): 2e-5 relative to the largest entry covers what
a few hundred float32 additions in another order move, and is 1,000 times
tighter than a forgotten boundary or multiplier would need.
"""

import dataclasses
import glob
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.configs.granite_4_0_h_micro import program, reference, work
from benchmark.traffic import packed_documents
from tensorflowonspark_tpu.models import (granite_hybrid as gh, kernels,
                                         packed_rows)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(REPO, "benchmark", "configs", "granite_4_0_h_micro")
BIG_SEED = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits
TOL = 2e-5


def _published():
    with open(os.path.join(CONFIG_DIR, "config.json")) as f:
        return json.load(f)


def _tiny_dict(config: gh.Config, learning_rate=1e-3) -> dict:
    """``Config.tiny()`` under the keys the configuration's file has."""
    return {
        # the list is longer than the depth, as the published one is
        "layer_types": list(config.layer_types) + ["attention", "mamba"],
        "num_hidden_layers": len(config.layer_types),
        "hidden_size": config.hidden_size,
        "shared_intermediate_size": config.intermediate_size,
        "vocab_size": config.vocab_size,
        "num_attention_heads": config.num_attention_heads,
        "num_key_value_heads": config.num_key_value_heads,
        "mamba_n_heads": config.mamba_n_heads,
        "mamba_d_head": config.mamba_d_head,
        "mamba_d_state": config.mamba_d_state,
        "mamba_n_groups": config.mamba_n_groups,
        "mamba_d_conv": config.mamba_d_conv,
        "mamba_chunk_size": config.mamba_chunk_size,
        "embedding_multiplier": config.embedding_multiplier,
        "residual_multiplier": config.residual_multiplier,
        "attention_multiplier": config.attention_multiplier,
        "logits_scaling": config.logits_scaling,
        "rms_norm_eps": config.rms_norm_eps, "dtype": config.dtype,
        "seq_len": config.seq_len, "parameters": gh.parameter_count(config),
        "optimizer": dict(gh.ADAMW, name="adamw",
                          learning_rate=learning_rate),
    }


def _rows(config: gh.Config, n: int, seed: int) -> dict:
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
    config = gh.Config.tiny()
    ref_config = _tiny_dict(config)
    weights = reference.make_weights(ref_config, BIG_SEED)
    params = {program.program_name(k): v for k, v in weights.items()}
    return config, ref_config, weights, params


@pytest.fixture(scope="module", autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _reference_loss(weights, batch, ref_config):
    tokens, seg = jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"])

    def row(u, s):
        x = reference.embed(weights["embed"], u, ref_config)
        for i, kind in enumerate(reference.layer_types(ref_config)):
            x = reference.layer(kind, reference._layer_leaves(weights, i), x,
                                s, ref_config)
        return reference.loss_sum(x, weights["embed"], weights["final_norm"],
                                  u, s, ref_config)

    counted = (seg[:, 1:] == seg[:, :-1]).sum()
    return jax.vmap(row)(tokens, seg).sum() / counted


# ---------------------------------------------------------------------------
# program against reference
# ---------------------------------------------------------------------------


def test_granite_logits_loss_and_every_leafs_gradient_match_the_reference(tiny):
    config, ref_config, weights, params = tiny
    batch = _rows(config, 2, 1)
    _close(gh.apply_tokens(params, None, batch["tokens"], batch["segment_ids"],
                           config),
           reference.forward(weights, jnp.asarray(batch["tokens"]),
                             jnp.asarray(batch["segment_ids"]), ref_config))
    loss, grads = jax.jit(jax.value_and_grad(gh.make_loss_fn(None, config)))(
        params, batch)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda w, b: _reference_loss(w, b, ref_config)))(weights, batch)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert set(grads) == {program.program_name(k) for k in want}
    for name, g in want.items():
        assert float(jnp.abs(g).max()) > 0, name    # every leaf is trained
        _close(grads[program.program_name(name)], g)


def _without_kept_products():
    """The decoder with nothing of the feed-forward's kept: the parent's."""
    return dataclasses.replace(gh._DECODER, saved=())


@pytest.mark.parametrize("layers", [("mamba",),
                                    ("mamba", "attention", "mamba")])
def test_granite_feed_forwards_make_their_wide_products_once(layers):
    """A layer's recomputation keeps the results of the feed-forward's two
    wide products (``packed_rows.SWIGLU_SAVED``, which ``_DECODER.saved``
    lists), so the lowered gradient of the loss holds two ``dot_general``
    a layer fewer than with nothing listed: 9 a feed-forward (3 forward, 6
    backward) for 11 (2 more made again)."""
    config = dataclasses.replace(gh.Config.tiny(), layer_types=layers)
    assert gh._DECODER.saved == packed_rows.SWIGLU_SAVED
    batch = _rows(config, 1, 3)
    params = gh.make_model(config).init(
        jax.random.PRNGKey(0), batch["tokens"], batch["segment_ids"])["params"]

    def products(decoder):
        return jax.jit(jax.grad(decoder.make_loss_fn(None, config))).lower(
            params, batch).as_text().count("stablehlo.dot_general")

    kept, again = products(gh._DECODER), products(_without_kept_products())
    assert again - kept == 2 * len(layers)


def test_granite_kept_products_are_the_ones_a_second_pass_made(tiny):
    """What is kept is what the recomputation made: the loss and every
    leaf's gradient are equal to the last bit with the two names kept and
    with ``saved`` emptied.  Operation by operation, not jitted: the same
    operations then run in the same order on both sides, where a compiler
    given two whole programs fuses the sums round the kept values its own
    way in each (a last bit of a fiftieth of the entries, on the CPU)."""
    config = dataclasses.replace(tiny[0], layer_types=tiny[0].layer_types[:3])
    assert set(config.layer_types) == {"mamba", "attention"}
    params = {k: tiny[3][k] for k in gh.leaf_shapes(config)}
    batch = _rows(config, 1, 4)

    def value_and_grad(decoder):
        with jax.disable_jit():
            return jax.value_and_grad(decoder.make_loss_fn(None, config))(
                params, batch)

    (loss, grads), (want_loss, want) = (
        value_and_grad(gh._DECODER), value_and_grad(_without_kept_products()))
    assert np.asarray(loss).tobytes() == np.asarray(want_loss).tobytes()
    assert set(grads) == set(want) == set(gh.leaf_shapes(config))
    for name, g in want.items():
        assert float(jnp.abs(g).max()) > 0, name
        np.testing.assert_array_equal(np.asarray(grads[name]), np.asarray(g),
                                      err_msg=name)


def test_granite_step_counts_the_layers_that_keep_their_products(
        tiny, monkeypatch):
    """``ffn_kept_layers_total`` a step: the configuration's layers while
    the decoder lists ``packed_rows.SWIGLU_SAVED``, 0 with ``saved``
    emptied; a ``Trainer.step`` adds it to the program's counters."""
    from tensorflowonspark_tpu import obs
    from tensorflowonspark_tpu.trainer import Trainer

    config = tiny[0]
    batch = _rows(config, 1, 5)
    assert gh.batch_counters(batch, config)["ffn_kept_layers_total"] == len(
        config.layer_types) > 1
    published = program.model_config(_published())
    assert gh.batch_counters(batch, published)["ffn_kept_layers_total"] == 10

    def total():
        return obs.get_registry().snapshot()["counters"].get(
            "ffn_kept_layers_total", 0)

    trainer = Trainer("granite_hybrid", config=config,
                      devices=jax.devices()[:1])
    before = total()
    trainer.step(batch)
    assert total() - before == len(config.layer_types)
    monkeypatch.setattr(gh, "_DECODER", _without_kept_products())
    assert gh.batch_counters(batch, config)["ffn_kept_layers_total"] == 0
    assert gh.batch_counters(batch, published)["ffn_kept_layers_total"] == 0


def test_granite_trainer_follows_the_reference_for_three_adamw_steps(tiny):
    """Through ``Trainer``: the seeded weights loaded a leaf at a time, three
    steps, then the losses, the first gradient's norms as AdamW's first
    moment shows them, and every parameter.  After three steps of AdamW a
    difference of 1e-6 in a gradient whose second moment is still tiny can
    move an update by its whole size (lr 1e-3 of a leaf of order 1e-2), so
    the parameters are held to 1e-3 of their largest entry; the change's
    norm, which the benchmark compares, to 1e-3."""
    from tensorflowonspark_tpu.trainer import Trainer

    config, ref_config, _, _ = tiny
    trainer = Trainer("granite_hybrid", config=config, learning_rate=1e-3,
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
    assert losses[2] < losses[0]
    for name in names:
        assert grad_norms[name] == pytest.approx(
            theirs["grad_norms"][name], rel=1e-4), name
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


def test_granite_bfloat16_activations_stay_near_the_float32_reference(tiny):
    """The configuration's own precision at the tiny size: bfloat16 keeps 8
    bits, and a loss near log(64) moves by well under a hundredth of itself."""
    import dataclasses

    config, ref_config, weights, params = tiny
    batch = _rows(config, 2, 3)
    loss = gh.make_loss_fn(None, dataclasses.replace(
        config, dtype="bfloat16"))(params, batch)
    assert float(loss) == pytest.approx(
        float(_reference_loss(weights, batch, ref_config)), rel=1e-2)


# ---------------------------------------------------------------------------
# the chunked scan against the recurrence, token by token
# ---------------------------------------------------------------------------


def _scan_inputs(t=40, heads=4, p=3, groups=2, n=5, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda a: jnp.asarray(a, jnp.float32)          # noqa: E731
    # documents of 7, 13, 1, 11 and 8 tokens: no chunk size divides them all
    seg = np.repeat(np.arange(5), [7, 13, 1, 11, 8]).astype(np.int32)[:t]
    return (f(rng.normal(size=(t, heads, p))),
            f(rng.uniform(0.01, 0.5, size=(t, heads))),
            f(-rng.uniform(1, 16, size=(heads,))),
            f(rng.normal(size=(t, groups, n))),
            f(rng.normal(size=(t, groups, n))), jnp.asarray(seg))


@pytest.fixture(scope="module")
def by_token():
    """The recurrence token by token (the reference's), and its gradients."""
    x, dt, a, b, c, seg = inputs = _scan_inputs()
    weigh = jnp.asarray(np.random.default_rng(1).normal(size=x.shape),
                        jnp.float32)

    def run(x, dt, a, b, c):
        y = reference.recurrence(x, dt, a, b, c, seg)
        return jnp.sum(y * weigh), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        run, (0, 1, 2, 3, 4), has_aux=True))(x, dt, a, b, c)
    return inputs, weigh, y, grads


@pytest.mark.parametrize("chunk", [1, 4, 7, 8, 16, 40, 64])
def test_granite_chunked_scan_is_the_token_recurrence(by_token, chunk):
    """Forward and the gradient to every operand, for chunks that divide the
    row (1, 4, 8, 40), that do not (7, 16: the row is padded), that hold
    whole documents and that cut them, and for one chunk longer than the
    row."""
    (x, dt, a, b, c, seg), weigh, want_y, want = by_token

    def chunked(x, dt, a, b, c):
        y = gh.ssd_scan(x, dt, a, b, c, seg, chunk, jnp.float32)
        return jnp.sum(y * weigh), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        chunked, (0, 1, 2, 3, 4), has_aux=True))(x, dt, a, b, c)
    _close(y, want_y)
    for g, w in zip(grads, want):
        _close(g, w)


# ---------------------------------------------------------------------------
# the scan on the Pallas kernels (interpret mode), the rule that picks them
# ---------------------------------------------------------------------------

#: a chunk of 16 tokens, 32 heads (two blocks of sixteen) of 4 values, state 8
FUSED_CHUNK = 16
LAYOUTS = {
    "one_document": [48],
    "boundary_on_a_chunks_edge": [16, 32],
    "boundary_inside_a_tile": [7, 13, 1, 19, 8],
    "a_document_a_token": [1] * 48,
    "row_not_a_multiple_of_the_chunk": [7, 13, 1, 11, 8],     # 40 tokens
}


def _fused_inputs(lengths, dtype):
    rng = np.random.default_rng(4)
    t, heads, p, n = sum(lengths), 32, 4, 8
    f = lambda a, d=jnp.float32: jnp.asarray(a, jnp.float32).astype(d)  # noqa
    seg = jnp.asarray(np.repeat(np.arange(len(lengths)) + 3, lengths)
                      .astype(np.int32))
    return (f(rng.normal(size=(t, heads, p)), dtype),
            f(rng.uniform(0.01, 0.5, size=(t, heads))),
            f(-rng.uniform(1, 16, size=(heads,))),
            f(rng.normal(size=(t, 1, n)), dtype),
            f(rng.normal(size=(t, 1, n)), dtype), seg,
            f(rng.normal(size=(t, heads, p))))


def _scan_and_gradients(scan, inputs, dtype):
    x, dt, a, b, c, seg, weigh = inputs

    def run(x, dt, a, b, c):
        y = scan(x, dt, a, b, c, seg, FUSED_CHUNK, dtype)
        return jnp.sum(y * weigh), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        run, (0, 1, 2, 3, 4), has_aux=True))(x, dt, a, b, c)
    return (y,) + grads


@pytest.fixture
def kernels_on_the_cpu(monkeypatch):
    """The rule says "fused" whatever the shapes, and the kernels run in
    Pallas's interpreter: ``ssd_scan`` then pads the row, calls
    ``ssd_pallas.fused_scan`` and cuts the padding off, as on a chip."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(gh, "scan_runs_fused", lambda *shape: True)
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_granite_fused_scan_is_the_token_recurrence(layout, dtype,
                                                    kernels_on_the_cpu):
    """``y`` and the gradient to each of ``x``, ``dt``, ``a``, ``b``, ``c``.
    In float32 against the recurrence token by token, to the tolerance the
    ``jnp`` form is held to (the running sums' gradient comes from two
    identities, ``sum_j dW_ij W_ij = <dy_i, y_i>`` and ``sum_i dW_ij W_ij =
    <(dt x)_j, d(dt x)_j>``, not from the tile).  With bfloat16 operands against the ``jnp``
    form at the same dtype: both round the same operands (the tile, ``dt
    x``, the states) to 8 bits before each product, but sum in another
    order and round the gradients to ``x``, ``b`` and ``c`` once where the
    ``jnp`` form rounds twice; one bfloat16 step is 2 ** -8 = 0.4% of a
    value, so 1.5e-2 of the largest entry is a few steps, and a missed term
    or boundary is of order 1."""
    dtype = jnp.dtype(dtype)
    inputs = _fused_inputs(LAYOUTS[layout], dtype)
    got = _scan_and_gradients(gh.ssd_scan, inputs, dtype)
    if dtype == jnp.float32:
        want = _scan_and_gradients(
            lambda x, dt, a, b, c, seg, chunk, dtype:
            reference.recurrence(x, dt, a, b, c, seg), inputs, dtype)
        tol = TOL
    else:
        want = _scan_and_gradients(_plain_scan, inputs, dtype)
        tol = 1.5e-2
    for name, g, w in zip(("y", "x", "dt", "a", "b", "c"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if name == "a":
            # a head's gradient to ``a`` is a sum over tokens of what the
            # gradient to ``dt`` holds a token of, and where no decay acts
            # (a document a token) it is exactly 0, the kernels' two equal
            # sums apart by their rounding: judged on the scale of ``dt``'s
            w = np.append(w, np.abs(want[2]).max())
            g = np.append(g, w[-1])
        _close(g, w, tol)


def _plain_scan(*args):
    """``ssd_scan``'s ``jnp`` form while the fixture says "fused"."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(gh, "scan_runs_fused", lambda *shape: False)
        return gh.ssd_scan(*args)


def test_granite_scan_rule_picks_the_kernels_on_a_tpu_at_the_published_shapes(
        monkeypatch):
    published = program.model_config(_published())
    tiny = gh.Config.tiny()
    shape = lambda c: (c.mamba_chunk_size, c.mamba_n_heads,    # noqa: E731
                       c.mamba_d_head, c.mamba_n_groups, c.mamba_d_state)
    assert shape(published) == (256, 64, 64, 1, 128)
    # here the backend is the CPU: the jnp form, whatever the shapes
    assert jax.default_backend() == "cpu"
    assert not gh.scan_runs_fused(*shape(published))
    assert not gh.scan_runs_fused(*shape(tiny))
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    assert gh.scan_runs_fused(*shape(published))
    assert not gh.scan_runs_fused(*shape(tiny))
    # each of the kernels' tiles has to be whole
    for chunk, heads, p, groups, n in [(64, 64, 64, 1, 128),
                                       (256, 60, 64, 1, 128),
                                       (256, 64, 32, 1, 128),
                                       (256, 64, 64, 2, 128),
                                       (256, 64, 64, 1, 64)]:
        assert not gh.scan_runs_fused(chunk, heads, p, groups, n)
    assert gh.scan_runs_fused(128, 16, 128, 1, 256)


def test_granite_fused_scan_kernels_carry_the_ssm_scan_scope():
    """``benchmark/device_scopes.py`` finds the scan by ``ssm_scan`` as a
    word of a device operation's ``op_name``: every kernel call of the
    forward pass and of the gradient, lowered for a TPU under the scope
    ``mamba_mixer`` opens, has to carry it (a ``custom_vjp``'s backward
    function does not inherit its caller's scope, and a kernel's own name
    is no word boundary)."""
    import re

    from tensorflowonspark_tpu.models import ssd_pallas

    t, heads, p, n, chunk = 512, 16, 64, 128, 256
    assert ssd_pallas.fits(chunk, heads, p, 1, n)
    bf = jnp.bfloat16
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((t, heads, p), bf), ((t, heads), jnp.float32),
        ((heads,), jnp.float32), ((t, 1, n), bf), ((t, 1, n), bf),
        ((t,), jnp.int32))]

    def loss(x, dt, a, b, c, seg):
        with jax.named_scope("ssm_scan"):
            y = ssd_pallas.fused_scan(x, dt, a, b, c, seg, chunk, bf)
        return jnp.sum(y * y)   # the gradient needs the forward's y

    word = re.compile(r"\bssm_scan\b")
    for fn, kernels in ((loss, 2), (jax.grad(loss, (0, 1, 2, 3, 4)), 4)):
        text = jax.jit(fn).trace(*shapes).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
        locations = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))

        def name_of(line):
            """The name a line's location carries (``loc("name"(...))``),
            through the aliases."""
            where = re.search(r"loc\((#loc\d+)\)\s*$", line).group(1)
            return locations[where]

        # a kernel call sits in the function of its ``jax.jit`` (the XLA
        # inliner then joins the call site's name and the kernel's own):
        # the call sites carry the scope
        inside, function = {}, None
        for line in text.splitlines():
            opened = re.search(r"func\.func (?:public |private )?@(\w+)",
                               line)
            function = opened.group(1) if opened else function
            if "stablehlo.custom_call @tpu_custom_call" in line:
                inside.setdefault(function, []).append(line)
        assert sum(len(v) for v in inside.values()) == kernels
        sites = 0
        for function, calls in inside.items():
            if function == "main":
                named = calls
            else:
                named = [line for line in text.splitlines()
                         if re.search(rf"call @{function}\(", line)]
            assert named
            sites += len(named)
            for line in named:
                assert word.search(name_of(line)), name_of(line)
        assert sites == kernels


def test_granite_step_counts_the_execution_of_its_scan(tiny, monkeypatch):
    """One ``Trainer.step``: exactly one of ``ssm_scan_fused_steps_total``
    and ``ssm_scan_plain_steps_total`` goes up by one, by the rule
    ``ssd_scan`` applied when the step was traced; the other is on the
    record with what it had."""
    from tensorflowonspark_tpu import obs
    from tensorflowonspark_tpu.trainer import Trainer

    config = tiny[0]
    trainer = Trainer("granite_hybrid", config=config,
                      devices=jax.devices()[:1])
    names = ("ssm_scan_fused_steps_total", "ssm_scan_plain_steps_total")

    def totals():
        counters = obs.get_registry().snapshot()["counters"]
        return np.array([counters.get(k, 0) for k in names])

    before = totals()
    trainer.step(_rows(config, 2, 13))
    assert set(names) <= set(obs.get_registry().snapshot()["counters"])
    np.testing.assert_array_equal(totals() - before, [0, 1])    # the CPU
    staged = trainer.shard(_rows(config, 2, 14))
    before = totals()
    trainer.step(staged)
    np.testing.assert_array_equal(totals() - before, [0, 1])
    # what a chip's step at the published shapes counts
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    counts = gh.batch_counters(_rows(config, 1, 15),
                               program.model_config(_published()))
    assert (counts["ssm_scan_fused_steps_total"],
            counts["ssm_scan_plain_steps_total"]) == (1, 0)
    assert gh.batch_counters(_rows(config, 1, 15), config)[
        "ssm_scan_plain_steps_total"] == 1      # tiny shapes: still jnp
    # attention's execution is on the record beside the scan's
    counters = obs.get_registry().snapshot()["counters"]
    assert counters["attention_plain_steps_total"] >= 2
    assert counters.get("attention_fused_steps_total", 0) == 0


@pytest.mark.parametrize("size", [4, 16, 48])
@pytest.mark.parametrize("documents", ["one", "uneven", "every_four",
                                       "ids_not_rising"])
def test_granite_document_attention_is_masked_softmax_attention(size, documents):
    t, kv, rep, hd = 48, 2, 3, 5
    rng = np.random.default_rng(2)
    seg = jnp.asarray({
        "one": np.zeros(t), "uneven": np.sort(rng.integers(0, 6, t)),
        "every_four": np.repeat(np.arange(12), 4),
        "ids_not_rising": np.array([7] * 5 + [3] * 30 + [9] * 13),
    }[documents].astype(np.int32))
    q, k, v, weigh = (jnp.asarray(rng.normal(size=s), jnp.float32) for s in (
        (t, kv, rep, hd), (t, kv, hd), (t, kv, hd), (t, kv, rep, hd)))

    def dense(q, k, v):
        at = jnp.arange(t)
        mask = (at[:, None] >= at[None, :]) & (seg[:, None] == seg[None, :])
        w = jax.nn.softmax(jnp.where(
            mask, 0.3 * jnp.einsum("ikrd,jkd->krij", q, k), -jnp.inf), -1)
        return jnp.sum(jnp.einsum("krij,jkd->ikrd", w, v) * weigh)

    def blocked(q, k, v):
        return jnp.sum(gh.document_attention(q, k, v, seg, 0.3, size,
                                             jnp.float32) * weigh)

    got, grads = jax.value_and_grad(blocked, (0, 1, 2))(q, k, v)
    want, want_grads = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(grads, want_grads):
        _close(g, w)


# ---------------------------------------------------------------------------
# packing, the vocabulary's slice, the depth cut
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("side", ["program", "reference"])
def test_granite_packed_documents_do_not_see_each_other(tiny, side):
    """Each document's logits inside a packed row are those of the document
    alone: the convolution's look-back, the recurrent state and the
    attention mask all stop at its first token."""
    config, ref_config, weights, params = tiny
    # documents of 5, 11, 5 and 11 tokens: neither the scan's chunk (8) nor
    # attention's block (16) ends where a document does
    batch = {"tokens": _rows(config, 1, 5)["tokens"],
             "segment_ids": np.repeat(np.arange(4), [5, 11, 5, 11])[None]
             .astype(np.int32)}

    @jax.jit
    def logits(tokens, seg):
        if side == "program":
            return gh.apply_tokens(params, None, tokens, seg, config)[0]
        return reference.forward(weights, tokens, seg, ref_config)[0]

    packed = logits(batch["tokens"], batch["segment_ids"])
    seg = batch["segment_ids"][0]
    for doc in sorted(set(seg.tolist())):
        mine = np.flatnonzero(seg == doc)
        alone = logits(batch["tokens"][:, mine], np.zeros((1, len(mine)),
                                                          np.int32))
        _close(packed[mine], alone)
    whole = logits(batch["tokens"], np.zeros_like(batch["segment_ids"]))
    second = np.flatnonzero(seg == 1)
    assert float(jnp.abs(whole[second] - packed[second]).max()) > 1e-4


def test_granite_vocabulary_slice_gives_the_whole_models_columns(tiny):
    """A chip that holds the first rows of the tied embedding computes, for
    ids inside its slice, the same columns of the logits as the whole
    vocabulary's model."""
    import dataclasses

    config, _, _, params = tiny
    held = config.vocab_size // 4
    sliced = dict(params, embed=params["embed"][:held])
    batch = _rows(config, 2, 7)
    tokens = batch["tokens"] % held
    whole = gh.apply_tokens(params, None, tokens, batch["segment_ids"], config)
    part = gh.apply_tokens(sliced, None, tokens, batch["segment_ids"],
                           dataclasses.replace(config, vocab_size=held))
    assert part.shape[-1] == held
    _close(part, whole[..., :held])


def test_granite_depth_cut_keeps_the_first_periods_layer_types():
    published = _published()
    model = program.model_config(published)
    assert len(published["layer_types"]) == 40
    assert list(model.layer_types) == published["layer_types"][:10]
    assert model.layer_types == gh.PERIOD
    assert [i for i, k in enumerate(model.layer_types)
            if k == "attention"] == [5]
    assert tuple(published["layer_types"]) == gh.PUBLISHED_LAYERS
    assert reference.layer_types(published) == list(gh.PERIOD)
    assert work.layer_types(published) == list(gh.PERIOD)
    # every width is the published one
    assert (model.hidden_size, model.intermediate_size, model.d_inner,
            model.conv_dim, model.head_dim, model.mamba_d_state) == (
                2048, 8192, 4096, 4352, 64, 128)
    assert model.vocab_size * 8 == published["published"]["vocab_size"]
    for key in ("num_hidden_layers", "vocab_size", "dataset"):
        assert key in published["reduced"]


def test_granite_parameter_count_is_the_configurations():
    published = _published()
    shapes = reference.leaf_shapes(published)
    count = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert count == published["parameters"] == 772_160_448
    assert gh.parameter_count(program.model_config(published)) == count
    assert {program.program_name(k): tuple(s) for k, (s, _) in shapes.items()
            } == gh.leaf_shapes(program.model_config(published))
    # by hand: a mamba layer, the attention layer, embedding and final norm
    mamba = (2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * 2048
             + 3 * 2048 * 8192 + 2 * 2048)
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192 + 2 * 2048
    assert (mamba, attn) == (76_182_976, 60_821_504)
    assert count == 9 * mamba + attn + 12_544 * 2048 + 2048


def test_granite_seeded_leaves_can_be_made_again_one_at_a_time(tiny):
    _, ref_config, weights, _ = tiny
    again = reference.make_leaf(ref_config, BIG_SEED, "l01/in_proj")
    np.testing.assert_array_equal(again, weights["l01/in_proj"])
    other = reference.make_leaf(ref_config, BIG_SEED + 1, "l01/in_proj")
    assert float(jnp.abs(other - again).max()) > 0
    dt = jax.nn.softplus(weights["l00/dt_bias"])
    assert 1e-3 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.0001
    a = jnp.exp(weights["l00/A_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0


# ---------------------------------------------------------------------------
# counters, traffic, operation counts
# ---------------------------------------------------------------------------


def test_granite_step_counts_tokens_loss_tokens_and_documents(tiny):
    """``lm_loss_tokens_total`` a step is the count of positions whose next
    token is in the same document, whether the step is handed the host's
    batch or one staged ahead with ``Trainer.shard``."""
    from tensorflowonspark_tpu import obs
    from tensorflowonspark_tpu.trainer import Trainer

    config = tiny[0]
    trainer = Trainer("granite_hybrid", config=config,
                      devices=jax.devices()[:1])

    def totals():
        counters = obs.get_registry().snapshot()["counters"]
        return np.array([counters.get(k, 0) for k in (
            "lm_tokens_total", "lm_loss_tokens_total", "lm_documents_total",
            "trainer_steps_total")])

    batch = _rows(config, 2, 9)
    seg = batch["segment_ids"]
    want = [seg.size, int((seg[:, 1:] == seg[:, :-1]).sum()),
            sum(len(set(row.tolist())) for row in seg), 1]
    assert want[1] == seg.size - want[2]
    before = totals()
    trainer.step(batch)
    np.testing.assert_array_equal(totals() - before, want)
    staged = trainer.shard(batch)
    trainer.shard(_rows(config, 2, 11))     # staged ahead, never stepped
    before = totals()
    trainer.step(staged)
    np.testing.assert_array_equal(totals() - before, want)
    del staged
    import gc

    # the completion watcher holds a staged batch's arrays until they are
    # ready on the device (PR 37): let it finish before looking
    trainer._watcher.close()
    gc.collect()
    assert not trainer._staged_counts       # the counts went with the arrays


TRAFFIC = {"records": 12, "shards": 4, "seq_len": 256, "vocab": 500,
           "zipf_s": 1.0, "doc_median": 40, "doc_sigma": 1.2, "doc_min": 4,
           "doc_max": 256}


def test_granite_packed_rows_are_made_again_as_they_were_written(tmp_path):
    from tensorflowonspark_tpu import tfrecord

    made = packed_documents.generate(TRAFFIC, BIG_SEED, str(tmp_path / "a"))
    again = packed_documents.generate(TRAFFIC, BIG_SEED, str(tmp_path / "b"))
    other = packed_documents.generate(TRAFFIC, BIG_SEED + 1,
                                      str(tmp_path / "c"))
    read = lambda d: [open(p, "rb").read() for p in sorted(   # noqa: E731
        glob.glob(d["glob"]))]
    assert read(made) == read(again) and read(made) != read(other)
    assert made["records"] == 12 and len(read(made)) == 4
    parse = program.tfrecord_parse_fn({})
    seen = {}
    for path in sorted(glob.glob(made["glob"])):
        for payload in tfrecord.read_records(path, verify=True):
            row = parse(payload)
            seen[int(row["id"])] = row
    assert sorted(seen) == list(range(12))
    rows = packed_documents.rows(TRAFFIC, BIG_SEED, [3, 11])
    batch = program.host_batch({k: np.stack([seen[i][k] for i in (3, 11)])
                                for k in ("tokens", "segment_ids")})
    for key in ("tokens", "segment_ids"):
        assert batch[key].dtype == np.int32
        np.testing.assert_array_equal(batch[key], rows[key])


def test_granite_packed_rows_are_whole_documents_cut_at_the_rows_end():
    params = dict(TRAFFIC, seq_len=8192, vocab=12544, doc_median=600,
                  doc_min=16, doc_max=8192)
    rows = packed_documents.rows(params, BIG_SEED, range(48))
    seg, tokens = rows["segment_ids"], rows["tokens"]
    assert seg.shape == tokens.shape == (48, 8192)
    assert (seg[:, 0] == 0).all() and (np.diff(seg, axis=1) >= 0).all()
    assert (np.diff(seg, axis=1) <= 1).all()        # numbered in order
    documents = seg.max(axis=1) + 1
    assert 4 <= documents.mean() <= 10              # six or seven a row
    lengths = np.concatenate([np.bincount(r)[:-1] for r in seg])    # uncut
    assert lengths.min() >= 16 and 300 <= np.median(lengths) <= 900
    assert 0 <= tokens.min() and tokens.max() < 12544
    counts = np.bincount(tokens.ravel(), minlength=12544)
    top = np.sort(counts)[::-1].astype(np.float64)
    slope = np.polyfit(np.log(np.arange(1, 51)), np.log(top[:50]), 1)[0]
    assert slope == pytest.approx(-1.0, abs=0.1)    # Zipf s = 1
    assert counts.argmax() != 0                     # a seeded permutation


def test_granite_operation_counts_match_the_hand_worked_figures(tiny):
    published = _published()
    in_proj, out_proj, mlp = 2048 * 8512, 4096 * 2048, 3 * 2048 * 8192
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    matmul = 9 * (in_proj + out_proj + mlp) + attn + mlp + 12_544 * 2048
    assert work.matmul_parameters(published) == matmul == 771_883_008
    step = work.step_work(published, 1)
    assert step["flops"] == 6 * matmul * 8192
    assert step["bytes"] == 2 * 4 * 8192 + 28 * 772_160_448
    assert step["examples"] == 1
    scan = work.scan_work(published, 1)
    assert scan["flops"] == 15 * 64 * 64 * 128 * 8192 * 9
    assert scan["bytes"] == ((5 * 4096 + 6 * 128) * 2 + 3 * 64 * 4) * 8192 * 9
    # the tiny size, by hand: three mamba layers and one attention layer of
    # width 32 (d_inner 64, conv 80, 4 heads), feed-forward 64, 64 ids
    tiny_config = tiny[1]
    mamba = 32 * (64 + 80 + 4) + 64 * 32
    assert work.matmul_parameters(tiny_config) == (
        3 * mamba + (2 * 32 * 32 + 2 * 32 * 16) + 4 * 3 * 32 * 64 + 64 * 32)
    assert work.scan_work(tiny_config, 2) == {
        "flops": 15 * 4 * 16 * 8 * 2 * 32 * 3,
        "bytes": ((5 * 64 + 6 * 8) * 4 + 3 * 4 * 4) * 2 * 32 * 3}


# ---------------------------------------------------------------------------
# the cell's per-layer readers
# ---------------------------------------------------------------------------

FIXTURE_TRACE = os.path.join(REPO, "tests", "benchmark_checks", "fixtures",
                             "tiny_spans_v5e.xplane.pb.gz")
NEW_METRICS = ("ssm_scan_device_ms", "ssm_mixer_share_pct",
               "attention_device_ms", "ssm_scan_roofline_pct",
               "loss_tokens_per_s_chip")


def _cell_run(**over):
    from benchmark import peaks, spec

    cell = spec.cell(spec.load(REPO), "granite_h_micro_packed_8k")
    run = {"cell": cell, "notes": [], "peaks": peaks.PEAKS["TPU v5 lite"],
           "trainer": {"window": {"steps": 50, "seconds": 30.0},
                       "t_window_start": 0.0}, "driver": {}}
    run.update(over)
    return run


def test_granite_device_scopes_cut_a_recorded_trace_by_scope_name():
    """A recorded v5e trace of the tiny ResNet step: a scope's time is the
    union of the operations whose ``op_name`` holds its name as a word."""
    from benchmark import device_scopes, program_spans

    out = device_scopes.reduce_xplane(
        FIXTURE_TRACE, ["ResNet", "Conv_0", "Conv", "ssm_scan"])
    whole = program_spans.reduce_xplane(FIXTURE_TRACE)
    assert out["steps"] == whole["steps"] > 0
    busy = sum(whole["phase_s"].values())
    assert 0 < out["scope_s"]["Conv_0"] < out["scope_s"]["ResNet"] <= busy
    assert out["scope_s"]["Conv"] == 0      # a word, not a prefix
    assert out["scope_s"]["ssm_scan"] == 0  # a program without the scope
    assert len(out["top_ops"]) == 10
    assert out["top_ops"][0][2] >= out["top_ops"][-1][2] > 0


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_granite_reader_finds_nothing_where_the_program_wrote_nothing(metric):
    """An untraced run, or a program with neither the scopes nor the
    counters (the parent of the PR that added them): no value, no error."""
    from benchmark import spec

    reader = spec.module("benchmark", "metrics", metric)
    assert reader.read(_cell_run(_program={
        "spans": {}, "dropped": 0, "counters": None})) is None
    assert reader.read(_cell_run(
        _device_scopes={"steps": 5, "scope_s": {"ssm_scan": 0.0,
                                                "ssm_mixer": 0.0,
                                                "attention": 0.0},
                        "top_ops": []},
        _program={"spans": {}, "dropped": 0,
                  "counters": {"n": {"counters": {}}}})) is None


def test_granite_readers_turn_scope_seconds_and_counters_into_the_metrics():
    from benchmark import spec

    run = _cell_run(
        _device_scopes={"steps": 5, "top_ops": [], "scope_s": {
            "ssm_scan": 0.5, "ssm_mixer": 1.25, "attention": 0.2}},
        _program={"spans": {}, "dropped": 0, "counters": {
            "trainer": {"counters": {"lm_loss_tokens_total": 8180.0 * 60,
                                     "trainer_steps_total": 60.0}},
            "driver": {"counters": {}}}})
    run["trainer"]["trace"] = {"steps": 5, "busy_s": 2.5}

    def read(name):
        return spec.module("benchmark", "metrics", name).read(run)

    assert read("ssm_scan_device_ms") == pytest.approx(100.0)
    assert read("attention_device_ms") == pytest.approx(40.0)
    assert read("ssm_mixer_share_pct") == pytest.approx(50.0)
    # the recurrence's bytes at 819 GB/s are its bound: 3.19 GB -> 3.89 ms
    assert read("ssm_scan_roofline_pct") == pytest.approx(
        100 * (3_189_768_192 / 819e9) / 0.1)
    assert any("memory bound" in note for note in run["notes"])
    assert read("loss_tokens_per_s_chip") == pytest.approx(8180 * 50 / 30.0)
