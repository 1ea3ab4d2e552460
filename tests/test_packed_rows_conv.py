"""``packed_rows.causal_conv`` in its two executions: the Pallas kernels of
``models/conv_pallas.py``, run here in Pallas's interpreter
(``pltpu.force_tpu_interpret_mode``), against the ``jnp`` form in the same
file's caller, which stays the oracle; the rule that picks between them; and
the counters that say which a step ran.

Tolerances: both executions take the same operands to float32 and sum a
token's taps in the same order, so the values differ by a rounding of the
last float32 bit (and of one bfloat16 step, 2 ** -8 of a value, where the
result leaves in bfloat16 and that bit decides its rounding); the gradients
to the taps and the bias sum 256 rows in another order.  A missed halo, mask
or carry is of order 1.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu.models import (conv_pallas, granite_hybrid,
                                          kernels, kimi_linear, lfm2_moe,
                                          mla_moe, packed_rows)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, C, TILE = 128, 256, 32

#: the three callers' forms: taps, the operands' type, a (C,) bias, SiLU,
#: the operand a product with a second array and a gate on the result, the
#: result's type
FORMS = {
    "granite_4_taps_bias_silu_to_bfloat16":
        (4, "bfloat16", True, True, False, "bfloat16"),
    "kimi_4_taps_silu_to_float32":
        (4, "bfloat16", False, True, False, "float32"),
    "kimi_4_taps_silu_to_bfloat16":
        (4, "bfloat16", False, True, False, "bfloat16"),
    "lfm2_3_taps_on_a_product_with_a_gate":
        (3, "bfloat16", False, False, True, "bfloat16"),
    "lfm2_3_taps_float32_operands":
        (3, "float32", False, False, True, "float32"),
    "float32_4_taps_bias_silu":
        (4, "float32", True, True, False, "float32"),
}
#: the tokens at which a document starts, over four tiles of 32 rows
ROWS = {
    "one_document": [],
    "starts_on_a_tiles_first_row": [32, 96],
    "starts_on_a_tiles_last_row": [31, 95],
    "starts_one_past_a_tiles_first_row": [33, 65],
    "documents_shorter_than_the_taps": [30, 31, 33, 34, 64, 66, 67, 127],
    "every_kind_of_start": [5, 31, 32, 33, 62, 64, 65, 97, 98, 99],
}


def _segments(starts):
    seg = np.zeros(T, np.int32)
    for s in starts:
        seg[s:] += 3        # ids differ, they need not be consecutive
    return jnp.asarray(seg)


def _operands(form):
    taps, dtype, bias, silu, gated, out = form
    rng = np.random.default_rng(taps + 10 * bias + 100 * gated)

    def normal():
        return jnp.asarray(rng.normal(size=(T, C)), jnp.float32
                           ).astype(dtype)

    leaves = {"x": normal(),
              "w": jnp.asarray(rng.uniform(-.5, .5, (taps, C)), jnp.float32)}
    if bias:
        leaves["b"] = jnp.asarray(rng.uniform(-.5, .5, (C,)), jnp.float32)
    if gated:
        leaves["times"], leaves["gate"] = normal(), normal()
    return leaves, jnp.asarray(rng.normal(size=(T, C)), jnp.float32)


def _value_and_grads(conv, form, leaves, cot, seg):
    _, _, _, silu, _, out = form

    def loss(leaves):
        y = conv(leaves["x"], leaves["w"], leaves.get("b", 0.0), seg,
                 times=leaves.get("times"), gate=leaves.get("gate"),
                 silu=silu, out=jnp.dtype(out))
        return jnp.sum(y.astype(jnp.float32) * cot), y

    (_, y), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(leaves)
    return y, grads


def _close(got, want, tol):
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert not np.isnan(got).any()
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-6)


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("form", sorted(FORMS))
def test_conv_kernels_are_the_shifted_sums(form, rows):
    """The kernels against the ``jnp`` form: the value and the gradients of
    the operand, of the second factor and the gate where the form has them,
    of the taps and of the bias, over rows whose documents start on a
    tile's first row, on its last, one past it, and with documents shorter
    than the taps."""
    form, seg = FORMS[form], _segments(ROWS[rows])
    taps, dtype = form[0], jnp.dtype(form[1])
    assert conv_pallas.fits(T, C, taps, TILE)
    leaves, cot = _operands(form)
    want, want_grads = _value_and_grads(packed_rows.causal_conv, form,
                                        leaves, cot, seg)
    with pltpu.force_tpu_interpret_mode():
        got, grads = _value_and_grads(
            lambda *a, **kw: conv_pallas.fused_conv(*a, rows=TILE, **kw),
            form, leaves, cot, seg)
    step = 2e-6 if dtype == jnp.float32 else 5e-3
    _close(got, want, 2e-6 if form[5] == "float32" else 5e-3)
    assert sorted(grads) == sorted(want_grads)
    for name in grads:
        assert float(jnp.abs(want_grads[name].astype(jnp.float32)).max()) > 0
        _close(grads[name], want_grads[name],
               2e-5 if name in ("w", "b") else step)


def test_a_tap_never_reaches_into_the_document_before():
    """NaN in every token of the documents around one: its rows of the
    result and of the operand's gradient hold none (the mask selects, it
    does not multiply by zero), forward and backward, across the tiles'
    edges."""
    seg = _segments([31, 64])
    mine = np.asarray(seg) == 3         # tokens 31 to 63
    rng = np.random.default_rng(0)
    x = jnp.asarray(np.where(mine[:, None], rng.normal(size=(T, C)), np.nan),
                    jnp.float32)
    cot = jnp.asarray(np.where(mine[:, None], np.ones((T, C)), np.nan),
                      jnp.float32)
    w = jnp.asarray(rng.uniform(-.5, .5, (4, C)), jnp.float32)

    def both(conv):
        y, vjp = jax.vjp(lambda x: conv(x, w, 0.0, seg, silu=True), x)
        return y, vjp(cot)[0]

    want = jax.jit(lambda: both(packed_rows.causal_conv))()
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(lambda: both(
            lambda *a, **kw: conv_pallas.fused_conv(*a, rows=TILE, **kw)))()
    for g, w_ in zip(got, want):
        assert not np.isnan(np.asarray(g)[mine]).any()
        np.testing.assert_allclose(np.asarray(g)[mine], np.asarray(w_)[mine],
                                   rtol=1e-5, atol=1e-6)


def test_tap_positions_are_the_document_positions_clipped():
    seg = _segments(ROWS["every_kind_of_start"])
    for taps in (2, 3, 4, 7):
        np.testing.assert_array_equal(
            conv_pallas.tap_positions(seg, taps)[:, 0],
            jnp.minimum(packed_rows.document_positions(seg), taps - 1))


@pytest.mark.parametrize("t,c,taps,b,backend,fused", [
    (8192, 4352, 4, "array", "tpu", True),      # granite's
    (8192, 4096, 4, 0.0, "tpu", True),          # Kimi Linear's
    (8192, 2048, 3, 0.0, "tpu", True),          # LFM2's
    (8192, 4352, 4, "array", "cpu", False),     # another backend
    (8192, 4352, 8, 0.0, "tpu", False),         # taps past the halo
    (8192, 4000, 4, 0.0, "tpu", False),         # no whole rows of lanes
    (8000, 4096, 4, 0.0, "tpu", False),         # no whole tiles of rows
    (64, 16, 4, 0.0, "tpu", False),             # Config.tiny()'s
])
def test_the_rule_reads_backend_and_shapes(t, c, taps, b, backend, fused,
                                           monkeypatch):
    monkeypatch.setattr(kernels, "backend", lambda: backend)
    b = np.zeros(c, np.float32) if b == "array" else b
    assert packed_rows.conv_runs_fused(t, c, taps, b) is fused


def test_causal_conv_calls_the_kernels_where_the_rule_says(monkeypatch):
    """``causal_conv`` with the backend patched to a TPU at a shape the
    kernels' own tile divides runs them (the interpreter here) and agrees
    with the ``jnp`` form it takes on the CPU; at a row their tile does
    not divide it calls none."""
    t = 2 * conv_pallas.ROW_TILE
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(t, 128)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(-.5, .5, (4, 128)), jnp.float32)
    seg = jnp.asarray((np.arange(t) >= conv_pallas.ROW_TILE - 1
                       ).astype(np.int32))
    kw = dict(silu=True, out=jnp.bfloat16, scopes=("a", "b"))
    want = packed_rows.causal_conv(x, w, 0.0, seg, **kw)
    seen = []
    real = conv_pallas.fused_conv
    monkeypatch.setattr(conv_pallas, "fused_conv",
                        lambda *a, **k: seen.append(1) or real(*a, **k))
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    np.testing.assert_array_equal(
        packed_rows.causal_conv(x[:-8], w, 0.0, seg[:-8], **kw), want[:-8])
    assert not seen
    with pltpu.force_tpu_interpret_mode():
        got = packed_rows.causal_conv(x, w, 0.0, seg, **kw)
    assert seen
    _close(got, want, 5e-3)


def test_the_kernels_at_their_own_tiles_through_causal_conv(monkeypatch):
    """Two tiles of rows by two of channels at the kernels' own sizes, a
    document starting on the second tile's second row, through
    ``causal_conv`` with the backend patched to a TPU: the value and the
    gradients against the ``jnp`` form's (the backward kernel's carry starts
    anew with every tile of channels).  (The models call it under
    ``jax.vmap`` and ``jax.checkpoint``, which the interpreter's callbacks
    cannot run under: the compile tests for the described chip hold the
    whole steps.)"""
    t, c = 2 * conv_pallas.ROW_TILE, 2 * conv_pallas.COL_TILE
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(t, c)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(-.5, .5, (4, c)), jnp.float32)
    b = jnp.asarray(rng.uniform(-.5, .5, (c,)), jnp.float32)
    seg = jnp.asarray((np.arange(t) > conv_pallas.ROW_TILE).astype(np.int32))
    cot = jnp.asarray(rng.normal(size=(t, c)), jnp.float32)

    def loss(x, w, b):
        y = packed_rows.causal_conv(x, w, b, seg, silu=True,
                                    out=jnp.bfloat16, scopes=("a", "b"))
        return jnp.sum(y.astype(jnp.float32) * cot)

    want, want_grads = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(x, w, b)
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    assert packed_rows.conv_runs_fused(t, c, 4, b)
    with pltpu.force_tpu_interpret_mode():
        got, grads = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(x, w, b)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w_, tol in zip(grads, want_grads, (5e-3, 5e-5, 5e-5)):
        _close(g, w_, tol)


def _published(name: str):
    import importlib

    program = importlib.import_module(f"benchmark.configs.{name}.program")
    with open(os.path.join(REPO, "benchmark", "configs", name,
                           "config.json")) as f:
        return program.model_config(json.load(f))


@pytest.mark.parametrize("model,backend,fused", [
    ("granite_tiny", "tpu", 0), ("granite_4_0_h_micro", "cpu", 0),
    ("granite_4_0_h_micro", "tpu", 1),
    ("lfm2_tiny", "tpu", 0), ("lfm2_8b_a1b", "cpu", 0),
    ("lfm2_8b_a1b", "tpu", 1),
    ("kimi_tiny", "tpu", 0), ("kimi_linear_48b_a3b", "cpu", 0),
    ("kimi_linear_48b_a3b", "tpu", 1),
])
def test_the_three_models_count_the_execution_of_their_convolution(
        model, backend, fused, monkeypatch):
    """``batch_counters`` of the three models that call ``causal_conv``
    names ``conv_fused_steps_total`` and ``conv_plain_steps_total``, one of
    them 1 and the other 0, by the rule the step's trace applied at the
    batch's shapes."""
    lib = {"granite": granite_hybrid, "lfm2": lfm2_moe,
           "kimi": kimi_linear}[model.split("_")[0]]
    config = (lib.Config.tiny() if model.endswith("tiny")
              else _published(model))
    monkeypatch.setattr(kernels, "backend", lambda: backend)
    batch = {"segment_ids": np.zeros((1, config.seq_len), np.int32)}
    counts = lib.batch_counters(batch, config)
    assert (counts["conv_fused_steps_total"],
            counts["conv_plain_steps_total"]) == (fused, 1 - fused)


def test_a_step_counts_the_execution_of_its_convolution():
    """One ``Trainer.step`` of a model that calls ``causal_conv``: exactly
    one of ``conv_fused_steps_total`` and ``conv_plain_steps_total`` goes up
    by one, by the rule the step's trace applied (here the CPU's: the
    ``jnp`` form); the other is on the record with what it had."""
    from tensorflowonspark_tpu import obs
    from tensorflowonspark_tpu.trainer import Trainer

    config = granite_hybrid.Config.tiny()
    trainer = Trainer("granite_hybrid", config=config,
                      devices=jax.devices()[:1])
    names = ("conv_fused_steps_total", "conv_plain_steps_total")

    def totals():
        counters = obs.get_registry().snapshot()["counters"]
        return np.array([counters.get(k, 0) for k in names])

    for seed in (13, 14):
        before = totals()
        trainer.step(granite_hybrid.example_batch(config, 2, seed))
        assert set(names) <= set(obs.get_registry().snapshot()["counters"])
        np.testing.assert_array_equal(totals() - before, [0, 1])


def test_a_model_without_the_convolution_names_neither_counter():
    config = mla_moe.Config.tiny()
    counts = mla_moe.batch_counters(
        {"segment_ids": np.zeros((1, config.seq_len), np.int32)}, config)
    assert not [name for name in counts if name.startswith("conv_")]


def test_the_docs_say_when_the_kernels_run():
    from tensorflowonspark_tpu import obs

    for text in (obs.__doc__, packed_rows.__doc__):
        assert "conv_fused_steps_total" in text
        assert "conv_plain_steps_total" in text
        assert "conv_runs_fused" in text
