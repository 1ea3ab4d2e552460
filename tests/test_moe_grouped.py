"""The grouped products of ``moe.routed_experts`` in their two executions:
the Pallas kernels of ``parallel/grouped_pallas.py``, run here in Pallas's
interpreter (``pltpu.force_tpu_interpret_mode``), against
``jax.lax.ragged_dot`` in float32, which stays the oracle; the rule that
picks between them; the counters that say which a step ran; and what a
start pays for: the kernel calls a lowered step holds, and none in the
program that initialises the parameters.

The interpreter fills what no kernel wrote with NaN, the strictest stand-in
for "anything": a test that passes here reads no row of no group.

Tolerances: both executions round the same operands (``rows``, ``w`` and the
cotangent) to ``dtype`` and accumulate exact products in float32, so they
differ by the order of their sums: 2e-5 of the largest entry.  The gradient
to the rows leaves the kernel in ``dtype``: one bfloat16 step is 2 ** -8 of a
value, so 1e-2 of the largest entry; a missed visit, boundary or group is
of order 1.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from tensorflowonspark_tpu.models import (
    afmoe, kernels, kimi_linear, lfm2_moe, mellum_moe, mla_moe)
from tensorflowonspark_tpu.parallel import grouped_pallas, moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TILE = 256
ROWS, K, N = 6 * TILE, 128, 256

#: rows a group, over six row tiles
PATTERNS = {
    "even_groups_every_boundary_inside_a_tile": [384, 384, 384, 384],
    "one_group_with_half_the_rows": [768, 100, 200, 50],
    "empty_groups_first": [0, 0, 500, 300],
    "empty_groups_in_the_middle": [300, 0, 0, 400],
    "empty_groups_last": [512, 300, 0, 0],
    "empty_group_on_a_tiles_edge_before_dead_tiles": [512, 0, 0, 0],
    "no_live_row": [0, 0, 0, 0],
    "every_row_live": [512, 512, 256, 256],
    "every_row_live_and_an_empty_group_last": [1024, 512, 0],
    "boundaries_on_a_tiles_edge": [512, 512, 100],
    "boundaries_inside_a_tile": [200, 500, 324, 100],
    "three_groups_inside_one_tile": [40, 8, 130, 1, 600],
    "live_rows_end_inside_a_tile": [300, 300, 0, 200],
    "one_row": [0, 1, 0],
}


@pytest.fixture(autouse=True)
def _fresh_routed_part():
    """The routed part sits under ``jax.jit``, which keeps its traces: a
    test that watches one being made (the calls to ``grouped_product``, the
    text of a lowered step) starts from none."""
    moe._routed_part.cache_clear()
    yield
    moe._routed_part.cache_clear()


def _published(name: str):
    import importlib

    program = importlib.import_module(f"benchmark.configs.{name}.program")
    with open(os.path.join(REPO, "benchmark", "configs", name,
                           "config.json")) as f:
        return program.model_config(json.load(f))


def _operands(sizes, dtype):
    """``rows``, ``w``, the cotangent ``d`` and the live rows' mask; NaN in
    every row of no group, of ``rows`` and of ``d``."""
    rng = np.random.default_rng(len(sizes) + sum(sizes))
    live = (np.arange(ROWS) < sum(sizes))[:, None]

    def normal(*shape):
        return rng.normal(size=shape).astype(np.float32)

    rows = jnp.asarray(np.where(live, normal(ROWS, K), np.nan)).astype(dtype)
    d = jnp.asarray(np.where(live, normal(ROWS, N), np.nan))
    return rows, jnp.asarray(normal(len(sizes), K, N)), d, live


def _oracle(rows, w, d, live, sizes, dtype):
    """``jax.lax.ragged_dot`` in float32 on the operands as the kernels see
    them (rounded to ``dtype``; the rows of no group zero) and its
    gradients."""
    f32 = jnp.float32

    def rounded(a):
        return jnp.where(live if a.ndim == 2 else True, a, 0
                         ).astype(dtype).astype(f32)

    out, vjp = jax.vjp(
        lambda r, w_: jax.lax.ragged_dot(
            r, w_, jnp.asarray(sizes, jnp.int32),
            precision=jax.lax.Precision.HIGHEST), rounded(rows), rounded(w))
    return (out,) + vjp(rounded(d))


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert not np.isnan(got).any()
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_grouped_kernels_are_ragged_dot(pattern, dtype):
    """The forward product and both gradients on live rows; the gradient of
    an empty group's weights exact zeros; NaN past the live rows (in the
    rows and in the cotangent) in no live output."""
    sizes, dtype = PATTERNS[pattern], jnp.dtype(dtype)
    assert grouped_pallas.ROW_TILE == TILE and sum(sizes) <= ROWS
    assert grouped_pallas.fits(ROWS, K, N, dtype)
    rows, w, d, live = _operands(sizes, dtype)

    @jax.jit
    def fused(rows, w, d):
        visits = grouped_pallas.plan(jnp.asarray(sizes, jnp.int32), ROWS)
        out, vjp = jax.vjp(lambda r, w_: grouped_pallas.grouped_product(
            r, w_, visits, "moe_experts"), rows, w)
        return (out,) + vjp(d)

    with pltpu.force_tpu_interpret_mode():
        out, d_rows, d_w = fused(rows, w, d)
    want, want_rows, want_w = _oracle(rows, w, d, live, sizes, dtype)
    assert (out.dtype, d_rows.dtype, d_w.dtype) == (
        jnp.float32, dtype, jnp.float32)
    _close(jnp.where(live, out, 0), want, 2e-5)
    _close(jnp.where(live, d_rows, 0), want_rows,
           2e-5 if dtype == jnp.float32 else 1e-2)
    _close(d_w, want_w, 2e-5)
    for group, size in enumerate(sizes):
        if size == 0:
            np.testing.assert_array_equal(np.asarray(d_w[group]), 0.0)
        else:
            assert float(jnp.abs(d_w[group]).max()) > 0


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_grouped_plan_visits_the_tiles_a_group_has_rows_in(pattern):
    """The grid's length and every visit from the plan, against a count by
    hand: a group's visits are consecutive and rise by tile, cover its rows
    and no tile beyond them, an empty group has one, and no tile past the
    live rows is visited for a group that has rows."""
    sizes = PATTERNS[pattern]
    groups, tiles, offsets, count = jax.jit(
        lambda s: grouped_pallas.plan(s, ROWS))(jnp.asarray(sizes, jnp.int32))
    count = int(count)
    groups, tiles = np.asarray(groups)[:count], np.asarray(tiles)[:count]
    ends = np.cumsum(sizes)
    np.testing.assert_array_equal(offsets, np.concatenate([[0], ends]))
    want = []
    for g, (end, size) in enumerate(zip(ends, sizes)):
        start = end - size
        met = (range(start // TILE, (end - 1) // TILE + 1) if size
               else [min(start // TILE, ROWS // TILE - 1)])
        want += [(g, t) for t in met]
    assert list(zip(groups.tolist(), tiles.tolist())) == want
    assert count <= ROWS // TILE + len(sizes) - 1
    # an output tile is revisited by consecutive visits only
    order = [t for i, t in enumerate(tiles) if i == 0 or tiles[i - 1] != t]
    assert order == sorted(set(order))


@pytest.mark.parametrize("rows,k,n,backend,fused", [
    (12288, 2048, 1536, "tpu", True), (32768, 2048, 1536, "tpu", True),
    (12288, 1536, 2048, "tpu", True), (32768, 1536, 2048, "tpu", True),
    (24576, 2048, 1792, "tpu", True), (32768, 2048, 1792, "tpu", True),
    (24576, 1792, 2048, "tpu", True), (32768, 1792, 2048, "tpu", True),
    (12288, 2048, 1536, "cpu", False), (24576, 2048, 1792, "gpu", False),
    (24576, 2048, 1792 - 64, "tpu", False),     # half a row of lanes
    (24576 - 8, 2048, 1792, "tpu", False),      # whole sublanes, no tile
    (256, 128, 128, "tpu", True), (128, 128, 128, "tpu", False),
    (0, 128, 128, "tpu", False),
    (512, 16384, 16384, "tpu", False),          # blocks beyond VMEM
])
def test_grouped_rule_picks_the_kernels_on_a_tpu_at_shapes_that_fill_tiles(
        rows, k, n, backend, fused, monkeypatch):
    monkeypatch.setattr(kernels, "backend", lambda: backend)
    assert moe.grouped_runs_fused(rows, k, n, jnp.bfloat16) is fused
    assert grouped_pallas.fits(rows, k, n, "bfloat16") is (
        fused or backend != "tpu")


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("overflow", [False, True])
def test_grouped_rule_keeps_the_kernels_out_of_what_a_start_pays_for(
        overflow, backend, monkeypatch):
    """At shapes that fit, on a TPU: the kernels in the form a step takes
    when its slots fit and nowhere else — not in the overflow form (it ran
    in none of the benchmark's 5,000 ``lfm2`` layer-steps and doubled the
    kernels a start loads).  (A module that initialises traces no forward
    pass at all: ``tests/test_packed_decoder.py``.)"""
    monkeypatch.setattr(kernels, "backend", lambda: backend)
    assert moe.grouped_runs_fused(
        24576, 2048, 1792, jnp.bfloat16, overflow=overflow) is (
            backend == "tpu" and not overflow)


@pytest.mark.parametrize("model", ["glm_4_7_flash", "lfm2_8b_a1b"])
def test_published_shapes_fill_the_kernels_tiles_in_both_forms(model,
                                                               monkeypatch):
    """The two sizes ``routed_experts`` traces its routed part at, at the
    published widths: both whole row tiles that fit the kernels' memory
    (either form could run there), the rule puts the form a step takes on
    the kernels and leaves the overflow form on ``ragged_dot``;
    ``Config.tiny()``'s shapes do not fit."""
    config = _published(model)
    lib = mla_moe if model.startswith("glm") else lfm2_moe
    e = (config.n_routed_experts if lib is mla_moe else config.num_experts)
    slots = config.seq_len * config.num_experts_per_tok
    prefix = moe.prefix_rows(slots, len(config.experts_held), e)
    assert (slots, prefix) == (32768, 12288 if lib is mla_moe else 24576)
    d, f = config.hidden_size, config.moe_intermediate_size
    assert (d, f) == (2048, 1536 if lib is mla_moe else 1792)
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    for rows in (prefix, slots):
        for k, n in ((d, f), (f, d)):
            assert grouped_pallas.fits(rows, k, n, config.dtype)
            assert moe.grouped_runs_fused(rows, k, n, config.dtype)
            assert not moe.grouped_runs_fused(rows, k, n, config.dtype,
                                              overflow=True)
    tiny = lib.Config.tiny()
    assert not moe.grouped_runs_fused(
        tiny.seq_len * tiny.num_experts_per_tok, tiny.hidden_size,
        tiny.moe_intermediate_size, tiny.dtype)


@pytest.mark.parametrize("model,backend,fused", [
    ("glm_tiny", "cpu", 0), ("glm_tiny", "tpu", 0),
    ("glm_4_7_flash", "cpu", 0), ("glm_4_7_flash", "tpu", 1),
    ("lfm2_tiny", "cpu", 0), ("lfm2_tiny", "tpu", 0),
    ("lfm2_8b_a1b", "cpu", 0), ("lfm2_8b_a1b", "tpu", 1),
])
def test_both_models_count_the_execution_of_their_grouped_products(
        model, backend, fused, monkeypatch):
    """``batch_counters`` of both expert models names
    ``moe_grouped_fused_steps_total`` and ``moe_grouped_plain_steps_total``,
    one of them 1 and the other 0, by the rule the step's trace applied at
    the batch's shapes, beside attention's pair."""
    lib = mla_moe if model.startswith("glm") else lfm2_moe
    config = (lib.Config.tiny() if model.endswith("tiny")
              else _published(model))
    monkeypatch.setattr(kernels, "backend", lambda: backend)
    batch = {"segment_ids": np.zeros((1, config.seq_len), np.int32)}
    counts = lib.batch_counters(batch, config)
    assert (counts["moe_grouped_fused_steps_total"],
            counts["moe_grouped_plain_steps_total"]) == (fused, 1 - fused)
    assert (counts["attention_fused_steps_total"]
            + counts["attention_plain_steps_total"]) == 1


@pytest.mark.parametrize("model, lib, sizes", [
    ("glm_4_7_flash", mla_moe, (6144, 12288, 32768)),
    ("lfm2_8b_a1b", lfm2_moe, (12288, 24576, 32768)),
    ("kimi_linear_48b_a3b", kimi_linear, (3072, 6144, 65536)),
    ("mellum2_12b_a2_5b", mellum_moe, (24576, 49152, 65536)),
    ("trinity_mini", afmoe, (12288, 24576, 65536)),
])
def test_the_three_sizes_at_the_published_shapes(model, lib, sizes,
                                                 monkeypatch):
    """``moe.row_sizes`` of the five cells' routed layers: ``tight_rows``
    (half over the even share, in whole row tiles of the kernels),
    ``prefix_rows`` as it was, all the slots; the first two on the kernels
    at the published widths, the last on ``ragged_dot``."""
    config = _published(model)
    routing = lib.routing(config)
    shape = (config.seq_len * routing.top_k, len(routing.held),
             routing.n_experts)
    assert moe.row_sizes(*shape) == sizes
    assert (moe.tight_rows(*shape), moe.prefix_rows(*shape)) == sizes[:2]
    even = shape[0] * shape[1] // shape[2]
    assert sizes[0] == 3 * even // 2 and sizes[0] % TILE == 0
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    d, f = config.hidden_size, config.moe_intermediate_size
    for rows in sizes[:2]:
        assert grouped_pallas.fits(rows, d, f, config.dtype)
        assert grouped_pallas.fits(rows, f, d, config.dtype)
        assert moe.grouped_runs_fused(rows, d, f, config.dtype)


@pytest.mark.parametrize("slots, n_held, n_experts, sizes", [
    (1024, 2, 12, (256, 512, 1024)),    # 256 rows: a tile
    (1024, 1, 12, (256, 1024)),         # tight is prefix_rows: one form
    (1536, 3, 12, (768, 1152, 1536)),   # 576 rows: two and a quarter
    (2048, 4, 16, (768, 1536, 2048)),   # 768 rows: three tiles
    (1024, 4, 12, (512, 1024)),         # a third held: prefix_rows is all
    (1024, 12, 12, (1024,)),            # all held
    (96, 2, 8, (72, 96)),               # under a tile: prefix_rows
    (8, 1, 64, (8,)),                   # a sublane is all the slots
], ids=lambda v: "_".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_tight_rows_are_whole_tiles_under_prefix_rows(slots, n_held,
                                                      n_experts, sizes):
    """``tight_rows`` is half over the even share rounded up to whole
    tiles of 256 rows and never more than ``prefix_rows``, which keeps its
    values; sizes that coincide are one form (where a third of the experts
    are held ``prefix_rows`` is all the slots, and the tight size stays)."""
    shape = slots, n_held, n_experts
    tight, prefix = moe.tight_rows(*shape), moe.prefix_rows(*shape)
    assert tight <= prefix <= slots
    assert 2 * n_experts * tight >= 3 * slots * n_held or tight == prefix
    assert tight % TILE == 0 or tight == prefix
    assert tight - TILE < -(-3 * slots * n_held // (2 * n_experts))
    assert moe.row_sizes(*shape) == sizes
    assert sizes[-1] == slots and list(sizes) == sorted(set(sizes))
    assert set(sizes) <= {tight, prefix, slots}


def _layer(dtype, seed=3):
    """512 tokens of 128, top-2 of 12 experts 128 wide, two held: 1,024
    slots, of which ``moe.prefix_rows`` takes 512 — both whole row tiles."""
    rng = np.random.default_rng(seed)

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)

    return {"x": normal(512, 128).astype(dtype),
            "router": normal(128, 12, scale=0.1),
            "gate": normal(2, 128, 128, scale=0.1),
            "up": normal(2, 128, 128, scale=0.1),
            "down": normal(2, 128, 128, scale=0.1)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("form", ["tight", "prefix", "overflow", "third"])
def test_routed_experts_on_the_kernels_are_routed_experts(form, dtype,
                                                          monkeypatch):
    """``routed_experts`` end to end with its grouped products on the
    kernels (the backend patched to a TPU, the interpreter running them)
    against the same call on ``jax.lax.ragged_dot``: ``y``, ``counts`` and
    the gradients to ``x``, to the router (the gates' path) and to the three
    weight stacks.  ``tight``: two of twelve experts held and no bias, a
    sixth of the slots land here and the tight form's 256 rows run on the
    kernels.  ``prefix``: a small bias towards the two held experts lands
    twice that, past ``tight_rows``, and the prefix form's 512 rows run on
    the kernels.  ``overflow``: a bias sends every choice to the two held
    experts, 1,024 live rows overflow ``prefix_rows``' 512 and the device
    takes the overflow form, which stays on ``ragged_dot`` beside the two
    forms that are traced on the kernels and not run.  ``third``: four of
    twelve held, ``prefix_rows`` is all 1,024 slots and no form is past it:
    the tight form's 512 rows run, the other is traced, both on the
    kernels."""
    dtype = jnp.dtype(dtype)
    layer = _layer(dtype)
    held = (3, 7, 1, 10) if form == "third" else (3, 7)
    for name in ("gate", "up", "down"):
        layer[name] = jnp.concatenate([layer[name]] * (len(held) // 2))
    bias = jnp.zeros(12).at[jnp.asarray(held[:2])].set(
        {"prefix": 0.15, "overflow": 10.0}.get(form, 0.0))
    sizes = moe.row_sizes(1024, len(held), 12)
    assert sizes == ((512, 1024) if form == "third" else (256, 512, 1024))

    def run(**how):
        def loss(leaves):
            y, counts = moe.routed_experts(
                leaves["x"], leaves["router"], bias, leaves["gate"],
                leaves["up"], leaves["down"], held, top_k=2, scale=1.5,
                **how)
            weigh = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)
                            ).reshape(y.shape)
            return jnp.sum(y.astype(jnp.float32) * weigh), (y, counts)

        return jax.jit(jax.value_and_grad(loss, has_aux=True))(layer)

    seen = []
    real = grouped_pallas.grouped_product
    monkeypatch.setattr(grouped_pallas, "grouped_product",
                        lambda rows, *a: seen.append(rows.shape[0])
                        or real(rows, *a))
    # on another backend no kernel is traced: no interpreter is needed
    (_, (want, want_counts)), want_grads = run()
    assert not seen
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    assert moe.grouped_runs_fused(512, 128, 128, dtype)
    with pltpu.force_tpu_interpret_mode():
        (_, (y, counts)), grads = run()
    assert set(seen) == set(sizes[:2])  # the form past prefix_rows: none
    np.testing.assert_array_equal(counts, want_counts)
    landed = int(np.asarray(counts)[list(held)].sum())
    assert landed > 0 and sum(landed > n for n in sizes[:-1]) == {
        "tight": 0, "prefix": 1, "overflow": 2, "third": 0}[form]
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    _close(y, want, tol)
    for name in want_grads:
        assert float(jnp.abs(want_grads[name]).max()) > 0, name
        _close(grads[name], want_grads[name], tol)


def _landing(landed: int):
    """:func:`_layer` in float32 with a router and tokens that land exactly
    ``landed`` of the 1,024 slots on the held experts 3 and 7: a token's
    first three features say which pair of experts it scores highest
    (both held, one held, none), by 4 in the logits where the other 125
    features move them by 0.1."""
    layer = _layer(jnp.float32)
    pairs = np.asarray([(3, 7), (3, 5), (5, 9)])
    kind = np.full(512, 2)
    kind[:landed // 2] = 0
    kind[landed // 2:landed // 2 + landed % 2] = 1
    x = np.array(layer["x"])
    x[:, :3] = np.eye(3)[kind]
    router = np.array(layer["router"]) * 0.1
    router[:3] = 0
    for row, pair in enumerate(pairs):
        router[row, pair] = 4.0
    return dict(layer, x=jnp.asarray(x), router=jnp.asarray(router))


@pytest.mark.parametrize("landed, rung", [
    (256, 0), (257, 1), (512, 1), (513, 2)],
    ids=["tight", "tight_and_one", "prefix", "prefix_and_one"])
def test_the_device_takes_the_smallest_size_that_fits_and_all_are_equal(
        landed, rung, monkeypatch):
    """Counts at ``tight_rows`` (256 of 1,024 slots), one over, at
    ``prefix_rows`` (512) and one over: the device runs the form at the
    smallest size that holds them (the forward pass and the backward pass,
    each choosing for itself, run products at that row count and at no
    other), and in float32 what it returns is what each size that holds the
    count gives alone: the output, the gradient to the tokens and the
    gradient to the router (the gates' path) to the last bit — a row's
    numbers never depend on how many rows there are —, the gradients to the
    three weight stacks to the order of a sum: they contract the rows'
    axis, which the CPU's product cuts into blocks by its length (equal at
    256 and 512 rows, 6e-7 of the largest entry apart at 1,024, where the
    rows past the live ones add exact zeros in another order)."""
    layer, sizes = _landing(landed), (256, 512, 1024)
    assert moe.row_sizes(1024, 2, 12) == sizes
    ran, real = [], jax.lax.ragged_dot

    def ragged_dot(rows, *args, **kwargs):
        jax.debug.callback(lambda: ran.append(rows.shape[0]))
        return real(rows, *args, **kwargs)

    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)

    def run():
        def loss(leaves):
            y, counts = moe.routed_experts(
                leaves["x"], leaves["router"], jnp.zeros(12), leaves["gate"],
                leaves["up"], leaves["down"], (3, 7), top_k=2, scale=1.5)
            weigh = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)
                            ).reshape(y.shape)
            return jnp.sum(y * weigh), (y, counts)

        del ran[:]
        out = jax.jit(jax.value_and_grad(loss, has_aux=True))(layer)
        jax.effects_barrier()
        return out, set(ran)

    ((_, (y, counts)), grads), rows = run()
    assert int(np.asarray(counts)[[3, 7]].sum()) == landed
    assert rows == {sizes[rung]}
    assert float(jnp.abs(y).max()) > 0
    for name in grads:
        assert float(jnp.abs(grads[name]).max()) > 0, name
    for alone in sizes[rung:]:
        monkeypatch.setattr(moe, "row_sizes", lambda *_: (alone,))
        ((_, (y_alone, _)), grads_alone), rows = run()
        assert rows == {alone}
        np.testing.assert_array_equal(y, y_alone)
        for name in ("x", "router"):
            np.testing.assert_array_equal(grads[name], grads_alone[name],
                                          err_msg=f"{name} at {alone}")
        for name in ("gate", "up", "down"):
            _close(grads[name], grads_alone[name], 2e-6)


def test_routing_state_counts_the_layers_that_fit_the_tight_size():
    """``step_routing_state`` at sizes where the three differ (512 tokens
    choosing 2 of 12, two held: 256, 512, 1,024): a layer whose held slots
    are at ``tight_rows`` or under is counted in ``tight``, one past
    ``prefix_rows`` in ``overflow`` as before, one between in neither, and
    ``routing_counters`` names both rows."""
    landed = [0, 256, 257, 512, 513, 1024]
    counts = np.zeros((len(landed), 12), np.int32)
    counts[:, 3] = [n // 2 for n in landed]
    counts[:, 7] = [n - n // 2 for n in landed]
    counts[:, 0] = 1024 - np.asarray(landed)
    state = {name: jnp.full(shape, 2, dtype) for name, (shape, dtype) in
             moe.routing_state_shapes(12, len(landed)).items()}
    new = moe.step_routing_state(state, jnp.asarray(counts), (3, 7), top_k=2,
                                 speed=0.0, tokens=512)
    assert new["tight"].tolist() == [3, 3, 2, 2, 2, 2]
    assert new["overflow"].tolist() == [2, 2, 2, 2, 3, 3]
    assert new["tight"].dtype == new["overflow"].dtype == jnp.int32
    shown = moe.routing_counters(new, (3, 7))
    np.testing.assert_array_equal(shown["moe_tight_layers_total"],
                                  new["tight"])
    np.testing.assert_array_equal(shown["moe_overflow_layers_total"],
                                  new["overflow"])


# ---------------------------------------------------------------------------
# What a start pays for: the kernel calls of the programs a Trainer builds
# ---------------------------------------------------------------------------

def _fitting(lib, held):
    """An expert model small enough to trace in seconds whose grouped
    products fit the kernels' tiles: 512 tokens of width 128, top-2 of 12
    experts 256 wide (so the gate's and the down product differ in shape),
    two expert layers (``mla_moe``: two and the prediction module's)."""
    import dataclasses

    experts = ({"n_routed_experts": 12, "num_hidden_layers": 3}
               if lib is mla_moe else {"num_experts": 12})
    return dataclasses.replace(
        lib.Config.tiny(), hidden_size=128, moe_intermediate_size=256,
        experts_held=held, num_experts_per_tok=2, seq_len=512,
        attention_block=128, loss_block=128, dtype="bfloat16", **experts)


def _kernel_calls(text: str):
    """``(call sites, functions that hold a kernel)`` of a lowered module:
    the kernels sit under ``jax.jit`` (``kernels.jitted``), so a
    call site is a ``call @_rows_product…`` and a kernel's code is in the
    module once a function, not once a call."""
    import re

    calls = re.findall(r"call @(_(?:rows|weights)_product\w*)\(", text)
    return len(calls), text.count("tpu_custom_call"), len(set(calls))


@pytest.mark.parametrize("model", ["lfm2_moe", "mla_moe"])
def test_a_start_pays_for_the_kernels_a_step_runs_and_no_others(
        model, monkeypatch):
    """The guard on a warm start (PR 41 was refused for 6 s of it): with the
    backend patched to a TPU and the programs only lowered for one.

    - What ``Trainer.__init__`` traces to make the parameters holds no
      kernel and no forward pass, at its own example's shapes (the Trainer
      is built here) and at the step's: a module that initialises only
      declares its variables.
    - The step's expert layers share one routed part (``jax.jit``: two
      functions, the forward's and the backward's, called once a layer
      each; ``mla_moe``'s prediction module, whose operations a profile
      tells from the main layers' by their scope, has its own), so whatever
      the layers' number a step is traced with, and its module holds,
      twelve kernel calls once a part and size on the kernels — the tight
      form's (256 rows here) and the prefix form's (512), none for the
      overflow form — in eight functions a size (a product's forward and
      its two gradients, at the gate's and at the down product's shape, and
      the forward products once more as the backward pass asks for them; a
      kernel's body is unrolled at its row count, so two sizes share
      none): the tight size is what PR 50 added to a start, a later change
      that adds as much again fails here, not on the ledger's ``setup_s``.
    - A model that holds a third of the experts has no form past
      ``prefix_rows`` (all the slots): two sizes, both on the kernels, no
      ``ragged_dot``."""
    import re

    from tensorflowonspark_tpu.parallel import mesh as mesh_lib
    from tensorflowonspark_tpu.trainer import Trainer

    lib = mla_moe if model == "mla_moe" else lfm2_moe
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    for held, forms in (((3, 7), 3), ((3, 7, 1, 10), 2)):
        config = _fitting(lib, held)
        slots = config.seq_len * config.num_experts_per_tok
        prefix = moe.prefix_rows(slots, len(held), 12)
        assert (prefix < slots) == (forms == 3)
        assert len(moe.row_sizes(slots, len(held), 12)) == forms
        on_kernels = min(forms, 2)      # every size but the overflow one
        assert moe.grouped_runs_fused(prefix, 128, 256, config.dtype)
        assert lib.batch_counters(
            {"segment_ids": np.zeros((1, 512), np.int32)},
            config)["moe_grouped_fused_steps_total"] == 1
        trainer = Trainer(model, config=config, devices=jax.devices()[:1])
        init = jax.jit(lambda: trainer.model.init(
            jax.random.PRNGKey(0), tokens, tokens)).trace()
        # no routed layer at all is traced, on the kernels or off them
        assert "ragged_dot" not in str(init.jaxpr)
        assert "pallas_call" not in str(init.jaxpr)
        assert _kernel_calls(init.lower(
            lowering_platforms=("tpu",)).as_text()) == (0, 0, 0)
        with mesh_lib.active_mesh(trainer.mesh):
            step = trainer.train_step.trace(
                trainer.state, {"tokens": tokens, "segment_ids": tokens}
            ).lower(lowering_platforms=("tpu",)).as_text()
        layers, parts = (3, 2) if lib is mla_moe else (2, 1)
        assert config.expert_layers == layers
        assert len(re.findall(r"func\.func private @routed\w*\(",
                              step)) == 2 * parts
        assert len(re.findall(r"call @routed\w*\(", step)) == 2 * layers
        assert _kernel_calls(step) == (
            12 * on_kernels * parts, 8 * on_kernels, 8 * on_kernels), (
                held, forms)
        # the overflow form's products, where there is one
        assert ("ragged_dot" in step) == (forms == 3)
