"""``packed_rows.document_attention``'s two executions: the Pallas kernels of
``models/attention_pallas.py``, run here in Pallas's interpreter
(``pltpu.force_tpu_interpret_mode``), against the ``jnp`` form in the same
file, which stays the oracle; the rule that picks between them; the counters
that say which a step ran.

Tolerances: in float32 the two differ only by the order of their sums (a
block of 512 keys where the ``jnp`` form here sums 64): 2e-5 of the largest
entry.  With bfloat16 operands both round the same operands (``q``, ``k``,
``v``, ``d_out``, and ``p`` and ``ds`` before their products) to 8 bits, but
a probability rounds against another running maximum and the gradients sum
in another order; one bfloat16 step is 2 ** -8 = 0.4% of a value, so 1.5e-2
of the largest entry is a few steps, and a missed block, boundary or scale
is of order 1.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.models import (afmoe, attention_pallas,
                                          granite_hybrid, kernels,
                                          kimi_linear, lfm2_moe, mellum_moe,
                                          mla_moe, packed_rows)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK = 512     # the backward pass's; the forward's are twice that

#: documents' lengths a row: 512 tokens are one block of the kernels' own
#: forward and backward (a short row is one block), 1,024 are one forward
#: and two backward (three block pairs, one of them below the diagonal)
LAYOUTS = {
    "one_document": [1024],
    "documents_end_on_a_blocks_edge": [512, 512],
    "documents_end_inside_a_block": [200, 500, 324],
    "a_document_a_token": [1] * 512,
    "ids_not_rising": [300, 212],
}


def _published(name: str):
    import importlib

    program = importlib.import_module(f"benchmark.configs.{name}.program")
    with open(os.path.join(REPO, "benchmark", "configs", name,
                           "config.json")) as f:
        return program.model_config(json.load(f))


def _inputs(lengths, kv, rep, hd, dtype, ids=None):
    rng = np.random.default_rng(len(lengths) + hd)
    t = sum(lengths)
    ids = np.arange(len(lengths)) + 3 if ids is None else np.asarray(ids)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)

    seg = jnp.asarray(np.repeat(ids, lengths).astype(np.int32))
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


def _oracle(dtype, scale):
    """The ``jnp`` form, in blocks of 64, whatever the rule says."""
    return lambda q, k, v, seg: packed_rows._attend()(
        q, k, v, seg, scale, 64, dtype, ("attention",))


def _close(got, want, tol):
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), name


@pytest.fixture
def kernels_on_the_cpu(monkeypatch):
    """The rule says "fused" where the shapes fit, as on a chip, and the
    kernels run in Pallas's interpreter."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hd", [128, 256])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_attention_kernels_are_the_jnp_form(layout, hd, dtype,
                                            kernels_on_the_cpu):
    """Output, ``dq``, ``dk`` and ``dv`` of ``document_attention`` on the
    kernels (two heads, the kernels' own blocks) against the ``jnp`` form."""
    dtype = jnp.dtype(dtype)
    ids = [9, 2] if layout == "ids_not_rising" else None
    inputs = _inputs(LAYOUTS[layout], 2, 1, hd, dtype, ids)
    t, scale = inputs[0].shape[0], 1.0 / np.sqrt(hd)
    assert packed_rows.attention_runs_fused(t, hd)
    got = _output_and_gradients(
        lambda q, k, v, seg: packed_rows.document_attention(
            q, k, v, seg, scale, 64, dtype), inputs)
    want = _output_and_gradients(_oracle(dtype, scale), inputs)
    _close(got, want, 2e-5 if dtype == jnp.float32 else 1.5e-2)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)])
def test_attention_kernels_at_other_blocks_and_shared_key_heads(
        blocks, dtype, kernels_on_the_cpu):
    """Blocks of queries and of keys of different sizes, the forward pass's
    other than the backward's (a row is then four blocks of the smaller:
    loops of more than one turn, two blocks on the diagonal), and two query
    heads on one key head (``dk`` and ``dv`` are their sum)."""
    dtype = jnp.dtype(dtype)
    inputs = _inputs([17, 300, 1, 194], 1, 2, 128, dtype)
    got = _output_and_gradients(
        lambda q, k, v, seg: attention_pallas.fused_attention(
            q, k, v, seg, 0.125, dtype, ("attention",), blocks, blocks[::-1]),
        inputs)
    want = _output_and_gradients(_oracle(dtype, 0.125), inputs)
    _close(got, want, 2e-5 if dtype == jnp.float32 else 1.5e-2)


#: a row of 1,024 tokens in four blocks of 256, both passes.  ``many``:
#: block 0 holds documents 0 and 1, block 1 document 1, block 2 documents 2
#: and 3, block 3 document 3 — blocks 0 and 1 are wholly other documents'
#: than the queries of blocks 2 and 3
SPOILT_ROWS = {"one": [1024], "many": [100, 412, 30, 482]}
#: (what holds the NaN, where) -> the blocks it reaches in the ``jnp`` form,
#: whose loops follow the shapes alone, and on a row of many documents in
#: the kernels (on a row that is one document they reach the same blocks)
SPOILT = {
    ("v", "the_first_block"): ([0, 1, 2, 3], [0, 1]),
    ("v", "the_last_block"): ([3], [3]),
    ("q", "the_first_block"): ([0], [0]),
    ("q", "the_last_block"): ([0, 1, 2, 3], [2, 3]),
    ("d_out", "the_first_block"): ([0], [0]),
    ("d_out", "the_last_block"): ([0, 1, 2, 3], [2, 3]),
}


@pytest.mark.parametrize("at", ["the_first_block", "the_last_block"])
@pytest.mark.parametrize("spoilt", ["v", "q", "d_out"])
@pytest.mark.parametrize("documents", ["one", "many"])
def test_attention_kernels_stop_at_a_documents_edge(documents, spoilt, at,
                                                    kernels_on_the_cpu):
    """A value that is not a number reaches everything that visits its
    block, through a probability of exactly 0 where the mask hides it (0 x
    NaN): in ``v`` the output of every block of queries that visits the
    block of keys, in ``q`` and ``d_out`` the ``dk`` and the ``dv`` of every
    block of keys that visits the block of queries.  In the ``jnp`` form
    that is every block on or below the diagonal, whatever the documents
    are.  In the kernels a block that is **wholly another document's is not
    visited**: a NaN in the first block's ``v`` no longer reaches the later
    documents' queries, one in the last block's ``q`` or ``d_out`` no longer
    reaches the earlier documents' ``dk`` or ``dv``; a block that shares a
    document with its visitors spoils them as in the ``jnp`` form (block 0
    holds the start of block 1's document), and the row that is one document
    is spoiled whole as before."""
    q, k, v, seg, d_out = _inputs(SPOILT_ROWS[documents], 2, 1, 128,
                                  jnp.float32)
    t, size = seg.shape[0], 256
    token = 5 if at == "the_first_block" else t - 1
    q, v, d_out = (x.at[token].set(jnp.nan) if name == spoilt else x
                   for name, x in (("q", q), ("v", v), ("d_out", d_out)))

    def reached(attend):
        def run(q, k, v, d_out):
            out, vjp = jax.vjp(lambda q, k, v: attend(q, k, v, seg), q, k, v)
            return (out,) + vjp(d_out)

        out, _, dk, dv = jax.block_until_ready(jax.jit(run)(q, k, v, d_out))
        read = {"v": out, "q": dk, "d_out": dv}[spoilt]
        blocks = np.isnan(np.asarray(read)).reshape(t // size, -1)
        assert (blocks.all(1) | ~blocks.any(1)).all()   # whole blocks
        return np.flatnonzero(blocks.any(1)).tolist()

    by_shapes, by_documents = SPOILT[spoilt, at]
    assert reached(lambda q, k, v, seg: packed_rows._attend()(
        q, k, v, seg, 0.1, size, jnp.float32, ())) == by_shapes
    assert reached(lambda q, k, v, seg: attention_pallas.fused_attention(
        q, k, v, seg, 0.1, jnp.float32, (), (size, size), (size, size))
    ) == (by_shapes if documents == "one" else by_documents)


def _decides_loops(kernel):
    """``(loops, what decides how long each runs)`` of a kernel's jaxpr: a
    ``while``'s condition's constants and carried values (the counter and
    its bound), not what its body closes over (it reads the ids for its
    mask); a ``cond``'s predicate."""
    loops, decides = 0, []
    for e in kernel.eqns:
        if e.primitive.name == "while":
            n, m = e.params["cond_nconsts"], e.params["body_nconsts"]
            decides += list(e.invars[:n]) + list(e.invars[n + m:])
            loops += 1
        elif e.primitive.name == "cond":
            decides += e.invars[:1]
    return loops, {v for v in decides if hasattr(v, "count")}


def _computed_from(kernel, sources):
    """Variables of a kernel's jaxpr computed from ``sources``."""
    tainted = set(sources)
    for e in kernel.eqns:
        if tainted & {v for v in e.invars if hasattr(v, "count")}:
            tainted |= set(e.outvars)
    return tainted - set(sources)


@pytest.mark.parametrize("window,loops", [(None, 1), (300, 2)])
def test_attention_kernels_grid_comes_from_the_shapes_and_the_loops_from_the_bounds(
        window, loops):
    """The forward kernel's jaxpr for a row: the grid comes from the shapes
    alone; the segment ids themselves enter as two operands of the kernel
    call and are read into the mask's comparison only; what decides how
    long a loop runs is made of ``program_id`` arithmetic and the vector of
    bounds (the call's first operand, made of the ids outside the kernel by
    ``first_key_blocks``) and of nothing else the kernel reads."""
    t, hd = 1024, 128
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((t, 2 * hd), jnp.float32), ((t, 2 * hd), jnp.float32),
        ((t, 2 * hd), jnp.float32), ((t,), jnp.int32))]
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, seg: attention_pallas._forward(
            q, k, v, seg, 0.1, jnp.dtype("float32"), hd, BLOCK, BLOCK // 2,
            window))(*shapes)
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (2, t // BLOCK)
    kernel = call.params["jaxpr"]
    bounds, seg_refs = kernel.invars[0], kernel.invars[4:6]
    assert bounds.aval.shape == (t // BLOCK,)
    assert [r.aval.shape for r in seg_refs] == [
        (BLOCK, 1), (t // (BLOCK // 2), 1, BLOCK // 2)]
    from_ids = _computed_from(kernel, seg_refs)
    assert from_ids         # the mask, the scores under it and what follows
    n, decides = _decides_loops(kernel)
    assert n == loops and not decides & from_ids
    assert decides & _computed_from(kernel, [bounds])
    # and of nothing else: every other operand, output and scratch buffer
    # the kernel reads is as far from the loops' bounds as the ids are
    others = [v for v in kernel.invars if v is not bounds]
    assert not decides & _computed_from(kernel, others)


#: documents' lengths of a row of 8,192 tokens, the last one cut at the
#: row's end as the traffic cuts it
BOUNDED_ROWS = {
    "one_document": [8192],
    "many_documents": [700, 90, 2300, 1500, 333, 40, 3229],
    "an_edge_on_a_blocks_edge": [1024, 2048, 512, 1536, 3072],
    "a_16_token_document": [2040, 16, 3000, 16, 1000, 16, 2104],
    "short_documents": [16, 100] * 70 + [72],
}


def _segment_ids(lengths):
    return np.repeat(np.arange(len(lengths)) + 3, lengths).astype(np.int32)


@pytest.mark.parametrize("window", [None, 1024, 2048])
@pytest.mark.parametrize("tile", [(1024, 1024), (512, 512), (256, 128)])
@pytest.mark.parametrize("row", sorted(BOUNDED_ROWS))
def test_loops_bounds_against_the_brute_force_mask(row, tile, window):
    """The blocks the two kernels' loops visit (``forward_bounds`` and
    ``backward_bounds`` clipped by ``first_key_blocks`` and
    ``past_query_blocks``, the diagonal's blocks always) against the mask
    ``j <= i and same document and i - j < window`` made whole: no block
    left out holds a pair the mask admits, the first and the last visited
    block of every block of queries (forward) and of keys (backward) do, and
    ``visited`` counts them."""
    seg = _segment_ids(BOUNDED_ROWS[row])
    t, (bq, bk) = seg.shape[0], tile
    at = np.arange(t)
    admits = (at[:, None] >= at[None, :]) & (seg[:, None] == seg[None, :])
    if window is not None:
        admits &= at[:, None] - at[None, :] < window
    holds = admits.reshape(t // bq, bq, t // bk, bk).any(axis=(1, 3))
    i, j = np.arange(t // bq), np.arange(t // bk)
    first, _ = attention_pallas.forward_bounds(
        i, bq, bk, window, attention_pallas.first_key_blocks(seg, bq, bk))
    past = (i * bq) // bk + max(1, bq // bk)    # past the diagonal's blocks
    forward = (j[None, :] >= first[:, None]) & (j[None, :] < past[:, None])
    _, last = attention_pallas.backward_bounds(
        j, bq, bk, window, t // bq,
        attention_pallas.past_query_blocks(seg, bq, bk))
    backward = (i[:, None] >= ((j * bk) // bq)[None, :]) & (
        i[:, None] < last[None, :])
    for visits in (forward, backward):
        assert not (holds & ~visits).any()
    assert holds[i, first].all() and holds[i, past - 1].all()
    assert holds[(j * bk) // bq, j].all() and holds[last - 1, j].all()
    assert attention_pallas.visited(t, tile, tile, window, seg) == (
        forward.sum(), backward.sum())
    # and the shapes' and the window's bounds alone reach them all
    reached = attention_pallas.visited(t, tile, tile, window)
    assert attention_pallas.visited(
        t, tile, tile, window, np.zeros(t, np.int32)) == reached
    assert all(a <= b for a, b in zip((forward.sum(), backward.sum()),
                                      reached))


#: rows of 1,024 tokens for blocks of 128 and 256: loops of several turns,
#: documents that end on a block's edge and inside one, a 16-token document
EDGE_ROWS = {
    "many_documents": [90, 300, 41, 400, 193],
    "an_edge_on_a_blocks_edge": [256, 128, 384, 256],
    "a_16_token_document": [250, 16, 500, 16, 242],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("row", sorted(EDGE_ROWS))
def test_attention_kernels_that_stop_at_edges_are_the_jnp_form(
        row, window, dtype, kernels_on_the_cpu):
    """Output, ``dq``, ``dk`` and ``dv`` of the kernels on rows whose
    documents make the loops start late and end early (eight blocks of
    queries by four of keys forward, the other way round backward, two query
    heads on a key head), with a window and without, against the ``jnp``
    form, which visits every block."""
    dtype = jnp.dtype(dtype)
    inputs = _inputs(EDGE_ROWS[row], 1, 2, 128, dtype)
    seg = np.asarray(inputs[3])
    visited = attention_pallas.visited(
        1024, (128, 256), (256, 128), window, seg)
    reached = attention_pallas.visited(1024, (128, 256), (256, 128), window)
    assert visited[0] < reached[0] and visited[1] < reached[1]
    got = _output_and_gradients(
        lambda q, k, v, seg: attention_pallas.fused_attention(
            q, k, v, seg, 0.125, dtype, ("attention",), (128, 256),
            (256, 128), window=window), inputs)
    want = _output_and_gradients(
        lambda q, k, v, seg: packed_rows._attend()(
            q, k, v, seg, 0.125, 64, dtype, ("attention",), window), inputs)
    _close(got, want, 2e-5 if dtype == jnp.float32 else 1.5e-2)


@pytest.mark.parametrize("window", [None, 200])
def test_attention_kernels_are_the_loops_of_the_shapes_to_the_last_bit(
        window, kernels_on_the_cpu, monkeypatch):
    """A block left out adds exactly 0: on a row of many documents the two
    kernels' results — the output and the log-sum-exp, ``dq``, ``dk`` and
    ``dv`` — with the loops' bounds made of the documents equal, bit for
    bit, those with the bounds forced to the shapes' (every block of keys
    from the first, every block of queries to the last: the loops as they
    were before they followed the documents)."""
    q, k, v, seg, d_out = _inputs(EDGE_ROWS["many_documents"], 1, 2, 128,
                                  jnp.float32)
    t, hd, size = seg.shape[0], 128, 128
    q2, k2, v2, do2 = (x.reshape(t, -1) for x in (q, k, v, d_out))
    delta = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, t // size, 1, size)), jnp.float32)

    def both_kernels():
        out, lse = attention_pallas._forward(
            q2, k2, v2, seg, 0.125, jnp.dtype("float32"), hd, size, size,
            window)
        return (out, lse) + tuple(attention_pallas._backward(
            q2, k2, v2, seg, lse, do2, delta, 0.125, jnp.dtype("float32"),
            hd, size, size, window))

    # under ``jax.jit``, a function of its own each time: traced once
    # before the bounds are forced and once after
    by_documents = jax.block_until_ready(jax.jit(lambda: both_kernels())())
    assert (np.asarray(attention_pallas.first_key_blocks(seg, size, size))
            > 0).any()
    assert (np.asarray(attention_pallas.past_query_blocks(seg, size, size))
            < t // size).any()
    monkeypatch.setattr(
        attention_pallas, "first_key_blocks",
        lambda seg, bq, bk: jnp.zeros(t // bq, jnp.int32))
    monkeypatch.setattr(
        attention_pallas, "past_query_blocks",
        lambda seg, bq, bk: jnp.full(t // bk, t // bq, jnp.int32))
    by_shapes = jax.block_until_ready(jax.jit(lambda: both_kernels())())
    for name, got, want in zip(("out", "lse", "dq", "dk", "dv"),
                               by_documents, by_shapes):
        assert not np.isnan(np.asarray(want)).any(), name
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want), name)


def test_attention_rule_picks_the_kernels_on_a_tpu_where_heads_fill_lanes(
        monkeypatch):
    assert attention_pallas.BACKWARD_BLOCKS == (BLOCK, BLOCK)
    assert attention_pallas.FORWARD_BLOCKS == (2 * BLOCK, 2 * BLOCK)
    glm = _published("glm_4_7_flash")
    granite = _published("granite_4_0_h_micro")
    shapes = {      # a row's tokens, a head's width
        "glm": (glm.seq_len, glm.qk_head_dim),
        "granite": (granite.seq_len, granite.head_dim),
        "glm_tiny": (32, mla_moe.Config.tiny().qk_head_dim),
        "granite_tiny": (32, granite_hybrid.Config.tiny().head_dim),
    }
    assert (glm.num_attention_heads,) + shapes["glm"] == (20, 8192, 256)
    assert (granite.num_attention_heads, granite.num_key_value_heads
            ) + shapes["granite"] == (32, 8, 8192, 64)
    # here the backend is the CPU: the jnp form, whatever the shapes
    assert jax.default_backend() == "cpu"
    assert not any(packed_rows.attention_runs_fused(*s)
                   for s in shapes.values())
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    assert packed_rows.attention_runs_fused(*shapes["glm"])
    for name in ("granite", "glm_tiny", "granite_tiny"):
        assert not packed_rows.attention_runs_fused(*shapes[name]), name
    # a head fills whole rows of lanes, the row is whole blocks (a short
    # row one block), and a head's row fits the fast memory
    for t, hd in [(512, 128), (1024, 128), (384, 256), (2048, 384)]:
        assert packed_rows.attention_runs_fused(t, hd)
    for t, hd in [(8192, 192), (8192 - 256, 256), (1536, 128), (320, 128),
                  (16384, 256)]:
        assert not packed_rows.attention_runs_fused(t, hd)


@pytest.mark.parametrize("model,backend,fused", [
    ("glm_tiny", "cpu", 0), ("glm_tiny", "tpu", 0),
    ("glm_4_7_flash", "cpu", 0), ("glm_4_7_flash", "tpu", 1),
    ("granite_tiny", "cpu", 0), ("granite_tiny", "tpu", 0),
    ("granite_4_0_h_micro", "cpu", 0), ("granite_4_0_h_micro", "tpu", 0),
])
def test_both_models_count_the_execution_of_their_attention(
        model, backend, fused, monkeypatch):
    """``batch_counters`` of both models names ``attention_fused_steps_total``
    and ``attention_plain_steps_total``, one of them 1 and the other 0, by
    the rule the step's trace applied at the batch's shapes."""
    lib = mla_moe if model.startswith("glm") else granite_hybrid
    config = (lib.Config.tiny() if model.endswith("tiny")
              else _published(model))
    monkeypatch.setattr(kernels, "backend", lambda: backend)
    batch = {"segment_ids": np.zeros((1, config.seq_len), np.int32)}
    counts = lib.batch_counters(batch, config)
    assert (counts["attention_fused_steps_total"],
            counts["attention_plain_steps_total"]) == (fused, 1 - fused)


#: model -> (its module, its configuration's directory, the window of each
#: of its attention layers)
LAYER_MIXES = {
    "glm": (mla_moe, "glm_4_7_flash", lambda c: [None] * 6),
    "mellum2": (mellum_moe, "mellum2_12b_a2_5b", lambda c: [
        c.sliding_window if m == "sliding_attention" else None
        for _, m, _ in mellum_moe.layer_kinds(c)]),
    "trinity": (afmoe, "trinity_mini", lambda c: [
        c.sliding_window if m == "sliding_attention" else None
        for _, m, _ in afmoe.layer_kinds(c)]),
}


@pytest.mark.parametrize("model", sorted(LAYER_MIXES))
def test_models_count_the_blocks_their_attention_kernels_visit(
        model, monkeypatch):
    """``attention_blocks_visited_total`` and
    ``attention_blocks_reached_total`` of the three models whose attention
    runs on the kernels, at their published layers each at its own window:
    ``attention_pallas.visited`` with the batch's segment ids and without
    them, summed over the layers; equal on rows that are one document each;
    0 and 0 where the rule picks the ``jnp`` form (here, the CPU)."""
    lib, name, windows = LAYER_MIXES[model]
    config = _published(name)
    windows = windows(config)
    assert {"glm": (6, 0), "mellum2": (1, 3), "trinity": (1, 4)}[model] == (
        windows.count(None), len(windows) - windows.count(None))
    t = config.seq_len
    rows = np.stack([_segment_ids(BOUNDED_ROWS[row]) for row in (
        "many_documents", "short_documents", "one_document")])
    blocks = (t, attention_pallas.FORWARD_BLOCKS,
              attention_pallas.BACKWARD_BLOCKS)

    def counted(seg):
        counts = lib.batch_counters({"segment_ids": seg}, config)
        return (counts["attention_blocks_visited_total"],
                counts["attention_blocks_reached_total"])

    assert counted(rows) == (0, 0)      # the jnp form: no kernel, no count
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    reached = sum(sum(attention_pallas.visited(*blocks, w)) for w in windows)
    assert counted(rows) == (
        sum(sum(attention_pallas.visited(*blocks, w, rows)) for w in windows),
        len(rows) * reached)
    assert counted(rows)[0] == sum(counted(rows[n:n + 1])[0]
                                   for n in range(len(rows)))
    visited, _ = counted(rows[:2])
    assert 0 < visited < 2 * reached
    assert counted(rows[2:]) == (reached, reached)
    if model == "glm":      # the hand count: 36 forward, 136 backward
        assert reached == 6 * (36 + 136)


@pytest.mark.parametrize("model", ["granite", "lfm2", "kimi_linear"])
def test_models_on_the_jnp_form_count_no_blocks(model, monkeypatch):
    """Heads of 64 (granite, LFM2) and keys of 192 beside values of 128
    (Kimi Linear) run the ``jnp`` form on a TPU too, whose loops visit every
    block the shapes reach: both counters are named and stay 0."""
    lib, name = {"granite": (granite_hybrid, "granite_4_0_h_micro"),
                 "lfm2": (lfm2_moe, "lfm2_8b_a1b"),
                 "kimi_linear": (kimi_linear, "kimi_linear_48b_a3b")}[model]
    config = _published(name)
    monkeypatch.setattr(kernels, "backend", lambda: "tpu")
    counts = lib.batch_counters({"segment_ids": _segment_ids(
        BOUNDED_ROWS["many_documents"])[None]}, config)
    assert counts["attention_plain_steps_total"] == 1
    assert (counts["attention_blocks_visited_total"],
            counts["attention_blocks_reached_total"]) == (0, 0)
