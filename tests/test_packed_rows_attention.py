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

from tensorflowonspark_tpu.models import (attention_pallas, granite_hybrid,
                                          kernels, mla_moe, packed_rows)

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


@pytest.mark.parametrize("at", ["the_first_block", "the_last_block"])
@pytest.mark.parametrize("documents", ["one", "many"])
def test_attention_kernels_visit_another_documents_blocks(
        documents, at, kernels_on_the_cpu):
    """Every block on or below the diagonal is visited and masked, whatever
    the documents are, and no block above it: a value that is not a number
    among a block's ``v`` reaches every query that visits the block, through
    a probability of exactly 0 where the mask hides it (0 x NaN), in the
    kernels as in the ``jnp`` form.  In the first block it spoils the whole
    row, where the later blocks are another document's as where they are
    the same one's: a kernel that skipped a block by its segment ids would
    leave those queries clean, and a step's time would follow its row.  In
    the last block it spoils that block's queries alone."""
    lengths = {"one": [1024], "many": [100, 412, 30, 482]}[documents]
    q, k, v, seg, _ = _inputs(lengths, 2, 1, 128, jnp.float32)
    t, size = seg.shape[0], 256         # four blocks a row, both forms
    v = v.at[5 if at == "the_first_block" else t - 1].set(jnp.nan)
    got = np.isnan(np.asarray(jax.jit(
        lambda q, k, v, seg: attention_pallas.fused_attention(
            q, k, v, seg, 0.1, jnp.float32, (), (size, size), (size, size))
    )(q, k, v, seg)))
    want = np.isnan(np.asarray(jax.jit(
        lambda q, k, v, seg: packed_rows._attend()(
            q, k, v, seg, 0.1, size, jnp.float32, ()))(q, k, v, seg)))
    np.testing.assert_array_equal(got, want)
    spoiled = 0 if at == "the_first_block" else t - size
    assert got[spoiled:].all() and not got[:spoiled].any()


def test_attention_kernels_grid_and_loops_come_from_the_shapes_alone():
    """The kernels' jaxpr for a row: the segment ids enter as two operands
    of the kernel call and nowhere else — no grid bound, loop bound or
    ``pl.when`` condition outside the kernels is computed from them, and
    inside them the ids are read into the mask's comparison only (the
    loops' bounds are ``program_id`` arithmetic)."""
    t, hd = 1024, 128
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((t, 2 * hd), jnp.float32), ((t, 2 * hd), jnp.float32),
        ((t, 2 * hd), jnp.float32), ((t,), jnp.int32))]
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, seg: attention_pallas._forward(
            q, k, v, seg, 0.1, jnp.dtype("float32"), hd, BLOCK, BLOCK // 2)
    )(*shapes)
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (2, t // BLOCK)
    kernel = call.params["jaxpr"]
    seg_refs = set(kernel.invars[3:5])

    def reads_seg(eqns):
        """Variables computed from what the kernel reads of the ids."""
        tainted = set(seg_refs)
        for e in eqns:
            if tainted & {v for v in e.invars if hasattr(v, "count")}:
                tainted |= set(e.outvars)
        return tainted

    tainted = reads_seg(kernel.eqns) - seg_refs
    assert tainted      # the mask, the scores under it and what follows
    loops = 0
    for e in kernel.eqns:
        if e.primitive.name == "while":
            # what decides how long it runs: the condition's constants and
            # the carried values (the counter and its bound), not what the
            # body closes over (it reads the ids for its mask)
            n, m = e.params["cond_nconsts"], e.params["body_nconsts"]
            decides = list(e.invars[:n]) + list(e.invars[n + m:])
            loops += 1
        elif e.primitive.name == "cond":
            decides = e.invars[:1]
        else:
            continue
        assert not (tainted & {v for v in decides if hasattr(v, "count")})
    assert loops == 1


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
