"""The chunked gated delta rule of ``kimi_linear.kda_scan`` as Pallas TPU
kernels: the same mathematics a (block of heads, pair of chunks) cell at a
time, so that nothing of a chunk's own — the running sums, the decay's
factors, the pairwise terms, ``w``, ``u`` — is written to or read from HBM,
forward or backward.

For a chunk of 64 tokens and one head (``G`` the running sum of ``g`` inside
the chunk, ``S`` the state entering it, K x V; a pair of tokens of different
documents contributes nothing, a token behind a document's first sees no
``S``)::

    B[t, r] = sum_c k_tc k_rc exp(G_tc - G_rc)      (r < t)   A = Diag(beta) B
    P[t, r] = sum_c q_tc k_rc exp(G_tc - G_rc)      (r <= t)
    T = (I + A)^-1                                  # unit lower triangle
    [u0 | w] = T [beta v | beta k exp G]            # U = u0 - w S
    u = u0 - w S          o = (q exp G) S + P u
    S' = Diag(exp G_C) S + sum_r (k_r exp(G_C - G_r)) u_r^T

A cell is two chunks, 128 rows: what a chunk makes of its own tokens (``G``,
``B``, ``P``, ``T``, ``u0``, ``w``) is made for both at once on 128 x 128
matrices that are zero outside the chunks' two blocks (a pair of tokens of
two chunks is masked as a pair of two documents is), which fills the MXU's
rows and halves the products a token; the state then crosses the two chunks
one after the other.  Two kernels under one ``jax.custom_vjp``
(:func:`fused_scan`), grid (block of heads, cell), the cells in turn (last
first in the backward pass) and the state — its gradient in the backward
pass — in VMEM scratch across them, float32, transposed (V x K: a decay
scales its lanes):

- ``kda_forward`` reads the cell's slice of ``q``, ``k``, ``v``, ``g`` (T, H
  x 128: a block of heads is a block of lanes, no transpose), ``beta`` and
  the documents' marks once, writes ``o``, the state that entered the cell
  and the two chunks' inverses side by side (64 x 128), both in the
  products' type, as the products take them;
- ``kda_backward`` makes the cell's own quantities again from the same
  operands, takes the inverses as saved, makes the second chunk's entering
  state again from the first's, and writes the five gradients.  The
  inverse's gradient is ``-T^T dT T^T``, two products, not a differentiated
  substitution.

A pairwise term is a product of two factors relative to the first token of
the row's sub-block (16 tokens), ``exp(G_t - G_ref)`` and ``exp(min(G_ref -
G_r, EXPONENT_CAP))``: every exponent is a decay or capped, never ``exp(G)
exp(-G)`` over a chunk (``kimi_linear.kda_scan`` says why).  ``G`` is a
product with the chunks' triangles of ones and the inverse is built from
1 x 1 blocks by doubling — ``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R
P^-1, Q^-1]]``, all blocks of a level at once as ``X - X R X`` on the whole
matrix, a level of all the cell's heads before the next (a level's two
products wait on each other, the heads' do not: taken a head at a time the
ten products of a 64 x 64 inverse cost 7.6 ms a layer, so 3.0: PERF.md
section 6, PR 45) —, both float32 at the highest precision; every other
product takes operands in ``dtype`` and accumulates in float32.  Nothing is
skipped by what the documents are: every cell costs the same whatever its
row holds.

``kimi_linear.kda_scan_runs_fused`` says when this runs
(``kernels.runs_fused`` of :func:`fits`); interpret mode
(``pltpu.force_tpu_interpret_mode``) runs it on the CPU for the tests.
"""

from __future__ import annotations

import functools
import types

import numpy as np

from tensorflowonspark_tpu.models.kernels import compiler_params, jitted
from tensorflowonspark_tpu.models.kimi_linear import EXPONENT_CAP, sub_block
from tensorflowonspark_tpu.models.packed_rows import under

#: a head's keys and values: one row of lanes each
LANES = 128
#: tokens of a chunk, and of a grid cell: two chunks, a row of lanes
CHUNK = 64
CELL = 2 * CHUNK
#: tokens of a sub-block
SUB = sub_block(CHUNK)
#: heads a grid cell handles (a grid step costs about 0.35 us: PERF.md)
HEADS_A_BLOCK = 4
#: columns of the documents' marks a token: its document's index in the row,
#: whether that is the document of the chunk before's last token (it reads
#: the entering state), whether it is the chunk's last token's (the chunk
#: hands it on), and whether those two are one (the state goes through)
_DOC, _SEES, _TAIL, _THROUGH = range(4)
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def fits(chunk: int, heads: int, dk: int, dv: int) -> bool:
    """Whether the kernels' tiles exist at these shapes: keys and values
    whole rows of 128 lanes, chunks of 64 tokens (two fill a cell's 128
    rows, a quarter is a whole tile), the heads in whole blocks."""
    return (dk == LANES and dv == LANES and chunk == CHUNK
            and heads % HEADS_A_BLOCK == 0)


def _dot(a, b, contract=(1, 0), exact: bool = False):
    """``a`` and ``b`` contracted over one axis each, accumulated in
    float32; ``exact``: float32 operands at the highest precision."""
    import jax
    import jax.numpy as jnp

    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


def _marks(marks_ref, row_ref):
    """What a cell's heads share: the pairs' masks (a pair of tokens of
    two chunks or of two documents is no pair), the tokens' marks as float32
    columns, the triangle of ones and the doubling's blocks."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    marks = marks_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (CELL, CELL), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (CELL, CELL), 1)
    chunk = (row & -CHUNK) == (col & -CHUNK)
    same = chunk & (marks[:, _DOC:_DOC + 1] == row_ref[...])
    levels, s = [], 1
    while s < CHUNK:    # the block under the diagonal of every 2s x 2s block
        levels.append(((row & -(2 * s)) == (col & -(2 * s)))
                      & ((row & s) != 0) & ((col & s) == 0))
        s *= 2
    through = marks[:, _THROUGH:_THROUGH + 1].astype(f32)
    return types.SimpleNamespace(
        chunk=chunk, upto=same & (row >= col), below=same & (row > col),
        lower=(chunk & (row >= col)).astype(f32),
        eye=(row == col).astype(f32), levels=levels, rows=row[:, :1],
        sees=marks[:, _SEES:_SEES + 1].astype(f32),
        tail=marks[:, _TAIL:_TAIL + 1].astype(f32),
        through=[through[c * CHUNK:c * CHUNK + 1] for c in (0, 1)])


def _inverses(a, m):
    """``(I + a_h)^-1`` of every head's strictly lower triangular ``a_h``
    (128, 128) float32, zero outside its two chunks' blocks: from 1 x 1
    blocks by doubling, a level of all the heads at a time (a level's two
    products wait on each other, the heads' do not)."""
    import jax.numpy as jnp

    x = [m.eye - jnp.where(m.levels[0], a_h, 0.0) for a_h in a]
    for level in m.levels[1:]:
        under_ = [_dot(jnp.where(level, a_h, 0.0), x_h, exact=True)
                  for a_h, x_h in zip(a, x)]
        x = [x_h - _dot(x_h, u_h, exact=True) for x_h, u_h in zip(x, under_)]
    return x


def _chunks(rows):
    """The two chunks' slices of a cell's rows."""
    return [rows[c * CHUNK:(c + 1) * CHUNK] for c in (0, 1)]


def _local(q, k, v, g, beta, m, dtype):
    """A cell's own quantities for one head, from its operands alone (all
    but the inverse)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    n = CELL // SUB
    at = [slice(i * SUB, (i + 1) * SUB) for i in range(n)]
    q32, k32, v32 = q.astype(f32), k.astype(f32), v.astype(f32)
    gsum = _dot(m.lower, g, exact=True)                             # G
    decayed = jnp.exp(gsum)
    ref = [gsum[i * SUB:i * SUB + 1] for i in range(n)]
    to_row = jnp.concatenate(
        [jnp.exp(gsum[at[i]] - ref[i]) for i in range(n)], axis=0)
    q_row, k_row = (q32 * to_row).astype(dtype), (k32 * to_row).astype(dtype)
    factor, keys, rows, both = [], [], [], []
    for i in range(n):      # a row's sub-block against every key
        factor.append(jnp.exp(jnp.minimum(ref[i] - gsum, EXPONENT_CAP)))
        keys.append((k32 * factor[i]).astype(dtype))
        rows.append(jnp.concatenate([q_row[at[i]], k_row[at[i]]], axis=0))
        both.append(_dot(rows[i], keys[i], (1, 1)))
    q_k = jnp.where(m.upto, jnp.concatenate([b[:SUB] for b in both], axis=0),
                    0.0).astype(dtype)
    k_k = jnp.where(m.below, jnp.concatenate([b[SUB:] for b in both], axis=0),
                    0.0)
    seen = m.sees * decayed
    v_k = jnp.concatenate([(beta * v32).astype(dtype),
                           (beta * seen * k32).astype(dtype)], axis=1)
    last = [slice((c + 1) * CHUNK - 1, (c + 1) * CHUNK) for c in (0, 1)]
    end = m.tail * jnp.exp(
        jnp.where(m.rows < CHUNK, gsum[last[0]], gsum[last[1]]) - gsum)
    return types.SimpleNamespace(
        at=at, beta=beta, q32=q32, k32=k32, v32=v32, gsum=gsum, ref=ref, to_row=to_row, factor=factor, keys=keys,
        rows=rows, q_k=q_k, k_k=k_k, seen=seen, v_k=v_k, end=end,
        q_seen=_chunks((seen * q32).astype(dtype)),
        to_end=_chunks((end * k32).astype(dtype)),
        keep=[m.through[c] * decayed[last[c]] for c in (0, 1)])


def _packed(inverse):
    """A cell's inverse (128, 128), zero outside its two chunks' blocks, as
    (64, 128): the second chunk's block beside the first's."""
    return inverse[:CHUNK] + inverse[CHUNK:]


def _unpacked(packed, m):
    import jax.numpy as jnp

    return jnp.where(m.chunk, jnp.concatenate([packed, packed], axis=0),
                     jnp.zeros((), packed.dtype))


def _heads(hb, refs, beta_ref, m, dtype):
    """:func:`_local` of each of a cell's ``hb`` heads."""
    return [_local(*(ref[:, LANES * j:LANES * (j + 1)] for ref in refs),
                   beta_ref[:, j:j + 1], m, dtype) for j in range(hb)]


def _forward_kernel(hb, dtype, q_ref, k_ref, v_ref, g_ref, beta_ref,
                    marks_ref, row_ref, o_ref, states_ref, inverse_ref,
                    state):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    m = _marks(marks_ref, row_ref)
    xs = _heads(hb, (q_ref, k_ref, v_ref, g_ref), beta_ref, m, dtype)
    inverses = _inverses([x.k_k * x.beta for x in xs], m)
    for j, (x, inverse) in enumerate(zip(xs, inverses)):
        lanes = slice(LANES * j, LANES * (j + 1))
        inverse = inverse.astype(dtype)
        inverse_ref[:, lanes] = _packed(inverse)
        u_w = _dot(inverse, x.v_k)                          # [u0 | w]
        u0, w = _chunks(u_w[:, :LANES]), _chunks(u_w[:, LANES:].astype(dtype))
        entering = state[j]                                 # (V, K) float32
        states_ref[lanes, :] = entering.astype(dtype)
        u, across = [], []
        for c in (0, 1):
            held = entering.astype(dtype)
            u.append((u0[c] - _dot(w[c], held, (1, 1))).astype(dtype))
            across.append(_dot(x.q_seen[c], held, (1, 1)))
            entering = entering * x.keep[c] + _dot(u[c], x.to_end[c], (0, 0))
        state[j] = entering
        o_ref[:, lanes] = (jnp.concatenate(across, axis=0)
                           + _dot(x.q_k, jnp.concatenate(u, axis=0))
                           ).astype(o_ref.dtype)


def _backward_kernel(hb, dtype, q_ref, k_ref, v_ref, g_ref, beta_ref,
                     marks_ref, row_ref, states_ref, inverse_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, d_state):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    m = _marks(marks_ref, row_ref)
    xs = _heads(hb, (q_ref, k_ref, v_ref, g_ref), beta_ref, m, dtype)
    for j, x in enumerate(xs):
        lanes = slice(LANES * j, LANES * (j + 1))
        beta = x.beta
        inverse = _unpacked(inverse_ref[:, lanes], m)
        u_w = _dot(inverse, x.v_k)
        u0, w = _chunks(u_w[:, :LANES]), _chunks(u_w[:, LANES:].astype(dtype))
        # the two chunks forward again: the second's entering state
        first = states_ref[lanes, :]                        # (V, K), dtype
        u = [(u0[0] - _dot(w[0], first, (1, 1))).astype(dtype)]
        second = first.astype(f32) * x.keep[0] + _dot(u[0], x.to_end[0],
                                                      (0, 0))
        held = [first, second.astype(dtype)]
        u.append((u0[1] - _dot(w[1], held[1], (1, 1))).astype(dtype))
        entering = [first.astype(f32), second]

        # o = q_seen S + q_k u;  S' = keep S + to_end^T u;  u = u0 - w S
        d_o = do_ref[:, lanes].astype(dtype)
        d_q_k = jnp.where(m.upto, _dot(d_o, jnp.concatenate(u, axis=0),
                                       (1, 1)), 0.0)
        d_inside = _chunks(_dot(x.q_k, d_o, (0, 0)))
        d_left = d_state[j]                                 # (V, K) float32
        d_q_seen, d_to_end, d_u, d_w, d_keep = ([None, None] for _ in
                                                range(5))
        for c in (1, 0):
            d_oc = d_o[c * CHUNK:(c + 1) * CHUNK]
            d_leftb = d_left.astype(dtype)
            d_q_seen[c] = _dot(d_oc, held[c])
            d_u[c] = (d_inside[c] + _dot(x.to_end[c], d_leftb, (1, 1))
                      ).astype(dtype)
            d_to_end[c] = _dot(u[c], d_leftb)
            d_w[c] = (-_dot(d_u[c], held[c])).astype(dtype)
            d_keep[c] = jnp.sum(d_left * entering[c], axis=0, keepdims=True)
            d_left = (d_left * x.keep[c] + _dot(d_oc, x.q_seen[c], (0, 0))
                      - _dot(d_u[c], w[c], (0, 0)))
        d_state[j] = d_left
        d_q_seen, d_to_end = (jnp.concatenate(a, axis=0)
                              for a in (d_q_seen, d_to_end))

        # [u0 | w] = T [beta v | beta seen k];  T = (I + A)^-1
        d_u_w = jnp.concatenate([jnp.concatenate(d_u, axis=0),
                                 jnp.concatenate(d_w, axis=0)], axis=1)
        d_inverse = _dot(d_u_w, x.v_k, (1, 1))
        d_v_k = _dot(inverse, d_u_w, (0, 0))
        d_vb, d_kb = d_v_k[:, :LANES], d_v_k[:, LANES:]
        exact = inverse.astype(f32)
        d_a = -_dot(_dot(exact, d_inverse, (0, 0), exact=True), exact,
                    (1, 1), exact=True)
        d_k_k = jnp.where(m.below, d_a * beta, 0.0)
        d_beta = (jnp.sum(d_a * x.k_k, axis=1, keepdims=True)
                  + jnp.sum(d_vb * x.v32 + d_kb * x.seen * x.k32, axis=1,
                            keepdims=True))

        # the running sums' gradient: what hangs on exp G and on the ends
        d_k = beta * x.seen * d_kb + x.end * d_to_end
        to_end_arg = x.end * x.k32 * d_to_end
        d_gsum = ((beta * x.k32 * d_kb + x.q32 * d_q_seen) * x.seen
                  - to_end_arg)
        for c, args in enumerate(_chunks(to_end_arg)):
            d_gsum = d_gsum + jnp.where(
                m.rows == (c + 1) * CHUNK - 1,
                jnp.sum(args, axis=0, keepdims=True) + x.keep[c] * d_keep[c],
                0.0)
        # the pairwise terms, a row's sub-block at a time
        d_q_row, d_k_row = [], []
        for i, at in enumerate(x.at):
            d_both = jnp.concatenate([d_q_k[at], d_k_k[at]], axis=0
                                     ).astype(dtype)
            d_rows = _dot(d_both, x.keys[i])                # (2 SUB, K)
            d_q_row.append(d_rows[:SUB])
            d_k_row.append(d_rows[SUB:])
            d_keys = _dot(d_both, x.rows[i], (0, 0))        # (128, K)
            d_k = d_k + d_keys * x.factor[i]
            arg = jnp.where(x.ref[i] - x.gsum < EXPONENT_CAP,
                            d_keys * x.k32 * x.factor[i], 0.0)
            d_gsum = d_gsum - arg + jnp.where(
                m.rows == i * SUB, jnp.sum(arg, axis=0, keepdims=True), 0.0)
        d_q_row = jnp.concatenate(d_q_row, axis=0)
        d_k_row = jnp.concatenate(d_k_row, axis=0)
        from_row = (d_q_row * x.q32 + d_k_row * x.k32) * x.to_row
        d_gsum = d_gsum + from_row
        for i, at in enumerate(x.at):
            d_gsum = d_gsum - jnp.where(
                m.rows == i * SUB,
                jnp.sum(from_row[at], axis=0, keepdims=True), 0.0)

        dq_ref[:, lanes] = (d_q_row * x.to_row + x.seen * d_q_seen
                            ).astype(dq_ref.dtype)
        dk_ref[:, lanes] = (d_k + d_k_row * x.to_row).astype(dk_ref.dtype)
        dv_ref[:, lanes] = (beta * d_vb).astype(dv_ref.dtype)
        dg_ref[:, lanes] = _dot(m.lower, d_gsum, (0, 0), exact=True)
        dbeta_ref[:, j:j + 1] = d_beta


def _specs(hb: int, cells: int, backward: bool):
    """The blocks of a grid cell (block of heads ``h``, step ``s``: cell
    ``s`` of the row, or ``cells - 1 - s`` in the backward pass): a block of
    heads' operands, their ``beta``, the tokens' marks and their documents
    as a row, the heads' states, their inverses."""
    from jax.experimental import pallas as pl

    def at(s):
        return cells - 1 - s if backward else s

    wide = pl.BlockSpec((CELL, hb * LANES), lambda h, s: (at(s), h))
    beta = pl.BlockSpec((None, CELL, hb), lambda h, s: (h, at(s), 0))
    marks = pl.BlockSpec((CELL, 4), lambda h, s: (at(s), 0))
    row = pl.BlockSpec((None, 1, CELL), lambda h, s: (at(s), 0, 0))
    states = pl.BlockSpec((None, hb * LANES, LANES),
                          lambda h, s: (at(s), h, 0))
    inverse = pl.BlockSpec((CHUNK, hb * LANES), lambda h, s: (at(s), h))
    return wide, beta, marks, row, states, inverse


def _forward(dtype, q, k, v, g, beta, marks, row):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nhb, _, hb = beta.shape
    cells = row.shape[0]
    wide, beta_spec, marks_spec, row_spec, states, inverse = _specs(
        hb, cells, False)
    return pl.pallas_call(
        functools.partial(_forward_kernel, hb, dtype), grid=(nhb, cells),
        in_specs=[wide, wide, wide, wide, beta_spec, marks_spec, row_spec],
        out_specs=[wide, states, inverse],
        out_shape=[jax.ShapeDtypeStruct(v.shape, dtype),
                   jax.ShapeDtypeStruct((cells, nhb * hb * LANES, LANES),
                                        dtype),
                   jax.ShapeDtypeStruct((cells * CHUNK, nhb * hb * LANES),
                                        dtype)],
        scratch_shapes=[pltpu.VMEM((hb, LANES, LANES), jnp.float32)],
        compiler_params=compiler_params(VMEM_LIMIT_BYTES), name="kda_forward",
    )(q, k, v, g, beta, marks, row)


def _backward(dtype, q, k, v, g, beta, marks, row, states, inverse, d_o):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nhb, _, hb = beta.shape
    cells = row.shape[0]
    wide, beta_spec, marks_spec, row_spec, states_spec, inverse_spec = _specs(
        hb, cells, True)
    return pl.pallas_call(
        functools.partial(_backward_kernel, hb, dtype), grid=(nhb, cells),
        in_specs=[wide, wide, wide, wide, beta_spec, marks_spec, row_spec,
                  states_spec, inverse_spec, wide],
        out_specs=[wide, wide, wide, wide, beta_spec],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in (q, k, v, g, beta)],
        scratch_shapes=[pltpu.VMEM((hb, LANES, LANES), jnp.float32)],
        compiler_params=compiler_params(VMEM_LIMIT_BYTES), name="kda_backward",
    )(q, k, v, g, beta, marks, row, states, inverse, d_o)


@functools.lru_cache(maxsize=None)
def _core(dtype, saved: tuple, scopes: tuple):
    """The two kernels under one ``jax.custom_vjp`` (made once a type, the
    names ``saved`` its outputs and states are kept under and the scopes
    its backward pass opens)."""
    import jax
    from jax.ad_checkpoint import checkpoint_name

    def fwd(*xs):
        o, *kept = (checkpoint_name(a, n) for a, n in zip(
            jitted(_forward, 0)(dtype, *xs), saved))
        return o, (xs, kept)

    def bwd(res, d_o):
        xs, kept = res
        # a custom_vjp's backward function does not inherit the caller's
        # scopes
        with under(scopes):
            grads = jitted(_backward, 0)(dtype, *xs, *kept, d_o)
        return tuple(grads) + tuple(
            np.zeros(a.shape, jax.dtypes.float0) for a in xs[5:])

    core = jax.custom_vjp(lambda *xs: fwd(*xs)[0])
    core.defvjp(fwd, bwd)
    return core


def fused_scan(q, k, v, g, beta, doc, last, before, dtype, saved: tuple,
               scopes: tuple = ()):
    """``kimi_linear.kda_scan`` on the kernels, for a row of whole cells
    (pairs of chunks) at shapes that :func:`fits` admits: ``q``, ``k`` and
    ``v`` (T, H, 128) in ``dtype``, ``g`` (T, H, 128) and ``beta`` (T, H)
    float32, ``doc`` (chunks, 64) the documents' indices in the row,
    ``last`` and ``before`` (chunks,) the one of a chunk's last token and of
    the chunk before's -> (T, H, 128) in ``dtype``.  Its outputs, the state
    entering every cell and the cells' inverses are named ``saved`` for a
    caller's ``jax.checkpoint``; the backward pass runs under the
    ``jax.named_scope``s ``scopes``."""
    import jax.numpy as jnp

    t, heads, _ = q.shape
    hb = HEADS_A_BLOCK
    dtype = jnp.dtype(dtype)
    marks = jnp.stack(
        [doc, doc == before[:, None], doc == last[:, None],
         jnp.broadcast_to((last == before)[:, None], doc.shape)],
        axis=-1).astype(jnp.int32).reshape(t, 4)
    o = _core(dtype, tuple(saved), tuple(scopes))(
        q.reshape(t, heads * LANES).astype(dtype),
        k.reshape(t, heads * LANES).astype(dtype),
        v.reshape(t, heads * LANES).astype(dtype),
        g.reshape(t, heads * LANES).astype(jnp.float32),
        beta.astype(jnp.float32).reshape(t, heads // hb, hb
                                         ).transpose(1, 0, 2),
        marks, doc.astype(jnp.int32).reshape(t // CELL, 1, CELL))
    return o.reshape(t, heads, LANES)
