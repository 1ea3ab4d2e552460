"""The chunked state-space scan of ``granite_hybrid.ssd_scan`` as Pallas TPU
kernels: the same mathematics a (chunk, block of heads) tile at a time, so
that no tensor of shape chunks x heads x Q x Q is written to or read from
HBM, forward or backward.

For a chunk of Q tokens and a head ``h`` (``cs`` the within-chunk running sum
of ``dt * a``, ``G = C B^T`` shared by the heads of the one group)::

    y_i = sum_{j <= i, same document} exp(cs_i - cs_j) G_ij dt_j x_j   (tile)
          + from_start_i (S_in C_i)                      (the entering state)
    S_out = through * S_in + sum_j dt_j to_end_j x_j B_j^T  (the state left)

Three kernels under one ``jax.custom_vjp`` (:func:`fused_scan`), grid
(chunk, block of heads), the heads of a block taken in pairs so that a pair's
``2 P`` values fill the lanes (P 64: one row of 128):

- ``ssd_states``: ``(v * scale)^T M`` a head — the state a chunk leaves
  (``v = x``, ``scale = dt * to_end``, ``M = B``) and, in the backward pass,
  the gradient to the entering state (``v = dy``, ``scale = from_start``,
  ``M = C``);
- ``ssd_output``: the tile, its product with ``dt x`` and the entering
  state's share of ``y``;
- ``ssd_backward``: the tile made again from the saved operands, and every
  gradient that meets it.

The masked ``G`` is made once a chunk (scratch, at the first block of heads).
A head's tile is made in square blocks of 128 tokens, the blocks below and on
the diagonal only (the block above it is zero whatever the data is; nothing
is skipped by what the documents are, so every step costs the same): the
decay is ``exp(cs_i - cs_j)`` in float32, clamped at 0 on the diagonal blocks,
where an entry above the diagonal would overflow before the masked ``G``
multiplies it by zero.  Products take operands in ``x.dtype`` and accumulate
in float32.  The hand-over of the state between chunks stays a float32
``lax.scan`` in XLA, and the running sums, ``to_end``, ``from_start`` and
``through`` (chunks x heads x Q values) stay ``jnp`` code that JAX
differentiates.

The backward kernel takes the gradient to the running sums from two
identities, not from the tile: with ``W = G * decay`` and ``dW = dy (dt
x)^T``, ``sum_j dW_ij W_ij = <dy_i, (W dt x)_i>`` and ``sum_i dW_ij W_ij =
<(dt x)_j, (W^T dy)_j>``, both of them (Q, 2P) products the kernel has
anyway; there ``W`` is taken to float32's precision, as the ``jnp`` form has
it (the rounded tile and the rounding's rest, two products each).  A head's
sums over its lanes are taken on the transpose, which leaves them as rows.

What per-head-per-token values the kernels need as a column (``cs_i``,
``dt``, the two scales) reaches them as rows (chunks, heads, Q), four values
by sixteen heads a block, and is transposed on the chip.

``granite_hybrid.scan_runs_fused`` says when this runs
(``kernels.runs_fused`` of :func:`fits`); interpret mode
(``pltpu.force_tpu_interpret_mode``) runs it on the CPU for the tests.
"""

from __future__ import annotations

import functools

import numpy as np

from tensorflowonspark_tpu.models.kernels import (
    compiler_params, dot as _dot, jitted)

#: heads a grid cell handles: 16 measured faster than 8 at the published
#: shapes (4.6 against 4.9 ms a layer, forward twice and backward: PERF.md)
HEADS_A_BLOCK = 16
#: rows of the packed per-head values: cs, dt, dt * to_end, from_start
_CS, _DT, _W, _FS = range(4)


def fits(chunk: int, heads: int, p: int, groups: int, n: int) -> bool:
    """Whether the kernels' tiles exist at these shapes: one group, a pair
    of heads filling whole rows of 128 lanes, the state's width and the
    chunk whole rows too, heads in blocks of sixteen."""
    return (groups == 1 and (2 * p) % 128 == 0 and n % 128 == 0
            and chunk % 128 == 0 and heads % HEADS_A_BLOCK == 0)


def _pair(cols, quantity: int, hb: int, j: int, half):
    """(Q, 2P): the column of ``quantity`` of head ``2j`` over the first P
    lanes, of head ``2j + 1`` over the rest."""
    import jax.numpy as jnp

    at = quantity * hb + 2 * j
    return jnp.where(half, cols[:, at:at + 1], cols[:, at + 1:at + 2])


def _mask(seg_row):
    """``same document and j <= i`` (Q, Q) from the chunk's segment ids as a
    row (1, Q)."""
    import jax
    import jax.numpy as jnp

    q = seg_row.shape[1]
    seg_col = jnp.broadcast_to(seg_row, (8, q)).T[:, 0:1]
    return ((seg_col == seg_row)
            & (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
               >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)))


def _masked_scores(c, b, seg_row):
    """``where(same document and j <= i, C_i . B_j, 0)`` (Q, Q) float32."""
    import jax.numpy as jnp

    return jnp.where(_mask(seg_row), _dot(c, b, (1, 1)), 0.0)


def _half(q: int, p: int):
    import jax
    import jax.numpy as jnp

    return jax.lax.broadcasted_iota(jnp.int32, (q, 2 * p), 1) < p


def _states_kernel(quantity, hb, p, dtype, v_ref, rows_ref, m_ref, out_ref):
    import jax.numpy as jnp

    q = v_ref.shape[0]
    half = _half(q, p)
    cols = rows_ref[...].T
    m = m_ref[...]
    for j in range(hb // 2):
        at = slice(2 * p * j, 2 * p * (j + 1))
        scaled = (v_ref[:, at].astype(jnp.float32)
                  * _pair(cols, quantity, hb, j, half)).astype(dtype)
        out_ref[at, :] = _dot(scaled, m, (0, 0))


def _blocks(q: int):
    """The tile in square blocks of at most 128 tokens: the rows of each
    block, and the block pairs (i, j) with j <= i — a block above the
    diagonal is all zero."""
    size = 128 if q % 128 == 0 else q
    at = [slice(k * size, (k + 1) * size) for k in range(q // size)]
    return at, [(i, j) for i in range(len(at)) for j in range(i + 1)]


def _add(held: list, i: int, term) -> None:
    """``held[i] += term``, where nothing is held yet as well."""
    held[i] = term if held[i] is None else held[i] + term


def _weights(scores_ref, cols, rows, h: int, ri, rj, diagonal: bool, dtype):
    """One block of a head's tile, ``scores * exp(cs_i - cs_j)``, in
    ``dtype``, the decay and the block itself in float32.  Below the diagonal blocks every
    ``j < i``, so the exponent needs no clamp."""
    import jax.numpy as jnp

    exponent = cols[ri, h:h + 1] - rows[h:h + 1, rj]
    if diagonal:
        exponent = jnp.minimum(exponent, 0.0)
    decay = jnp.exp(exponent)
    exact = scores_ref[ri, rj] * decay
    return exact.astype(dtype), decay, exact


def _output_kernel(hb, p, dtype, x_ref, b_ref, c_ref, seg_ref, rows_ref,
                   entering_ref, y_ref, scores_ref):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    q = x_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _():
        scores_ref[...] = _masked_scores(c_ref[...], b_ref[...],
                                         seg_ref[...])

    half = _half(q, p)
    rows = rows_ref[...]
    cols = rows.T
    c = c_ref[...]
    at, pairs = _blocks(q)
    for j in range(hb // 2):
        lanes = slice(2 * p * j, 2 * p * (j + 1))
        xdt = (x_ref[:, lanes].astype(f32) * _pair(cols, _DT, hb, j, half)
               ).astype(dtype)
        y = _dot(c, entering_ref[lanes, :].astype(dtype), (1, 1)) * _pair(
            cols, _FS, hb, j, half)
        inside = [None] * len(at)
        for side in (0, 1):
            mine = jnp.where(half if side == 0 else ~half, xdt,
                             jnp.zeros_like(xdt))
            for bi, bj in pairs:
                weights = _weights(scores_ref, cols, rows,
                                   _CS * hb + 2 * j + side, at[bi], at[bj],
                                   bi == bj, dtype)[0]
                _add(inside, bi, _dot(weights, mine[at[bj]], (1, 0)))
        y_ref[:, lanes] = y + jnp.concatenate(inside, axis=0)


def _backward_kernel(hb, p, dtype, x_ref, b_ref, c_ref, seg_ref, rows_ref,
                     entering_ref, dy_ref, dleft_ref, dx_ref, db_ref, dc_ref,
                     drows_ref, scores_ref, dscores_ref):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    q = x_ref.shape[0]
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        scores_ref[...] = _masked_scores(c_ref[...], b_ref[...],
                                         seg_ref[...])
        dscores_ref[...] = jnp.zeros_like(dscores_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    half = _half(q, p)
    rows = rows_ref[...]
    cols = rows.T
    b, c = b_ref[...], c_ref[...]
    at, pairs = _blocks(q)
    db, dc = jnp.zeros(db_ref.shape, f32), jnp.zeros(dc_ref.shape, f32)

    def put(quantity, j, values):
        """A head's gradient to one of its per-token values: the sum of
        ``values`` (Q, 2P) over the head's lanes, taken on the transpose so
        that it is a row, as ``drows`` holds it."""
        turned = values.T
        for side in (0, 1):
            at_row = quantity * hb + 2 * j + side
            drows_ref[at_row:at_row + 1, :] = jnp.sum(
                turned[side * p:(side + 1) * p], axis=0, keepdims=True)

    for j in range(hb // 2):
        lanes = slice(2 * p * j, 2 * p * (j + 1))
        x = x_ref[:, lanes].astype(f32)
        dt, w, fs = (_pair(cols, i, hb, j, half) for i in (_DT, _W, _FS))
        xdt = x * dt
        xdtb = xdt.astype(dtype)
        dy = dy_ref[:, lanes]
        dyb = dy.astype(dtype)
        # the entering state's share of y: y += from_start * (C S^T)
        entering = entering_ref[lanes, :].astype(dtype)
        put(_FS, j, dy * _dot(c, entering, (1, 1)))
        dc = dc + _dot((dy * fs).astype(dtype), entering, (1, 0))
        # the state left: left = (w x)^T B
        dleft = dleft_ref[lanes, :].astype(dtype)
        dxw = _dot(b, dleft, (1, 1))
        put(_W, j, x * dxw)
        db = db + _dot((x * w).astype(dtype), dleft, (1, 0))
        # the tile, a block at a time: W = scores * decay, y_in = W (dt x)
        # ``rest``: what the tile loses when it is rounded to ``dtype``, kept
        # for the running sums' gradient alone, where the ``jnp`` form has
        # the tile in float32 (without it the gradient to ``a`` is a fifth
        # further from float32's: PERF.md, PR 33)
        dxdt, inside = [None] * len(at), [None] * len(at)
        dxdt_rest, inside_rest = [None] * len(at), [None] * len(at)
        for side in (0, 1):
            mine = half if side == 0 else ~half
            dym = jnp.where(mine, dyb, jnp.zeros_like(dyb))
            xm = jnp.where(mine, xdtb, jnp.zeros_like(xdtb))
            for bi, bj in pairs:
                ri, rj = at[bi], at[bj]
                weights, decay, exact = _weights(
                    scores_ref, cols, rows, _CS * hb + 2 * j + side, ri, rj,
                    bi == bj, dtype)
                dscores_ref[ri, rj] += _dot(dym[ri], xdtb[rj], (1, 1)) * decay
                _add(dxdt, bj, _dot(weights, dym[ri], (0, 0)))
                _add(inside, bi, _dot(weights, xm[rj], (1, 0)))
                rest = (exact - weights.astype(f32)).astype(dtype)
                _add(dxdt_rest, bj, _dot(rest, dym[ri], (0, 0)))
                _add(inside_rest, bi, _dot(rest, xm[rj], (1, 0)))
        dxdt = jnp.concatenate(dxdt, axis=0)
        put(_DT, j, x * dxdt)
        # d cs_i = sum_j dW_ij W_ij - sum_i' dW_i'i W_i'i, and the two sums
        # are <dy_i, y_in_i> and <(dt x)_i, d(dt x)_i>: with dy and dt x as
        # the products took them, each dW_ij W_ij is the same number in
        # both, and what one token gains another loses exactly
        put(_CS, j,
            dyb.astype(f32) * jnp.concatenate(
                [u + v for u, v in zip(inside, inside_rest)], axis=0)
            - xdtb.astype(f32) * (dxdt + jnp.concatenate(dxdt_rest, axis=0)))
        dx_ref[:, lanes] = (dxdt * dt + dxw * w).astype(dx_ref.dtype)

    db_ref[...] += db
    dc_ref[...] += dc

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        dg = jnp.where(_mask(seg_ref[...]), dscores_ref[...], 0.0
                       ).astype(dtype)
        dc_ref[...] += _dot(dg, b, (1, 0))
        db_ref[...] += _dot(dg, c, (0, 0))


def _shapes(x2, rows):
    nc, nhb, four_hb, q = rows.shape
    hb = four_hb // 4
    return nc, nhb, hb, q, x2.shape[1] // (nhb * hb)


def _tile_specs(hb: int, q: int, p: int, n: int):
    """The blocks of a grid cell (chunk ``c``, block of heads ``k``): a
    block of heads' values, the chunk's ``B`` or ``C``, the heads' states,
    their packed rows, the chunk's segment ids."""
    from jax.experimental import pallas as pl

    wide = pl.BlockSpec((q, hb * p), lambda c, k: (c, k))
    narrow = pl.BlockSpec((q, n), lambda c, k: (c, 0))
    state = pl.BlockSpec((None, hb * p, n), lambda c, k: (c, k, 0))
    rows = pl.BlockSpec((None, None, 4 * hb, q), lambda c, k: (c, k, 0, 0))
    seg = pl.BlockSpec((None, 1, q), lambda c, k: (c, 0, 0))
    return wide, narrow, state, rows, seg


def _states(v2, rows, m2, quantity: int):
    """``out[c, h] = (v_h * scale_h)^T M`` over chunk ``c``: (chunks,
    heads x P, N) float32; ``scale`` is row block ``quantity`` of ``rows``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    nc, nhb, hb, q, p = _shapes(v2, rows)
    n = m2.shape[1]
    wide, narrow, state, rows_spec, _ = _tile_specs(hb, q, p, n)
    return pl.pallas_call(
        functools.partial(_states_kernel, quantity, hb, p, m2.dtype),
        grid=(nc, nhb), in_specs=[wide, rows_spec, narrow], out_specs=state,
        out_shape=jax.ShapeDtypeStruct((nc, nhb * hb * p, n), jnp.float32),
        compiler_params=compiler_params(), name="ssd_states")(v2, rows, m2)


def _output(x2, b2, c2, seg3, rows, entering):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nc, nhb, hb, q, p = _shapes(x2, rows)
    n = b2.shape[1]
    wide, narrow, state, rows_spec, seg = _tile_specs(hb, q, p, n)
    return pl.pallas_call(
        functools.partial(_output_kernel, hb, p, x2.dtype),
        grid=(nc, nhb),
        in_specs=[wide, narrow, narrow, seg, rows_spec, state],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((q, q), jnp.float32)],
        compiler_params=compiler_params(), name="ssd_output",
    )(x2, b2, c2, seg3, rows, entering)


def _backward(x2, b2, c2, seg3, rows, entering, dy, dleft):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nc, nhb, hb, q, p = _shapes(x2, rows)
    n = b2.shape[1]
    wide, narrow, state, rows_spec, seg = _tile_specs(hb, q, p, n)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_backward_kernel, hb, p, x2.dtype),
        grid=(nc, nhb),
        in_specs=[wide, narrow, narrow, seg, rows_spec, state, wide, state],
        out_specs=[wide, narrow, narrow, rows_spec],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x2.dtype),
                   jax.ShapeDtypeStruct(b2.shape, f32),
                   jax.ShapeDtypeStruct(c2.shape, f32),
                   jax.ShapeDtypeStruct(rows.shape, f32)],
        scratch_shapes=[pltpu.VMEM((q, q), f32), pltpu.VMEM((q, q), f32)],
        compiler_params=compiler_params(), name="ssd_backward",
    )(x2, b2, c2, seg3, rows, entering, dy, dleft)


def _hand_over(through, left, p: int):
    """The state entering each chunk: ``S_{c+1} = through_c S_c + left_c``
    from ``S_0 = 0``, float32.  ``through`` (chunks, heads), ``left``
    (chunks, heads x P, N)."""
    import jax
    import jax.numpy as jnp

    def cross(state, inp):
        keep, new = inp
        return state * keep[:, None, None] + new, state

    by_head = left.reshape(*through.shape, p, left.shape[-1])
    return jax.lax.scan(cross, jnp.zeros(by_head.shape[1:], jnp.float32),
                        (through, by_head))[1].reshape(left.shape)


def _hand_back(through, entering, d_entering, p: int):
    """The transpose of :func:`_hand_over`: the gradients to ``left`` and to
    ``through``."""
    import jax
    import jax.numpy as jnp

    def back(g, inp):       # g: the gradient to the state a chunk leaves
        keep, state, d_state = inp
        return (g * keep[:, None, None] + d_state,
                (g, jnp.sum(g * state, axis=(1, 2))))

    by_head = (*through.shape, p, entering.shape[-1])
    dleft, dthrough = jax.lax.scan(
        back, jnp.zeros(by_head[1:], jnp.float32),
        (through, entering.reshape(by_head), d_entering.reshape(by_head)),
        reverse=True)[1]
    return dleft.reshape(entering.shape), dthrough


def _core_fwd(x2, b2, c2, seg3, rows, through):
    p = _shapes(x2, rows)[-1]
    entering = _hand_over(
        through, jitted(_states, 3)(x2, rows, b2, _W), p)
    return (jitted(_output)(x2, b2, c2, seg3, rows, entering),
            (x2, b2, c2, seg3, rows, through, entering))


def _core_bwd(saved, dy):
    import jax

    x2, b2, c2, seg3, rows, through, entering = saved
    p = _shapes(x2, rows)[-1]
    # a custom_vjp's backward function does not inherit the caller's scope
    with jax.named_scope("ssm_scan"):
        dleft, dthrough = _hand_back(
            through, entering, jitted(_states, 3)(dy, rows, c2, _FS), p)
        dx, db, dc, drows = jitted(_backward)(
            x2, b2, c2, seg3, rows, entering, dy, dleft)
    return (dx, db.astype(b2.dtype), dc.astype(c2.dtype),
            np.zeros(seg3.shape, jax.dtypes.float0), drows, dthrough)


@functools.lru_cache(maxsize=None)
def _core():
    import jax

    @jax.custom_vjp
    def core(x2, b2, c2, seg3, rows, through):
        return _core_fwd(x2, b2, c2, seg3, rows, through)[0]

    core.defvjp(_core_fwd, _core_bwd)
    return core


def fused_scan(x, dt, a, b, c, seg, chunk: int, dtype):
    """``granite_hybrid.ssd_scan`` on the kernels, for a row whose length is
    a multiple of ``chunk`` and one group: same arguments, (T, H, P)
    float32."""
    import jax.numpy as jnp

    t, heads, p = x.shape
    n = b.shape[-1]
    nc = t // chunk
    hb = HEADS_A_BLOCK
    segc = seg.reshape(nc, chunk)
    # (chunks, heads, Q): the running sums of the log decay and what hangs
    # on them, as ssd_scan's jnp form has them
    cs = jnp.cumsum((dt * a).reshape(nc, chunk, heads), axis=1
                    ).transpose(0, 2, 1)
    last = segc[:, -1]
    to_end = jnp.exp(cs[..., -1:] - cs) * (segc == last[:, None])[:, None, :]
    through = jnp.exp(cs[..., -1]) * jnp.concatenate(
        [jnp.zeros((1,), bool), last[1:] == last[:-1]])[:, None]
    before = jnp.concatenate([jnp.full((1,), -2, seg.dtype), last[:-1]])
    from_start = jnp.exp(cs) * (segc == before[:, None])[:, None, :]
    dtr = dt.reshape(nc, chunk, heads).transpose(0, 2, 1)
    rows = jnp.stack([v.reshape(nc, heads // hb, hb, chunk) for v in (
        cs, dtr, dtr * to_end, from_start)], axis=2
    ).reshape(nc, heads // hb, 4 * hb, chunk)
    y = _core()(x.reshape(t, heads * p).astype(dtype),
                b.reshape(t, n).astype(dtype), c.reshape(t, n).astype(dtype),
                segc[:, None, :].astype(jnp.int32), rows, through)
    return y.reshape(t, heads, p)
