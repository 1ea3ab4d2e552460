"""``packed_rows.document_attention`` as Pallas TPU kernels: the same blocked
causal attention inside documents, a (block of queries x block of keys)
score tile at a time in VMEM, so that no heads x Q x K float32 tensor is
written to or read from HBM, forward or backward.

One row of ``T`` tokens, head ``h`` of ``kv x rep`` (its keys and values
those of head ``h // rep``), the heads side by side along the lanes: ``q``
is (T, heads x hd) and a head's block is a column of whole rows of 128
lanes (``hd`` a multiple of 128), so nothing is transposed on the way in or
out.  Two kernels under one ``jax.custom_vjp`` (:func:`fused_attention`):

- ``attention_forward``, grid (head, block of queries): the head's keys and
  values stay in VMEM, the blocks of keys up to the queries' own are visited
  in a loop inside the kernel with the running maximum, sum and output in
  scratch; the log-sum-exp leaves as a row;
- ``attention_backward``, grid (head, block of keys), the blocks of keys in
  order: the head's queries and ``d_out`` stay in VMEM and the blocks of
  queries from the keys' own to the row's end are visited.  A tile's
  probabilities are made again from the saved log-sum-exp, once, and meet
  all five products there: the scores and ``dp`` (keys x queries, so that
  the per-query log-sum-exp and ``delta`` are rows and the products for
  ``dk`` and ``dv`` need no transpose), ``dv``, ``dk`` and ``dq``, which is
  summed over the blocks of keys in a float32 scratch of the head's whole
  row and leaves a block at a time as its rows become final.

The mathematics is the ``jnp`` form's to the rounding: the mask is ``j <= i
and same document``, masked scores are -1e30, every product takes operands
in ``dtype`` (``p`` and ``ds`` cast before theirs) and accumulates in
float32, the softmax is float32.  Only the blocks the diagonal crosses
compare positions.

**The loops stop at a document's edge.**  The grid comes from the shapes
alone and the segment ids themselves are read into the mask only, but two
small int32 vectors made of them outside the kernels (:func:`document_starts`:
a running maximum of the places where the id changes, and its mirror) reach
the kernels as scalars in SMEM and clip the loops: a block of queries starts
at ``first_key_block[i]``, the block of keys that holds the first token of
its first query's document, and a block of keys ends at
``past_query_block[j]``, past the last block of queries that holds a token
of its last key's document.  This rests on ``packed_rows``' contract —
documents are contiguous and their ids differ —: every pair of a block left
out is of two documents, hidden by the mask, and adds exactly 0 to the
running sum, the output, ``dq``, ``dk`` and ``dv``.  A visited block still
holds other documents' pairs and masks them as before; the blocks the
diagonal crosses are always visited.  So **a step's time follows its row**:
a row that is one document costs what the shapes say, a row of many short
ones about half of it (:func:`visited` counts both, and
``packed_rows.row_counters`` writes them into the program's counters).

Under a sliding ``window`` (``i - j < window``) the loops **skip the blocks
the window cannot reach**: a block of queries starts at the first block of
keys that holds a key within ``window - 1`` of its first query, a block of
keys ends at the last block of queries whose first query is within ``window
- 1`` of its last key (:func:`forward_bounds`, :func:`backward_bounds`:
``program_id`` arithmetic with the window in it, clipped by the documents'
bound: the later start, the earlier end).
The blocks the window's far edge crosses compare positions as the
diagonal's do; the blocks wholly inside compare documents only.  The same
blocks serve a window as serve none (read on the chip at 8,192 x 32/4 x 128
and a window of 1,024, kernels alone, ms a call, PERF.md, PR 47: forward
2.94 at 1,024 x 1,024, 2.86 at 512 x 1,024, 3.16 at 512 x 512, 5.23 at 256
x 256, for 5.35 with no window; forward and backward 8.26 with the backward
pass at 512 x 512, 8.28 at 256 x 512, 8.87 at 256 x 256, 8.94 at 1,024 x
1,024, for 16.12 with no window).

``packed_rows.attention_runs_fused`` says when this runs
(``kernels.runs_fused`` of :func:`fits`); interpret mode
(``pltpu.force_tpu_interpret_mode``) runs it on the CPU for the tests.
"""

from __future__ import annotations

import functools

import numpy as np

from tensorflowonspark_tpu.models.kernels import (
    compiler_params, dot as _dot, jitted)
from tensorflowonspark_tpu.models.packed_rows import name_saved, under

#: (queries, keys) a tile, forward and backward, chosen on the chip at the
#: published 8,192 x 20 x 256 in bfloat16 (kernels alone, ms a call; PERF.md,
#: PR 35, holds every reading): forward 6.93 at 256 x 512, 5.59 at 512 x
#: 512, 5.41 at 1,024 x 512, 5.00 at 512 x 1,024, 4.90 at 1,024 x 1,024;
#: backward 10.05 at 512 x 512, 10.36 at 1,024 x 512, 10.38 at 256 x 512
FORWARD_BLOCKS = (1024, 1024)
BACKWARD_BLOCKS = (512, 512)
#: a head's whole row stays in VMEM (keys and values forward; queries,
#: ``d_out`` and the float32 ``dq`` backward): 24 MiB and the tiles at
#: 8,192 x 256; the limit is half of what a v5e has
VMEM_LIMIT_BYTES = 64 * 2 ** 20
ROW_ENTRIES = 8192 * 256
MASKED = -1e30


def _blocks(t: int, blocks: tuple) -> tuple:
    """A short row is one block."""
    return tuple(min(b, t) for b in blocks)


def fits(t: int, hd: int) -> bool:
    """Whether the kernels' tiles exist at these shapes: a head's row fills
    whole rows of 128 lanes, the row of tokens is whole blocks of queries
    and of keys of whole rows of lanes too, and a head's row fits the fast
    memory."""
    return (hd % 128 == 0 and t % 128 == 0 and t * hd <= ROW_ENTRIES
            and all(t % b == 0 for b in _blocks(
                t, FORWARD_BLOCKS + BACKWARD_BLOCKS)))


def _rows_at(i, size: int):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(i * size, size), size)


def _offsets(rows: int, cols: int, by_row: bool):
    """(rows, cols) int32: how far an entry's query lies beyond its key
    when both blocks start at the same token; queries along the rows
    (``by_row``) or along the columns."""
    import jax
    import jax.numpy as jnp

    r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return r - c if by_row else c - r


def _forward_kernel(scale, dtype, bk, window, first_key_ref, q_ref, k_ref,
                    v_ref, seg_q_ref, seg_k_ref, out_ref, lse_ref, m_ref,
                    l_ref, acc_ref):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    bq = q_ref.shape[0]
    i = pl.program_id(1)
    q, seg_q = q_ref[...], seg_q_ref[...]
    m_ref[...] = jnp.full(m_ref.shape, MASKED, f32)
    l_ref[...] = jnp.zeros(l_ref.shape, f32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    def visit(j, diagonal: bool, edge: bool = False):
        at = _rows_at(j, bk)
        mask = seg_q == seg_k_ref[j]
        if diagonal:
            mask = mask & (_offsets(bq, bk, True) >= j * bk - i * bq)
        if edge:
            mask = mask & (_offsets(bq, bk, True) < window + j * bk - i * bq)
        s = jnp.where(mask, _dot(q, k_ref[at, :], (1, 1)) * scale, MASKED)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        # a row that has met no key of its document yet holds ones here
        # (exp(-1e30 + 1e30)); the first real score's fade is exactly 0
        p = jnp.exp(s - m_new)
        fade = jnp.exp(m - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * fade + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * fade + _dot(
            p.astype(dtype), v_ref[at, :], (1, 0))

    def visits(lo, hi, edge: bool):
        def body(j, carry):
            visit(j, False, edge)
            return carry

        jax.lax.fori_loop(lo, hi, body, 0)

    below = (i * bq) // bk      # blocks of keys wholly before the queries
    first, inside = forward_bounds(i, bq, bk, window, first_key_ref[i])
    if window is not None:
        visits(first, inside, True)
    visits(inside, below, False)
    for d in range(max(1, bq // bk)):
        visit(below + d, True, _edge_on_diagonal(window, bq, bk))
    l = l_ref[...]
    out_ref[...] = (acc_ref[...] / l).astype(out_ref.dtype)
    lse = m_ref[...] + jnp.log(l)
    lse_ref[...] = jnp.broadcast_to(lse, (bq, 128)).T[0:1]


def _backward_kernel(scale, dtype, bq, window, past_query_ref, q_ref, do_ref,
                     k_ref, v_ref, seg_k_ref, seg_q_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    bk = k_ref.shape[0]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, f32)

    dk_acc[...] = jnp.zeros(dk_acc.shape, f32)
    dv_acc[...] = jnp.zeros(dv_acc.shape, f32)
    k, v, seg_k = k_ref[...], v_ref[...], seg_k_ref[...]

    def visit(i, diagonal: bool, edge: bool = False):
        at = _rows_at(i, bq)
        q, do = q_ref[at, :], do_ref[at, :]
        mask = seg_k == seg_q_ref[i]
        if diagonal:
            mask = mask & (_offsets(bk, bq, False) >= j * bk - i * bq)
        if edge:
            mask = mask & (_offsets(bk, bq, False) < window + j * bk - i * bq)
        s = jnp.where(mask, _dot(k, q, (1, 1)) * scale, MASKED)
        p = jnp.exp(s - lse_ref[i])
        ds = p * (_dot(v, do, (1, 1)) - delta_ref[i]) * scale
        p, ds = p.astype(dtype), ds.astype(dtype)
        dv_acc[...] += _dot(p, do, (1, 0))
        dk_acc[...] += _dot(ds, q, (1, 0))
        dq_acc[at, :] += _dot(ds, k, (0, 0))

    def visits(lo, hi, edge: bool):
        def body(i, carry):
            visit(i, False, edge)
            return carry

        jax.lax.fori_loop(lo, hi, body, 0)

    first = (j * bk) // bq      # the first block of queries at these keys
    crossed = max(1, bk // bq)
    for d in range(crossed):
        visit(first + d, True, _edge_on_diagonal(window, bq, bk))
    inside, last = backward_bounds(j, bq, bk, window, q_ref.shape[0] // bq,
                                   past_query_ref[j])
    visits(first + crossed, inside, False)
    if window is not None:
        visits(inside, last, True)
    dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
    dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
    # no later block of keys reaches these queries
    dq_ref[...] = dq_acc[_rows_at(j, bk), :].astype(dq_ref.dtype)


def _edge_on_diagonal(window, bq: int, bk: int) -> bool:
    """Whether a block the diagonal crosses holds a query and a key
    ``window`` or more apart (they are under ``max(bq, bk)`` apart)."""
    return window is not None and window < max(bq, bk)


def _np_or_jnp(x):
    """numpy for what the host counts (Python and numpy numbers), ``jnp``
    for what a kernel or a step traces: one arithmetic for both."""
    if isinstance(x, (int, np.integer, np.ndarray)):
        return np
    import jax.numpy as jnp

    return jnp


def document_starts(seg):
    """(..., T) int32: the token at which every token's document starts, on
    the rows ``seg`` (..., T).  From ``packed_rows``' contract that
    **documents are contiguous and their ids differ**: a token whose id is
    not the one before it starts a document, and every token up to the next
    such one is that document's.  A running maximum over the places where
    the id changes; ``seg`` a numpy array (the host's count) or a ``jnp``
    one (the kernels' operands)."""
    xp = _np_or_jnp(seg)
    if xp is np:
        running_max = functools.partial(np.maximum.accumulate, axis=-1)
    else:
        import jax

        running_max = functools.partial(jax.lax.cummax, axis=seg.ndim - 1)
    starts = xp.concatenate([xp.ones(seg.shape[:-1] + (1,), bool),
                             seg[..., 1:] != seg[..., :-1]], axis=-1)
    return running_max(xp.where(
        starts, xp.arange(seg.shape[-1], dtype=xp.int32), 0))


def first_key_blocks(seg, bq: int, bk: int):
    """(..., T / bq) int32, one a block of queries of the forward pass: the
    document of block ``i``'s first query starts at token ``p`` — no query
    of the block sees a key before it (:func:`document_starts`) —; the
    value is ``p // bk``, never past the diagonal's block."""
    return document_starts(seg)[..., ::bq] // bk


def past_query_blocks(seg, bq: int, bk: int):
    """(..., T / bk) int32, one a block of keys of the backward pass: the
    document of block ``j``'s last key ends before token ``e`` — no query
    from ``e`` on sees a key of the block; the mirror of a start:
    :func:`document_starts` of the row read from its end —; the value is
    ``(e + bq - 1) // bq``, never before the diagonal's blocks."""
    t = seg.shape[-1]
    ends = t - document_starts(seg[..., ::-1])[..., ::-1]
    return (ends[..., bk - 1::bk] + bq - 1) // bq


def forward_bounds(i, bq: int, bk: int, window, first_key=0) -> tuple:
    """``(first, inside)`` of the forward kernel's block ``i`` of queries,
    the blocks of keys before the diagonal's in two ranges: from ``first``
    to ``inside`` the ones the window's far edge crosses (none without a
    ``window``), from ``inside`` to the diagonal's the ones wholly inside.
    Under a window ``first`` is the first block of keys with a key inside
    the first query's window and ``inside`` the first whose every key is
    inside the last query's (no further than the diagonal's); both are then
    no earlier than ``first_key``, the first block that holds the queries'
    document (:func:`first_key_blocks`; 0: the shapes and the window alone).
    ``i`` a ``program_id`` or numpy numbers."""
    xp = _np_or_jnp(i)
    if window is None:
        return first_key, first_key
    below = (i * bq) // bk
    first = xp.maximum(i * bq - (window - 1), 0) // bk
    inside = xp.clip((xp.maximum(i * bq + bq - window, 0) + bk - 1) // bk,
                     first, below)
    return xp.maximum(first, first_key), xp.maximum(inside, first_key)


def backward_bounds(j, bq: int, bk: int, window, end, past_query=None
                    ) -> tuple:
    """``(inside, last)`` of the backward kernel's block ``j`` of keys, both
    ends of a range of the blocks of queries behind the diagonal's (of
    ``end`` in the row): up to ``inside`` the ones wholly inside the
    window, from ``inside`` to ``last`` the ones its far edge crosses (none
    without a ``window``).  Under a window ``inside`` is past the last
    block whose every query's window holds the block's first key and
    ``last`` past the last with a query whose window holds its last key;
    both are then no later than ``past_query``, past the last block that
    holds the keys' document (:func:`past_query_blocks`; None: the shapes and
    the window alone), which is never before the diagonal's."""
    xp = _np_or_jnp(j)
    if window is None:
        inside = last = end
    else:
        after = (j * bk) // bq + max(1, bk // bq)
        last = xp.minimum((j * bk + bk + window - 2) // bq + 1, end)
        inside = xp.clip((xp.maximum(j * bk + window - bq + 1, 0) + bq - 1)
                         // bq, after, xp.maximum(last, after))
    if past_query is None:
        return inside, last
    return xp.minimum(inside, past_query), xp.minimum(last, past_query)


def visited(t: int, forward: tuple, backward: tuple, window=None, seg=None
            ) -> tuple:
    """``(forward, backward)``: the (block of queries, block of keys) pairs
    a head's two kernels' loops visit at each pass's (queries, keys) a tile,
    from the bounds the kernels compute: on the rows ``seg`` (..., ``t``) of
    segment ids, summed over them, or, without them, on one row of ``t``
    tokens by the shapes and the ``window`` alone (what a row that is one
    document visits, and what every row did before the loops followed the
    documents)."""
    seg = None if seg is None else np.asarray(seg)
    bq, bk = _blocks(t, forward)
    i = np.arange(t // bq)
    first, _ = forward_bounds(i, bq, bk, window, 0 if seg is None
                              else first_key_blocks(seg, bq, bk))
    n_forward = np.sum((i * bq) // bk + max(1, bq // bk) - first)
    bq, bk = _blocks(t, backward)
    j = np.arange(t // bk)
    _, last = backward_bounds(j, bq, bk, window, t // bq, None if seg is None
                              else past_query_blocks(seg, bq, bk))
    return int(n_forward), int(np.sum(last - (j * bk) // bq))


def _forward(q2, k2, v2, seg, scale, dtype, hd, bq, bk, window=None):
    """``out`` (T, heads x hd) in ``dtype`` and the log-sum-exp (heads,
    T / bq, 1, bq) float32."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    t, heads = q2.shape[0], q2.shape[1] // hd
    rep = q2.shape[1] // k2.shape[1]
    queries = pl.BlockSpec((bq, hd), lambda h, i: (i, h))
    row = pl.BlockSpec((t, hd), lambda h, i: (0, h // rep))
    return pl.pallas_call(
        functools.partial(_forward_kernel, scale, dtype, bk, window),
        grid=(heads, t // bq),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), queries, row, row,
                  pl.BlockSpec((bq, 1), lambda h, i: (i, 0)),
                  pl.BlockSpec((t // bk, 1, bk), lambda h, i: (0, 0, 0))],
        out_specs=[queries, pl.BlockSpec((None, None, 1, bq),
                                         lambda h, i: (h, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(q2.shape, dtype),
                   jax.ShapeDtypeStruct((heads, t // bq, 1, bq), f32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), f32), pltpu.VMEM((bq, 1), f32),
                        pltpu.VMEM((bq, hd), f32)],
        compiler_params=compiler_params(VMEM_LIMIT_BYTES),
        name="attention_forward",
    )(first_key_blocks(seg, bq, bk), q2, k2, v2, seg.reshape(t, 1),
      seg.reshape(t // bk, 1, bk))


def _backward(q2, k2, v2, seg, lse, do2, delta, scale, dtype, hd, bq, bk,
              window=None):
    """``dq``, ``dk``, ``dv`` a query head (T, heads x hd): ``dq`` in
    ``dtype``; ``dk`` and ``dv`` too where a key head serves one query
    head, else float32 for the sum over its ``rep``.  ``lse`` and ``delta``
    (heads, T / bq, 1, bq) float32."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    t, heads = q2.shape[0], q2.shape[1] // hd
    rep = q2.shape[1] // k2.shape[1]
    row = pl.BlockSpec((t, hd), lambda h, j: (0, h))
    keys = pl.BlockSpec((bk, hd), lambda h, j: (j, h // rep))
    per_query = pl.BlockSpec((None, t // bq, 1, bq),
                             lambda h, j: (h, 0, 0, 0))
    gradient = pl.BlockSpec((bk, hd), lambda h, j: (j, h))
    summed = jax.ShapeDtypeStruct(q2.shape, dtype if rep == 1 else f32)
    return pl.pallas_call(
        functools.partial(_backward_kernel, scale, dtype, bq, window),
        grid=(heads, t // bk),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), row, row, keys, keys,
                  pl.BlockSpec((bk, 1), lambda h, j: (j, 0)),
                  pl.BlockSpec((t // bq, 1, bq), lambda h, j: (0, 0, 0)),
                  per_query, per_query],
        out_specs=[gradient, gradient, gradient],
        out_shape=[jax.ShapeDtypeStruct(q2.shape, dtype), summed, summed],
        scratch_shapes=[pltpu.VMEM((t, hd), f32), pltpu.VMEM((bk, hd), f32),
                        pltpu.VMEM((bk, hd), f32)],
        compiler_params=compiler_params(VMEM_LIMIT_BYTES),
        name="attention_backward",
    )(past_query_blocks(seg, bq, bk), q2, do2, k2, v2, seg.reshape(t, 1),
      seg.reshape(t // bq, 1, bq), lse, delta)


def _heads_along_lanes(x, dtype):
    """(T, ..., hd) -> (T, heads x hd) in ``dtype``."""
    return x.reshape(x.shape[0], -1).astype(dtype)


def _attend_primal(q, k, v, seg, scale, dtype, scopes, forward, backward,
                   window):
    out, lse = jitted(_forward, (4, 5, 6, 7, 8, 9))(
        *(_heads_along_lanes(x, dtype) for x in (q, k, v)), seg, scale,
        dtype, q.shape[-1], *forward, window)
    return out.reshape(q.shape), lse


def _attend_fwd(q, k, v, seg, *static):
    out, lse = name_saved(*_attend_primal(q, k, v, seg, *static))
    return out, (q, k, v, seg, out, lse)


def _attend_bwd(scale, dtype, scopes, forward, backward, window, saved,
                d_out):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    q, k, v, seg, out, lse = saved
    t, kv, rep, hd = q.shape
    bq = backward[0]
    with under(scopes):
        delta = jnp.sum(d_out.astype(f32) * out.astype(f32), axis=-1)
        dq, dk, dv = jitted(_backward, (7, 8, 9, 10, 11, 12))(
            *(_heads_along_lanes(x, dtype) for x in (q, k, v)), seg,
            lse.reshape(kv * rep, t // bq, 1, bq),
            _heads_along_lanes(d_out, dtype),
            delta.reshape(t // bq, 1, bq, kv * rep).transpose(3, 0, 1, 2),
            scale, dtype, hd, *backward, window)
        if rep > 1:
            dk, dv = (g.reshape(q.shape).sum(2) for g in (dk, dv))
    return (dq.reshape(q.shape).astype(q.dtype),
            dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype),
            np.zeros(seg.shape, jax.dtypes.float0))


@functools.lru_cache(maxsize=None)
def _attend():
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
    def attend(q, k, v, seg, scale, dtype, scopes, forward, backward,
               window):
        return _attend_primal(q, k, v, seg, scale, dtype, scopes, forward,
                              backward, window)[0]

    attend.defvjp(_attend_fwd, _attend_bwd)
    return attend


def fused_attention(q, k, v, seg, scale: float, dtype, scopes: tuple,
                    forward: tuple = FORWARD_BLOCKS,
                    backward: tuple = BACKWARD_BLOCKS, window=None):
    """``packed_rows.document_attention`` on the kernels, for shapes that
    :func:`fits` admits: ``q`` (T, kv, rep, hd), ``k`` and ``v`` (T, kv,
    hd), ``seg`` (T,); returns (T, kv, rep, hd) in ``dtype``.  The backward
    pass runs under the ``jax.named_scope``s ``scopes``.  ``forward`` and
    ``backward`` are each pass's (queries, keys) a tile; ``window`` is a
    sliding-window layer's (None: none)."""
    import jax.numpy as jnp

    t = q.shape[0]
    return _attend()(q, k, v, seg.astype(jnp.int32), float(scale),
                     jnp.dtype(dtype), tuple(scopes), _blocks(t, forward),
                     _blocks(t, backward),
                     None if window is None else int(window))
