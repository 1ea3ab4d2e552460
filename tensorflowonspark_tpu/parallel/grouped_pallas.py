"""The grouped products of ``moe.routed_experts`` as Pallas TPU kernels that
visit the row tiles holding a live row and no others.

``rows`` (R, K) are sorted by group: the first ``group_sizes[0]`` rows are
group 0's, the next ``group_sizes[1]`` group 1's, and the rows past
``sum(group_sizes)`` belong to no group.  Three products under one
``jax.custom_vjp`` (:func:`grouped_product`), the arithmetic of
``jax.lax.ragged_dot`` a tile at a time — operands in ``rows``' type,
float32 accumulation, no slot dropped:

- ``grouped_rows``: ``out[i] = rows[i] @ w[group of i]``, (R, N) float32;
- ``grouped_rows`` again against ``w`` transposed, for the gradient to the
  rows: ``d_rows[i] = d[i] @ w[group of i].T``, (R, K) in ``rows``' type;
- ``grouped_weights``: ``d_w[g] = rows_g.T @ d_g``, the rows' axis contracted
  a group at a time, (H, K, N) float32.

The weights come as they are kept (float32) and are cast to ``rows``' type
inside ``grouped_rows``, a group's block once for all its visits: no copy of
them in ``rows``' type is written to memory or read from it.

**The grid comes from ``group_sizes`` on the device** (:func:`plan`, scalar
prefetch): a *visit* is a pair (group, tile of ``ROW_TILE`` rows) whose
rows meet; a group's visits are consecutive and rise by tile, a tile that
two groups share is visited once by each under a row mask, a tile past the
live rows is never visited, and the grid's second axis is as long as the
visits are — a product's time follows the step's live rows, not the buffer.
A group's weights stay in VMEM for all its visits (a block spans the whole
contraction, so consecutive visits ask for the block they have): the weights
are read once a column tile, whatever the row tile is.

**What a product leaves in rows that belong to no group.**  ``grouped_rows``
writes only the rows of the visited group: a row past the live ones in the
last visited tile holds what the output's buffer in VMEM held, an unvisited
tile what the output's memory held — anything, a NaN too, and not zero.
Rows are independent in these two products, so nothing there reaches a live
row, and the caller masks what it goes on to sum (``moe.over_rows`` keeps
its two ``jnp.where(live, ...)``).  ``grouped_weights`` contracts the rows'
axis, so it zeroes the rows outside the group in both operands of a shared
tile before the product (0 x NaN would be NaN), and **an empty group is
visited once to write exact zeros** (the tile fetched for that visit is not
used).

``moe.grouped_runs_fused`` says when this runs (``kernels.runs_fused`` of
:func:`fits`); interpret mode
(``pltpu.force_tpu_interpret_mode``) runs it on the CPU for the tests.
"""

from __future__ import annotations

import functools

import numpy as np

from tensorflowonspark_tpu.models.kernels import (
    compiler_params, dot as _dot, jitted)

#: rows a tile (:func:`plan`'s and both kernels').  With a group's weights
#: resident the choice is between the boundary tiles' waste and the MXU's
#: fill, and on the chip it hardly matters (PERF.md section 6, PR 42, holds
#: the readings, made for PR 41: a product alone at 128 / 256 / 512 / 1,024
#: rows a tile takes 0.458 / 0.438 / 0.459 / 0.512 ms at LFM2's shape and
#: 0.265 / 0.249 / 0.244 / 0.248 at GLM's); 256 also makes the smaller
#: program (a product is unrolled: 84 MB less code in a step of 120 kernels
#: than at 512)
ROW_TILE = 256
#: the widest column tile: the output's columns (``grouped_rows``) or the
#: contraction's (``grouped_weights``) are cut into the fewest equal tiles of
#: whole rows of 128 lanes at most this wide: the rows are read once a
#: column tile and a grid step costs its 0.35 us, so few and wide (at 512
#: wide, seven tiles for 1,792 columns, a step of ``lfm2_8b_a1b_packed_8k``
#: took 5 ms longer; PERF.md section 6, PR 42)
COL_TILE = 1024
#: a group's float32 weights (2,048 x 896: 7.3 MB), a tile of rows and a
#: float32 tile of the result, each twice (a block is fetched while its
#: predecessor is worked on), and the weights once more in bfloat16: 22 MB
#: forward, 19 MB for the weights' gradient; the limit is half of what a
#: v5e has
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def col_tile(n: int) -> int:
    """The largest divisor of ``n`` in whole rows of 128 lanes that is at
    most :data:`COL_TILE` (1,792 -> 896, 1,536 -> 768, 2,048 -> 1,024)."""
    return max(c for c in range(128, min(n, COL_TILE) + 1, 128) if n % c == 0)


def fits(rows: int, k: int, n: int, dtype) -> bool:
    """Whether the kernels' tiles exist at these shapes: the rows are whole
    row tiles, ``k`` and ``n`` whole rows of 128 lanes, and a kernel's
    blocks fit three quarters of the fast memory it may use (the widest
    block — a group's float32 weights in one kernel, a float32 block of
    their gradient in the other — twice, the weights once more in
    ``dtype``, and a tile of rows of each operand and of the result,
    twice)."""
    import jax.numpy as jnp

    if rows % ROW_TILE or k % 128 or n % 128 or min(rows, k, n) <= 0:
        return False
    block = max(col_tile(k) * n, k * col_tile(n))
    need = block * (8 + jnp.dtype(dtype).itemsize) + 8 * ROW_TILE * (k + n)
    return 4 * need <= 3 * VMEM_LIMIT_BYTES


def plan(group_sizes, rows: int):
    """The visits of ``group_sizes`` (H,) over ``rows`` rows in tiles of
    :data:`ROW_TILE``: ``(groups, tiles, offsets, count)``, the kernels'
    prefetched scalars and the length of their grid.  Visit ``v`` is group
    ``groups[v]`` at tile ``tiles[v]`` for ``v < count``; ``offsets``
    (H + 1,) are the groups' first rows and the live rows' end.  A group has
    a visit for every tile its rows meet, an empty group one, at the tile
    its neighbour's next visit is at (the weights' gradient writes its zeros
    there, the rows' product skips it and writes nothing the neighbour does
    not overwrite): at most ``rows / ROW_TILE + H - 1``."""
    import jax.numpy as jnp

    i32 = jnp.int32
    h, n_tiles = group_sizes.shape[0], rows // ROW_TILE
    sizes = group_sizes.astype(i32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // ROW_TILE, n_tiles - 1)
    met = jnp.where(sizes > 0, (ends - 1) // ROW_TILE - first + 1, 1)
    before = jnp.cumsum(met) - met
    most = n_tiles + h - 1
    groups = jnp.repeat(jnp.arange(h, dtype=i32), met,
                        total_repeat_length=most)
    tiles = first[groups] + jnp.arange(most, dtype=i32) - before[groups]
    return (groups, jnp.clip(tiles, 0, n_tiles - 1),
            jnp.concatenate([jnp.zeros((1,), i32), ends]), jnp.sum(met))


def _visit(groups, tiles, offsets, size: int):
    """``(whole, shared, mask)`` of this grid step's visit: whether every
    row of its tile is its group's, whether only some are (neither for an
    empty group), and the (size, 1) mask of those."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    v = pl.program_id(1)
    start, end = offsets[groups[v]], offsets[groups[v] + 1]
    first = tiles[v] * size
    row = first + jax.lax.broadcasted_iota(jnp.int32, (size, 1), 0)
    whole = (start <= first) & (first + size <= end)
    return (whole, jnp.logical_not(whole) & (end > start),
            (row >= start) & (row < end))


def _new_group(groups):
    """Whether this grid step's visit is the first of its group (in this
    pass over the visits)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    v = pl.program_id(1)
    return (v == 0) | (groups[jnp.maximum(v, 1) - 1] != groups[v])


def _rows_kernel(contract, groups, tiles, offsets, rows_ref, w_ref, out_ref,
                 cast_ref):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    whole, shared, mask = _visit(groups, tiles, offsets, rows_ref.shape[0])

    @pl.when(_new_group(groups))
    def _():
        # the group's weights in the rows' type, once for all its visits
        cast_ref[...] = w_ref[...].astype(cast_ref.dtype)

    @pl.when(whole)
    def _():
        out_ref[...] = _dot(rows_ref[...], cast_ref[...], contract
                            ).astype(out_ref.dtype)

    @pl.when(shared)
    def _():
        # float32 for the select, as the chip's vector unit wants it
        out_ref[...] = jnp.where(
            mask, _dot(rows_ref[...], cast_ref[...], contract),
            out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _weights_kernel(groups, tiles, offsets, rows_ref, d_ref, out_ref):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    whole, shared, mask = _visit(groups, tiles, offsets, rows_ref.shape[0])

    @pl.when(_new_group(groups))
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, f32)

    @pl.when(whole)
    def _():
        out_ref[...] += _dot(rows_ref[...], d_ref[...], (0, 0))

    @pl.when(shared)
    def _():
        out_ref[...] += _dot(*(
            jnp.where(mask, ref[...].astype(f32), 0).astype(ref.dtype)
            for ref in (rows_ref, d_ref)), (0, 0))


def _rows_product(rows, w, visits, transposed: bool):
    """``rows`` (R, C) by ``w`` (H, K, N): (R, N) float32 where ``C`` is
    ``K``, and against ``w`` transposed (R, K) in ``rows``' type where ``C``
    is ``N`` (the gradient to rows of that type)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (r, c), (_, k, n) = rows.shape, w.shape
    if transposed:
        wide, tile, out_dtype = k, col_tile(k), rows.dtype
        block, at = (tile, n), lambda j, v, g, t, o: (g[v], j, 0)
    else:
        wide, tile, out_dtype = n, col_tile(n), jnp.float32
        block, at = (k, tile), lambda j, v, g, t, o: (g[v], 0, j)
    return pl.pallas_call(
        functools.partial(_rows_kernel, (1, 1) if transposed else (1, 0)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(wide // tile, visits[3]),
            in_specs=[pl.BlockSpec((ROW_TILE, c),
                                   lambda j, v, g, t, o: (t[v], 0)),
                      pl.BlockSpec((None,) + block, at)],
            out_specs=pl.BlockSpec((ROW_TILE, tile),
                                   lambda j, v, g, t, o: (t[v], j)),
            scratch_shapes=[pltpu.VMEM(block, rows.dtype)]),
        out_shape=jax.ShapeDtypeStruct((r, wide), out_dtype),
        compiler_params=compiler_params(VMEM_LIMIT_BYTES), name="grouped_rows",
    )(*visits[:3], rows, w)


def _weights_product(rows, d, visits, groups: int):
    """``d_w`` (H, K, N) float32 of ``rows`` (R, K) and ``d`` (R, N)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (_, k), n = rows.shape, d.shape[1]
    tile = col_tile(k)
    return pl.pallas_call(
        _weights_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(k // tile, visits[3]),
            in_specs=[pl.BlockSpec((ROW_TILE, tile),
                                   lambda j, v, g, t, o: (t[v], j)),
                      pl.BlockSpec((ROW_TILE, n),
                                   lambda j, v, g, t, o: (t[v], 0))],
            out_specs=pl.BlockSpec((None, tile, n),
                                   lambda j, v, g, t, o: (g[v], j, 0))),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        compiler_params=compiler_params(VMEM_LIMIT_BYTES),
        name="grouped_weights",
    )(*visits[:3], rows, d)


def _product_fwd(rows, w, visits, scope):
    return (jitted(_rows_product, (3,))(rows, w, visits, False),
            (rows, w, visits))


def _product_bwd(scope, saved, d):
    import jax

    rows, w, visits = saved
    with jax.named_scope(scope):
        d = d.astype(rows.dtype)
        d_rows = jitted(_rows_product, (3,))(d, w, visits, True)
        d_w = jitted(_weights_product, (3,))(
            rows, d, visits, w.shape[0]).astype(w.dtype)
    return d_rows, d_w, tuple(np.zeros(a.shape, jax.dtypes.float0)
                              for a in visits)


@functools.lru_cache(maxsize=None)
def _product():
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def product(rows, w, visits, scope):
        return _product_fwd(rows, w, visits, scope)[0]

    product.defvjp(_product_fwd, _product_bwd)
    return product


def grouped_product(rows, w, visits, scope: str):
    """``jax.lax.ragged_dot(rows, w.astype(rows.dtype), group_sizes,
    preferred_element_type=float32)`` on the kernels, for shapes that
    :func:`fits` admits and ``visits = plan(group_sizes, rows.shape[0])``:
    (R, N) float32 whose rows of no group hold anything (the module's
    docstring).  The gradients are ``rows``' type for the rows and float32
    for ``w`` (any type: it is cast to ``rows``' for the products); the
    backward pass opens the ``jax.named_scope`` ``scope`` itself (a custom
    backward pass is traced outside the scope its forward pass ran under)."""
    return _product()(rows, w, visits, scope)
