"""The depthwise causal convolution of ``packed_rows.causal_conv`` as Pallas
TPU kernels: one pass over the row a direction.  A (rows x channels) tile of
the operand is read once in its own type, the ``K - 1`` rows before it come
from a second small block of the same array, the taps and the bias stay
resident, and the result is written once in the type the caller names — no
shifted float32 copy of the row is written to memory or read from it.

For a token ``t`` at index ``pos_t`` inside its document (clipped at
``K - 1``: :func:`tap_positions`)::

    u_t = b + sum_{j < K, j <= pos_t} w[K-1-j] * x_{t-j}      (float32)
    y_t = gate_t * act(u_t)        act: SiLU or nothing; gate: optional

with ``x`` the operand or its product with a second array of its shape
(``times``: LFM2's two gates), taken in float32.  What the callers apply
straight to the convolution belongs inside the pass: a custom call is a
barrier to the compiler's fusions, and a float32 (T, C) result written and
read again would cost what the pass saves.

Two kernels under one ``jax.custom_vjp`` (:func:`fused_conv`), grid (tile of
channels, tile of rows), the rows innermost:

- ``conv_forward``: the tile with the ``HALO`` rows before it on top, each
  tap a rotation along the rows (``pltpu.roll``) under the documents' mask;
- ``conv_backward``: the tiles of a row **last to first**.  ``u`` and the
  activation's derivative are made again from the operand (no float32
  residual is kept), ``du`` is written nowhere: the gradient to the operand
  is the anti-causal sum over the ``K - 1`` rows after a token, whose
  ``du`` the tile after this one left in scratch (its first ``HALO`` rows
  and their positions), and the taps' and the bias's gradients accumulate in
  a resident (8, channels) float32 block over the rows' axis (taps in rows
  0 to ``K - 1``, the bias in row 7).

Same arithmetic as the ``jnp`` form, float32 inside, the same roundings at
the same places; the two differ by the order of the sums over rows in the
taps' gradients and by SiLU's derivative written out.

``packed_rows.conv_runs_fused`` says when this runs
(``kernels.runs_fused`` of :func:`fits`); interpret mode
(``pltpu.force_tpu_interpret_mode``) runs it on the CPU for the tests.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

from tensorflowonspark_tpu.models.kernels import compiler_params, jitted
from tensorflowonspark_tpu.models.packed_rows import under

#: rows a tile and the widest tile of channels (whole rows of 128 lanes),
#: chosen on the chip at the published shapes (PERF.md section 6, PR 44,
#: holds every reading).  At 8,192 x 4,352 in bfloat16, value / value and
#: gradient, ms a call: 0.525 / 1.429 at 256 rows, 0.446 / 1.254 at 512,
#: 0.412 / 1.183 at 1,024 (0.382 / 1.781 at 2,048), where the ``jnp`` form
#: takes 1.558 / 5.851; the channels' tile hardly matters (4,352 = 17 x
#: 256 leaves it 256; at 4,096, 256 / 512 / 1,024 lanes read 1.259 / 1.277
#: / 1.26)
ROW_TILE = 1024
COL_TILE = 512
#: rows of the second block, the ones before a tile: a bfloat16 tile is 16
#: rows, and ``K - 1`` of them are read
HALO = 16
#: rows of the taps' and the bias's gradient block (a float32 tile)
ACC_ROWS = 8
VMEM_LIMIT_BYTES = 64 * 2 ** 20


class Form(NamedTuple):
    """What a call's kernels are specialised to (static)."""
    taps: int
    silu: bool
    times: bool     # the operand is a product of two arrays
    bias: bool      # a (C,) bias; else the number ``shift``
    gate: bool      # the result is multiplied by a third array
    shift: float
    out: object     # the result's type


def col_tile(c: int) -> int:
    """The largest divisor of ``c`` in whole rows of 128 lanes that is at
    most :data:`COL_TILE` (4,352 -> 256, 4,096 and 2,048 -> 512)."""
    return max(n for n in range(128, min(c, COL_TILE) + 1, 128) if c % n == 0)


def fits(t: int, c: int, taps: int, rows: int = ROW_TILE) -> bool:
    """Whether the kernels' tiles exist at these shapes: the channels are
    whole rows of 128 lanes, the row of tokens whole tiles of ``rows``, and
    the taps reach no further back than a halo and leave the bias its row
    of the gradient block."""
    return (c > 0 and c % 128 == 0 and t > 0 and t % rows == 0
            and rows % HALO == 0 and 2 <= taps < ACC_ROWS)


def tap_positions(seg, taps: int):
    """(T, 1) int32: every token's index inside its document, clipped at
    ``taps - 1`` — the taps that reach back inside the document (documents
    are contiguous: ``seg[t-j] == seg[t]`` says the tokens between are the
    document's too)."""
    import jax.numpy as jnp

    pos = jnp.zeros(seg.shape, jnp.int32)
    for j in range(1, min(taps, seg.shape[0])):
        pos = pos + jnp.pad(seg[:-j] == seg[j:], (j, 0)).astype(jnp.int32)
    return pos[:, None]


def _f32(ref):
    import jax.numpy as jnp

    return ref[...].astype(jnp.float32)


def _take(refs: list, wanted: bool):
    return refs.pop(0) if wanted else None


def _shared(form: Form, refs) -> tuple:
    """``((x, its halo, times, its halo, w, b, pos, gate), the rest)`` of a
    kernel's refs, in :func:`_operands`' order; None what ``form`` lacks."""
    refs = list(refs)
    x, halo = refs.pop(0), refs.pop(0)
    times, times_halo = _take(refs, form.times), _take(refs, form.times)
    w, b = refs.pop(0), _take(refs, form.bias)
    return (x, halo, times, times_halo, w, b, refs.pop(0),
            _take(refs, form.gate)), refs


def _operand(x_ref, halo_ref, times_ref, times_halo_ref):
    """(HALO + R, C) float32: the rows before the tile, then the tile."""
    import jax.numpy as jnp

    x = jnp.concatenate([_f32(halo_ref), _f32(x_ref)], axis=0)
    if times_ref is not None:
        x = x * jnp.concatenate([_f32(times_halo_ref), _f32(times_ref)],
                                axis=0)
    return x


def _reaching(pos, j: int, shape):
    """(N, C) bool: the rows whose tap ``j`` stays inside the document."""
    import jax.numpy as jnp

    return jnp.broadcast_to(pos, shape) >= j


def _windows(ext, pos, taps: int) -> list:
    """``[x_{t-j} where tap j reaches it, else 0 for j < taps]`` over the
    rows of ``ext`` (N, C); ``pos`` (N, 1).  A rotation wraps: the first
    ``taps - 1`` rows are the halo's, which nothing reads."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    return [ext] + [
        jnp.where(_reaching(pos, j, ext.shape), pltpu.roll(ext, j, 0), 0.0)
        for j in range(1, taps)]


def _convolved(form: Form, windows: list, w_ref, b_ref):
    """``u`` over the windows' rows, summed in the ``jnp`` form's order."""
    k = form.taps
    u = windows[0] * w_ref[k - 1:k, :] + (
        b_ref[...] if form.bias else form.shift)
    for j in range(1, k):
        u = u + windows[j] * w_ref[k - 1 - j:k - j, :]
    return u


def _sigmoid(u):
    import jax.numpy as jnp

    return 1.0 / (1.0 + jnp.exp(-u))


def _positions(pos_ref):
    """(HALO + R, 1): the tile's positions under a halo's that nothing
    reads."""
    import jax.numpy as jnp

    return jnp.concatenate(
        [jnp.zeros((HALO, 1), jnp.int32), pos_ref[...]], axis=0)


def _forward_kernel(form: Form, *refs):
    (x_ref, halo_ref, times_ref, times_halo_ref, w_ref, b_ref, pos_ref,
     gate_ref), (out_ref,) = _shared(form, refs)
    ext = _operand(x_ref, halo_ref, times_ref, times_halo_ref)
    y = _convolved(form, _windows(ext, _positions(pos_ref), form.taps),
                   w_ref, b_ref)[HALO:]
    if form.silu:
        y = y * _sigmoid(y)
    if form.gate:
        y = _f32(gate_ref) * y
    out_ref[...] = y.astype(out_ref.dtype)


def _backward_kernel(form: Form, *refs):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    (x_ref, halo_ref, times_ref, times_halo_ref, w_ref, b_ref, pos_ref,
     gate_ref), refs = _shared(form, refs)
    dy_ref, dx_ref = refs.pop(0), refs.pop(0)
    dtimes_ref, dgate_ref = _take(refs, form.times), _take(refs, form.gate)
    acc_ref, du_after, pos_after = refs
    k, r = form.taps, x_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _():        # the row's last tile: nothing comes after it
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)
        du_after[...] = jnp.zeros(du_after.shape, f32)
        pos_after[...] = jnp.zeros(pos_after.shape, jnp.int32)

    windows = _windows(_operand(x_ref, halo_ref, times_ref, times_halo_ref),
                       _positions(pos_ref), k)
    u = _convolved(form, windows, w_ref, b_ref)[HALO:]
    du = _f32(dy_ref)
    if form.silu:
        s = _sigmoid(u)
        act, slope = u * s, s * (1.0 + u * (1.0 - s))
    else:
        act, slope = u, None
    if form.gate:
        dgate_ref[...] = (du * act).astype(dgate_ref.dtype)
        du = du * _f32(gate_ref)
    if slope is not None:
        du = du * slope
    for j in range(k):
        acc_ref[k - 1 - j:k - j, :] += jnp.sum(
            du * windows[j][HALO:], axis=0, keepdims=True)
    if form.bias:
        acc_ref[ACC_ROWS - 1:, :] += jnp.sum(du, axis=0, keepdims=True)
    # dx_s = sum_j w[K-1-j] du_{s+j} where tap j of token s+j reaches s
    ext = jnp.concatenate([du, du_after[...]], axis=0)
    pos = jnp.concatenate([pos_ref[...], pos_after[...]], axis=0)
    dx = ext * w_ref[k - 1:k, :]
    for j in range(1, k):
        dx = dx + pltpu.roll(
            jnp.where(_reaching(pos, j, ext.shape), ext, 0.0),
            r + HALO - j, 0) * w_ref[k - 1 - j:k - j, :]
    dx = dx[:r]
    if form.times:
        dtimes_ref[...] = (dx * _f32(x_ref)).astype(dtimes_ref.dtype)
        dx = dx * _f32(times_ref)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    du_after[...] = du[:HALO]
    pos_after[...] = pos_ref[:HALO, :]


def _specs(form: Form, t: int, c: int, rows: int, backward: bool):
    """``(grid, tile, in_specs)``: the grid, the BlockSpec of a (rows x
    channels) tile and those of the operands both kernels share, in the
    kernels' order.  Backward, the tiles of a row come last to first."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    cols, n = col_tile(c), t // rows
    at = (lambda i: n - 1 - i) if backward else (lambda i: i)
    tile = pl.BlockSpec((rows, cols), lambda j, i: (at(i), j))
    # the HALO rows before the tile (the row's first tile: its own first
    # rows, which every tap's mask leaves out)
    halo = pl.BlockSpec((HALO, cols), lambda j, i: (
        jnp.maximum(at(i) * (rows // HALO) - 1, 0), j))
    resident = [pl.BlockSpec((form.taps, cols), lambda j, i: (0, j))] + (
        [pl.BlockSpec((1, cols), lambda j, i: (0, j))] if form.bias else [])
    positions = pl.BlockSpec((rows, 1), lambda j, i: (at(i), 0))
    return ((c // cols, n), tile,
            [tile, halo] * (2 if form.times else 1) + resident
            + [positions] + ([tile] if form.gate else []))


def _operands(form: Form, x, times, w, b, pos, gate) -> list:
    return ([x, x] + ([times, times] if form.times else []) + [w]
            + ([b.reshape(1, -1)] if form.bias else []) + [pos]
            + ([gate] if form.gate else []))


def _forward(form: Form, rows: int, x, times, w, b, pos, gate):
    import jax
    from jax.experimental import pallas as pl

    grid, tile, in_specs = _specs(form, *x.shape, rows, backward=False)
    return pl.pallas_call(
        functools.partial(_forward_kernel, form), grid=grid,
        in_specs=in_specs, out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, form.out),
        compiler_params=compiler_params(VMEM_LIMIT_BYTES), name="conv_forward",
    )(*_operands(form, x, times, w, b, pos, gate))


def _backward(form: Form, rows: int, x, times, w, b, pos, gate, dy):
    """``(dx, dtimes or None, dgate or None, acc)``: the gradients to the
    (T, C) operands in their types and the (8, C) float32 block of the
    taps' and the bias's."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (t, c), f32 = x.shape, jnp.float32
    grid, tile, in_specs = _specs(form, t, c, rows, backward=True)
    cols = tile.block_shape[1]
    like = [a for a in (x, times, gate) if a is not None]
    outs = pl.pallas_call(
        functools.partial(_backward_kernel, form), grid=grid,
        in_specs=in_specs + [tile],
        out_specs=[tile] * len(like) + [
            pl.BlockSpec((ACC_ROWS, cols), lambda j, i: (0, j))],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in like] + [
            jax.ShapeDtypeStruct((ACC_ROWS, c), f32)],
        scratch_shapes=[pltpu.VMEM((HALO, cols), f32),
                        pltpu.VMEM((HALO, 1), jnp.int32)],
        compiler_params=compiler_params(VMEM_LIMIT_BYTES),
        name="conv_backward",
    )(*_operands(form, x, times, w, b, pos, gate), dy)
    outs = list(outs)
    return (outs.pop(0), _take(outs, form.times), _take(outs, form.gate),
            outs.pop(0))


def _conv_fwd(x, times, w, b, pos, gate, form, rows, scopes):
    return (jitted(_forward, (0, 1))(form, rows, x, times, w, b, pos, gate),
            (x, times, w, b, pos, gate))


def _conv_bwd(form, rows, scopes, saved, dy):
    import jax

    x, times, w, b, pos, gate = saved
    with under(scopes):
        dx, dtimes, dgate, acc = jitted(_backward, (0, 1))(
            form, rows, x, times, w, b, pos, gate, dy)
        dw = acc[:form.taps].astype(w.dtype)
        db = (acc[ACC_ROWS - 1].reshape(b.shape).astype(b.dtype)
              if form.bias else None)
    return (dx, dtimes, dw, db, np.zeros(pos.shape, jax.dtypes.float0),
            dgate)


@functools.lru_cache(maxsize=None)
def _conv():
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
    def conv(x, times, w, b, pos, gate, form, rows, scopes):
        return _conv_fwd(x, times, w, b, pos, gate, form, rows, scopes)[0]

    conv.defvjp(_conv_fwd, _conv_bwd)
    return conv


def fused_conv(x, w, b, seg, *, times=None, gate=None, silu: bool = False,
               out=None, scopes: tuple = (), rows: int = ROW_TILE):
    """``packed_rows.causal_conv`` on the kernels, for shapes that
    :func:`fits` admits: same arguments (``b`` a (C,) array or a Python
    number), the result in ``out`` (float32 where not given).  The gradients
    to ``x``, ``times`` and ``gate`` come in their operands' types, the
    taps' and the bias's in theirs, summed in float32.  The backward pass
    runs under the ``jax.named_scope``s ``scopes``; ``rows`` is the rows a
    tile."""
    import jax.numpy as jnp

    bias = not isinstance(b, (int, float))
    form = Form(taps=w.shape[0], silu=bool(silu), times=times is not None,
                bias=bias, gate=gate is not None,
                shift=0.0 if bias else float(b),
                out=jnp.dtype(out or jnp.float32))
    return _conv()(x, times, w.astype(jnp.float32),
                   b.astype(jnp.float32) if bias else None,
                   tap_positions(seg, form.taps), gate, form, int(rows),
                   tuple(scopes))
