"""The mathematics the decoders trained on packed rows have in common
(``granite_hybrid``, ``mla_moe``, ``lfm2_moe``, ``kimi_linear``,
``mellum_moe``, ``afmoe``; ``packed_decoder`` holds their skeleton): the RMS
norm, the product with operands in the activations' type, the SwiGLU
feed-forward, the positions inside documents and the rotary embedding at
them (plain or YaRN frequencies), the depthwise causal convolution that
stops at a document's first token, causal attention inside documents a block
of queries at a time (the values at their own width; under a sliding window
the blocks behind it not visited), the grouped-query layer (with an output
gate or without, rotated or position-free) and latent attention over it, and
the next-token cross-entropy a block of tokens at a time.  A packed row is
``T`` tokens with segment ids ``s`` (the document's number inside the row;
documents are contiguous and their ids differ).  One implementation of each
piece, each called by two models or more (:func:`causal_conv`: granite's
state-space mixers, LFM2's gated short convolutions and Kimi Linear's
delta-rule mixers; :func:`rope` and :func:`document_positions`: GLM's latent
attention and the grouped-query layer; :func:`grouped_query_attention`:
LFM2's, with plain RoPE and no window, Mellum's, with a window and a
rotation by layer type, and ``afmoe``'s, gated, with a window and RoPE in
its sliding layers and neither in its full ones; :func:`latent_attention`:
GLM's, with a query latent and RoPE, and Kimi Linear's, with neither): what
is measured on one model's cell is what the others run.

Attention and the convolution are each one algorithm with two executions
(:func:`document_attention`, :func:`causal_conv`): on a TPU at shapes that
fill their tiles (a head in whole rows of 128 lanes — GLM's 20 x 256 and
Mellum's and ``afmoe``'s 32/4 x 128, not granite's and LFM2's 32/8 x 64 nor
Kimi Linear's
keys of 192 beside values of 128 —; the channels in whole rows of lanes and the row in whole tiles:
the published 8,192 x 4,352, x 4,096 and x 2,048) the Pallas kernels of
``attention_pallas`` and ``conv_pallas``, anywhere else (``Config.tiny()``,
the tests) the ``jnp`` forms in this file, which are also the kernels'
oracles.  :func:`attention_runs_fused` and :func:`conv_runs_fused` are the
rules (``kernels.runs_fused``), and the models' steps count which applied
(``attention_fused_steps_total`` / ``attention_plain_steps_total``,
``conv_fused_steps_total`` / ``conv_plain_steps_total``).  The attention
kernels' loops stop at a document's edge, so a step on them follows its
rows (``attention_blocks_visited_total`` of
``attention_blocks_reached_total``); the ``jnp`` form visits every block the
shapes and the window reach.

What a step of packed rows adds to the program's counters from its host
batch (:func:`row_counters`) and the zoo's example rows (:func:`example_rows`)
are here too: host code, one copy for the six.

JAX is imported where it is used, as in the models.
"""

from __future__ import annotations

import functools

import numpy as np

from tensorflowonspark_tpu.models.kernels import runs_fused, step_counters


def rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                     keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def mm(spec, a, b, dtype, out=None):
    """A product with operands in ``dtype``, accumulated in float32."""
    import jax.numpy as jnp

    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32
                      ).astype(out or dtype)


def block(total: int, want: int) -> int:
    """The largest divisor of ``total`` that is at most ``want``."""
    return next(b for b in range(min(want, total), 0, -1) if total % b == 0)


def attention_runs_fused(t: int, hd: int, vd: int | None = None) -> bool:
    """Whether :func:`document_attention` runs on the kernels of
    ``attention_pallas`` on a row of ``t`` tokens, heads of ``hd`` and
    values of ``vd`` (``hd`` where not given): ``kernels.runs_fused`` of
    ``attention_pallas.fits`` (whole rows of 128 lanes, whole blocks of
    tokens; any number of heads), and the values as wide as the keys (the
    kernels take one width)."""
    from tensorflowonspark_tpu.models import attention_pallas

    return runs_fused(attention_pallas, t, hd, when=vd in (None, hd))


#: what :func:`swiglu` names (``checkpoint_name``): the results of its two
#: wide products, in the activations' type.  A model that lists them under
#: ``Decoder.saved`` makes them once a step: ``silu(gate) * up`` is made
#: again from the kept pair, an element-wise pass, and the down projection's
#: result is nobody's residual.  A name no policy lists lowers to nothing.
SWIGLU_SAVED = ("ffn_gate", "ffn_up")


def swiglu(h, w_gate, w_up, w_down):
    """``W_down (silu(h W_gate) * (h W_up))`` on (T, D) tokens, the two
    wide products' results named :data:`SWIGLU_SAVED`."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    dtype = h.dtype
    gate = checkpoint_name(mm("td,df->tf", h, w_gate, dtype), SWIGLU_SAVED[0])
    up = checkpoint_name(mm("td,df->tf", h, w_up, dtype), SWIGLU_SAVED[1])
    act = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32))
    return mm("tf,fd->td", act, w_down, dtype)


def document_positions(seg):
    """(T,) int32: the index of every token inside its document."""
    import jax
    import jax.numpy as jnp

    at = jnp.arange(seg.shape[0], dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
    return at - jax.lax.cummax(jnp.where(first, at, 0))


def rope_frequencies(theta: float, half: int):
    """(half,) float32: plain RoPE's ``theta ** (-i / half)``."""
    import jax.numpy as jnp

    return theta ** (-jnp.arange(half, dtype=jnp.float32) / half)


def yarn_ramp(half: int, theta: float, original: int, beta_fast: float,
              beta_slow: float) -> tuple:
    """``(low, high, ramp)`` of YaRN's blend (arXiv:2309.00071, as
    ``transformers``' ``_compute_yarn_parameters`` writes it): frequency
    ``i`` turns ``c(r)`` times over the ``original`` positions where ``c(r)
    = 2 half ln(original / (2 pi r)) / (2 ln theta)``; ``low =
    floor(c(beta_fast))`` and ``high = ceil(c(beta_slow))`` inside ``[0, 2
    half - 1]``, ``ramp`` (half,) ``clip((i - low) / (high - low), 0, 1)``:
    0 where a frequency is kept, 1 where it is divided by the factor."""
    import math

    def turns(r):
        return (2 * half * math.log(original / (2 * math.pi * r))
                / (2 * math.log(theta)))

    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), 2 * half - 1)
    span = (high - low) or 0.001
    return low, high, np.clip((np.arange(half) - low) / span, 0.0,
                              1.0).astype(np.float32)


def yarn_frequencies(theta: float, half: int, factor: float, original: int,
                     beta_fast: float = 32.0, beta_slow: float = 1.0):
    """(half,) float32: plain RoPE's frequencies, those that turn fewer
    than ``beta_slow`` times over the ``original`` positions divided by
    ``factor``, those that turn more than ``beta_fast`` times kept, the ones
    between blended (:func:`yarn_ramp`).  Static: the same at every
    length."""
    ramp = yarn_ramp(half, theta, original, beta_fast, beta_slow)[2]
    return rope_frequencies(theta, half) * ((1.0 - ramp) + ramp / factor)


def rope(x, pos, freq, factor: float = 1.0):
    """Rotary embedding over the last axis of ``x`` (T, ..., R), the two
    halves rotated (``[a | b] -> [a cos - b sin | b cos + a sin]``), at the
    positions ``pos`` (T,) by the frequencies ``freq`` (R / 2,), cosine and
    sine times ``factor`` (YaRN's attention factor; 1: plain); float32
    inside."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    angle = pos.astype(jnp.float32)[:, None] * freq
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def conv_runs_fused(t: int, c: int, taps: int, b=0.0) -> bool:
    """Whether :func:`causal_conv` runs on the kernels of ``conv_pallas``
    on a row of ``t`` tokens and ``c`` channels with ``taps`` taps and the
    bias ``b``: ``kernels.runs_fused`` of ``conv_pallas.fits`` (whole rows
    of 128 lanes, whole tiles of tokens, the taps inside the halo), and the
    bias a (C,) array or a Python number."""
    from tensorflowonspark_tpu.models import conv_pallas

    return runs_fused(conv_pallas, t, c, taps, when=isinstance(
        b, (int, float)) or np.shape(b) == (c,))


def causal_conv(xbc, w, b, seg, *, times=None, gate=None, silu: bool = False,
                out=None, scopes: tuple = ()):
    """Depthwise causal convolution over a packed row: ``u_t = b + sum_j
    w[K-1-j] * x_{t-j}`` over the taps ``j < K`` whose token ``t-j`` is in
    ``t``'s document, and what its callers apply straight to it: ``y =
    gate * silu(u)``.  ``xbc`` (T, C), ``w`` (K, C), ``b`` (C,) or a number,
    ``seg`` (T,).  ``x`` is ``xbc`` or, where ``times`` (T, C) is given,
    their product; ``silu`` and ``gate`` (T, C) are each left out where not
    given; everything is float32 inside and the result is cast once, to
    ``out`` (float32 where not given).

    One algorithm, two executions (:func:`conv_runs_fused`): on a TPU, at
    shapes that fill its tiles, the kernels of ``conv_pallas`` read a tile
    of the row once, keep the ``K - 1`` rows before it and write the result
    once, forward and backward (the backward pass under the
    ``jax.named_scope``s ``scopes``, the caller's: the forward pass runs
    under the caller's own); anywhere else the ``jnp`` form below runs —
    ``K`` shifted sums that JAX differentiates —, which is also the kernels'
    oracle."""
    import jax
    import jax.numpy as jnp

    taps, t = w.shape[0], xbc.shape[0]
    if conv_runs_fused(t, xbc.shape[1], taps, b):
        from tensorflowonspark_tpu.models import conv_pallas

        return conv_pallas.fused_conv(xbc, w, b, seg, times=times, gate=gate,
                                      silu=silu, out=out, scopes=scopes)
    x32 = xbc.astype(jnp.float32)
    if times is not None:
        x32 = x32 * times.astype(jnp.float32)
    y = x32 * w[taps - 1] + b
    for j in range(1, min(taps, t)):
        back = jnp.pad(x32[:-j], ((j, 0), (0, 0)))
        same = jnp.pad(seg[:-j], (j, 0), constant_values=-1) == seg
        y = y + jnp.where(same[:, None], back, 0.0) * w[taps - 1 - j]
    if silu:
        y = jax.nn.silu(y)
    if gate is not None:
        y = gate.astype(jnp.float32) * y
    return y.astype(out or jnp.float32)


def _scores(qb, kb, sq, sk, pq, pk, scale, dtype, window=None):
    """One block of queries against one block of keys: the scaled scores
    (kv, rep, i, j) and the mask ``j <= i and same document`` and, under a
    ``window``, ``i - j < window``."""
    import jax.numpy as jnp

    s = mm("ikrd,jkd->krij", qb, kb, dtype, out=jnp.float32) * scale
    mask = (pq[:, None] >= pk[None, :]) & (sq[:, None] == sk[None, :])
    if window is not None:
        mask = mask & (pq[:, None] - pk[None, :] < window)
    return s, mask


def first_key_block(i, size: int, window):
    """The first block of ``size`` keys that a block of ``size`` queries
    visits, ``i`` the queries' block: 0, or under a ``window`` the block of
    the key ``window - 1`` before the block's first query.  From the shapes
    and the window alone."""
    import jax.numpy as jnp

    if window is None:
        return 0
    return jnp.maximum(i * size - (window - 1), 0) // size


def _attend_fwd(q, k, v, seg, scale, size, dtype, window=None):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    t, kv, rep, hd = q.shape
    vd = v.shape[-1]
    n = t // size
    kb, vb = k.reshape(n, size, kv, hd), v.reshape(n, size, kv, vd)
    segb, posb = seg.reshape(n, size), jnp.arange(t).reshape(n, size)

    def block_(args):
        qb, sq, pq, i = args

        def keys(j, carry):
            m, l, acc = carry
            s, mask = _scores(qb, kb[j], sq, segb[j], pq, posb[j], scale,
                              dtype, window)
            m_new = jnp.maximum(m, jnp.max(jnp.where(mask, s, -1e30), -1))
            p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
            fade = jnp.exp(m - m_new)
            return (m_new, l * fade + p.sum(-1), acc * fade[..., None]
                    + mm("krij,jkd->krid", p, vb[j], dtype, out=f32))

        m, l, acc = jax.lax.fori_loop(
            first_key_block(i, size, window), i + 1, keys, (
                jnp.full((kv, rep, size), -1e30, f32),
                jnp.zeros((kv, rep, size), f32),
                jnp.zeros((kv, rep, size, vd), f32)))
        return ((acc / l[..., None]).transpose(2, 0, 1, 3).astype(dtype),
                m + jnp.log(l))

    out, lse = jax.lax.map(block_, (
        q.reshape(n, size, kv, rep, hd), segb, posb, jnp.arange(n)))
    return out.reshape(t, kv, rep, vd), lse


#: what attention's forward rule names (``checkpoint_name``) of a layer: its
#: output and its log-sum-exp, the two things its backward pass reads that
#: only the forward blocks make.  A recomputed layer keeps them
#: (``packed_decoder.run_layer``) and runs those blocks once a step.
ATTENTION_SAVED = ("attention_out", "attention_lse")


def name_saved(out, lse):
    """``(out, lse)`` under the names :data:`ATTENTION_SAVED`."""
    from jax.ad_checkpoint import checkpoint_name

    return tuple(map(checkpoint_name, (out, lse), ATTENTION_SAVED))


def under(scopes):
    """The ``jax.named_scope``s ``scopes``, opened one inside the other: a
    custom backward pass is traced outside the scopes its forward pass was
    called under, and opens them again itself."""
    import contextlib

    import jax

    stack = contextlib.ExitStack()
    for scope in scopes:
        stack.enter_context(jax.named_scope(scope))
    return stack


def _attend_bwd(scale, size, dtype, scopes, window, saved, d_out):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    q, k, v, seg, out, lse = saved
    t, kv, rep, hd = q.shape
    vd = v.shape[-1]
    n = t // size
    kb, vb = k.reshape(n, size, kv, hd), v.reshape(n, size, kv, vd)
    segb, posb = seg.reshape(n, size), jnp.arange(t).reshape(n, size)
    delta = jnp.sum(d_out.astype(f32) * out.astype(f32), axis=-1)

    def block_(carry, args):
        qb, dob, sq, pq, i, lse_b, delta_b = args

        def keys(j, inner):
            dq, dk, dv = inner
            s, mask = _scores(qb, kb[j], sq, segb[j], pq, posb[j], scale,
                              dtype, window)
            p = jnp.where(mask, jnp.exp(s - lse_b[..., None]), 0.0)
            dp = mm("ikrd,jkd->krij", dob, vb[j], dtype, out=f32)
            ds = p * (dp - delta_b[..., None]) * scale
            return (dq + mm("krij,jkd->ikrd", ds, kb[j], dtype, out=f32),
                    dk.at[j].add(mm("krij,ikrd->jkd", ds, qb, dtype,
                                    out=f32)),
                    dv.at[j].add(mm("krij,ikrd->jkd", p, dob, dtype,
                                    out=f32)))

        dq, dk, dv = jax.lax.fori_loop(
            first_key_block(i, size, window), i + 1, keys,
            (jnp.zeros(qb.shape, f32),) + carry)
        return (dk, dv), dq.astype(dtype)

    with under(scopes):
        (dk, dv), dq = jax.lax.scan(
            block_, (jnp.zeros(kb.shape, f32), jnp.zeros(vb.shape, f32)), (
                q.reshape(n, size, kv, rep, hd),
                d_out.reshape(n, size, kv, rep, vd), segb, posb,
                jnp.arange(n), lse,
                delta.reshape(n, size, kv, rep).transpose(0, 2, 3, 1)))
    return (dq.reshape(q.shape), dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype),
            np.zeros(seg.shape, jax.dtypes.float0))


@functools.lru_cache(maxsize=None)
def _attend():
    """The blocked attention with its own backward pass (made once: the
    module imports JAX only when it is used)."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
    def attend(q, k, v, seg, scale, size, dtype, scopes, window=None):
        return _attend_fwd(q, k, v, seg, scale, size, dtype, window)[0]

    def fwd(q, k, v, seg, scale, size, dtype, scopes, window=None):
        out, lse = name_saved(*_attend_fwd(q, k, v, seg, scale, size, dtype,
                                           window))
        return out, (q, k, v, seg, out, lse)

    attend.defvjp(fwd, _attend_bwd)
    return attend


def document_attention(q, k, v, seg, scale: float, size: int, dtype,
                       scopes: tuple = ("attention",), window=None):
    """Causal attention inside documents over one packed row, blocks of
    queries against blocks of keys with a running softmax: a block of
    queries visits the blocks of keys up to its own, so no score above the
    diagonal is ever made and none is held beyond its block.  The backward
    pass recomputes each block's probabilities from the saved log-sum-exp,
    under the ``jax.named_scope``s ``scopes`` (the caller's: the forward
    pass runs under the caller's own).  ``q`` (T, kv, rep, hd), ``k`` (T,
    kv, hd), ``v`` (T, kv, vd) — values at their own width, which a
    latent-attention layout may make narrower than its keys —, ``seg``
    (T,), the documents contiguous and their ids different; returns (T, kv,
    rep, vd).

    ``window`` (a sliding-window layer's; None: none): a query also sees
    only the ``window`` keys up to its own (``i - j < window``, itself
    among them; places in the row, which inside a document differ as the
    places in it do), and **a block of queries starts at the first block of
    keys its window reaches** (:func:`first_key_block`): the blocks behind
    the window are not visited, forward or backward.

    One algorithm, two executions (:func:`attention_runs_fused`): on a TPU,
    where a head fills whole rows of lanes, the kernels of
    ``attention_pallas`` keep each score tile on the chip, forward and
    backward (they take keys and values of one width); anywhere else the
    ``jnp`` form above runs in blocks of ``size``, which is also the
    kernels' oracle.  **What a row costs differs between the two.**  In the
    ``jnp`` form the loops' bounds come from the shapes and the window
    alone: the blocks of another document are visited and masked, and every
    row costs the same whatever its documents are.  The kernels' loops also
    stop at a document's edge (``attention_pallas.first_key_blocks``,
    ``past_query_blocks``: a block none of whose pairs the mask admits is
    not visited, and adds exactly 0 where it is), so a row of many short
    documents costs less than a row that is one, which costs what the shapes
    say: there a step's time follows its rows (:func:`row_counters` counts
    by how much)."""
    if attention_runs_fused(q.shape[0], q.shape[-1], v.shape[-1]):
        from tensorflowonspark_tpu.models import attention_pallas

        return attention_pallas.fused_attention(q, k, v, seg, scale, dtype,
                                                scopes, window=window)
    return _attend()(q, k, v, seg, scale, size, dtype, tuple(scopes), window)


#: what a gated grouped-query layer names (``checkpoint_name``) of its gate:
#: the fifth projection's result, in the activations' type, before the
#: sigmoid
GATE_SAVED = "attention_gate"


def grouped_query_attention(params, prefix: str, h, seg, pos, *, heads: int,
                            kv: int, hd: int, eps: float, size: int, freq,
                            factor: float = 1.0, window=None,
                            scopes: tuple = ("attention",),
                            inner: str | None = None, gate: bool = False):
    """Grouped-query attention on one row, every query and key head normed
    (one RMS scale of a head's width each: ``q_norm``, ``k_norm``) and then
    turned by :func:`rope` (``freq``, ``factor``) at the positions ``pos``
    or, where ``freq`` is None, left as they are (no positional encoding;
    ``pos`` is not read): ``h`` (T, D) -> (T, D) from ``wq``, ``wk``,
    ``wv``, ``wo`` under ``prefix``.  Query head ``i`` reads key head ``i //
    (heads / kv)``; the softmax is scaled by ``1 / sqrt(hd)`` and runs in
    :func:`document_attention` in blocks of ``size`` queries, under a
    ``window`` where the layer has one, and under the ``jax.named_scope``
    ``inner`` where the caller names the blocks apart from the
    projections (a model with layers of two masks).

    ``gate`` (a layout whose attention has an output gate): a fifth
    projection ``wg`` (D, heads hd) of ``h`` goes through a sigmoid, in
    float32, and multiplies attention's output element by element before
    ``wo``, under the ``jax.named_scope`` ``attention_gate``; the result is
    then ``(out, open)``, ``open`` the sum of the sigmoids (float32, no
    gradient: what the program's counters show of the gate)."""
    import math

    import jax
    import jax.numpy as jnp

    dtype, t, rep = h.dtype, h.shape[0], heads // kv
    q = mm("td,de->te", h, params[prefix + "wq"], dtype)
    k = mm("td,de->te", h, params[prefix + "wk"], dtype)
    v = mm("td,de->te", h, params[prefix + "wv"], dtype).reshape(t, kv, hd)
    def turn(x):
        return x if freq is None else rope(x, pos, freq, factor)

    with jax.named_scope("qk_norm_rope"):
        q = turn(rms(q.reshape(t, kv, rep, hd), params[prefix + "q_norm"],
                     eps))
        k = turn(rms(k.reshape(t, kv, hd), params[prefix + "k_norm"], eps))
    inner = (inner,) if inner else ()
    with under(inner):
        o = document_attention(q, k, v, seg, 1.0 / math.sqrt(hd), size,
                               dtype, tuple(scopes) + inner, window)
    o = o.reshape(t, heads * hd)
    if gate:
        from jax.ad_checkpoint import checkpoint_name

        with jax.named_scope("attention_gate"):
            g = jax.nn.sigmoid(checkpoint_name(
                mm("td,de->te", h, params[prefix + "wg"], dtype),
                GATE_SAVED).astype(jnp.float32))
            o = (o.astype(jnp.float32) * g).astype(dtype)
            opened = jax.lax.stop_gradient(jnp.sum(g))
    out = mm("te,ed->td", o, params[prefix + "wo"], dtype)
    return (out, opened) if gate else out


def latent_attention(params, prefix: str, h, seg, pos, *, heads: int,
                     nope: int, rope_dim: int, v_dim: int, kv_rank: int,
                     eps: float, size: int, q_rank=None, theta=None,
                     scopes: tuple = ("attention",)):
    """Multi-head latent attention (arXiv:2405.04434) on one row: ``h``
    (T, D) -> (T, D).  Keys and values are expanded from one normed latent
    of ``kv_rank`` a token (``kv_a``, ``kv_a_norm``, ``kv_b``); a head's key
    is its own ``nope`` numbers and ``rope_dim`` more that ``kv_a`` writes
    once for all heads, its value ``v_dim`` wide.  Two things are the
    caller's layout's: the queries come through a normed latent of
    ``q_rank`` (``q_a``, ``q_a_norm``, ``q_b``) or, where it is None, from
    one projection ``wq``; the shared ``rope_dim`` numbers of queries and
    keys are turned by RoPE(``theta``) at the positions ``pos`` or, where
    ``theta`` is None, are left as they are (no positional encoding; ``pos``
    is not read).  The softmax is scaled by ``(nope + rope_dim) ** -0.5``
    and runs in ``document_attention`` as ``kv`` = heads, ``rep`` = 1, in
    blocks of ``size`` queries; ``wo`` writes the heads back."""
    import jax
    import jax.numpy as jnp

    dtype, t = h.dtype, h.shape[0]
    with jax.named_scope("mla_project"):
        if q_rank is None:
            q = mm("td,de->te", h, params[prefix + "wq"], dtype)
        else:
            c_q = rms(mm("td,dr->tr", h, params[prefix + "q_a"], dtype),
                      params[prefix + "q_a_norm"], eps)
            q = mm("tr,re->te", c_q, params[prefix + "q_b"], dtype)
        q = q.reshape(t, heads, nope + rope_dim)
        kv_a = mm("td,dr->tr", h, params[prefix + "kv_a"], dtype)
        c_kv = rms(kv_a[:, :kv_rank], params[prefix + "kv_a_norm"], eps)
        kv = mm("tr,re->te", c_kv, params[prefix + "kv_b"], dtype).reshape(
            t, heads, nope + v_dim)
        k_rope = kv_a[:, kv_rank:]
        if theta is not None:
            freq = rope_frequencies(theta, rope_dim // 2)
            q = jnp.concatenate(
                [q[..., :nope], rope(q[..., nope:], pos, freq)], -1)
            k_rope = rope(k_rope, pos, freq)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_rope[:, None, :], (t, heads, rope_dim))], -1)
    o = document_attention(
        q[:, :, None, :], k, kv[..., nope:], seg,
        (nope + rope_dim) ** -0.5, size, dtype, scopes)
    return mm("te,ed->td", o.reshape(t, heads * v_dim),
              params[prefix + "wo"], dtype)


def loss_positions(seg, ahead: int = 1):
    """(T,) bool: position ``t`` is scored against the token ``ahead``
    places on where that token and every one between are ``t``'s
    document's."""
    import jax.numpy as jnp

    t = seg.shape[0]
    valid = jnp.arange(t) < t - ahead
    for k in range(1, ahead + 1):
        valid = valid & (jnp.roll(seg, -k) == seg)
    return valid


def blocked_cross_entropy(x, logits_fn, targets, valid, want: int):
    """Sum of the cross-entropies of ``logits_fn(x_t)`` (float32 logits)
    against ``targets_t`` over the positions ``valid``: the logits exist a
    block of at most ``want`` tokens at a time, and are made again in the
    backward pass.  ``x`` (T, D), ``targets`` and ``valid`` (T,)."""
    import jax
    import jax.numpy as jnp

    t = x.shape[0]
    size = block(t, want)

    def block_(args):
        xb, ub, vb = args
        logits = logits_fn(xb)
        picked = jnp.take_along_axis(logits, ub[:, None], axis=1)[:, 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        return jnp.sum(jnp.where(vb, nll, 0.0))

    sums = jax.lax.map(jax.checkpoint(block_), (
        x.reshape(t // size, size, -1), targets.reshape(-1, size),
        valid.reshape(-1, size)))
    return jnp.sum(sums)


def row_counters(segment_ids, head_dim: int, windows: tuple = (None,),
                 v_head_dim: int | None = None,
                 conv: tuple | None = None) -> dict:
    """What one step of packed rows adds to the program's counters, whatever
    the model.  From its host batch's segment ids (B, T): tokens, tokens
    that bear a loss (the next token is the same document's) and documents
    (runs of one segment id).  From the rules its trace applied, a pair
    each (``kernels.step_counters``): attention's
    (:func:`attention_runs_fused`; ``windows``: the window of every
    attention layer the step runs, None where a layer has none, empty for
    a model with no attention layer; ``v_head_dim``: its values' width
    where it is not ``head_dim``) and, for a model that calls
    :func:`causal_conv` (``conv``: its (channels, taps); None: the pair is
    left out), the convolution's.

    And how far the attention kernels' loops followed the documents:
    ``attention_blocks_visited_total``, the (block of queries, block of
    keys) pairs a head's two kernels visit on these rows, forward and
    backward at the kernels' own tiles, summed over the layers each at its
    own window, of ``attention_blocks_reached_total``, what the shapes and
    the windows alone reach (``attention_pallas.visited`` with the ids and
    without: the arithmetic the kernels' operands are made by).  Equal on
    rows that are one document each; both 0 where the ``jnp`` form runs,
    whose loops visit every block reached."""
    from tensorflowonspark_tpu.models import attention_pallas

    seg = np.asarray(segment_ids)
    same = seg[:, 1:] == seg[:, :-1]
    fused = bool(windows) and attention_runs_fused(
        seg.shape[1], head_dim, v_head_dim)
    visited = reached = 0
    for window in set(windows) if fused else ():
        blocks = (seg.shape[1], attention_pallas.FORWARD_BLOCKS,
                  attention_pallas.BACKWARD_BLOCKS, window)
        layers = windows.count(window)
        visited += layers * sum(attention_pallas.visited(*blocks, seg))
        reached += layers * seg.shape[0] * sum(
            attention_pallas.visited(*blocks))
    counts = {"lm_tokens_total": int(seg.size),
              "lm_loss_tokens_total": int(same.sum()),
              "lm_documents_total": int(seg.shape[0] + (~same).sum()),
              **step_counters("attention", fused, bool(windows)),
              "attention_blocks_visited_total": visited,
              "attention_blocks_reached_total": reached}
    if conv is not None:
        counts.update(step_counters(
            "conv", conv_runs_fused(seg.shape[1], *conv)))
    return counts


#: the ``jax.named_scope`` round a layer's blocks of scores, softmax and
#: values by the layer's published type, in a model with layers of two masks
BLOCKS_SCOPE = {"sliding_attention": "window_attention",
                "full_attention": "full_attention"}


def mask_pairs(segment_ids, window=None) -> int:
    """Query-key pairs a head's mask admits on the rows ``segment_ids`` (B,
    T): ``j <= i`` in the same document and, under a ``window``, ``i - j <
    window``.  A document of ``n`` tokens holds ``n (n + 1) / 2``, or ``w (w
    + 1) / 2 + (n - w) w`` where it is longer than the window."""
    seg = np.asarray(segment_ids)
    edge = np.ones((seg.shape[0], 1), bool)
    starts = np.flatnonzero(np.concatenate(
        [edge, seg[:, 1:] != seg[:, :-1]], axis=1).reshape(-1))
    n = np.diff(np.append(starts, seg.size)).astype(np.int64)
    w = n if window is None else np.minimum(n, window)
    return int(np.sum(w * (w + 1) // 2 + (n - w) * w))


def example_rows(vocab_size: int, batch_size: int, seed: int, t: int) -> dict:
    """``batch_size`` packed rows of ``t`` tokens, two documents each (the
    models' ``example_batch``)."""
    rng = np.random.RandomState(seed)
    cut = rng.randint(1, t, size=(batch_size, 1))
    return {"tokens": rng.randint(0, vocab_size,
                                  size=(batch_size, t)).astype(np.int32),
            "segment_ids": (np.arange(t)[None, :] >= cut).astype(np.int32)}
