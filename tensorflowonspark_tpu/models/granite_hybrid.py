"""Hybrid state-space / attention decoder (the ``granitemoehybrid`` layout
with no routed experts): Mamba-2 mixers, a NoPE GQA layer among them,
SwiGLU feed-forwards, Granite's four multipliers, trained on packed rows.

Published shape: ``ibm-granite/granite-4.0-h-micro`` ``config.json``; the
state-space mixer is Mamba-2's SSD (Dao & Gu, arXiv:2405.21060).  For a row
of tokens ``u`` with segment ids ``s`` (the document's number inside the
row; documents are contiguous and their ids differ)::

    x = embedding_multiplier * E[u]
    x = x + residual_multiplier * mixer_i(rms(x))          # every layer i
    x = x + residual_multiplier * W_down(silu(h W_gate) * (h W_up)), h = rms(x)
    logits = rms(x) @ E.T / logits_scaling                  # tied head

    mamba:      [z | xBC | dt] = h W_in
                xBC = silu(conv(xBC) + b)     # causal, depthwise, a tap that
                                              # would reach another document
                                              # reads zero
                [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
                S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T    # S = 0 entering a
                y_t = S_t C_t + D x_t                         # document
                out = rms_w(y * silu(z)) W_out                # norm a group
    attention:  softmax(attention_multiplier * q k^T) v, no positional
                encoding, mask ``j <= i and s_j == s_i``

Three places honour document boundaries: the convolution's look-back, the
recurrent state and the attention mask.  The recurrence runs in the chunked
(SSD) form (:func:`ssd_scan`), the state carried between chunks in float32:
on a TPU at shapes that fill their tiles (the published ones do) as the
Pallas kernels of ``ssd_pallas``, anywhere else as ``jnp`` code.
:func:`scan_runs_fused` is the rule, and a step counts which applied
(``ssm_scan_fused_steps_total`` / ``ssm_scan_plain_steps_total``).
The norm, the products, the feed-forward, the convolution, the attention
and the blocked loss are ``packed_rows``'s, the layer loop, the loss over
rows and the registry's surface ``packed_decoder``'s (its docstring says what
holds for every such decoder).  Attention's kernels want a head to fill
whole rows of 128 lanes (``packed_rows.attention_runs_fused``), so the
published 32/8 heads of 64 keep the ``jnp`` form on every backend, and a
step says so (``attention_plain_steps_total``).  Recomputation and the
blocked attention and loss are why a row of 8,192 tokens at the published
widths trains on one chip beside 16 bytes of state a parameter.  Half of a
step is the feed-forwards', so a layer's recomputation keeps the results of
their two wide products (``packed_rows.SWIGLU_SAVED``, 2 x tokens x
intermediate x 2 B a layer) and makes them once a step; a step counts the
layers that do (``ffn_kept_layers_total``).

``jax.named_scope`` names a device trace can be cut by: ``ssm_mixer`` (the
whole mixer) > ``ssm_conv``, ``ssm_scan``; ``attention``; ``mlp``;
``lm_head``.
"""

from __future__ import annotations

import dataclasses

from tensorflowonspark_tpu.models import packed_decoder
from tensorflowonspark_tpu.models.kernels import runs_fused, step_counters
from tensorflowonspark_tpu.models.packed_rows import (
    SWIGLU_SAVED, block as _block, causal_conv, document_attention,
    mm as _mm, rms as _rms, row_counters, swiglu)

#: no sequence-parallel sharding: the scan's state does not cross ``sp`` yet
SEQUENCE_AXES: dict = {}

#: the recipe :func:`make_optimizer` builds (a continued-pre-training AdamW)
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}

#: one period of the published pattern: nine state-space layers to one
#: attention layer, the attention layer sixth
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
#: the published model is four periods (attention at 5, 15, 25 and 35)
PUBLISHED_LAYERS = PERIOD * 4


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 100352        # rows of the vocabulary held here
    hidden_size: int = 2048
    layer_types: tuple = PUBLISHED_LAYERS
    intermediate_size: int = 8192   # the shared SwiGLU MLP
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    seq_len: int = 8192             # tokens a packed row
    attention_block: int = 256      # queries scored at a time
    loss_block: int = 2048          # tokens whose logits are held at a time

    @classmethod
    def tiny(cls) -> "Config":
        return cls(vocab_size=64, hidden_size=32,
                   layer_types=("mamba", "mamba", "attention", "mamba"),
                   intermediate_size=64, num_attention_heads=4,
                   num_key_value_heads=2, mamba_n_heads=4, mamba_d_head=16,
                   mamba_d_state=8, mamba_chunk_size=8, dtype="float32",
                   seq_len=32, attention_block=16, loss_block=16)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state


def layer_kinds(config: Config) -> list:
    """``(prefix, mixer)`` of every layer in forward order: ``mixer`` is
    ``"mamba"`` or ``"attention"``."""
    return [(f"l{i:02d}_", mixer)
            for i, mixer in enumerate(config.layer_types)]


def leaf_shapes(config: Config) -> dict:
    """Name -> shape of every parameter, in forward order."""
    d, f = config.hidden_size, config.intermediate_size
    out = {"embed": (config.vocab_size, d)}
    for p, kind in layer_kinds(config):
        out[p + "norm1"] = (d,)
        if kind == "mamba":
            out[p + "in_proj"] = (d, config.d_inner + config.conv_dim
                                  + config.mamba_n_heads)
            out[p + "conv_w"] = (config.mamba_d_conv, config.conv_dim)
            out[p + "conv_b"] = (config.conv_dim,)
            out[p + "dt_bias"] = (config.mamba_n_heads,)
            out[p + "A_log"] = (config.mamba_n_heads,)
            out[p + "D"] = (config.mamba_n_heads,)
            out[p + "gate_norm"] = (config.d_inner,)
            out[p + "out_proj"] = (config.d_inner, d)
        elif kind == "attention":
            kv = config.num_key_value_heads * config.head_dim
            out[p + "wq"] = (d, d)
            out[p + "wk"] = (d, kv)
            out[p + "wv"] = (d, kv)
            out[p + "wo"] = (d, d)
        else:
            raise ValueError(f"layer {p}: unknown type {kind!r}")
        out[p + "norm2"] = (d,)
        out[p + "mlp_gate"] = (d, f)
        out[p + "mlp_up"] = (d, f)
        out[p + "mlp_down"] = (f, d)
    out["final_norm"] = (d,)
    return out


# ---------------------------------------------------------------------------
# The mathematics, over the flat parameter dict; the mixers one row at a time
# ---------------------------------------------------------------------------


def scan_runs_fused(chunk: int, heads: int, p: int, groups: int,
                    n: int) -> bool:
    """Whether :func:`ssd_scan` runs on the kernels of ``ssd_pallas``:
    ``kernels.runs_fused`` of ``ssd_pallas.fits`` (the published 256, 64 x
    64, one group, 128 fill the tiles; ``Config.tiny()``'s do not)."""
    from tensorflowonspark_tpu.models import ssd_pallas

    return runs_fused(ssd_pallas, chunk, heads, p, groups, n)


def ssd_scan(x, dt, a, b, c, seg, chunk: int, dtype):
    """The selective state-space recurrence of one packed row in the
    chunked (SSD) form: ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T`` with
    ``S = 0`` entering a document, ``y_t = S_t C_t``.

    ``x`` (T, H, P), ``dt`` (T, H) float32, ``a`` (H,) float32 negative,
    ``b`` and ``c`` (T, G, N) with H a multiple of G, ``seg`` (T,).  Inside
    a chunk of ``chunk`` tokens the recurrence is a masked product (every
    decay an ``exp`` of a difference of within-chunk sums, float32); the
    state crosses chunks in a ``lax.scan``, float32.  Products take
    operands in ``dtype``.  ``T`` need not be a multiple of ``chunk``: the
    row is padded with a document of its own.  Returns (T, H, P) float32.

    One algorithm, two executions (:func:`scan_runs_fused`): on a TPU, at
    shapes that fill its tiles, the kernels of ``ssd_pallas`` compute the
    same terms a (chunk, block of heads) tile at a time and never write a
    chunks x heads x Q x Q tensor; anywhere else the ``jnp`` form below
    runs, which is also the kernels' oracle.
    """
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    t, heads, p = x.shape
    groups, n = b.shape[1:]
    rep = heads // groups
    pad = (-t) % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                       for v in (x, dt, b, c))
        seg = jnp.pad(seg, (0, pad), constant_values=-1)
    if scan_runs_fused(chunk, heads, p, groups, n):
        from tensorflowonspark_tpu.models import ssd_pallas

        return ssd_pallas.fused_scan(x, dt, a, b, c, seg, chunk, dtype)[:t]
    nc = (t + pad) // chunk
    segc = seg.reshape(nc, chunk)
    # within-chunk running sums of the log decay, (nc, H, Q)
    cs = jnp.cumsum((dt * a).reshape(nc, chunk, heads), axis=1
                    ).transpose(0, 2, 1)
    xdt = (x.astype(f32) * dt[..., None]).reshape(nc, chunk, groups, rep, p)
    bc = b.reshape(nc, chunk, groups, n)
    cc = c.reshape(nc, chunk, groups, n)

    # inside a chunk: y_i += sum_{j<=i, same document} exp(cs_i - cs_j)
    #                          (C_i . B_j) dt_j x_j
    mask = ((segc[:, :, None] == segc[:, None, :])
            & jnp.tril(jnp.ones((chunk, chunk), bool)))
    decay = jnp.exp(jnp.where(mask[:, None],
                              cs[..., :, None] - cs[..., None, :], -jnp.inf))
    scores = _mm("cign,cjgn->cgij", cc, bc, dtype, out=f32)
    weights = scores[:, :, None] * decay.reshape(nc, groups, rep, chunk, chunk)
    y = _mm("cgrij,cjgrp->cigrp", weights, xdt, dtype, out=f32)

    # what a chunk leaves behind: its tokens of the last token's document,
    # decayed to the chunk's end
    last = segc[:, -1]
    to_end = jnp.exp(cs[..., -1:] - cs) * (segc == last[:, None])[:, None, :]
    left = _mm("cjgn,cjgrp->cgrpn", bc,
               xdt * to_end.transpose(0, 2, 1).reshape(
                   nc, chunk, groups, rep, 1), dtype, out=f32)
    # ... which the next chunk receives if it ends in the same document
    through = jnp.exp(cs[..., -1]) * jnp.concatenate(
        [jnp.zeros((1,), bool), last[1:] == last[:-1]])[:, None]

    def cross(state, inp):
        keep, new = inp
        return state * keep[..., None, None] + new, state

    _, entering = jax.lax.scan(
        cross, jnp.zeros(left.shape[1:], f32),
        (through.reshape(nc, groups, rep), left))
    # a token reads the entering state if no document began before it
    before = jnp.concatenate([jnp.full((1,), -2, seg.dtype), last[:-1]])
    from_start = jnp.exp(cs) * (segc == before[:, None])[:, None, :]
    y = y + (_mm("cign,cgrpn->cigrp", cc, entering, dtype, out=f32)
             * from_start.transpose(0, 2, 1).reshape(
                 nc, chunk, groups, rep, 1))
    return y.reshape(t + pad, heads, p)[:t]


def mamba_mixer(params, prefix: str, h, seg, config: Config):
    """The Mamba-2 mixer on one row: ``h`` (T, D) -> (T, D)."""
    import jax
    import jax.numpy as jnp

    f32, dtype = jnp.float32, h.dtype
    heads, p = config.mamba_n_heads, config.mamba_d_head
    groups, n = config.mamba_n_groups, config.mamba_d_state
    d_inner, t = config.d_inner, h.shape[0]
    w_in = params[prefix + "in_proj"]
    zx = _mm("td,de->te", h, w_in[:, :d_inner + config.conv_dim], dtype)
    # the step sizes stay float32 from the product on: exp(dt A) is taken
    dt = _mm("td,dh->th", h, w_in[:, d_inner + config.conv_dim:], dtype,
             out=f32)
    z, xbc = zx[:, :d_inner], zx[:, d_inner:]
    with jax.named_scope("ssm_conv"):
        xbc = causal_conv(
            xbc, params[prefix + "conv_w"], params[prefix + "conv_b"], seg,
            silu=True, out=dtype, scopes=("ssm_mixer", "ssm_conv"))
    x = xbc[:, :d_inner].reshape(t, heads, p)
    b = xbc[:, d_inner:d_inner + groups * n].reshape(t, groups, n)
    c = xbc[:, d_inner + groups * n:].reshape(t, groups, n)
    dt = jax.nn.softplus(dt + params[prefix + "dt_bias"])
    with jax.named_scope("ssm_scan"):
        y = ssd_scan(x, dt, -jnp.exp(params[prefix + "A_log"]), b, c, seg,
                     config.mamba_chunk_size, dtype)
    y = y + params[prefix + "D"][:, None] * x.astype(f32)
    y = y.reshape(t, d_inner) * jax.nn.silu(z.astype(f32))
    # the gated norm: a group's channels share one mean square
    y = y.reshape(t, groups, d_inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + config.rms_norm_eps)
    y = (y.reshape(t, d_inner) * params[prefix + "gate_norm"]).astype(dtype)
    return _mm("te,ed->td", y, params[prefix + "out_proj"], dtype)


def attention(params, prefix: str, h, seg, config: Config):
    """Grouped-query attention without positional encoding on one row:
    ``h`` (T, D) -> (T, D)."""
    dtype, t = h.dtype, h.shape[0]
    kv, hd = config.num_key_value_heads, config.head_dim
    rep = config.num_attention_heads // kv
    q = _mm("td,de->te", h, params[prefix + "wq"], dtype)
    k = _mm("td,de->te", h, params[prefix + "wk"], dtype).reshape(t, kv, hd)
    v = _mm("td,de->te", h, params[prefix + "wv"], dtype).reshape(t, kv, hd)
    o = document_attention(q.reshape(t, kv, rep, hd), k, v, seg,
                           config.attention_multiplier,
                           _block(t, config.attention_block), dtype)
    return _mm("te,ed->td", o.reshape(t, kv * rep * hd),
               params[prefix + "wo"], dtype)


def _layer(mixer: str, prefix: str, config: Config, scopes: tuple, lp, x,
           seg, pos, bias):
    """One layer on a batch of rows: ``x`` (B, T, D) -> ``(x, None)`` (no
    router, no counts; ``pos`` and ``bias`` are not read)."""
    import jax

    res, eps = config.residual_multiplier, config.rms_norm_eps
    h = _rms(x, lp[prefix + "norm1"], eps)
    if mixer == "mamba":
        scope, mix = "ssm_mixer", lambda hr, sr: mamba_mixer(
            lp, prefix, hr, sr, config)
    else:
        scope, mix = "attention", lambda hr, sr: attention(
            lp, prefix, hr, sr, config)
    with jax.named_scope(scope):
        x = x + res * jax.vmap(mix)(h, seg)
    with jax.named_scope("mlp"):
        h = _rms(x, lp[prefix + "norm2"], eps).reshape(-1, x.shape[-1])
        return x + res * swiglu(
            h, lp[prefix + "mlp_gate"], lp[prefix + "mlp_up"],
            lp[prefix + "mlp_down"]).reshape(x.shape), None


def _embed(params, tokens, config: Config):
    import jax.numpy as jnp

    return (config.embedding_multiplier
            * jnp.take(params["embed"], tokens, axis=0)).astype(
                jnp.dtype(config.dtype))


def logits(params, x, config: Config):
    """The tied head on states ``x`` (N, D): float32 (N, V)."""
    import jax.numpy as jnp

    h = _rms(x, params["final_norm"], config.rms_norm_eps)
    return _mm("td,vd->tv", h, params["embed"], h.dtype,
               out=jnp.float32) / config.logits_scaling


# ---------------------------------------------------------------------------
# The zoo's surface
# ---------------------------------------------------------------------------


def _init(config: Config):
    """``(name, shape) ->`` a leaf's initializer, Mamba-2's published
    defaults: ``A_log = log(uniform[1, 16])``,
    ``dt_bias`` the inverse softplus of log-uniform [1e-3, 1e-1], ``D = 1``,
    the convolution as PyTorch's ``Conv1d`` leaves it, normal(0, 0.02)
    matrices, unit norms."""
    import flax.linen as nn

    ones, normal = nn.initializers.ones, nn.initializers.normal(0.02)
    conv = packed_decoder.conv_taps(config.mamba_d_conv)
    by_leaf = {"A_log": packed_decoder.a_log,
               "dt_bias": packed_decoder.dt_bias, "D": ones,
               "conv_w": conv, "conv_b": conv, "norm1": ones, "norm2": ones,
               "gate_norm": ones, "final_norm": ones}
    return lambda name, shape: by_leaf.get(
        name[4:] if name[1:3].isdigit() else name, normal)


_DECODER = packed_decoder.Decoder(
    adamw=ADAMW, leaf_shapes=leaf_shapes, layers=layer_kinds, layer=_layer,
    logits=logits, init=_init, embed=_embed, saved=SWIGLU_SAVED,
    example_tokens=lambda config: 2 * config.mamba_chunk_size)
make_model = _DECODER.make_model
make_optimizer = _DECODER.make_optimizer
make_loss_fn = _DECODER.make_loss_fn        # loss(params, batch): no router
make_forward_fn = _DECODER.make_forward_fn
parameter_count = _DECODER.parameter_count
example_batch = _DECODER.example_batch
apply_tokens = _DECODER.apply_tokens        # its ``bias`` is None: no router


def batch_counters(batch, config: Config) -> dict:
    """What one step adds to the program's counters:
    ``packed_rows.row_counters`` (the host batch's tokens, loss tokens and
    documents, and which executions of attention and of the mixers'
    convolution its trace applied) and, by
    the same kind of rule (:func:`scan_runs_fused`), one step of the scan on
    the kernels or as ``jnp`` code, the other named with 0; and
    ``ffn_kept_layers_total``, the layers whose recomputation keeps the
    feed-forward's two wide products (all, or none where the decoder's
    ``saved`` does not list ``packed_rows.SWIGLU_SAVED``)."""
    scans = "mamba" in config.layer_types
    kept = set(SWIGLU_SAVED) <= set(_DECODER.saved)
    return {"ffn_kept_layers_total": len(layer_kinds(config)) if kept else 0,
            **row_counters(batch["segment_ids"], config.head_dim,
                           (None,) * config.layer_types.count("attention"),
                           conv=(config.conv_dim, config.mamba_d_conv)
                           if scans else None),
            **step_counters("ssm_scan", scan_runs_fused(
                config.mamba_chunk_size, config.mamba_n_heads,
                config.mamba_d_head, config.mamba_n_groups,
                config.mamba_d_state), scans)}
