"""Hybrid short-convolution / attention mixture-of-experts decoder (the
``lfm2_moe`` layout: gated short-convolution mixers, a QK-normed RoPE GQA
layer among them, leading dense layers, then sigmoid-routed experts with a
correction bias and no shared expert), trained on packed rows.

Published shape: ``LiquidAI/LFM2-8B-A1B`` ``config.json``.  For a row of
tokens ``u`` with segment ids ``s`` (documents are contiguous and their ids
differ), ``p_t`` the index of token ``t`` inside its document::

    x = E[u]
    layer i:  x += mixer_i(rms(x));  x += FFN_i(rms(x))
    conv:       [B | C | z] = h W_in;  v = B * z
                c_t = sum_j w[K-1-j] * v_{t-j}   # causal, depthwise, K taps,
                                                 # a tap that would reach
                                                 # another document reads 0
                out = (C * c) W_out              # no state, no scan
    attention:  q = h W_q, k = h W_k, v = h W_v (GQA); every query and key
                head normed (one RMS scale of a head's width each), then
                turned by RoPE(theta, p_t), the halves rotated
                o = softmax(q k^T / sqrt(hd), mask j <= i and s_j == s_i) v
                out = concat(o) W_o
    FFN:        the first ``num_dense_layers`` layers SwiGLU of width
                ``intermediate_size``; every later one
                sc = sigmoid(h W_r) in float32;  chosen = top-k of (sc + b_i)
                g_e = scaling * sc_e / (sum over chosen of sc + 1e-6)
                y = sum over e chosen and held here of g_e Expert_e(h)
    head:       logits = rms(x) E^T (tied);  loss = mean CE(logits_t, u_{t+1})
                over t with s_{t+1} == s_t
    every expert layer, once a step:  c_e = tokens that chose e;
                b_e += bias_update_speed * sign(mean(c) - c_e)

Nothing here is this model's alone but the two mixers' wiring: the norm, the
products, the convolution, the rotation, the attention and the blocked loss
are ``packed_rows``'s, the routed layer and the routing state (the ``moe``
collection) ``parallel/moe.py``'s, the layer loop, the feed-forward half of
a layer, the positions and the registry's surface ``packed_decoder``'s,
whose docstring says what holds for every such decoder
(``Config.experts_held`` among it).  The published heads of 64 half-fill a
row of lanes, so attention runs as ``jnp`` code on every backend
(``packed_rows.attention_runs_fused``) and a step says so
(``attention_plain_steps_total``).

``jax.named_scope`` names a device trace can be cut by: ``conv_mixer`` >
``conv_in_proj``, ``short_conv`` (the two gates and the convolution),
``conv_out_proj``; ``attention`` > ``qk_norm_rope``; ``mlp`` (the dense
feed-forward); ``moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine`` (``routed_experts``'); ``lm_head``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from tensorflowonspark_tpu.models import packed_decoder
from tensorflowonspark_tpu.models.packed_rows import (
    block, causal_conv, grouped_query_attention, mm, rms, rope_frequencies,
    row_counters)

#: no sequence-parallel sharding: the convolution has no halo over ``sp`` yet
SEQUENCE_AXES: dict = {}

#: the recipe :func:`make_optimizer` builds (a continued-pre-training AdamW)
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}

#: the collection of non-gradient state (``packed_decoder.COLLECTION``)
COLLECTION = packed_decoder.COLLECTION

#: the published pattern: two dense ``conv`` layers, then ``A c c c`` four
#: times and ``A c c`` twice
PUBLISHED_LAYERS = (("conv", "conv")
                    + ("full_attention", "conv", "conv", "conv") * 4
                    + ("full_attention", "conv", "conv") * 2)

#: the epsilon the public implementation adds to the chosen scores' sum
GATE_SUM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 65536         # rows of the vocabulary held here
    hidden_size: int = 2048
    layer_types: tuple = PUBLISHED_LAYERS   # the layers run, in order
    num_dense_layers: int = 2       # of them, the first with a dense SwiGLU
    intermediate_size: int = 7168   # the dense layers' SwiGLU
    moe_intermediate_size: int = 1792
    num_experts: int = 32           # the router's width
    experts_held: tuple = tuple(range(32))
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3           # the short convolution's taps
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    bias_update_speed: float = 0.001
    init_std: float = 0.02
    dtype: str = "bfloat16"
    seq_len: int = 8192             # tokens a packed row
    attention_block: int = 256      # queries scored at a time
    loss_block: int = 2048          # tokens whose logits are held at a time

    def __post_init__(self):
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.head_dim % 2:
            raise ValueError("RoPE turns pairs: the head size is odd")

    @classmethod
    def tiny(cls) -> "Config":
        return cls(vocab_size=64, hidden_size=32,
                   layer_types=("conv", "full_attention", "conv"),
                   num_dense_layers=1, intermediate_size=64,
                   moe_intermediate_size=16, num_experts=8,
                   experts_held=(2, 5), num_experts_per_tok=2,
                   num_attention_heads=2, num_key_value_heads=1,
                   dtype="float32", seq_len=32, attention_block=16,
                   loss_block=16)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def expert_layers(self) -> int:
        """Layers with a router."""
        return max(len(self.layer_types) - self.num_dense_layers, 0)


def layer_kinds(config: Config) -> list:
    """``(prefix, mixer, ffn)`` of every layer in forward order: ``mixer``
    is ``"conv"`` or ``"full_attention"``, ``ffn`` ``"dense"`` or
    ``"experts"``."""
    return [(f"l{i:02d}_", mixer,
             "dense" if i < config.num_dense_layers else "experts")
            for i, mixer in enumerate(config.layer_types)]


def leaf_shapes(config: Config) -> dict:
    """Name -> shape of every parameter, in forward order."""
    d, hd = config.hidden_size, config.head_dim
    out = {"embed": (config.vocab_size, d)}
    for p, mixer, ffn in layer_kinds(config):
        out[p + "norm1"] = (d,)
        if mixer == "conv":
            out[p + "in_proj"] = (d, 3 * d)
            out[p + "conv_w"] = (config.conv_L_cache, d)
            out[p + "out_proj"] = (d, d)
        else:
            out[p + "wq"] = (d, config.num_attention_heads * hd)
            out[p + "wk"] = (d, config.num_key_value_heads * hd)
            out[p + "wv"] = (d, config.num_key_value_heads * hd)
            out[p + "q_norm"] = (hd,)
            out[p + "k_norm"] = (hd,)
            out[p + "wo"] = (config.num_attention_heads * hd, d)
        out[p + "norm2"] = (d,)
        out.update(packed_decoder.ffn_leaf_shapes(
            p, ffn, d, config.intermediate_size, config.moe_intermediate_size,
            routing(config)))
    out["final_norm"] = (d,)
    return out


def routing(config: Config):
    """This layout's routed layers, as ``parallel/moe.py`` names them; the
    correction bias stays where ``use_expert_bias`` is off."""
    from tensorflowonspark_tpu.parallel import moe

    return moe.Routing(
        n_experts=config.num_experts, layers=config.expert_layers,
        held=config.experts_held, top_k=config.num_experts_per_tok,
        scale=config.routed_scaling_factor,
        normalize=config.norm_topk_prob, sum_eps=GATE_SUM_EPS,
        speed=config.bias_update_speed if config.use_expert_bias else 0.0)


# ---------------------------------------------------------------------------
# The mathematics, over the flat parameter dict
# ---------------------------------------------------------------------------


def conv_mixer(params, prefix: str, h, seg):
    """The gated short convolution on one row: ``h`` (T, D) -> (T, D).  The
    gates and the convolution are float32 between the two products
    (``c * conv(b * z)``, inside ``packed_rows.causal_conv``)."""
    import jax

    dtype, d = h.dtype, h.shape[1]
    with jax.named_scope("conv_in_proj"):
        bcz = mm("td,de->te", h, params[prefix + "in_proj"], dtype)
    with jax.named_scope("short_conv"):
        b, c, z = (bcz[:, i * d:(i + 1) * d] for i in range(3))
        y = causal_conv(b, params[prefix + "conv_w"], 0.0, seg, times=z,
                        gate=c, out=dtype,
                        scopes=("conv_mixer", "short_conv"))
    with jax.named_scope("conv_out_proj"):
        return mm("te,ed->td", y, params[prefix + "out_proj"], dtype)


def attention(params, prefix: str, h, seg, pos, config: Config):
    """``packed_rows.grouped_query_attention`` at this layout's sizes: plain
    RoPE(``rope_theta``) over a whole head, no window."""
    return grouped_query_attention(
        params, prefix, h, seg, pos, heads=config.num_attention_heads,
        kv=config.num_key_value_heads, hd=config.head_dim,
        eps=config.norm_eps, size=block(h.shape[0], config.attention_block),
        freq=rope_frequencies(config.rope_theta, config.head_dim // 2))


def _layer(mixer: str, ffn: str, prefix: str, config: Config, scopes: tuple,
           lp, x, seg, pos, bias):
    """One layer on a batch of rows: ``x`` (B, T, D) -> ``(x, counts)``;
    ``counts`` is (E,) zeros for a dense layer.  ``bias`` (E,) enters the
    experts' choice where ``use_expert_bias``."""
    import jax
    import jax.numpy as jnp

    eps = config.norm_eps
    if mixer == "conv":
        scope, mix = "conv_mixer", lambda hr, sr, pr: conv_mixer(
            lp, prefix, hr, sr)
    else:
        scope, mix = "attention", lambda hr, sr, pr: attention(
            lp, prefix, hr, sr, pr, config)
    with jax.named_scope(scope):
        x = x + jax.vmap(mix)(rms(x, lp[prefix + "norm1"], eps), seg, pos)
    if ffn == "experts" and not config.use_expert_bias:
        bias = jnp.zeros_like(bias)
    return packed_decoder.feed_forward(lp, prefix, ffn, x, bias, eps,
                                       routing(config), scopes=scopes)


def logits(params, x, config: Config):
    """The tied head on states ``x`` (N, D): float32 (N, V)."""
    import jax.numpy as jnp

    h = rms(x, params["final_norm"], config.norm_eps)
    return mm("td,vd->tv", h, params["embed"], h.dtype, out=jnp.float32)


# ---------------------------------------------------------------------------
# The zoo's surface
# ---------------------------------------------------------------------------


def _init(config: Config):
    """``(name, shape) ->`` a leaf's initializer: unit norms, normal
    matrices, the taps as PyTorch's ``Conv1d`` leaves them."""
    import flax.linen as nn

    normal, out = packed_decoder.normals(config.init_std,
                                         len(config.layer_types))
    taps = packed_decoder.conv_taps(config.conv_L_cache)

    def init(name, shape):
        if len(shape) == 1:
            return nn.initializers.ones
        if name.endswith("_conv_w"):
            return taps
        return out if name.endswith(("_wo", "_out_proj", "_down")) else normal

    return init


_DECODER = packed_decoder.Decoder(
    adamw=ADAMW, leaf_shapes=leaf_shapes, layers=layer_kinds, layer=_layer,
    logits=logits, init=_init, routing=routing, positions=True)
collection_shapes = _DECODER.collection_shapes
hidden_states = _DECODER.hidden_states
apply_tokens = _DECODER.apply_tokens
loss_terms = _DECODER.next_token_terms
make_model = _DECODER.make_model
make_optimizer = _DECODER.make_optimizer
make_loss_fn = _DECODER.make_loss_fn
make_forward_fn = _DECODER.make_forward_fn
device_counters = _DECODER.device_counters
counter_rows = _DECODER.counter_rows
parameter_count = _DECODER.parameter_count
example_batch = _DECODER.example_batch


def batch_counters(batch, config: Config) -> dict:
    """What one step adds to the program's counters
    (``packed_rows.row_counters``: the host batch's tokens, loss tokens and
    documents, and which executions of attention and of the short
    convolution its trace applied; and ``moe.grouped_step_counters``: which
    execution of the routed experts' grouped products)."""
    from tensorflowonspark_tpu.parallel import moe

    seg = np.asarray(batch["segment_ids"])
    return {**row_counters(seg, config.head_dim,
                           (None,) * config.layer_types.count(
                               "full_attention"),
                           conv=(config.hidden_size, config.conv_L_cache)
                           if "conv" in config.layer_types else None),
            **moe.grouped_step_counters(
                seg.size, routing(config), config.hidden_size,
                config.moe_intermediate_size, config.dtype)}
