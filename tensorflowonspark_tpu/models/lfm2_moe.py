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

``Config.experts_held`` says which of the ``num_experts`` this chip holds
(all of them unless told otherwise): the router stays as wide as published,
the held experts' part of the result is computed
(``parallel/moe.py::routed_experts``) and what the others would have added is
left out.  No exchange runs and none is stood in for.  The correction biases
and the counts behind them are the ``moe`` collection
(``moe.routing_state_shapes``), as ``mla_moe``'s.

Nothing here is this model's alone but the two mixers' wiring: the norm, the
products, the SwiGLU, the convolution, the positions, the rotation, the
attention and the blocked loss are ``packed_rows``'s (``granite_hybrid`` and
``mla_moe`` call them too), the routed layer and the routing state
``parallel/moe.py``'s.  Parameters are float32, activations
``Config.dtype``; every layer is recomputed in the backward pass, attention
runs a block of queries at a time and the loss a block of tokens at a time;
none of the three is an option.  The published heads of 64 half-fill a row
of lanes, so attention runs as ``jnp`` code on every backend
(``packed_rows.attention_runs_fused``) and a step says so
(``attention_plain_steps_total``).

``jax.named_scope`` names a device trace can be cut by: ``conv_mixer`` >
``conv_in_proj``, ``short_conv`` (the two gates and the convolution),
``conv_out_proj``; ``attention`` > ``qk_norm_rope``; ``mlp`` (the dense
feed-forward); ``moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine`` (``routed_experts``'); ``lm_head``.

The flax module only registers the parameters and the collection (flat
dicts); the mathematics is in pure functions over them.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

from tensorflowonspark_tpu.models.packed_rows import (
    block, blocked_cross_entropy, causal_conv, document_attention,
    document_positions, example_rows, loss_positions, mm, rms, rope,
    row_counters, swiglu)

#: no sequence-parallel sharding: the convolution has no halo over ``sp`` yet
SEQUENCE_AXES: dict = {}

#: the recipe :func:`make_optimizer` builds (a continued-pre-training AdamW)
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}

#: the collection of non-gradient state (``parallel/moe.py``'s)
COLLECTION = "moe"

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
    f, held = config.moe_intermediate_size, len(config.experts_held)
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
        if ffn == "dense":
            out[p + "mlp_gate"] = (d, config.intermediate_size)
            out[p + "mlp_up"] = (d, config.intermediate_size)
            out[p + "mlp_down"] = (config.intermediate_size, d)
        else:
            out[p + "router"] = (d, config.num_experts)
            out[p + "experts_gate"] = (held, d, f)
            out[p + "experts_up"] = (held, d, f)
            out[p + "experts_down"] = (held, f, d)
    out["final_norm"] = (d,)
    return out


def parameter_count(config: Config) -> int:
    return sum(int(np.prod(s)) for s in leaf_shapes(config).values())


def collection_shapes(config: Config) -> dict:
    """The ``moe`` collection: a row an expert layer, in forward order."""
    from tensorflowonspark_tpu.parallel import moe

    return moe.routing_state_shapes(config.num_experts, config.expert_layers)


# ---------------------------------------------------------------------------
# The mathematics, over the flat parameter dict
# ---------------------------------------------------------------------------


def conv_mixer(params, prefix: str, h, seg, initializing: bool = False):
    """The gated short convolution on one row: ``h`` (T, D) -> (T, D).  The
    gates and the convolution are float32 between the two products
    (``c * conv(b * z)``, inside ``packed_rows.causal_conv``, which also
    reads ``initializing``: the module is only learning its parameters from
    this trace)."""
    import jax

    dtype, d = h.dtype, h.shape[1]
    with jax.named_scope("conv_in_proj"):
        bcz = mm("td,de->te", h, params[prefix + "in_proj"], dtype)
    with jax.named_scope("short_conv"):
        b, c, z = (bcz[:, i * d:(i + 1) * d] for i in range(3))
        y = causal_conv(b, params[prefix + "conv_w"], 0.0, seg, times=z,
                        gate=c, out=dtype,
                        scopes=("conv_mixer", "short_conv"),
                        initializing=initializing)
    with jax.named_scope("conv_out_proj"):
        return mm("te,ed->td", y, params[prefix + "out_proj"], dtype)


def attention(params, prefix: str, h, seg, pos, config: Config):
    """Grouped-query attention on one row, every query and key head normed
    and then rotated: ``h`` (T, D) -> (T, D).  Query head ``i`` reads key
    head ``i // (heads / kv)``."""
    import jax

    dtype, t = h.dtype, h.shape[0]
    kv, hd = config.num_key_value_heads, config.head_dim
    rep = config.num_attention_heads // kv
    q = mm("td,de->te", h, params[prefix + "wq"], dtype)
    k = mm("td,de->te", h, params[prefix + "wk"], dtype)
    v = mm("td,de->te", h, params[prefix + "wv"], dtype).reshape(t, kv, hd)
    with jax.named_scope("qk_norm_rope"):
        q = rope(rms(q.reshape(t, kv, rep, hd), params[prefix + "q_norm"],
                     config.norm_eps), pos, config.rope_theta)
        k = rope(rms(k.reshape(t, kv, hd), params[prefix + "k_norm"],
                     config.norm_eps), pos, config.rope_theta)
    o = document_attention(q, k, v, seg, 1.0 / math.sqrt(hd),
                           block(t, config.attention_block), dtype)
    return mm("te,ed->td", o.reshape(t, kv * rep * hd), params[prefix + "wo"],
              dtype)


def _layer(mixer: str, ffn: str, prefix: str, config: Config,
           initializing: bool, lp, x, seg, pos, bias):
    """One layer on a batch of rows: ``x`` (B, T, D) -> ``(x, counts)``;
    ``counts`` is (E,) zeros for a dense layer.  ``initializing``: the
    module is only learning its parameters from this trace
    (``moe.routed_experts``)."""
    import jax
    import jax.numpy as jnp

    from tensorflowonspark_tpu.parallel import moe

    eps = config.norm_eps
    if mixer == "conv":
        scope, mix = "conv_mixer", lambda hr, sr, pr: conv_mixer(
            lp, prefix, hr, sr, initializing)
    else:
        scope, mix = "attention", lambda hr, sr, pr: attention(
            lp, prefix, hr, sr, pr, config)
    with jax.named_scope(scope):
        x = x + jax.vmap(mix)(rms(x, lp[prefix + "norm1"], eps), seg, pos)
    h = rms(x, lp[prefix + "norm2"], eps).reshape(-1, x.shape[-1])
    if ffn == "dense":
        with jax.named_scope("mlp"):
            y = swiglu(h, lp[prefix + "mlp_gate"], lp[prefix + "mlp_up"],
                       lp[prefix + "mlp_down"])
        counts = jnp.zeros((config.num_experts,), jnp.int32)
    else:
        y, counts = moe.routed_experts(
            h, lp[prefix + "router"], bias, lp[prefix + "experts_gate"],
            lp[prefix + "experts_up"], lp[prefix + "experts_down"],
            config.experts_held, top_k=config.num_experts_per_tok,
            scale=config.routed_scaling_factor,
            normalize=config.norm_topk_prob, sum_eps=GATE_SUM_EPS,
            initializing=initializing)
    return x + y.reshape(x.shape), counts


def hidden_states(params, bias, tokens, seg, config: Config,
                  initializing: bool = False):
    """``(x, counts)``: the hidden states before the last norm (B, T, D) and
    the tokens that chose each expert, (expert layers, E) int32 in forward
    order.  ``bias`` (expert layers, E) enters the choice where
    ``use_expert_bias``."""
    import jax
    import jax.numpy as jnp

    pos = jax.vmap(document_positions)(seg)
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        jnp.dtype(config.dtype))
    if not config.use_expert_bias:
        bias = jnp.zeros_like(bias)
    counts = []
    for prefix, mixer, ffn in layer_kinds(config):
        mine = {k: v for k, v in params.items() if k.startswith(prefix)}
        row = bias[len(counts)] if ffn == "experts" else None
        x, c = jax.checkpoint(functools.partial(
            _layer, mixer, ffn, prefix, config, initializing))(
                mine, x, seg, pos, row)
        if ffn == "experts":
            counts.append(c)
    return x, jnp.stack(counts) if counts else jnp.zeros(
        (0, config.num_experts), jnp.int32)


def _logits(params, x, config: Config):
    import jax.numpy as jnp

    h = rms(x, params["final_norm"], config.norm_eps)
    return mm("td,vd->tv", h, params["embed"], h.dtype, out=jnp.float32)


def apply_tokens(params, bias, tokens, segment_ids, config: Config,
                 initializing: bool = False):
    """Teacher-forced forward: (B, T) tokens and segment ids -> (B, T, V)
    float32 logits of the tied head.  ``initializing`` is the calling
    module's ``is_initializing()`` (``moe.routed_experts`` reads it)."""
    import jax

    x, _ = hidden_states(params, bias, tokens, segment_ids, config,
                         initializing)
    with jax.named_scope("lm_head"):
        return jax.vmap(lambda xr: _logits(params, xr, config))(x)


def loss_terms(params, bias, tokens, segment_ids, config: Config):
    """``(sum of the cross-entropies, positions counted, counts)`` of a
    batch of packed rows: position ``t`` is scored against ``u_{t+1}`` where
    that is the same document's; the logits exist a block of tokens at a
    time."""
    import jax
    import jax.numpy as jnp

    x, counts = hidden_states(params, bias, tokens, segment_ids, config)

    def row(xr, u, s):
        valid = loss_positions(s)
        return blocked_cross_entropy(
            xr, lambda xb: _logits(params, xb, config), jnp.roll(u, -1),
            valid, config.loss_block), jnp.sum(valid)

    with jax.named_scope("lm_head"):
        total, count = jax.vmap(row)(x, tokens, segment_ids)
    return jnp.sum(total), jnp.sum(count), counts


# ---------------------------------------------------------------------------
# The zoo's surface
# ---------------------------------------------------------------------------


def make_model(config: Config, mesh=None):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    shapes, state = leaf_shapes(config), collection_shapes(config)
    ones = nn.initializers.ones
    normal = nn.initializers.normal(config.init_std)
    # the matrices that write into the residual stream start smaller, by
    # the layers that add to it (``mla_moe.make_model`` says why a seeded
    # router needs it)
    out = nn.initializers.normal(config.init_std / math.sqrt(
        2 * max(len(config.layer_types), 1)))

    def taps(key, shape, dtype):    # as PyTorch's ``Conv1d`` leaves them
        bound = 1.0 / math.sqrt(config.conv_L_cache)
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    def init(name, shape):
        if len(shape) == 1:
            return ones
        if name.endswith("_conv_w"):
            return taps
        return out if name.endswith(("_wo", "_out_proj", "_down")) else normal

    class Lfm2Moe(nn.Module):
        @nn.compact
        def __call__(self, tokens, segment_ids):
            params = {name: self.param(name, init(name, shape), shape,
                                       jnp.float32)
                      for name, shape in shapes.items()}
            bias = self.variable(
                COLLECTION, "bias", jnp.zeros, *state["bias"]).value
            for name in ("counts", "busiest", "overflow"):
                self.variable(COLLECTION, name, jnp.zeros, *state[name])
            return apply_tokens(params, bias, tokens, segment_ids, config,
                                initializing=self.is_initializing())

    return Lfm2Moe()


def make_optimizer(config: Config, learning_rate: float):
    import optax

    return optax.adamw(learning_rate, **ADAMW)


def make_loss_fn(module, config: Config):
    """``loss(params, collections, batch) -> (loss, new collections)``: the
    mean next-token cross-entropy over the positions whose next token is
    the same document's; the ``moe`` collection moves on a step."""
    import jax.numpy as jnp

    from tensorflowonspark_tpu.parallel import moe

    speed = config.bias_update_speed if config.use_expert_bias else 0.0

    def loss_fn(params, collections, batch):
        state = collections[COLLECTION]
        total, count, counts = loss_terms(
            params, state["bias"], batch["tokens"], batch["segment_ids"],
            config)
        return total / jnp.maximum(count, 1), {
            **collections, COLLECTION: moe.step_routing_state(
                state, counts, config.experts_held,
                top_k=config.num_experts_per_tok, speed=speed,
                tokens=batch["tokens"].size)}

    loss_fn.stateful = True
    return loss_fn


def make_forward_fn(module, config: Config):
    def forward(params, collections, batch):
        return apply_tokens(params, collections[COLLECTION]["bias"],
                            batch["tokens"], batch["segment_ids"], config)

    forward.stateful = True
    return forward


def batch_counters(batch, config: Config) -> dict:
    """What one step adds to the program's counters
    (``packed_rows.row_counters``: the host batch's tokens, loss tokens and
    documents, and which executions of attention and of the short
    convolution its trace applied; and ``moe.grouped_step_counters``: which
    execution of the routed experts' grouped products)."""
    from tensorflowonspark_tpu.parallel import moe

    seg = np.asarray(batch["segment_ids"])
    return {**row_counters(seg, config.head_dim,
                           "full_attention" in config.layer_types,
                           conv=(config.hidden_size, config.conv_L_cache)
                           if "conv" in config.layer_types else None),
            **moe.grouped_step_counters(
                seg.size, config.num_experts_per_tok,
                len(config.experts_held), config.num_experts,
                config.hidden_size, config.moe_intermediate_size,
                config.dtype)}


def device_counters(collections, config: Config) -> dict:
    """What the device decided, for the program's counters
    (``moe.routing_counters`` of the ``moe`` collection)."""
    from tensorflowonspark_tpu.parallel import moe

    return moe.routing_counters(collections[COLLECTION],
                                config.experts_held)


def example_batch(config: Config, batch_size: int = 8, seed: int = 0,
                  seq_len: int | None = None):
    """Packed rows of two documents each, ``seq_len`` tokens (at most 64
    unless told: a step compiles at the shape it is fed)."""
    return example_rows(config.vocab_size, batch_size, seed,
                        int(seq_len or min(config.seq_len, 64)))
