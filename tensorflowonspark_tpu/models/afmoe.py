"""Gated sliding-window / position-free attention mixture-of-experts decoder
(the ``afmoe`` layout: QK-normed grouped-query attention whose output a
sigmoid gate multiplies before ``wo``, three layers in four behind a sliding
window and turned by RoPE, the fourth over the whole document with no
position signal at all; four norms a layer, round both halves; an embedding
scaled by ``sqrt(hidden_size)``; leading dense layers, then sigmoid-routed
experts with a correction bias beside a shared one; an untied head), trained
on packed rows.

Published shape: ``arcee-ai/Trinity-Mini`` ``config.json``.  For a row of
tokens ``u`` with segment ids ``s`` (documents are contiguous and their ids
differ), ``p_t`` the index of token ``t`` inside its document::

    x = E[u] * sqrt(D)                                  (``mup_enabled``)
    layer l:  x += rms(attn_l(rms(x; norm1)); norm2)
              x += rms(ffn_l(rms(x; norm3)); norm4)
    attention:  q = h W_q, k = h W_k, v = h W_v (GQA), g = h W_g; every
                query and key head normed (one RMS scale of a head's width
                each); in a sliding layer q and k turned at p_t by RoPE
                (theta ** (-i / 64), the halves rotated), in a full layer
                left as they are
                o = softmax(q k^T / sqrt(hd), mask j <= i and s_j == s_i and,
                            in a sliding layer, i - j < sliding_window) v
                out = (concat(o) * sigmoid(g)) W_o
    ffn:        the layers whose published index is under
                ``num_dense_layers`` SwiGLU of width ``intermediate_size``;
                every later one
                sc = sigmoid(h W_r) in float32;  chosen = top-k of (sc + b_l)
                g_e = route_scale * sc_e / (sum over chosen of sc + 1e-20)
                y = Shared(h) + sum over e chosen and held here of
                    g_e Expert_e(h)                         (SwiGLU both)
    head:       logits = rms(x) W_head (untied);
                loss = mean CE(logits_t, u_{t+1}) over t with s_{t+1} == s_t
    every expert layer, once a step:  c_e = tokens that chose e;
                b_e += load_balance_coeff * sign(mean(c) - c_e)

Nothing here is a mixer of this model's own: the grouped-query layer with
its gate and its switch for no rotation, the norm, the products, the
rotation, the attention (``document_attention(window=...)``) and the blocked
loss are ``packed_rows``'s, the routed layer and the routing state
``parallel/moe.py``'s, the layer loop, the feed-forward half with its
post-norm, the scaled embedding, the positions and the registry's surface
``packed_decoder``'s, whose docstring says what holds for every such decoder
(``Config.experts_held`` among it).  The published heads of 128 fill a row of
lanes, so on a TPU at the published row both kinds of layer run on the
kernels of ``attention_pallas`` (``packed_rows.attention_runs_fused``): one
step runs the same function two ways, windowed and rotated, full and
position-free.

``jax.named_scope`` names a device trace can be cut by: ``embed_scale``;
``attention`` (a layer's first norm and mixer whole, both kinds) >
``qk_norm_rope``, ``attention_gate`` (the gate's product, sigmoid and
multiply), and round the blocks of scores, softmax and values
``window_attention`` in a sliding layer and ``full_attention`` in a full one;
``post_norm`` (both of a layer); ``mlp`` (the dense feed-forward);
``shared_expert``; ``moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine`` (``routed_experts``'); ``lm_head``.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from tensorflowonspark_tpu.models import packed_decoder
from tensorflowonspark_tpu.models.packed_rows import (
    BLOCKS_SCOPE, GATE_SAVED, block, grouped_query_attention, mask_pairs, mm,
    rms, rope_frequencies, row_counters)

#: no sequence-parallel sharding: a window has no neighbour's block over
#: ``sp`` yet
SEQUENCE_AXES: dict = {}

#: the recipe :func:`make_optimizer` builds (a continued-pre-training AdamW)
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}

#: the collection of non-gradient state (``packed_decoder.COLLECTION``)
COLLECTION = packed_decoder.COLLECTION

#: the published pattern: ``S S S F`` eight times
PUBLISHED_LAYERS = ("sliding_attention",) * 3 + ("full_attention",)

#: the epsilon the public implementation adds to the chosen scores' sum
GATE_SUM_EPS = 1e-20

#: the collection's row that adds up the gates' sigmoids, an expert layer,
#: and the counter that shows it (``packed_decoder.Decoder.gauges``)
GATE_OPEN = {"gate_open": "attention_gate_open_total"}

#: what a recomputed layer keeps besides attention's output and log-sum-exp:
#: the gate's projection (T, heads hd), and what each half adds before its
#: post-norm (T, D each), 134 MB a layer in bfloat16 at the published row.
#: A post-norm's backward pass reads what it normed, so a layer that keeps
#: none of them makes its whole feed-forward — the routed part's dispatch,
#: products and combine among it — a third time (a sibling's ``x + f``
#: needs no ``f``): measured, 283.1 ms a step with nothing kept, 268.8 with
#: the feed-forward's result, 265.5 with the mixer's too, 262.4 with the
#: gate's projection as well (PERF.md section 6, PR 51)
SAVED = (GATE_SAVED, packed_decoder.MIXER_ADDED, packed_decoder.FFN_ADDED)


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 200192        # rows of the vocabulary held here
    hidden_size: int = 2048
    head_dim: int = 128
    layer_types: tuple = PUBLISHED_LAYERS * 8   # every published layer's
    layers_run: tuple = tuple(range(32))        # of them, the ones run
    num_dense_layers: int = 2       # published layers under it are dense
    sliding_window: int = 2048      # a query sees itself and 2,047 before
    rope_theta: float = 10000.0     # the sliding layers'; full ones: none
    intermediate_size: int = 6144   # the dense layers' SwiGLU
    moe_intermediate_size: int = 1024
    num_experts: int = 128          # the router's width
    experts_held: tuple = tuple(range(128))
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    load_balance_coeff: float = 0.001   # the correction bias's step
    mup_enabled: bool = True        # the embedding enters times sqrt(D)
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    rms_norm_eps: float = 1e-5
    init_std: float = 0.02
    post_norm_init: float = 1.0     # norm2's and norm4's scales start at it
    dtype: str = "bfloat16"
    seq_len: int = 8192             # tokens a packed row
    attention_block: int = 256      # queries scored at a time (``jnp`` form)
    loss_block: int = 2048          # tokens whose logits are held at a time

    def __post_init__(self):
        unknown = set(self.layer_types) - set(BLOCKS_SCOPE)
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.head_dim % 2:
            raise ValueError("RoPE turns pairs: the head size is odd")
        if self.sliding_window < 1:
            raise ValueError("a query sees itself: the window is at least 1")
        if self.num_shared_experts < 1:
            raise ValueError("the layout has a shared expert")

    @classmethod
    def tiny(cls) -> "Config":
        return cls(vocab_size=64, hidden_size=32, head_dim=8,
                   layer_types=("sliding_attention", "sliding_attention",
                                "full_attention", "sliding_attention"),
                   layers_run=(0, 1, 2, 3), num_dense_layers=1,
                   sliding_window=12, intermediate_size=48,
                   moe_intermediate_size=16, num_experts=8,
                   experts_held=(2, 5), num_experts_per_tok=3,
                   num_attention_heads=4, num_key_value_heads=2,
                   dtype="float32", seq_len=48, attention_block=16,
                   loss_block=16)

    @property
    def expert_layers(self) -> int:
        """Layers with a router: those run past the leading dense ones."""
        return sum(at >= self.num_dense_layers for at in self.layers_run)


def layer_kinds(config: Config) -> list:
    """``(prefix, mixer, ffn)`` of every layer run, in forward order:
    ``mixer`` is the published ``layer_types`` at ``layers_run``
    (``"sliding_attention"`` or ``"full_attention"``), ``ffn`` ``"dense"``
    where the published index is under ``num_dense_layers``, else
    ``"experts"``."""
    return [(f"l{i:02d}_", config.layer_types[at],
             "dense" if at < config.num_dense_layers else "experts")
            for i, at in enumerate(config.layers_run)]


def leaf_shapes(config: Config) -> dict:
    """Name -> shape of every parameter, in forward order."""
    d, hd = config.hidden_size, config.head_dim
    out = {"embed": (config.vocab_size, d)}
    for p, _, ffn in layer_kinds(config):
        out[p + "norm1"] = (d,)
        out[p + "wq"] = (d, config.num_attention_heads * hd)
        out[p + "wk"] = (d, config.num_key_value_heads * hd)
        out[p + "wv"] = (d, config.num_key_value_heads * hd)
        out[p + "wg"] = (d, config.num_attention_heads * hd)
        out[p + "q_norm"] = (hd,)
        out[p + "k_norm"] = (hd,)
        out[p + "wo"] = (config.num_attention_heads * hd, d)
        out[p + "norm2"] = (d,)
        out[p + "norm3"] = (d,)
        out.update(packed_decoder.ffn_leaf_shapes(
            p, ffn, d, config.intermediate_size,
            config.moe_intermediate_size, routing(config),
            shared=config.moe_intermediate_size * config.num_shared_experts))
        out[p + "norm4"] = (d,)
    out["final_norm"] = (d,)
    out["head"] = (config.vocab_size, d)
    return out


def routing(config: Config):
    """This layout's routed layers, as ``parallel/moe.py`` names them:
    ``score_func`` scores, the chosen renormalised where ``route_norm``
    (over their sum plus :data:`GATE_SUM_EPS`) and scaled by
    ``route_scale``, a correction bias that moves ``load_balance_coeff`` a
    step."""
    from tensorflowonspark_tpu.parallel import moe

    return moe.Routing(
        n_experts=config.num_experts, layers=config.expert_layers,
        held=config.experts_held, top_k=config.num_experts_per_tok,
        scale=config.route_scale, normalize=config.route_norm,
        speed=config.load_balance_coeff, sum_eps=GATE_SUM_EPS,
        score=config.score_func)


# ---------------------------------------------------------------------------
# The mathematics, over the flat parameter dict
# ---------------------------------------------------------------------------


def attention(params, prefix: str, h, seg, pos, config: Config, kind: str):
    """``packed_rows.grouped_query_attention`` at this layout's sizes with
    its output gate, as a layer of ``kind``: behind ``sliding_window`` and
    turned by RoPE(``rope_theta``), or over the whole document and not
    turned at all; the blocks under the kind's scope.  Returns ``(out,
    open)``: the gate's sigmoids summed."""
    sliding = kind == "sliding_attention"
    return grouped_query_attention(
        params, prefix, h, seg, pos, heads=config.num_attention_heads,
        kv=config.num_key_value_heads, hd=config.head_dim,
        eps=config.rms_norm_eps,
        size=block(h.shape[0], config.attention_block),
        freq=rope_frequencies(config.rope_theta, config.head_dim // 2)
        if sliding else None, inner=BLOCKS_SCOPE[kind],
        window=config.sliding_window if sliding else None, gate=True)


def _layer(mixer: str, ffn: str, prefix: str, config: Config, scopes: tuple,
           lp, x, seg, pos, bias):
    """One layer on a batch of rows: ``x`` (B, T, D) -> ``(x, (counts,
    open))``; ``counts`` is (E,) zeros for a dense layer, ``open`` the sum of
    the layer's gate over its cells, rounded (``GATE_OPEN``)."""
    import jax
    import jax.numpy as jnp

    eps = config.rms_norm_eps
    with jax.named_scope("attention"):
        a, opened = jax.vmap(lambda hr, sr, pr: attention(
            lp, prefix, hr, sr, pr, config, mixer))(
                rms(x, lp[prefix + "norm1"], eps), seg, pos)
    x = packed_decoder.add_normed(x, a, lp[prefix + "norm2"], eps,
                                  packed_decoder.MIXER_ADDED)
    x, counts = packed_decoder.feed_forward(
        lp, prefix, ffn, x, bias, eps, routing(config), shared=True,
        scopes=scopes, norm="norm3", post_norm="norm4")
    return x, (counts, jnp.round(jnp.sum(opened)).astype(jnp.int32))


def embed(params, tokens, config: Config):
    """``E[u]``, times ``sqrt(hidden_size)`` where ``mup_enabled``."""
    return packed_decoder.embed(
        params, tokens, config,
        math.sqrt(config.hidden_size) if config.mup_enabled else None)


def logits(params, x, config: Config):
    """The untied head on states ``x`` (N, D): float32 (N, V)."""
    import jax.numpy as jnp

    h = rms(x, params["final_norm"], config.rms_norm_eps)
    return mm("td,vd->tv", h, params["head"], h.dtype, out=jnp.float32)


# ---------------------------------------------------------------------------
# The zoo's surface
# ---------------------------------------------------------------------------


def _init(config: Config):
    """``(name, shape) ->`` a leaf's initializer: unit norms, normal
    matrices (the embedding among them, as the public implementation draws
    it: it enters times ``sqrt(hidden_size)``), those that write into the
    residual stream smaller by the published depth; the post-norms' scales
    at ``post_norm_init``.  A post-norm makes its half of a layer add
    a vector of the RMS its scale says whatever the matrices' draw — the
    smaller draw of ``wo`` and ``down`` changes nothing here — and at a
    scale of 1 what a layer adds, much the same for every token of a
    document, is as large as a token's own embedding: a seeded router then
    sends most of a row to one expert (PERF.md section 6, PRs 47 and 51).
    A depth-scaled start (a scale under 1) keeps a token its own."""
    import flax.linen as nn

    normal, out = packed_decoder.normals(config.init_std,
                                         len(config.layer_types))
    post = nn.initializers.constant(config.post_norm_init)

    def init(name, shape):
        if name.endswith(("_norm2", "_norm4")):
            return post
        if len(shape) == 1:
            return nn.initializers.ones
        return out if name.endswith(("_wo", "_down")) else normal

    return init


_DECODER = packed_decoder.Decoder(
    adamw=ADAMW, leaf_shapes=leaf_shapes, layers=layer_kinds, layer=_layer,
    logits=logits, init=_init, routing=routing, embed=embed, positions=True,
    saved=SAVED, gauges=GATE_OPEN)
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
    documents, which execution of attention its trace applied and the
    blocks its kernels visit, each layer at its own window;
    ``moe.grouped_step_counters``: which execution of the routed experts'
    grouped products; what the two masks really admit on this batch's
    documents, a head, summed over the layers of each kind:
    ``packed_rows.mask_pairs``; and the cells of the gates that
    ``attention_gate_open_total`` adds up — a token's every head's numbers
    in every expert layer: that counter over this one is the gate's mean,
    a half while the seeded gate has not moved)."""
    from tensorflowonspark_tpu.parallel import moe

    seg = np.asarray(batch["segment_ids"])
    mixers = [mixer for _, mixer, _ in layer_kinds(config)]
    return {**row_counters(seg, config.head_dim, tuple(
                config.sliding_window if mixer == "sliding_attention"
                else None for mixer in mixers)),
            **moe.grouped_step_counters(
                seg.size, routing(config), config.hidden_size,
                config.moe_intermediate_size, config.dtype),
            "attention_window_pairs_total": mask_pairs(
                seg, config.sliding_window) * mixers.count(
                    "sliding_attention"),
            "attention_full_pairs_total": mask_pairs(seg) * mixers.count(
                "full_attention"),
            "attention_gate_cells_total": int(
                seg.size * config.num_attention_heads * config.head_dim
                * config.expert_layers)}
