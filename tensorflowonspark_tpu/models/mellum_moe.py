"""Sliding-window / full attention mixture-of-experts decoder (the ``mellum``
layout: QK-normed RoPE grouped-query attention in every layer, three layers
in four behind a sliding window and the fourth over the whole document with
YaRN frequencies of its own; every layer's feed-forward softmax-routed
experts, no dense layer, no shared expert, an untied head), trained on packed
rows.

Published shape: ``JetBrains/Mellum2-12B-A2.5B-Instruct`` ``config.json``.
For a row of tokens ``u`` with segment ids ``s`` (documents are contiguous
and their ids differ), ``p_t`` the index of token ``t`` inside its document::

    x = E[u]
    layer l:  x += attn_l(rms(x));  x += experts_l(rms(x))
    attention:  q = h W_q, k = h W_k, v = h W_v (GQA); every query and key
                head normed (one RMS scale of a head's width each), then
                turned at p_t by the layer type's frequencies f, cosine and
                sine times its factor a, the halves rotated:
                  sliding_attention:  f_i = theta ** (-i / 64),  a = 1
                  full_attention:     f_i = theta ** (-i / 64)
                                            * ((1 - ramp_i) + ramp_i / factor),
                                      a = attention_factor      (YaRN)
                o = softmax(q k^T / sqrt(hd), mask j <= i and s_j == s_i and,
                            in a sliding layer, i - j < sliding_window) v
                out = concat(o) W_o
    experts:    p = softmax(h W_r) over all the router's experts, float32
                chosen = top-k of p;  g_e = p_e / (sum over chosen of p)
                y = sum over e chosen and held here of g_e Expert_e(h)
    head:       logits = rms(x) W_head (untied);
                loss = mean CE(logits_t, u_{t+1}) over t with s_{t+1} == s_t

Nothing here is this model's alone but the choice of a window and a rotation
by layer type: the norm, the products, the grouped-query layer, both
rotations, the attention (``document_attention(window=...)``, which skips the
blocks a window cannot reach) and the blocked loss are ``packed_rows``'s, the
routed layer (``Routing.score`` ``"softmax"``) and the routing state (the
``moe`` collection: no correction bias exists, the bias stays zero and the
counts add up) ``parallel/moe.py``'s, the layer loop, the feed-forward half
of a layer, the positions and the registry's surface ``packed_decoder``'s,
whose docstring says what holds for every such decoder
(``Config.experts_held`` among it).  The published heads of 128 fill a row of
lanes, so on a TPU at the published row attention runs on the kernels of
``attention_pallas`` under grouped queries, eight query heads to a key head
(``packed_rows.attention_runs_fused``).

``jax.named_scope`` names a device trace can be cut by: ``attention`` (a
layer's norm and mixer whole, both kinds) > ``qk_norm_rope``, and round the
blocks of scores, softmax and values ``window_attention`` in a sliding layer
and ``full_attention`` in a full one; ``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine`` (``routed_experts``'); ``lm_head``.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from tensorflowonspark_tpu.models import packed_decoder
from tensorflowonspark_tpu.models.packed_rows import (
    BLOCKS_SCOPE, block, grouped_query_attention, mask_pairs, mm, rms,
    rope_frequencies, row_counters, yarn_frequencies)

#: no sequence-parallel sharding: a window has no neighbour's block over
#: ``sp`` yet
SEQUENCE_AXES: dict = {}

#: the recipe :func:`make_optimizer` builds (a continued-pre-training AdamW)
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}

#: the collection of non-gradient state (``packed_decoder.COLLECTION``)
COLLECTION = packed_decoder.COLLECTION

#: the published pattern: ``S S S F`` seven times
PUBLISHED_LAYERS = ("sliding_attention",) * 3 + ("full_attention",)

#: the rotations :func:`rotation` knows (``rope_parameters``' ``rope_type``)
ROPE_TYPES = ("default", "yarn")


def published_rope_parameters() -> dict:
    """``rope_parameters`` as published: a rotation a layer type."""
    return {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    }


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 98304         # rows of the vocabulary held here
    hidden_size: int = 2304
    head_dim: int = 128
    layer_types: tuple = PUBLISHED_LAYERS * 7   # every published layer's
    layers_run: tuple = tuple(range(28))        # of them, the ones run
    sliding_window: int = 1024      # a query sees itself and 1,023 before
    rope_parameters: dict = dataclasses.field(
        default_factory=published_rope_parameters)
    moe_intermediate_size: int = 896
    num_experts: int = 64           # the router's width
    experts_held: tuple = tuple(range(64))
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    rms_norm_eps: float = 1e-6
    init_std: float = 0.02
    embed_init_std: float = 1.0     # the embedding's own (see :func:`_init`)
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
        for kind in set(self.layer_types):
            if self.rope_parameters[kind]["rope_type"] not in ROPE_TYPES:
                raise ValueError(f"{kind}: unknown rope_type "
                                 f"{self.rope_parameters[kind]['rope_type']!r}")

    @classmethod
    def tiny(cls) -> "Config":
        return cls(vocab_size=64, hidden_size=32, head_dim=8,
                   layer_types=("sliding_attention", "sliding_attention",
                                "full_attention", "sliding_attention"),
                   layers_run=(1, 2, 3), sliding_window=12,
                   rope_parameters={
                       "full_attention": {
                           "rope_type": "yarn", "rope_theta": 10000.0,
                           "factor": 4.0,
                           "original_max_position_embeddings": 16,
                           "beta_fast": 2.0, "beta_slow": 0.25,
                           "attention_factor": 0.1 * math.log(4.0) + 1.0},
                       "sliding_attention": {"rope_type": "default",
                                             "rope_theta": 10000.0}},
                   moe_intermediate_size=16, num_experts=8,
                   experts_held=(2, 5), num_experts_per_tok=3,
                   num_attention_heads=4, num_key_value_heads=2,
                   embed_init_std=0.02,     # every layer's part shows
                   dtype="float32", seq_len=48, attention_block=16,
                   loss_block=16)

    @property
    def expert_layers(self) -> int:
        """Layers with a router: every layer run."""
        return len(self.layers_run)


def layer_kinds(config: Config) -> list:
    """``(prefix, mixer, ffn)`` of every layer run, in forward order:
    ``mixer`` is the published ``layer_types`` at ``layers_run``
    (``"sliding_attention"`` or ``"full_attention"``), ``ffn`` always
    ``"experts"`` (no layer is dense)."""
    return [(f"l{i:02d}_", config.layer_types[at], "experts")
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
        out[p + "q_norm"] = (hd,)
        out[p + "k_norm"] = (hd,)
        out[p + "wo"] = (config.num_attention_heads * hd, d)
        out[p + "norm2"] = (d,)
        out.update(packed_decoder.ffn_leaf_shapes(
            p, ffn, d, 0, config.moe_intermediate_size, routing(config)))
    out["final_norm"] = (d,)
    out["head"] = (config.vocab_size, d)
    return out


def routing(config: Config):
    """This layout's routed layers, as ``parallel/moe.py`` names them: a
    softmax over all the router's experts, the chosen renormalised where
    ``norm_topk_prob``, no correction bias (speed 0: it stays zero)."""
    from tensorflowonspark_tpu.parallel import moe

    return moe.Routing(
        n_experts=config.num_experts, layers=config.expert_layers,
        held=config.experts_held, top_k=config.num_experts_per_tok,
        scale=1.0, normalize=config.norm_topk_prob, speed=0.0,
        score="softmax")


def rotation(config: Config, kind: str) -> tuple:
    """``(frequencies (head_dim / 2,), factor)`` of a layer type's rotation
    (``rope_parameters[kind]``): ``"default"`` plain RoPE, ``"yarn"``
    ``packed_rows.yarn_frequencies`` with cosine and sine times
    ``attention_factor``."""
    p, half = config.rope_parameters[kind], config.head_dim // 2
    if p["rope_type"] == "default":
        return rope_frequencies(p["rope_theta"], half), 1.0
    return (yarn_frequencies(
        p["rope_theta"], half, p["factor"],
        p["original_max_position_embeddings"], p["beta_fast"],
        p["beta_slow"]), float(p["attention_factor"]))


# ---------------------------------------------------------------------------
# The mathematics, over the flat parameter dict
# ---------------------------------------------------------------------------


def attention(params, prefix: str, h, seg, pos, config: Config, kind: str):
    """``packed_rows.grouped_query_attention`` at this layout's sizes, as a
    layer of ``kind``: behind ``sliding_window`` or over the whole document,
    turned by the kind's own rotation, the blocks under the kind's scope."""
    freq, factor = rotation(config, kind)
    return grouped_query_attention(
        params, prefix, h, seg, pos, heads=config.num_attention_heads,
        kv=config.num_key_value_heads, hd=config.head_dim,
        eps=config.rms_norm_eps,
        size=block(h.shape[0], config.attention_block), freq=freq,
        factor=factor, inner=BLOCKS_SCOPE[kind],
        window=config.sliding_window if kind == "sliding_attention" else None)


def _layer(mixer: str, ffn: str, prefix: str, config: Config, scopes: tuple,
           lp, x, seg, pos, bias):
    """One layer on a batch of rows: ``x`` (B, T, D) -> ``(x, counts)``.
    The layout has no correction bias: the routing state's row enters the
    choice as zeros."""
    import jax
    import jax.numpy as jnp

    eps = config.rms_norm_eps
    with jax.named_scope("attention"):
        x = x + jax.vmap(lambda hr, sr, pr: attention(
            lp, prefix, hr, sr, pr, config, mixer))(
                rms(x, lp[prefix + "norm1"], eps), seg, pos)
    return packed_decoder.feed_forward(lp, prefix, ffn, x,
                                       jnp.zeros_like(bias), eps,
                                       routing(config), scopes=scopes)


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
    matrices, those that write into the residual stream smaller by the
    published depth; the embedding at a scale of its own.  At the matrices'
    0.02 what a layer adds to the residual stream — much the same for every
    token of a document — is as large as a token's own embedding, and a
    seeded router then sends half a row or more to one expert: whether the
    experts a chip holds are among the chosen few decides its step's time
    (PERF.md section 6, PR 47).  At 1 (PyTorch's ``nn.Embedding`` default)
    a token stays its own and the seeded router is about even."""
    import flax.linen as nn

    normal, out = packed_decoder.normals(config.init_std,
                                         len(config.layer_types))
    embedding = nn.initializers.normal(config.embed_init_std)

    def init(name, shape):
        if len(shape) == 1:
            return nn.initializers.ones
        if name == "embed":
            return embedding
        return out if name.endswith(("_wo", "_down")) else normal

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
    documents, which execution of attention its trace applied and the
    blocks its kernels visit, each layer at its own window;
    ``moe.grouped_step_counters``: which execution of the routed experts'
    grouped products; and what the two masks really admit on this batch's
    documents, a head, summed over the layers of each kind:
    ``packed_rows.mask_pairs``)."""
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
                "full_attention")}
