"""Latent-attention mixture-of-experts decoder (the DeepSeek-V2/V3 layout:
MLA, arXiv:2405.04434; sigmoid routing with a correction bias and the
multi-token-prediction module, arXiv:2412.19437), trained on packed rows.
``glm4_moe_lite`` and ``deepseek_v3`` configurations are this layout at
their own numbers.

For a row of tokens ``u`` with segment ids ``s`` (documents are contiguous
and their ids differ), ``p_t`` the index of token ``t`` inside its
document::

    x = E[u]
    layer i:  x += MLA_i(rms(x));  x += FFN_i(rms(x))
    MLA:      c_q = rms(h W_qa);  [q_nope | q_rope] a head = c_q W_qb
              [c_kv | k_rope] = h W_kva;  c_kv = rms(c_kv)
              [k_nope | v] a head = c_kv W_kvb
              q_rope, k_rope turned by RoPE(theta, position p_t), the halves
              rotated; k_rope is one vector shared by all heads
              o = softmax([q_nope|q_rope] [k_nope|k_rope]^T / sqrt(d_qk),
                          mask j <= i and s_j == s_i) v;  out = concat(o) W_o
    FFN:      the first ``first_k_dense_replace`` layers SwiGLU of width
              ``intermediate_size``; every later one
              sc = sigmoid(h W_r) in float32;  chosen = top-k of (sc + b_i)
              g_e = scaling * sc_e / sum over chosen of sc
              y = Shared(h) + sum over e chosen and held here of
                  g_e Expert_e(h)                        (SwiGLU both)
    head:     logits = rms(x) W_head (untied);  L_main = mean CE(logits_t,
              u_{t+1}) over t with s_{t+1} == s_t
    MTP:      h' = W_eh [rms_e(E[u_{t+1}]) ; rms_h(rms(x_t))];  one more
              expert layer on h', same mask and positions;
              L_mtp = mean CE(rms(h'_t) W_head, u_{t+2}) over t with
              s_t == s_{t+1} == s_{t+2};  E and W_head are the main model's
    loss = L_main + mtp_loss_weight L_mtp
    every expert layer, once a step:  c_e = tokens that chose e;
              b_e += bias_update_speed * sign(mean(c) - c_e)

``Config.experts_held`` are the experts this chip holds and the ``moe``
collection what takes no gradient (the correction biases, the cumulative
counts by expert, each layer's fullest expert and the steps in which a
layer's held slots overflowed ``moe.prefix_rows`` or fitted
``moe.tight_rows``): ``packed_decoder``'s
docstring has both, with what else holds for every packed-row decoder, and
:func:`device_counters` names what of the collection the program's counters
show.  On a TPU the published heads (20 x 256) run attention on the Pallas
kernels of ``attention_pallas`` (``packed_rows.attention_runs_fused``),
anywhere else and at ``Config.tiny()`` as ``jnp`` code, and a step counts
which applied (``attention_fused_steps_total`` /
``attention_plain_steps_total``).  The latent attention itself is
``packed_rows.latent_attention``, which ``kimi_linear`` calls too (with no
query latent, no rotation and values narrower than its keys).

``jax.named_scope`` names a device trace can be cut by: ``attention`` >
``mla_project`` (the latent projections, their norms, RoPE); ``mlp`` (the
dense feed-forward); ``shared_expert``; ``moe_router``, ``moe_dispatch``,
``moe_experts`` (the grouped products), ``moe_combine``; ``mtp`` (the whole
module but its head's loss); ``lm_head`` (both losses).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from tensorflowonspark_tpu.models import packed_decoder, packed_rows
from tensorflowonspark_tpu.models.packed_rows import (
    block, mm, rms, row_counters)

#: no sequence-parallel sharding: attention sees a whole row
SEQUENCE_AXES: dict = {}

#: the recipe :func:`make_optimizer` builds (a continued-pre-training AdamW)
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}

#: the collection of non-gradient state (``packed_decoder.COLLECTION``)
COLLECTION = packed_decoder.COLLECTION


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 154880        # rows of the vocabulary held here
    hidden_size: int = 2048
    num_hidden_layers: int = 47     # before the prediction module
    first_k_dense_replace: int = 1
    intermediate_size: int = 10240  # the dense layers' SwiGLU
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64      # the router's width
    experts_held: tuple = tuple(range(64))
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3
    bias_update_speed: float = 0.001
    init_std: float = 0.02
    dtype: str = "bfloat16"
    seq_len: int = 8192             # tokens a packed row
    attention_block: int = 256      # queries scored at a time
    loss_block: int = 2048          # tokens whose logits are held at a time

    def __post_init__(self):
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("one prediction module, or none")
        if self.qk_rope_head_dim % 2:
            raise ValueError("RoPE turns pairs: qk_rope_head_dim is odd")

    @classmethod
    def tiny(cls) -> "Config":
        return cls(vocab_size=64, hidden_size=32, num_hidden_layers=2,
                   intermediate_size=64, moe_intermediate_size=16,
                   n_routed_experts=16, experts_held=(2, 3),
                   num_experts_per_tok=3, num_attention_heads=4,
                   q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
                   qk_rope_head_dim=4, v_head_dim=12, dtype="float32",
                   seq_len=32, attention_block=16, loss_block=16)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def expert_layers(self) -> int:
        """Layers with a router, the prediction module's among them."""
        return (max(self.num_hidden_layers - self.first_k_dense_replace, 0)
                + self.num_nextn_predict_layers)


def layer_prefixes(config: Config) -> list:
    """``(prefix, kind)`` of every layer in forward order, the prediction
    module's last: ``kind`` is ``"dense"`` or ``"experts"``."""
    out = [(f"l{i:02d}_", "dense" if i < config.first_k_dense_replace
            else "experts") for i in range(config.num_hidden_layers)]
    if config.num_nextn_predict_layers:
        out.append(("mtp_", "experts"))
    return out


def leaf_shapes(config: Config) -> dict:
    """Name -> shape of every parameter, in forward order."""
    d, heads = config.hidden_size, config.num_attention_heads
    f = config.moe_intermediate_size
    out = {"embed": (config.vocab_size, d)}
    for p, kind in layer_prefixes(config):
        if p == "mtp_":
            out[p + "enorm"] = (d,)
            out[p + "hnorm"] = (d,)
            out[p + "eh_proj"] = (2 * d, d)
        out[p + "norm1"] = (d,)
        out[p + "q_a"] = (d, config.q_lora_rank)
        out[p + "q_a_norm"] = (config.q_lora_rank,)
        out[p + "q_b"] = (config.q_lora_rank, heads * config.qk_head_dim)
        out[p + "kv_a"] = (d, config.kv_lora_rank + config.qk_rope_head_dim)
        out[p + "kv_a_norm"] = (config.kv_lora_rank,)
        out[p + "kv_b"] = (config.kv_lora_rank, heads * (
            config.qk_nope_head_dim + config.v_head_dim))
        out[p + "wo"] = (heads * config.v_head_dim, d)
        out[p + "norm2"] = (d,)
        out.update(packed_decoder.ffn_leaf_shapes(
            p, kind, d, config.intermediate_size, f, routing(config),
            shared=f * config.n_shared_experts))
        if p == "mtp_":
            out[p + "head_norm"] = (d,)
    out["final_norm"] = (d,)
    out["head"] = (config.vocab_size, d)
    return out


def routing(config: Config):
    """This layout's routed layers, as ``parallel/moe.py`` names them."""
    from tensorflowonspark_tpu.parallel import moe

    return moe.Routing(
        n_experts=config.n_routed_experts, layers=config.expert_layers,
        held=config.experts_held, top_k=config.num_experts_per_tok,
        scale=config.routed_scaling_factor, normalize=config.norm_topk_prob,
        speed=config.bias_update_speed)


# ---------------------------------------------------------------------------
# The mathematics, over the flat parameter dict
# ---------------------------------------------------------------------------


def latent_attention(params, prefix: str, h, seg, pos, config: Config,
                     scopes: tuple = ("attention",)):
    """``packed_rows.latent_attention`` at this layout's sizes: the queries
    through a latent of ``q_lora_rank``, the shared part of queries and keys
    turned by RoPE(``rope_theta``)."""
    return packed_rows.latent_attention(
        params, prefix, h, seg, pos, heads=config.num_attention_heads,
        nope=config.qk_nope_head_dim, rope_dim=config.qk_rope_head_dim,
        v_dim=config.v_head_dim, kv_rank=config.kv_lora_rank,
        eps=config.rms_norm_eps,
        size=block(h.shape[0], config.attention_block),
        q_rank=config.q_lora_rank, theta=config.rope_theta, scopes=scopes)


def _layer(kind: str, prefix: str, config: Config, scopes: tuple, lp, x, seg,
           pos, bias):
    """One layer on a batch of rows: ``x`` (B, T, D) -> ``(x, counts)``;
    ``counts`` is (E,) zeros for a dense layer."""
    import jax

    eps = config.rms_norm_eps
    with jax.named_scope("attention"):
        x = x + jax.vmap(
            lambda hr, sr, pr: latent_attention(
                lp, prefix, hr, sr, pr, config, scopes + ("attention",))
        )(rms(x, lp[prefix + "norm1"], eps), seg, pos)
    return packed_decoder.feed_forward(
        lp, prefix, kind, x, bias, eps, routing(config), shared=True,
        scopes=scopes)


def prediction_states(params, bias_row, x_normed, tokens, seg, pos,
                      config: Config):
    """The multi-token-prediction module: the main model's normed hidden
    states (B, T, D) and the next tokens' embeddings through one more
    expert layer.  Returns ``(h', counts)``; position ``t`` of ``h'``
    predicts ``u_{t+2}``."""
    import jax
    import jax.numpy as jnp

    eps = config.rms_norm_eps
    with jax.named_scope("mtp"):
        nxt = jnp.take(params["embed"], jnp.roll(tokens, -1, axis=1),
                       axis=0).astype(x_normed.dtype)
        both = jnp.concatenate(
            [rms(nxt, params["mtp_enorm"], eps),
             rms(x_normed, params["mtp_hnorm"], eps)], axis=-1)
        h = mm("bte,ed->btd", both, params["mtp_eh_proj"], both.dtype)
        return packed_decoder.run_layer(
            _layer, params, "mtp_", ("experts",), config, h, seg, pos,
            bias_row, scopes=("mtp",))


def logits(params, x, config: Config, norm: str = "final_norm"):
    """The untied head on states ``x`` (N, D) normed by ``norm``: float32
    (N, V)."""
    import jax.numpy as jnp

    h = rms(x, params[norm], config.rms_norm_eps)
    return mm("td,vd->tv", h, params["head"], h.dtype, out=jnp.float32)


def loss_terms(params, bias, tokens, segment_ids, config: Config):
    """``(main sum, main positions, mtp sum, mtp positions, counts)`` of a
    batch of packed rows; ``counts`` (expert layers, E) int32, a row an
    expert layer in forward order, the prediction module's last."""
    import jax.numpy as jnp

    x, pos, counts = hidden_states(params, bias, tokens, segment_ids, config)

    def sums(states, norm, ahead):
        return packed_decoder.loss_sums(
            lambda xb: logits(params, xb, config, norm), states, tokens,
            segment_ids, config.loss_block, ahead)

    main = sums(x, "final_norm", 1)
    if not config.num_nextn_predict_layers:
        return (*main, jnp.float32(0.0), jnp.int32(0), jnp.stack(counts))
    h, c = prediction_states(
        params, bias[len(counts)],
        rms(x, params["final_norm"], config.rms_norm_eps), tokens,
        segment_ids, pos, config)
    return (*main, *sums(h, "mtp_head_norm", 2), jnp.stack(counts + [c]))


# ---------------------------------------------------------------------------
# The zoo's surface
# ---------------------------------------------------------------------------


def _init(config: Config):
    """``(name, shape) ->`` a leaf's initializer: unit norms, normal
    matrices."""
    import flax.linen as nn

    normal, out = packed_decoder.normals(config.init_std,
                                         config.num_hidden_layers)

    def init(name, shape):
        if len(shape) == 1:
            return nn.initializers.ones
        return out if name.endswith(("_wo", "_down")) else normal

    return init


#: the loop runs the main model's layers; :func:`loss_terms` the module's
_DECODER = packed_decoder.Decoder(
    adamw=ADAMW, leaf_shapes=leaf_shapes, layer=_layer, logits=logits,
    layers=lambda config: [
        layer for layer in layer_prefixes(config) if layer[0] != "mtp_"],
    init=_init, routing=routing, loss_terms=loss_terms, positions=True,
    later_weights=lambda config: (config.mtp_loss_weight,))
collection_shapes = _DECODER.collection_shapes
hidden_states = _DECODER.hidden_states
apply_tokens = _DECODER.apply_tokens
step_collection = _DECODER.step_collection
make_model = _DECODER.make_model
make_optimizer = _DECODER.make_optimizer
make_loss_fn = _DECODER.make_loss_fn
make_forward_fn = _DECODER.make_forward_fn
device_counters = _DECODER.device_counters
counter_rows = _DECODER.counter_rows
parameter_count = _DECODER.parameter_count
example_batch = _DECODER.example_batch


def batch_counters(batch, config: Config) -> dict:
    """What one step adds to the program's counters:
    ``packed_rows.row_counters`` (the host batch's tokens, loss tokens and
    documents, which execution of attention its trace applied and the
    blocks its kernels visit, every layer and the prediction module), which
    execution of the routed experts' grouped products
    (``moe.grouped_step_counters``) and the tokens that bear the second loss
    (the two next are the same document's)."""
    from tensorflowonspark_tpu.parallel import moe

    seg = np.asarray(batch["segment_ids"])
    same = seg[:, 1:] == seg[:, :-1]
    return {**row_counters(seg, config.qk_head_dim,
                           (None,) * len(layer_prefixes(config)),
                           config.v_head_dim),
            **moe.grouped_step_counters(
                seg.size, routing(config), config.hidden_size,
                config.moe_intermediate_size, config.dtype),
            "mtp_loss_tokens_total": int(
                (same[:, 1:] & same[:, :-1]).sum()
                if config.num_nextn_predict_layers else 0)}
