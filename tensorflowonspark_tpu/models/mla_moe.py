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

``Config.experts_held`` says which of the ``n_routed_experts`` this chip
holds (all of them unless told otherwise): the router stays as wide as
published, the held experts' part of the result is computed
(``parallel/moe.py::routed_experts``: every slot kept, the work sized to
the rows that landed here) and what the others would have added is left
out.  No exchange runs and none is stood in for.

The correction biases take no gradient.  They live, with the cumulative
count of tokens by expert, the cumulative size of each layer's fullest
expert and the steps in which a layer's held slots overflowed
``moe.prefix_rows``, in the ``moe`` collection, which the Trainer's stateful
step threads and checkpoints; :func:`device_counters` names what of it the
program's counters show.  (One data shard is what has run: on a
data-parallel mesh the bucketed step averages the replicas' biases and
keeps one replica's counts, ``ROADMAP.md`` B.)

Parameters are float32, activations ``Config.dtype``.  Every layer is
recomputed in the backward pass, attention runs a block of queries at a time
and each loss a block of tokens at a time (``packed_rows``, shared with
``granite_hybrid``); none of the three is an option.  On a TPU the published
heads (20 x 256) run attention on the Pallas kernels of ``attention_pallas``
(``packed_rows.attention_runs_fused`` is the rule), anywhere else and at
``Config.tiny()`` as ``jnp`` code, and a step counts which applied
(``attention_fused_steps_total`` / ``attention_plain_steps_total``).

The latent attention itself is ``packed_rows.latent_attention`` (since PR
43, when a second layout came to call it: ``kimi_linear``, with no query
latent, no rotation and values narrower than its keys; this layout's keys
and values happen to be one width, and the blocked attention no longer asks
for that).

``jax.named_scope`` names a device trace can be cut by: ``attention`` >
``mla_project`` (the latent projections, their norms, RoPE); ``mlp`` (the
dense feed-forward); ``shared_expert``; ``moe_router``, ``moe_dispatch``,
``moe_experts`` (the grouped products), ``moe_combine``; ``mtp`` (the whole
module but its head's loss); ``lm_head`` (both losses).

The flax module only registers the parameters and the collection (flat
dicts); the mathematics is in pure functions over them.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

from tensorflowonspark_tpu.models import packed_rows
from tensorflowonspark_tpu.models.packed_rows import (
    block, blocked_cross_entropy, document_positions, example_rows,
    loss_positions, mm, rms, row_counters, swiglu)

#: no sequence-parallel sharding: attention sees a whole row
SEQUENCE_AXES: dict = {}

#: the recipe :func:`make_optimizer` builds (a continued-pre-training AdamW)
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1}

#: the collection of non-gradient state (:func:`collection_shapes`)
COLLECTION = "moe"


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
    f, held = config.moe_intermediate_size, len(config.experts_held)
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
        if kind == "dense":
            out[p + "mlp_gate"] = (d, config.intermediate_size)
            out[p + "mlp_up"] = (d, config.intermediate_size)
            out[p + "mlp_down"] = (config.intermediate_size, d)
        else:
            out[p + "router"] = (d, config.n_routed_experts)
            out[p + "shared_gate"] = (d, f * config.n_shared_experts)
            out[p + "shared_up"] = (d, f * config.n_shared_experts)
            out[p + "shared_down"] = (f * config.n_shared_experts, d)
            out[p + "experts_gate"] = (held, d, f)
            out[p + "experts_up"] = (held, d, f)
            out[p + "experts_down"] = (held, f, d)
        if p == "mtp_":
            out[p + "head_norm"] = (d,)
    out["final_norm"] = (d,)
    out["head"] = (config.vocab_size, d)
    return out


def parameter_count(config: Config) -> int:
    return sum(int(np.prod(s)) for s in leaf_shapes(config).values())


def collection_shapes(config: Config) -> dict:
    """The ``moe`` collection: a row an expert layer, in forward order."""
    from tensorflowonspark_tpu.parallel import moe

    return moe.routing_state_shapes(config.n_routed_experts,
                                    config.expert_layers)


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


def expert_ffn(params, prefix: str, h, bias, config: Config,
               initializing: bool = False, scopes: tuple = ()):
    """The shared expert and the held routed experts on tokens ``h`` (N, D).
    Returns ``(y, counts)``, ``counts`` (E,) the tokens that chose each of
    the router's experts.  ``initializing``: the module is only learning its
    parameters from this trace; ``scopes``: the named scopes the layer sits
    under (both go to ``moe.routed_experts``)."""
    import jax

    from tensorflowonspark_tpu.parallel import moe

    with jax.named_scope("shared_expert"):
        y = swiglu(h, params[prefix + "shared_gate"],
                   params[prefix + "shared_up"],
                   params[prefix + "shared_down"])
    routed, counts = moe.routed_experts(
        h, params[prefix + "router"], bias, params[prefix + "experts_gate"],
        params[prefix + "experts_up"], params[prefix + "experts_down"],
        config.experts_held, top_k=config.num_experts_per_tok,
        scale=config.routed_scaling_factor, normalize=config.norm_topk_prob,
        initializing=initializing, scopes=scopes)
    return y + routed, counts


def _layer(kind: str, prefix: str, config: Config, scopes: tuple,
           initializing: bool, lp, x, seg, pos, bias):
    """One layer on a batch of rows: ``x`` (B, T, D) -> ``(x, counts)``;
    ``counts`` is (E,) zeros for a dense layer."""
    import jax
    import jax.numpy as jnp

    eps = config.rms_norm_eps
    with jax.named_scope("attention"):
        x = x + jax.vmap(
            lambda hr, sr, pr: latent_attention(
                lp, prefix, hr, sr, pr, config, scopes + ("attention",))
        )(rms(x, lp[prefix + "norm1"], eps), seg, pos)
    h = rms(x, lp[prefix + "norm2"], eps)
    if kind == "dense":
        with jax.named_scope("mlp"):
            y = swiglu(h.reshape(-1, h.shape[-1]), lp[prefix + "mlp_gate"],
                       lp[prefix + "mlp_up"], lp[prefix + "mlp_down"])
        counts = jnp.zeros((config.n_routed_experts,), jnp.int32)
    else:
        y, counts = expert_ffn(lp, prefix, h.reshape(-1, h.shape[-1]), bias,
                               config, initializing, scopes)
    return x + y.reshape(x.shape), counts


def _run_layer(params, prefix, kind, x, seg, pos, bias, config: Config,
               scopes: tuple = (), initializing: bool = False):
    import jax

    mine = {k: v for k, v in params.items() if k.startswith(prefix)}
    return jax.checkpoint(functools.partial(
        _layer, kind, prefix, config, scopes, initializing))(
            mine, x, seg, pos, bias)


def hidden_states(params, bias, tokens, seg, config: Config,
                  initializing: bool = False):
    """``(x, pos, counts)``: the main model's hidden states before the
    last norm (B, T, D), the positions inside documents, and a (E,) count a
    main expert layer (a list, forward order)."""
    import jax
    import jax.numpy as jnp

    pos = jax.vmap(document_positions)(seg)
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        jnp.dtype(config.dtype))
    counts = []
    for prefix, kind in layer_prefixes(config):
        if prefix == "mtp_":
            continue
        row = bias[len(counts)] if kind == "experts" else None
        x, c = _run_layer(params, prefix, kind, x, seg, pos, row, config,
                          initializing=initializing)
        if kind == "experts":
            counts.append(c)
    return x, pos, counts


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
        return _run_layer(params, "mtp_", "experts", h, seg, pos, bias_row,
                          config, scopes=("mtp",))


def _head(params, norm: str, config: Config):
    import jax.numpy as jnp

    def logits(x):
        h = rms(x, params[norm], config.rms_norm_eps)
        return mm("td,vd->tv", h, params["head"], h.dtype, out=jnp.float32)

    return logits


def apply_tokens(params, bias, tokens, segment_ids, config: Config,
                 initializing: bool = False):
    """Teacher-forced forward: (B, T) tokens and segment ids -> (B, T, V)
    float32 logits of the main head.  ``initializing`` is the calling
    module's ``is_initializing()`` (``moe.routed_experts`` reads it)."""
    import jax

    x, _, _ = hidden_states(params, bias, tokens, segment_ids, config,
                            initializing)
    with jax.named_scope("lm_head"):
        return jax.vmap(_head(params, "final_norm", config))(x)


def loss_terms(params, bias, tokens, segment_ids, config: Config):
    """``(main sum, main positions, mtp sum, mtp positions, counts)`` of a
    batch of packed rows; ``counts`` (expert layers, E) int32, a row an
    expert layer in forward order, the prediction module's last."""
    import jax
    import jax.numpy as jnp

    x, pos, counts = hidden_states(params, bias, tokens, segment_ids, config)

    def sums(states, norm, ahead):
        def row(xr, u, s):
            valid = loss_positions(s, ahead)
            return blocked_cross_entropy(
                xr, _head(params, norm, config), jnp.roll(u, -ahead), valid,
                config.loss_block), jnp.sum(valid)

        with jax.named_scope("lm_head"):
            total, count = jax.vmap(row)(states, tokens, segment_ids)
        return jnp.sum(total), jnp.sum(count)

    main = sums(x, "final_norm", 1)
    if not config.num_nextn_predict_layers:
        return (*main, jnp.float32(0.0), jnp.int32(0), jnp.stack(counts))
    h, c = prediction_states(
        params, bias[len(counts)],
        rms(x, params["final_norm"], config.rms_norm_eps), tokens,
        segment_ids, pos, config)
    return (*main, *sums(h, "mtp_head_norm", 2), jnp.stack(counts + [c]))


def step_collection(collection: dict, counts, config: Config,
                    tokens: int) -> dict:
    """The ``moe`` collection after a step whose ``tokens`` tokens chose
    ``counts`` (expert layers, E): ``moe.step_routing_state`` at this
    configuration's experts held, choices a token and bias speed."""
    from tensorflowonspark_tpu.parallel import moe

    return moe.step_routing_state(
        collection, counts, config.experts_held,
        top_k=config.num_experts_per_tok, speed=config.bias_update_speed,
        tokens=tokens)


# ---------------------------------------------------------------------------
# The zoo's surface
# ---------------------------------------------------------------------------


def make_model(config: Config, mesh=None):
    import flax.linen as nn
    import jax.numpy as jnp

    shapes, state = leaf_shapes(config), collection_shapes(config)
    ones = nn.initializers.ones
    normal = nn.initializers.normal(config.init_std)
    # the matrices that write into the residual stream start smaller, by
    # the layers that add to it (GPT-2's and Megatron-LM's scaled
    # initialisation): at one size for all, every token's hidden state is
    # one shared vector after a layer and the router sends a row's tokens
    # to the same few experts
    out = nn.initializers.normal(config.init_std / math.sqrt(
        2 * max(config.num_hidden_layers, 1)))

    def init(name, shape):
        if len(shape) == 1:
            return ones
        return out if name.endswith(("_wo", "_down")) else normal

    class MlaMoe(nn.Module):
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

    return MlaMoe()


def make_optimizer(config: Config, learning_rate: float):
    import optax

    return optax.adamw(learning_rate, **ADAMW)


def make_loss_fn(module, config: Config):
    """``loss(params, collections, batch) -> (loss, new collections)``: the
    mean next-token cross-entropy plus ``mtp_loss_weight`` times the mean
    cross-entropy of the token after, each over the positions whose targets
    are the same document's; the ``moe`` collection moves on a step."""
    import jax.numpy as jnp

    def loss_fn(params, collections, batch):
        state = collections[COLLECTION]
        main, n_main, mtp, n_mtp, counts = loss_terms(
            params, state["bias"], batch["tokens"], batch["segment_ids"],
            config)
        loss = (main / jnp.maximum(n_main, 1)
                + config.mtp_loss_weight * mtp / jnp.maximum(n_mtp, 1))
        return loss, {**collections,
                      COLLECTION: step_collection(
                          state, counts, config, batch["tokens"].size)}

    loss_fn.stateful = True
    return loss_fn


def make_forward_fn(module, config: Config):
    def forward(params, collections, batch):
        return apply_tokens(params, collections[COLLECTION]["bias"],
                            batch["tokens"], batch["segment_ids"], config)

    forward.stateful = True
    return forward


def batch_counters(batch, config: Config) -> dict:
    """What one step adds to the program's counters:
    ``packed_rows.row_counters`` (the host batch's tokens, loss tokens and
    documents, and which execution of attention its trace applied), which
    execution of the routed experts' grouped products
    (``moe.grouped_step_counters``) and the tokens that bear the second loss
    (the two next are the same document's)."""
    from tensorflowonspark_tpu.parallel import moe

    seg = np.asarray(batch["segment_ids"])
    same = seg[:, 1:] == seg[:, :-1]
    return {**row_counters(seg, config.qk_head_dim,
                           v_head_dim=config.v_head_dim),
            **moe.grouped_step_counters(
                seg.size, config.num_experts_per_tok,
                len(config.experts_held), config.n_routed_experts,
                config.hidden_size, config.moe_intermediate_size,
                config.dtype),
            "mtp_loss_tokens_total": int(
                (same[:, 1:] & same[:, :-1]).sum()
                if config.num_nextn_predict_layers else 0)}


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
